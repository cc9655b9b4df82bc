"""Pallas TPU kernels of the witness gang: record (§4.2) and gc (§4.5).

``gang_record_pallas`` and ``gang_gc_pallas`` (below) back every
``DeviceWitness`` and the fused cluster batch.  They compile as Mosaic
kernels for the TPU; run on the CPU they take interpret mode.  A single-key
op is a group of one key, so ``ops.gang_record``, ``ops.gang_record_groups``
and ``ops.gang_fastpath_batch`` share the record kernel.

Memory budget
-------------
VMEM per grid cell (T = TILE_ROWS = 8192, W = 4): each
plane block is [4, 8192] int32, 128 KiB, held as 256 KiB because VMEM pads
4 sublanes to 8.  The record kernel keeps 6 input and 6 output blocks, each
double-buffered: 24 x 256 KiB = 6 MiB.  The gc kernel keeps 6 + 1 input
blocks ([1, 8192] aged-row mask, also padded to 8 sublanes) and 2 outputs:
18 x 256 KiB = 4.5 MiB.  Both fit v5e's 16 MiB scoped VMEM and do not grow
with the gang: more lanes mean more grid cells.  SMEM holds the prefetched
work items: 4 words per key + 3 per group, plus 1 reason per group — the
fused batch at B = 1024, f = 3 (3072 groups of one key) is 96 KiB of 1 MiB.
Each SMEM array is rounded up to 1024 words, so the record kernel holds at
most 31744 single-key groups (B = 10581 at f = 3) or 21504 two-key groups,
and the gc kernel 43008 entries; the wrappers raise before a compile that
would overflow.  The TPU
compiler's ``memory_analysis()`` for a described v5e at the smoke's gang
(64 lanes x 1024 sets: six [4, 65536] planes) reports, in HBM:

  =====================  ==============  ============  ==========
  jitted impl            arguments (B)   outputs (B)   temps (B)
  =====================  ==============  ============  ==========
  _gang_record_impl          6,320,128     6,304,256           0
  _gang_groups_impl          6,340,608     6,312,448           0
  _gang_gc_impl              6,316,544     6,296,064           0
  _gang_fastpath_impl        6,520,832     6,521,856   3,322,368
  =====================  ==============  ============  ==========

(B = 1024, two-key groups, 16 rings of 1024; ``tests/test_tpu_compile.py``
repeats these compiles.)  The six planes are 6,291,456 B of each.

Donation / aliasing contract
----------------------------
The record kernel aliases all six planes to its outputs, the gc kernel
``occ`` and ``age`` (``input_output_aliases``).  What that buys, and what
it does not:

  * WITHIN one jitted program the planes are updated in place: the
    pallas_call consumes its operand buffers instead of allocating and
    copying a second set, and in ``ops.gang_fastpath_batch`` the table
    threads prep -> kernel -> result with no intermediate copy.
  * ACROSS public-op calls the jax.jit boundary still owns the buffers:
    without jit-level donation (``donate_argnums``) XLA materializes fresh
    output buffers per call, and we deliberately do not donate there — the
    oracle tests replay one table against several ops, and the CPU (where
    the kernels run in interpret mode) ignores jit donation anyway.

Op-class plane / merge-lattice consult (CRDT-CURP)
--------------------------------------------------
Occupancy packs the held op's merge-lattice class (repro.core.merge):
``occ == 0`` is empty, ``occ == 1 + class`` is occupied; class SET == 0, so
all-SET tables keep a 0/1 encoding.  Each key carries its class; a
same-key hit under a foreign rpc conflicts only when
``(CONFLICT_MATRIX[cls] >> (occ - 1)) & 1`` is set — the matrix is a
static 16-entry constant that inlines into the kernel as a where-sum
(``ref.matrix_rows``), so the in-dispatch decision is bit-exact with the
Python ``Witness.record`` lattice check.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import U32, GangTable, matrix_rows

# ---------------------------------------------------------------------------
# Gang kernels: stacked lanes + kernel-held RIFL identity and gc-age state
# ---------------------------------------------------------------------------
# A GangTable is L witness tables stacked along ONE row axis, every plane laid
# out [W, R] with the rows (sets) along the 128 vector lanes (ref.GangTable).
# Every slot also holds the recording op's rpc identity and a gc-age counter:
# duplicate-retry acceptance (same key + same rpc), stale-gc suppression
# (clear only on key AND rpc match) and §4.5 age bumping all resolve inside
# the dispatch.  Reason codes (see repro.kernels.ref): 1 insert / 2 dup /
# 3 conflict / 4 set-full / 0 padding.
#
# Mosaic layout.  The grid walks the table in row tiles of T rows ([W, T]
# blocks, pipelined HBM <-> VMEM; the table outputs alias the inputs).  The
# work items (record groups / gc entries) arrive as flat int32 arrays in SMEM
# via scalar prefetch, stably sorted by tile, with ``tstart[t]`` the first
# item of tile t — so a tile walks exactly its own items, in caller order, and
# rows of different tiles never interact.  One item touches one row, i.e.
# one lane of one 128-lane column chunk of each plane: the kernel loads that
# aligned [W, 128] chunk, decides with lane-masked vector compares reduced to
# scalars, and writes the chunk back.  No gather, no scatter, no cumsum.

LANES = 128         # rows per vector chunk (TPU lane count)
TILE_ROWS = 8192    # rows per grid cell: 6 planes x [4, 8192] blocks
# v5e scalar memory, in int32 words.  Mosaic rounds each SMEM array up to
# SMEM_PAD words; the tile starts and the compiler's own scalars take less
# than SMEM_RESERVED.  A described-v5e compile matches this model exactly:
# record K=1 fits G = 31744 groups and not 32000, K=2 fits 21504 and not
# 21505, gc fits 43008 entries and not 43009.
SMEM_WORDS = 1 << 18
SMEM_PAD = 1024
SMEM_RESERVED = 1024


def smem_fits(lengths) -> bool:
    """Whether flat int32 SMEM arrays of ``lengths`` fit v5e's SMEM."""
    words = sum(-(-n // SMEM_PAD) * SMEM_PAD for n in lengths)
    return words <= SMEM_WORDS - SMEM_RESERVED


def check_smem(kernel: str, lengths) -> None:
    """Raise before the compile if the flat int32 SMEM arrays of
    ``lengths`` (prefetched work items and per-item outputs) overflow v5e's
    SMEM, where Mosaic would refuse with RESOURCE_EXHAUSTED."""
    if not smem_fits(lengths):
        words = sum(-(-n // SMEM_PAD) * SMEM_PAD for n in lengths)
        raise ValueError(
            f"{kernel}: {words} SMEM words of work items exceed the "
            f"{SMEM_WORDS - SMEM_RESERVED} that fit on v5e; split the batch")


def gang_tile_rows(n_rows: int, n_sets: int) -> int:
    """Rows per grid cell for a gang of ``n_rows`` (padded) rows: TILE_ROWS,
    but at least one witness's ``n_sets`` rows (so a multi-key group never
    spans two tiles) and at most the table.  All are powers of two, so the
    tile divides the table."""
    t = min(n_rows, max(TILE_ROWS, n_sets))
    assert n_rows % t == 0, (n_rows, t)
    return t


def _chunk(r):
    """Aligned [W, 128] column window holding local row ``r`` + its lane."""
    return (slice(None), pl.ds(pl.multiple_of(r - r % LANES, LANES), LANES)), \
        r % LANES


def _bitmask(mask, way_iota):
    """[W, 128] lane-masked bool -> scalar bitmask over ways (bit w = way w).
    At most one lane is set per way, so the sum is an OR."""
    return jnp.sum(jnp.where(mask, jnp.left_shift(1, way_iota), 0))


def _lowest(bits, W: int):
    """Index of the lowest set bit of a scalar way bitmask (W if none)."""
    low = jnp.int32(W)
    for w in range(W - 1, -1, -1):
        low = jnp.where((bits >> w) & 1 == 1, w, low)
    return low


def _make_gang_record_kernel(K: int, W: int, T: int):
    """Per-group all-or-nothing record, sequential in caller order within a
    tile.  Each group's K (padded) keys decide together against the table as
    earlier groups left it and, on accept, write in key order.  Free ways are
    RESERVED in key order (the k-th same-row inserter takes the row's
    (rank+1)-th free way), matching the Python placement loop — same-row keys
    of one group land in distinct ways.  A single-key op is a group of K=1."""
    def kernel(tstart, krow, khi_s, klo_s, kcv, grh, grl, gval,
               khi_in, klo_in, occ_in, rh_in, rl_in, age_in,
               rsn, khi, klo, occ, rh, rl, age):
        t = pl.program_id(0)
        planes = (khi, klo, occ, rh, rl, age)
        for dst, src in zip(planes, (khi_in, klo_in, occ_in, rh_in, rl_in,
                                     age_in)):
            dst[...] = src[...]
        lane_iota = jax.lax.broadcasted_iota(jnp.int32, (W, LANES), 1)
        way_iota = jax.lax.broadcasted_iota(jnp.int32, (W, LANES), 0)
        base = t * T

        def group(g, carry):
            rc, rs = grh[g], grl[g]
            keys = []
            # Decision pass: every key probes the pre-group table.
            for k in range(K):
                i = g * K + k
                r = jnp.clip(krow[i] - base, 0, T - 1)
                win, ln = _chunk(r)
                m = lane_iota == ln
                o = occ[win]
                qh, ql, cv = khi_s[i], klo_s[i], kcv[i]
                cls = jnp.maximum(cv - 1, 0)
                keym = m & (o > 0) & (khi[win] == qh) & (klo[win] == ql)
                rpcm = (rh[win] == rc) & (rl[win] == rs)
                # Foreign-rpc same-key hit conflicts only when the merge
                # lattice says so (occ packs 1 + class) — commuting classes
                # stack in sibling ways.
                confm = (keym & ~rpcm
                         & (((matrix_rows(cls) >> jnp.maximum(o - 1, 0)) & 1)
                            == 1))
                # The Python witness scans ways in order and stops at the
                # first same-key way that is a dup (same rpc) or conflicts:
                # whichever comes first decides.
                dway = _lowest(_bitmask(keym & rpcm, way_iota), W)
                cway = _lowest(_bitmask(confm, way_iota), W)
                keys.append(dict(
                    r=r, win=win, m=m, qh=qh, ql=ql, cls=cls, vk=cv > 0,
                    free=_bitmask(m & (o == 0), way_iota),
                    dway=dway, is_dup=dway < cway, conf=cway < dway,
                ))
            # Way reservation, in scalars: rank each inserting key among the
            # group's earlier same-row inserters; it seats iff free ways
            # remain and takes the (rank+1)-th free way.
            for k, key in enumerate(keys):
                is_dup = key["is_dup"]
                rank = jnp.int32(0)
                for j in range(k):
                    claim_j = keys[j]["vk"] & ~keys[j]["is_dup"]
                    rank += (claim_j & (keys[j]["r"] == key["r"])).astype(
                        jnp.int32)
                n_free = jnp.int32(0)
                way = jnp.int32(0)
                for w in range(W):
                    bit = (key["free"] >> w) & 1
                    n_free += bit
                    way = jnp.where((bit == 1) & (n_free == rank + 1), w, way)
                key["way"] = jnp.where(is_dup, key["dway"], way)
                key["ok"] = ~key["conf"] & (is_dup | (n_free > rank))
            acc = gval[g] == 1
            all_dup = jnp.bool_(True)
            any_vk = jnp.bool_(False)
            failed = jnp.bool_(False)
            fail_conf = jnp.bool_(False)
            for key in keys:
                acc &= key["ok"] | ~key["vk"]
                all_dup &= key["is_dup"] | ~key["vk"]
                any_vk |= key["vk"]
                # Reject reason comes from the FIRST failing key, like the
                # Python loop that returns at the first conflict/full key.
                fail = key["vk"] & ~key["ok"]
                fail_conf = jnp.where(fail & ~failed, key["conf"], fail_conf)
                failed |= fail
            reason = jnp.where(acc, jnp.where(all_dup & any_vk, 2, 1),
                               jnp.where(fail_conf, 3, 4))
            rsn[g] = jnp.where(gval[g] == 1, reason, 0).astype(jnp.int32)
            # Write pass, in key order; ways are pre-reserved so same-row
            # keys never alias.  Chunks reload: an earlier key of this group
            # may share the row.
            for key in keys:
                sel = key["m"] & (way_iota == key["way"]) & (acc & key["vk"])
                win = key["win"]
                for ref, v in zip(planes, (key["qh"], key["ql"],
                                           1 + key["cls"], rc, rs, 0)):
                    ref[win] = jnp.where(sel, v, ref[win])
            return carry

        jax.lax.fori_loop(tstart[t], tstart[t + 1], group, 0)
    return kernel


def _i32(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32) \
        if x.dtype == U32 else x.astype(jnp.int32)


def _tile_order(tiles: jnp.ndarray, n_tiles: int):
    """Stable sort of work items by tile (invalid items carry ``n_tiles``
    and sort past the end): returns (perm, tstart [n_tiles + 1])."""
    perm = jnp.argsort(tiles, stable=True)
    tstart = jnp.searchsorted(
        tiles[perm], jnp.arange(n_tiles + 1, dtype=jnp.int32)
    ).astype(jnp.int32)
    return perm, tstart


def _unsort(perm: jnp.ndarray, x_sorted: jnp.ndarray) -> jnp.ndarray:
    return jnp.zeros_like(x_sorted).at[perm].set(x_sorted)


def _plane_specs(W: int, T: int):
    tile = pl.BlockSpec((W, T), lambda t, *_: (0, t))
    return tile, pl.BlockSpec(memory_space=pltpu.SMEM)


@functools.partial(jax.jit, static_argnames=("n_sets", "interpret"))
def gang_record_pallas(
    table: GangTable,
    qhi: jnp.ndarray, qlo: jnp.ndarray,
    qrow: jnp.ndarray, qval: jnp.ndarray, qcls: jnp.ndarray,
    grh: jnp.ndarray, grl: jnp.ndarray, gval: jnp.ndarray,
    *, n_sets: int, interpret: bool,
):
    """One-dispatch batch of per-group all-or-nothing records.

    ``qhi/qlo/qrow/qval/qcls`` are [G, K] padded key arrays (MIXED lanes,
    global rows ``lane * n_sets + set``, validity, merge-lattice classes);
    every key of a group must sit in one lane.  ``grh/grl/gval`` are the
    per-group rpc identity and validity.  Groups resolve sequentially in
    index order per row — single-key ops are groups of size 1, bit-exact
    with ``Witness.record``.  Returns (reason per group [G], new gang table);
    all six planes alias their outputs.

    SMEM holds the prefetched work items and the reasons: 4 words per key
    + 4 per group (the fused cluster batch at B=1024, f=3 is 96 KiB of
    v5e's 1 MiB).  The most that fits is G = 31744 single-key groups (a
    fused batch of B = 10581 at f = 3) or G = 21504 two-key groups; a
    larger batch raises ValueError here (see ``check_smem``).
    """
    W, R = table.occ.shape
    G, K = qhi.shape
    check_smem("gang_record", [G * K] * 4 + [G] * 4)
    T = gang_tile_rows(R, n_sets)
    n_tiles = R // T
    tiles = jnp.where(gval == 1, qrow[:, 0] // T, n_tiles).astype(jnp.int32)
    perm, tstart = _tile_order(tiles, n_tiles)
    kcv = jnp.where(qval == 1, 1 + qcls.astype(jnp.int32), 0)
    items = [_i32(a[perm]).reshape(-1) for a in (qrow, qhi, qlo, kcv)]
    items += [_i32(a[perm]) for a in (grh, grl, gval)]
    tile, smem = _plane_specs(W, T)
    out = pl.pallas_call(
        _make_gang_record_kernel(K, W, T),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=8, grid=(n_tiles,),
            in_specs=[tile] * 6, out_specs=[smem] + [tile] * 6,
        ),
        out_shape=[jax.ShapeDtypeStruct((G,), jnp.int32)]
        + [jax.ShapeDtypeStruct((W, R), jnp.int32)] * 6,
        input_output_aliases={8 + p: 1 + p for p in range(6)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tstart, *items, *(_i32(a) for a in table))
    rsn = jnp.where(gval == 1, _unsort(perm, out[0]), 0)
    return rsn, GangTable(*(
        jax.lax.bitcast_convert_type(o, a.dtype) if a.dtype == U32 else o
        for o, a in zip(out[1:], table)
    ))


def _make_gang_gc_kernel(W: int, T: int, do_age: bool):
    def kernel(tstart, erow, ehi, elo, erh, erl,
               khi, klo, occ_in, rh, rl, age_in, aged,
               clr, occ, age):
        t = pl.program_id(0)
        occ[...] = occ_in[...]
        age[...] = age_in[...]
        lane_iota = jax.lax.broadcasted_iota(jnp.int32, (W, LANES), 1)

        def entry(e, carry):
            # Clear only where key AND rpc AND row all match — a newer
            # record under a different rpc survives a stale gc entry.
            win, ln = _chunk(jnp.clip(erow[e] - t * T, 0, T - 1))
            o = occ[win]
            hit = ((lane_iota == ln) & (o > 0)
                   & (khi[win] == ehi[e]) & (klo[win] == elo[e])
                   & (rh[win] == erh[e]) & (rl[win] == erl[e]))
            clr[e] = jnp.max(jnp.where(hit, 1, 0))
            occ[win] = jnp.where(hit, 0, o)
            age[win] = jnp.where(hit, 0, age[win])
            return carry

        jax.lax.fori_loop(tstart[t], tstart[t + 1], entry, 0)
        if do_age:
            # §4.5: survivors in aged rows age one round; empty slots reset.
            o = occ[...]
            age[...] = jnp.where(aged[...] == 1,
                                 jnp.where(o > 0, age[...] + 1, 0), age[...])
    return kernel


@functools.partial(
    jax.jit, static_argnames=("n_sets", "do_age", "interpret")
)
def gang_gc_pallas(
    table: GangTable,
    g_hi: jnp.ndarray, g_lo: jnp.ndarray,
    g_rh: jnp.ndarray, g_rl: jnp.ndarray,
    g_row: jnp.ndarray, g_valid: jnp.ndarray,
    aged_rows: jnp.ndarray,
    *, n_sets: int, do_age: bool = True, interpret: bool,
):
    """Gang gc: rpc-matched clears + in-kernel §4.5 aging, ONE dispatch.

    Entries carry (key lanes, rpc lanes, global row); a slot clears only on
    a full match, so stale entries never drop a newer same-key record.
    Entries apply in index order per row (a repeated entry clears once).
    Survivors in rows flagged by ``aged_rows`` ([R] 0/1) age by one round
    (cleared / empty slots reset to 0); ``do_age=False`` skips the aging.
    Returns (cleared bit per entry [G], new gang table); occ and age alias
    their outputs, key/rpc lanes are untouched.

    SMEM holds 5 words per entry plus the cleared bit: at most G = 43008
    entries fit on v5e; more raise ValueError (see ``check_smem``).
    """
    W, R = table.occ.shape
    check_smem("gang_gc", [g_hi.shape[0]] * 6)
    T = gang_tile_rows(R, n_sets)
    n_tiles = R // T
    tiles = jnp.where(g_valid == 1, g_row // T, n_tiles).astype(jnp.int32)
    perm, tstart = _tile_order(tiles, n_tiles)
    items = [_i32(a[perm]) for a in (g_row, g_hi, g_lo, g_rh, g_rl)]
    tile, smem = _plane_specs(W, T)
    out = pl.pallas_call(
        _make_gang_gc_kernel(W, T, do_age),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(n_tiles,),
            in_specs=[tile] * 6 + [pl.BlockSpec((1, T), lambda t, *_: (0, t))],
            out_specs=[smem, tile, tile],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(g_hi.shape, jnp.int32),
            jax.ShapeDtypeStruct((W, R), jnp.int32),
            jax.ShapeDtypeStruct((W, R), jnp.int32),
        ],
        input_output_aliases={8: 1, 11: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tstart, *items, *(_i32(a) for a in table),
      aged_rows.astype(jnp.int32).reshape(1, R))
    clr = jnp.where(g_valid == 1, _unsort(perm, out[0]), 0)
    return clr, table._replace(occ=out[1], age=out[2])

