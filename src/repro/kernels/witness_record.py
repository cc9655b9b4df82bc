"""Pallas TPU kernels: the witness record (§4.2) and gc kernels.

Two families live here.

* **The served gang kernels** (``gang_record_pallas``, ``gang_gc_pallas``;
  bottom of the file) back every ``DeviceWitness`` and the fused cluster
  batch.  They compile as Mosaic kernels for the TPU; see "Gang kernels"
  below for their layout.
* **Single-table comparison kernels** (the set-parallel
  ``witness_record_setpar_pallas`` / ``fastpath_record_scan_pallas``, the
  sequential ``witness_record_seq_pallas``, ``witness_record_txn_pallas``
  and ``witness_gc_pallas``) serve old-vs-new benchmarks and differential
  tests only.  They gather and scatter table rows by index vectors inside
  the kernel, which runs in interpret mode but which Mosaic refuses.

Set-parallel comparison kernel
------------------------------
The witness table is S sets x W ways of 2x32-bit keyhash slots.  Records are
order-dependent *within one set* (an accepted record occupies a way that later
same-key records must conflict with) but **commute across sets** — two records
that probe different sets touch disjoint table rows and disjoint accept bits.
The set-parallel kernel exploits exactly that independence:

  1. A prep pass (repro.kernels.ops._setpar_prep, plain XLA so it fuses with
     the hash) buckets the query batch by probed set ``lo & (S-1)``: a stable
     sort by set id, then a stable sort by rank-within-set.  After the second
     sort, "round" r (the r-th query of every set's run) is one contiguous,
     set-ascending span of the reordered batch.
  2. The kernel runs a grid over set-tiles (TILE_S rows of the table per grid
     cell).  Each cell loops over rounds; one round loads a contiguous query
     chunk (dynamic start, static size), masks it to this cell's sets, and
     resolves up to TILE_S sets **simultaneously** — every set in the round
     probes, conflict-checks, and fills its first free way in the same
     vectorized step.
  3. Accept bits are written round-chunk-contiguously into a [B] output that
     all grid cells revisit (accumulate-on-revisit, same pattern as
     conflict_scan); ops.py unsorts them back to caller order.

Gang kernels: memory budget
---------------------------
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
Both kernels declare ``input_output_aliases`` for the table buffers
(keys_hi/keys_lo/occ -> the corresponding outputs).  What that buys, and
what it does not:

  * WITHIN one jitted program the table is updated in place: the pallas_call
    consumes its operand buffer instead of allocating + copying a second
    [S, W] triple, and in the fused ``ops.fastpath_batch`` the table threads
    prep -> kernel -> result with no intermediate copy.
  * ACROSS public-op calls the jax.jit boundary still owns the buffers:
    without jit-level donation (``donate_argnums``) XLA materializes a fresh
    output buffer per call, and we deliberately do not donate there — the
    oracle/differential tests replay one table against several ops, and CPU
    (where the kernels run in interpret mode) ignores jit donation anyway.
    Cross-call in-place reuse is a TPU deployment follow-up (ROADMAP), wired
    by donating the table argument at the caller's jit boundary.

Op-class plane / merge-lattice consult (CRDT-CURP)
--------------------------------------------------
Occupancy packs the held op's merge-lattice class (repro.core.merge):
``occ == 0`` is empty, ``occ == 1 + class`` is occupied; class SET == 0, so
all-SET tables keep the legacy 0/1 encoding bit-exactly.  Record queries
carry a ``q_cls`` lane; a same-key hit conflicts only when
``(CONFLICT_MATRIX[q_cls] >> (occ - 1)) & 1`` is set — the matrix is a
static 16-entry constant that inlines into the kernel as a where-sum
(``ref.matrix_rows``), so the in-dispatch decision is bit-exact with the
Python ``Witness.record`` lattice check.  README.md details the encoding
and its VMEM cost (zero extra table bytes; one extra [B] query lane).

The sequential reference kernel (`witness_record_seq_pallas`, the pre-refactor
fori_loop design) is kept for the old-vs-new comparison in
benchmarks/fig_fastpath.py and for differential testing; it predates the
op-class plane (classless all-SET semantics, unchanged).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import U32, GangTable, WitnessTable, matrix_rows

# Default number of table rows (sets) handled by one grid cell.  At the
# paper's 1024x4 geometry one tile is the whole table (48 KiB — trivially
# VMEM-resident), so the grid has a single cell; larger geometries split into
# S/TILE_S cells.  Smaller tiles trade VMEM residency for redundant query
# scans (every cell walks the full round sequence and masks to its sets), so
# shrink the tile only when the table itself outgrows VMEM.
DEFAULT_TILE_SETS = 1024


# ---------------------------------------------------------------------------
# Set-parallel record kernel (optionally fused with the conflict scan)
# ---------------------------------------------------------------------------
def _setpar_kernel_body(
    tile_lo, r_blk, nrounds_ref, qhi_ref, qlo_ref, sets_ref, qcls_ref,
    rstart_ref, khi_in, klo_in, occ_in, acc_ref, khi_ref, klo_ref, occ_ref,
):
    """Resolve every set's (short, ordered) query run for one table tile.

    Queries arrive sorted by (rank-within-set, set): round r is a contiguous
    chunk in which each set appears at most once, so one round is a fully
    vectorized [r_blk]-wide probe/insert with no intra-round hazards.
    """
    TILE_S, W = khi_in.shape
    B = qhi_ref.shape[0]
    way_iota = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    rstart = rstart_ref[...]                      # [B + 1] round offsets
    n_rounds = nrounds_ref[0]

    def round_body(r, carry):
        khi, klo, occ = carry
        start = rstart[r]
        end = rstart[r + 1]
        # Static-size window clamped into range; the valid mask trims it to
        # the round's true [start, end) span.
        base = jnp.minimum(start, B - r_blk)
        win = pl.ds(base, r_blk)
        qhi_c = qhi_ref[win]
        qlo_c = qlo_ref[win]
        sets_c = sets_ref[win]
        qcls_c = qcls_ref[win]
        pos = base + jax.lax.iota(jnp.int32, r_blk)
        valid = (pos >= start) & (pos < end)
        row = sets_c - tile_lo
        in_tile = (row >= 0) & (row < TILE_S)
        m = valid & in_tile
        rowc = jnp.clip(row, 0, TILE_S - 1)
        row_hi = khi[rowc]                        # [r_blk, W] gathers
        row_lo = klo[rowc]
        row_occ = occ[rowc]
        # Merge-lattice consult: a same-key hit conflicts only when the
        # matrix row of the query's class has the held class's bit set
        # (occ packs 1 + class; all-SET tables reproduce the old any-hit
        # conflict exactly).
        mrow = matrix_rows(qcls_c)                # [r_blk] matrix rows
        wcls = jnp.maximum(row_occ - 1, 0)
        conflict = jnp.any(
            (row_occ > 0)
            & (row_hi == qhi_c[:, None])
            & (row_lo == qlo_c[:, None])
            & (((mrow[:, None] >> wcls) & 1) == 1),
            axis=1,
        )
        free = row_occ == 0
        has_free = jnp.any(free, axis=1)
        way = jnp.argmax(free, axis=1)            # first free way per set
        accq = m & ~conflict & has_free           # [r_blk]
        sel = (way_iota == way[:, None]) & accq[:, None]
        new_hi = jnp.where(sel, qhi_c[:, None], row_hi)
        new_lo = jnp.where(sel, qlo_c[:, None], row_lo)
        new_occ = jnp.where(sel, 1 + qcls_c[:, None], row_occ)
        # Distinct sets within a round => distinct rows: scatter is race-free.
        # Non-accepted lanes are routed out of range and dropped.
        srow = jnp.where(accq, rowc, TILE_S)
        khi = khi.at[srow].set(new_hi, mode="drop")
        klo = klo.at[srow].set(new_lo, mode="drop")
        occ = occ.at[srow].set(new_occ, mode="drop")
        acc_ref[win] = jnp.where(m, accq.astype(jnp.int32), acc_ref[win])
        return khi, klo, occ

    khi, klo, occ = jax.lax.fori_loop(
        0, n_rounds, round_body, (khi_in[...], klo_in[...], occ_in[...])
    )
    khi_ref[...] = khi
    klo_ref[...] = klo
    occ_ref[...] = occ


def _make_record_kernel(r_blk: int, tile_s: int):
    def kernel(nrounds_ref, qhi_ref, qlo_ref, sets_ref, qcls_ref, rstart_ref,
               khi_in, klo_in, occ_in,
               acc_ref, khi_ref, klo_ref, occ_ref):
        g = pl.program_id(0)

        @pl.when(g == 0)
        def _init_acc():
            # The [B] accept vector is revisited by every cell; cell 0 zeroes
            # it, later cells only overwrite their own sets' positions.
            acc_ref[...] = jnp.zeros_like(acc_ref)

        _setpar_kernel_body(
            g * tile_s, r_blk, nrounds_ref, qhi_ref, qlo_ref, sets_ref,
            qcls_ref, rstart_ref, khi_in, klo_in, occ_in,
            acc_ref, khi_ref, klo_ref, occ_ref,
        )
    return kernel


def _make_fused_kernel(r_blk: int, tile_s: int):
    """Record kernel fused with the §4.3 conflict scan: one pallas_call per
    batch resolves witness accept bits AND master-window conflicts."""
    def kernel(nrounds_ref, qhi_ref, qlo_ref, sets_ref, qcls_ref, rstart_ref,
               whi_ref, wlo_ref, wval_ref,
               khi_in, klo_in, occ_in,
               acc_ref, con_ref, khi_ref, klo_ref, occ_ref):
        g = pl.program_id(0)

        @pl.when(g == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            # Conflict scan touches the whole (tiny) unsynced window, so a
            # single cell computes it; the window stays VMEM-resident.
            # wval packs the window entry's class (0 invalid, else
            # 1 + class); the same matrix consult as the record path.
            qhi = qhi_ref[...]
            qlo = qlo_ref[...]
            wval = wval_ref[...]
            mrow = matrix_rows(qcls_ref[...])
            wcls = jnp.maximum(wval - 1, 0)
            eq = (
                (whi_ref[...][None, :] == qhi[:, None])
                & (wlo_ref[...][None, :] == qlo[:, None])
                & (wval[None, :] > 0)
                & (((mrow[:, None] >> wcls[None, :]) & 1) == 1)
            )
            con_ref[...] = jnp.any(eq, axis=1).astype(jnp.int32)

        _setpar_kernel_body(
            g * tile_s, r_blk, nrounds_ref, qhi_ref, qlo_ref, sets_ref,
            qcls_ref, rstart_ref, khi_in, klo_in, occ_in,
            acc_ref, khi_ref, klo_ref, occ_ref,
        )
    return kernel


def _grid_and_specs(S: int, W: int, B: int, tile_s: int):
    # A non-dividing tile would silently leave table rows uncovered (their
    # queries would all "reject" and their output rows would be garbage).
    assert S % tile_s == 0, f"tile_sets {tile_s} must divide n_sets {S}"
    grid = (S // tile_s,)
    full = lambda shape: pl.BlockSpec(shape, lambda g: tuple(0 for _ in shape))
    tile = pl.BlockSpec((tile_s, W), lambda g: (g, 0))
    return grid, full, tile


@functools.partial(
    jax.jit, static_argnames=("tile_sets", "interpret")
)
def witness_record_setpar_pallas(
    table: WitnessTable,
    qhi_f: jnp.ndarray, qlo_f: jnp.ndarray, sets_f: jnp.ndarray,
    qcls_f: jnp.ndarray, round_start: jnp.ndarray, n_rounds: jnp.ndarray,
    *, tile_sets: int = DEFAULT_TILE_SETS, interpret: bool,
):
    """Set-parallel batched record over prep-sorted queries.

    Inputs must come from ``ops._setpar_prep`` (sorted by (rank, set) with
    round offsets); ``qcls_f`` is the per-query merge-lattice op class in
    the same sorted order.  Returns (accepted-in-sorted-order [B], new
    table).  The table inputs are aliased to the table outputs
    (input_output_aliases); see the module docstring for the exact donation
    contract.
    """
    S, W = table.occ.shape
    (B,) = qhi_f.shape
    tile_s = min(tile_sets, S)
    r_blk = min(B, S)
    grid, full, tile = _grid_and_specs(S, W, B, tile_s)
    out = pl.pallas_call(
        _make_record_kernel(r_blk, tile_s),
        grid=grid,
        in_specs=[
            full((1,)), full((B,)), full((B,)), full((B,)), full((B,)),
            full((B + 1,)),
            tile, tile, tile,
        ],
        out_specs=[full((B,)), tile, tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((S, W), U32),
            jax.ShapeDtypeStruct((S, W), U32),
            jax.ShapeDtypeStruct((S, W), jnp.int32),
        ],
        input_output_aliases={6: 1, 7: 2, 8: 3},
        interpret=interpret,
    )(n_rounds, qhi_f, qlo_f, sets_f, qcls_f.astype(jnp.int32), round_start,
      table.keys_hi, table.keys_lo, table.occ)
    acc, khi, klo, occ = out
    return acc, WitnessTable(khi, klo, occ)


@functools.partial(
    jax.jit, static_argnames=("tile_sets", "interpret")
)
def fastpath_record_scan_pallas(
    table: WitnessTable,
    qhi_f: jnp.ndarray, qlo_f: jnp.ndarray, sets_f: jnp.ndarray,
    qcls_f: jnp.ndarray, round_start: jnp.ndarray, n_rounds: jnp.ndarray,
    w_hi: jnp.ndarray, w_lo: jnp.ndarray, w_valid: jnp.ndarray,
    *, tile_sets: int = DEFAULT_TILE_SETS, interpret: bool,
):
    """Fused fast-path kernel: set-parallel record + conflict scan in ONE
    pallas_call.  Same prep contract as witness_record_setpar_pallas; the
    window (w_hi/w_lo/w_valid) is the master's unsynced-op keyhash window,
    with ``w_valid`` packing each entry's op class (0 invalid, else
    1 + class) and ``qcls_f`` the per-query class, so the in-dispatch
    commutativity decision consults the same merge lattice as the record.

    Returns (accepted [B], conflicts [B], new table), accepted/conflicts in
    sorted order.
    """
    S, W = table.occ.shape
    (B,) = qhi_f.shape
    (U,) = w_hi.shape
    tile_s = min(tile_sets, S)
    r_blk = min(B, S)
    grid, full, tile = _grid_and_specs(S, W, B, tile_s)
    out = pl.pallas_call(
        _make_fused_kernel(r_blk, tile_s),
        grid=grid,
        in_specs=[
            full((1,)), full((B,)), full((B,)), full((B,)), full((B,)),
            full((B + 1,)),
            full((U,)), full((U,)), full((U,)),
            tile, tile, tile,
        ],
        out_specs=[full((B,)), full((B,)), tile, tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((S, W), U32),
            jax.ShapeDtypeStruct((S, W), U32),
            jax.ShapeDtypeStruct((S, W), jnp.int32),
        ],
        input_output_aliases={9: 2, 10: 3, 11: 4},
        interpret=interpret,
    )(n_rounds, qhi_f, qlo_f, sets_f, qcls_f.astype(jnp.int32), round_start,
      w_hi, w_lo, w_valid.astype(jnp.int32),
      table.keys_hi, table.keys_lo, table.occ)
    acc, con, khi, klo, occ = out
    return acc, con, WitnessTable(khi, klo, occ)


# ---------------------------------------------------------------------------
# Sequential reference kernel (pre-refactor design, kept for old-vs-new
# benchmarking and differential tests)
# ---------------------------------------------------------------------------
def _record_seq_kernel(qhi_ref, qlo_ref, khi_in, klo_in, occ_in,
                       acc_ref, khi_ref, klo_ref, occ_ref):
    S, W = khi_in.shape
    set_mask = jnp.uint32(S - 1)
    khi_ref[...] = khi_in[...]
    klo_ref[...] = klo_in[...]
    occ_ref[...] = occ_in[...]
    B = qhi_ref.shape[0]
    way_iota = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)

    def body(b, _):
        qhi = qhi_ref[pl.ds(b, 1)]                       # [1]
        qlo = qlo_ref[pl.ds(b, 1)]
        s = (qlo[0] & set_mask).astype(jnp.int32)
        row = (pl.ds(s, 1), slice(None))
        row_hi = khi_ref[row]                            # [1, W]
        row_lo = klo_ref[row]
        row_occ = occ_ref[row]
        conflict = jnp.any(
            (row_occ == 1) & (row_hi == qhi[0]) & (row_lo == qlo[0])
        )
        free = row_occ == 0
        has_free = jnp.any(free)
        way = jnp.argmax(free)           # first free way
        acc = jnp.logical_and(~conflict, has_free)
        sel = (way_iota == way) & acc
        khi_ref[row] = jnp.where(sel, qhi[0], row_hi)
        klo_ref[row] = jnp.where(sel, qlo[0], row_lo)
        occ_ref[row] = jnp.where(sel, 1, row_occ)
        acc_ref[pl.ds(b, 1)] = acc.astype(jnp.int32).reshape((1,))
        return 0

    jax.lax.fori_loop(0, B, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def witness_record_seq_pallas(
    table: WitnessTable, q_hi: jnp.ndarray, q_lo: jnp.ndarray,
    *, interpret: bool,
):
    """Pre-refactor sequential kernel: the whole batch is one ordered
    fori_loop over a single grid cell.  O(B) serial steps — the throughput
    ceiling fig_fastpath measures the set-parallel design against."""
    S, W = table.occ.shape
    (B,) = q_hi.shape
    out = pl.pallas_call(
        _record_seq_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((S, W), U32),
            jax.ShapeDtypeStruct((S, W), U32),
            jax.ShapeDtypeStruct((S, W), jnp.int32),
        ],
        interpret=interpret,
    )(q_hi.astype(U32), q_lo.astype(U32),
      table.keys_hi, table.keys_lo, table.occ)
    accepted, khi, klo, occ = out
    return accepted, WitnessTable(khi, klo, occ)


# ---------------------------------------------------------------------------
# Transactional probe: all-or-nothing multi-key record, ONE dispatch
# ---------------------------------------------------------------------------
def _record_txn_kernel(qhi_ref, qlo_ref, own_ref, valid_ref,
                       khi_in, klo_in, occ_in,
                       acc_ref, hit_ref, khi_ref, klo_ref, occ_ref):
    """All K keys of one op accept together or none do (§4.2 multi-object
    updates, without the record-then-rollback second dispatch).

    Decision pass (vectorized over K): every key probes the PRE-op table —
    conflict (same-key hit under a foreign rpc, i.e. ``own == 0``) vetoes
    the whole op, and each inserting key must SEAT: ranked among the op's
    earlier same-set inserters, it claims the set's (rank+1)-th free way, so
    two same-set keys of one op land in distinct ways (the old first-free
    placement aliased them and the second write clobbered the first) and
    the op rejects as full when a set cannot seat all of its keys.  Write
    pass (tiny fori_loop over K, predicated on the op-level accept bit):
    non-hit keys insert at their reserved way.
    """
    S, W = khi_in.shape
    K = qhi_ref.shape[0]
    set_mask = jnp.uint32(S - 1)
    qhi = qhi_ref[...]
    qlo = qlo_ref[...]
    own = own_ref[...]
    valid = valid_ref[...]
    khi0 = khi_in[...]
    klo0 = klo_in[...]
    occ0 = occ_in[...]
    sets = (qlo & set_mask).astype(jnp.int32)                  # [K]
    row_hi = khi0[sets]                                        # [K, W]
    row_lo = klo0[sets]
    row_occ = occ0[sets]
    hit = jnp.any(
        (row_occ > 0) & (row_hi == qhi[:, None]) & (row_lo == qlo[:, None]),
        axis=1,
    )
    free = row_occ == 0
    claim = (valid == 1) & ~hit
    k_iota = jax.lax.iota(jnp.int32, K)
    earlier = k_iota[None, :] < k_iota[:, None]                # [K, K] j < k
    rank = jnp.sum(
        ((sets[:, None] == sets[None, :]) & earlier
         & claim[None, :]).astype(jnp.int32),
        axis=1,
    )
    n_free = jnp.sum(free.astype(jnp.int32), axis=1)
    seat = n_free > rank
    cfree = jnp.cumsum(free.astype(jnp.int32), axis=1)
    selw = free & (cfree == (rank + 1)[:, None])
    way = jnp.argmax(selw, axis=1)                             # reserved way
    ok = jnp.where(own == 1, hit | seat, ~hit & seat)
    accepted = jnp.all(ok | (valid == 0))
    write = accepted & (valid == 1) & ~hit
    acc_ref[...] = accepted.astype(jnp.int32).reshape((1,))
    hit_ref[...] = (hit & (valid == 1)).astype(jnp.int32)
    way_iota = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)

    khi_ref[...] = khi0
    klo_ref[...] = klo0
    occ_ref[...] = occ0

    def body(k, _):
        s = sets[k]
        sel = (way_iota == way[k]) & write[k]                  # [1, W]
        row = (pl.ds(s, 1), slice(None))
        khi_ref[row] = jnp.where(sel, qhi[k], khi_ref[row])
        klo_ref[row] = jnp.where(sel, qlo[k], klo_ref[row])
        occ_ref[row] = jnp.where(sel, 1, occ_ref[row])
        return 0

    jax.lax.fori_loop(0, qhi.shape[0], body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def witness_record_txn_pallas(
    table: WitnessTable,
    q_hi: jnp.ndarray, q_lo: jnp.ndarray,
    own: jnp.ndarray, valid: jnp.ndarray,
    *, interpret: bool,
):
    """One-dispatch all-or-nothing record of one op's K (mixed-lane) keys.

    Returns (accepted [1], hit [K], new table): the table outputs alias the
    inputs (same donation contract as the other record kernels) and are
    bit-identical to the inputs when the op rejects — no rollback dispatch
    ever needed.  ``own`` marks keys held under this op's own rpc_id
    (idempotent retry hits, resolved host-side); ``valid`` masks padding.
    """
    S, W = table.occ.shape
    (K,) = q_hi.shape
    out = pl.pallas_call(
        _record_txn_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((1,), jnp.int32),
            jax.ShapeDtypeStruct((K,), jnp.int32),
            jax.ShapeDtypeStruct((S, W), U32),
            jax.ShapeDtypeStruct((S, W), U32),
            jax.ShapeDtypeStruct((S, W), jnp.int32),
        ],
        input_output_aliases={4: 2, 5: 3, 6: 4},
        interpret=interpret,
    )(q_hi.astype(U32), q_lo.astype(U32),
      own.astype(jnp.int32), valid.astype(jnp.int32),
      table.keys_hi, table.keys_lo, table.occ)
    acc, hit, khi, klo, occ = out
    return acc, hit, WitnessTable(khi, klo, occ)


# ---------------------------------------------------------------------------
# GC kernel (order-independent), with the same donation contract
# ---------------------------------------------------------------------------
def _gc_kernel(ghi_ref, glo_ref, khi_in, klo_in, occ_in, occ_ref):
    # occ[s,w] = 0 wherever (hi, lo) matches any gc entry.  G is one gc batch
    # (<= a sync batch), so the [S, W, G] compare cube stays tiny.
    khi = khi_in[...]
    klo = klo_in[...]
    occ = occ_in[...]
    ghi = ghi_ref[...]
    glo = glo_ref[...]
    m = (
        (khi[:, :, None] == ghi[None, None, :])
        & (klo[:, :, None] == glo[None, None, :])
        & (occ[:, :, None] > 0)
    )
    occ_ref[...] = jnp.where(jnp.any(m, axis=-1), 0, occ)


@functools.partial(jax.jit, static_argnames=("interpret",))
def witness_gc_pallas(
    table: WitnessTable, g_hi: jnp.ndarray, g_lo: jnp.ndarray,
    *, interpret: bool,
):
    """Clear synced entries.  The occupancy buffer is aliased in-program
    (input_output_aliases: occ in -> occ out), so the dispatch mutates one
    [S, W] occupancy buffer instead of copying it (module docstring has the
    full donation contract)."""
    S, W = table.occ.shape
    occ = pl.pallas_call(
        _gc_kernel,
        out_shape=jax.ShapeDtypeStruct((S, W), jnp.int32),
        input_output_aliases={4: 0},
        interpret=interpret,
    )(g_hi.astype(U32), g_lo.astype(U32),
      table.keys_hi, table.keys_lo, table.occ)
    return WitnessTable(table.keys_hi, table.keys_lo, occ)


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


def check_smem(kernel: str, lengths) -> None:
    """Raise before the compile if the flat int32 SMEM arrays of
    ``lengths`` (prefetched work items and per-item outputs) overflow v5e's
    SMEM, where Mosaic would refuse with RESOURCE_EXHAUSTED."""
    words = sum(-(-n // SMEM_PAD) * SMEM_PAD for n in lengths)
    if words > SMEM_WORDS - SMEM_RESERVED:
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
    (cleared / empty slots reset to 0); ``do_age=False`` is the rollback
    variant.  Returns (cleared bit per entry [G], new gang table); occ and
    age alias their outputs, key/rpc lanes are untouched.

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

