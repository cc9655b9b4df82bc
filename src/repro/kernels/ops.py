"""jit'd public wrappers around the CURP Pallas kernels.

Each op pads/validates shapes, picks interpret mode automatically (interpret
on CPU — the kernels target TPU), and exposes a pytree-friendly API used by
the device-side witness (repro.core.device_witness) and the fast-path
benchmarks.

Fast-path pipeline
------------------
``fastpath_batch`` is the one-dispatch-per-batch op: it fuses

    keyhash2x32 -> shard_route -> witness_record -> conflict_scan

into a single jitted call whose only pallas_call is the fused set-parallel
record+scan kernel (the hash/route/sort prep is plain XLA that fuses around
it).  The per-op path costs 3-4 device dispatches per update (hash, record,
scan, sometimes route); the fused path costs exactly one per *batch*.
``dispatch_count()`` exposes a host-side counter that fig_fastpath uses to
demonstrate the difference.

The set-parallel prep (``_setpar_prep``) buckets a query batch by probed set:
a stable sort by ``lo & (S-1)``, a rank-within-set computation, and a second
stable sort by rank — after which "round" r (the r-th query of every set) is
one contiguous span and the kernel resolves whole rounds vectorized across
sets.  See repro/kernels/witness_record.py for the kernel-side story and the
buffer-donation contract.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .conflict_scan import conflict_scan_pallas
from .keyhash import keyhash2x32_pallas
from .ref import (
    U32,
    GangTable,
    WitnessTable,
    conflict_matrix_np,
    gang_rows,
    matrix_rows,
    np_keyhash2x32,
    ref_conflict_scan,
    ref_gang_gc,
    ref_gang_record,
    ref_keyhash2x32,
    ref_witness_gc,
    ref_witness_record,
    ref_witness_record_txn,
)
from .witness_record import (
    DEFAULT_TILE_SETS,
    _unsort,
    fastpath_record_scan_pallas,
    gang_gc_pallas,
    gang_record_pallas,
    witness_gc_pallas,
    witness_record_seq_pallas,
    witness_record_setpar_pallas,
    witness_record_txn_pallas,
)

# ---------------------------------------------------------------------------
# Host-side dispatch accounting (benchmarks read this; see module docstring)
# ---------------------------------------------------------------------------
# Backed by the telemetry metrics registry ("kernels.dispatches") so the
# flight recorder sees device-program launches next to the protocol counters;
# the three functions below are kept as the stable public API.  The import is
# lazy because repro.core's package __init__ imports this module (device
# witness) — telemetry itself is a leaf with no repro imports.
_DISPATCH_COUNTER = "kernels.dispatches"


def _count_dispatch(n: int = 1) -> None:
    from repro.core.telemetry import registry

    registry().counter(_DISPATCH_COUNTER).inc(n)


def dispatch_count() -> int:
    """Jitted-program launches issued via this module since the last reset.

    Structural accounting, not a device-side trace: each public op wraps
    exactly one jitted program (every prep/pad step is host-side numpy, so
    the jitted call is the only device program a wrapper launches), and the
    counter increments once per wrapper call.  fig_fastpath uses it to show
    the API-level amortization — 3 program launches per op on the per-op
    path vs 1 per *batch* on the fused path.  It does not see launches made
    outside this module, nor would it catch a second pallas_call added
    inside an impl (the parity tests pin the impl's behavior instead).
    """
    from repro.core.telemetry import registry

    return registry().counter(_DISPATCH_COUNTER).value


def reset_dispatch_count() -> None:
    from repro.core.telemetry import registry

    registry().counter(_DISPATCH_COUNTER).reset()


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Slot-table routing (live reconfiguration)
# ---------------------------------------------------------------------------
# Default size of the slot table: keys hash to one of DEFAULT_N_SLOTS slots
# (mixed low lane mod n_slots) and a slot -> shard table names the owner.
# Migration moves SLOTS between shards by editing the table — the hash never
# changes, so only the gather array does.  Must match
# repro.core.shard.N_SLOTS (the pure-Python mirror).
DEFAULT_N_SLOTS = 256


def default_slot_map(n_shards: int, n_slots: int = DEFAULT_N_SLOTS) -> np.ndarray:
    """Round-robin slot -> shard table: slot i is owned by shard i % N.

    For power-of-two shard counts that divide ``n_slots`` this reproduces
    the pre-slot-map ``% n_shards`` placement exactly
    ((h % n_slots) % n == h % n when n | n_slots).
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    return (np.arange(n_slots, dtype=np.int32) % n_shards).astype(np.int32)


def _pad_to(x: jnp.ndarray, m: int, fill=0) -> Tuple[jnp.ndarray, int]:
    n = x.shape[0]
    pad = (-n) % m
    if pad:
        x = jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])
    return x, n


# ---------------------------------------------------------------------------
# Set-parallel prep: bucket the batch by probed set (traced; fuses into the
# surrounding jit)
# ---------------------------------------------------------------------------
def _setpar_prep(n_sets: int, q_hi: jnp.ndarray, q_lo: jnp.ndarray,
                 q_valid: jnp.ndarray | None = None):
    """Sort a query batch into round-contiguous set-parallel order.

    Returns (qhi_f, qlo_f, sets_f, round_start, n_rounds, perm) where
    ``perm`` maps final positions -> original batch positions,
    ``round_start[r]`` is the offset of round r in the final order (round r
    holds every set's r-th query, set-ascending), and ``n_rounds`` is a [1]
    int32 array (the longest per-set run).

    ``q_valid`` marks bucket-padding lanes: invalid queries get the
    out-of-range set id ``n_sets`` and rank B, so they sort to the tail,
    fall beyond ``n_rounds``, and are never touched by the kernel (their
    accept bit stays 0).  Permute any additional per-query arrays with the
    returned ``perm``.
    """
    (B,) = q_hi.shape
    sets = (q_lo & jnp.uint32(n_sets - 1)).astype(jnp.int32)       # [B]
    if q_valid is None:
        valid = jnp.ones((B,), jnp.int32)
    else:
        valid = q_valid.astype(jnp.int32)
        sets = jnp.where(valid == 1, sets, jnp.int32(n_sets))
    order1 = jnp.argsort(sets, stable=True)                        # by set
    sets_s = sets[order1]
    seg_count = jnp.zeros((n_sets,), jnp.int32).at[sets].add(
        valid, mode="drop"
    )
    seg_start = jnp.cumsum(seg_count) - seg_count                  # exclusive
    rank_s = jnp.where(
        sets_s < n_sets,
        jnp.arange(B, dtype=jnp.int32)
        - seg_start[jnp.clip(sets_s, 0, n_sets - 1)],
        jnp.int32(B),
    )
    # Stable sort by rank keeps the set-ascending order within each round.
    order2 = jnp.argsort(rank_s, stable=True)
    perm = order1[order2]
    rank_f = rank_s[order2]
    round_start = jnp.searchsorted(
        rank_f, jnp.arange(B + 1, dtype=jnp.int32)
    ).astype(jnp.int32)
    # Longest VALID run (invalid lanes carry the rank-B sentinel).
    n_rounds = (
        jnp.max(jnp.where(rank_f >= B, jnp.int32(-1), rank_f)) + 1
    ).reshape((1,))
    return q_hi[perm], q_lo[perm], sets_s[order2], round_start, n_rounds, perm


def _bucket(n: int, lo: int = 16) -> int:
    """Next power-of-two >= n (>= lo): stable jit-cache keys across the
    varying batch sizes the protocol layer produces."""
    b = lo
    while b < n:
        b <<= 1
    return b


def _pad_valid(B: int, *arrays):
    """Pad 1-D arrays to the bucket size; returns (padded..., valid).

    Host-side numpy on purpose: padding must happen OUTSIDE the jit (the
    cache keys on shapes, and bucketing is what keeps it O(log B)), and
    doing it in numpy means it costs zero device-op launches — the padded
    arrays enter the device once, at the jitted call's transfer.
    """
    pad = _bucket(B) - B
    valid = np.ones((B + pad,), np.int32)
    valid[B:] = 0
    out = tuple(
        np.concatenate([np.asarray(a), np.zeros((pad,), np.asarray(a).dtype)])
        if pad else np.asarray(a)
        for a in arrays
    )
    return out + (valid,)


@functools.partial(jax.jit, static_argnames=("interpret", "tile_sets"))
def _witness_record_impl(table: WitnessTable, q_hi, q_lo, q_cls, q_valid,
                         interpret: bool, tile_sets: int):
    S, _W = table.occ.shape
    qhi_f, qlo_f, sets_f, rstart, n_rounds, perm = _setpar_prep(
        S, q_hi, q_lo, q_valid
    )
    acc_f, new_table = witness_record_setpar_pallas(
        table, qhi_f, qlo_f, sets_f, q_cls[perm], rstart, n_rounds,
        tile_sets=tile_sets, interpret=interpret,
    )
    return _unsort(perm, acc_f), new_table


# ---------------------------------------------------------------------------
# Public ops
# ---------------------------------------------------------------------------
def keyhash2x32(hi, lo, *, block: int = 1024, interpret: bool | None = None):
    """Batched 64-bit-equivalent key hash as (hi, lo) uint32 lanes."""
    if interpret is None:
        interpret = not _on_tpu()
    _count_dispatch()
    hi = jnp.asarray(hi, U32)
    lo = jnp.asarray(lo, U32)
    hp, n = _pad_to(hi, block)
    lp, _ = _pad_to(lo, block)
    oh, ol = keyhash2x32_pallas(hp, lp, block=block, interpret=interpret)
    return oh[:n], ol[:n]


@functools.partial(jax.jit, static_argnames=("n_slots", "block", "interpret"))
def _shard_route_impl(hi, lo, slot_map, n_slots: int, block: int,
                      interpret: bool):
    _oh, ol = keyhash2x32_pallas(hi, lo, block=block, interpret=interpret)
    slots = (ol % jnp.uint32(n_slots)).astype(jnp.int32)
    return slot_map[slots]


def shard_route(hi, lo, n_shards: int | None = None, *,
                slot_map=None, n_slots: int = DEFAULT_N_SLOTS,
                block: int = 1024,
                interpret: bool | None = None) -> jnp.ndarray:
    """Batched key -> shard placement by SLOT-TABLE GATHER: keyhash2x32 mix,
    low lane mod ``n_slots`` picks a slot, ``slot_map[slot]`` names the
    shard.  Must agree bit-for-bit with the pure-Python
    ``repro.core.shard.SlotRouter`` (same fmix32 chain, same table) so
    device-side routing and protocol-side placement never disagree — on any
    slot map, including mid-migration ones.  Returns [N] int32 shard ids.

    ``slot_map`` is a traced array input, NOT a static arg: editing it (a
    live slot handover) never recompiles.  With only ``n_shards`` given, the
    round-robin ``default_slot_map`` is used — the mod-N compatibility
    placement.
    """
    if interpret is None:
        interpret = not _on_tpu()
    if slot_map is None:
        if n_shards is None:
            raise ValueError("shard_route needs n_shards or slot_map")
        slot_map = default_slot_map(n_shards, n_slots)
    slot_map = jnp.asarray(np.asarray(slot_map, np.int32))
    n_slots = int(slot_map.shape[0])
    _count_dispatch()
    hi = jnp.asarray(hi, U32)
    lo = jnp.asarray(lo, U32)
    hp, n = _pad_to(hi, block)
    lp, _ = _pad_to(lo, block)
    out = _shard_route_impl(hp, lp, slot_map, n_slots, block, interpret)
    return out[:n]


def witness_record(table: WitnessTable, q_hi, q_lo, q_cls=None,
                   *, interpret: bool | None = None,
                   tile_sets: int = DEFAULT_TILE_SETS):
    """Batched record RPCs against a device-side witness table, resolved by
    the set-parallel kernel (order preserved per set; sets in parallel).

    ``q_cls`` is the optional per-query merge-lattice op class
    (repro.core.merge; default SET, which reproduces the classless any-hit
    conflict rule).  Returns (accepted [B] int32, new_table).  Table buffers
    are aliased in-program (no intermediate copy inside the dispatch);
    rebind ``table`` to the returned table (see witness_record.py for the
    exact contract).
    """
    if interpret is None:
        interpret = not _on_tpu()
    _count_dispatch()
    q_hi = np.asarray(q_hi, np.uint32)
    q_lo = np.asarray(q_lo, np.uint32)
    (B,) = q_hi.shape
    q_cls = (np.zeros((B,), np.int32) if q_cls is None
             else np.asarray(q_cls, np.int32))
    q_hi, q_lo, q_cls, valid = _pad_valid(B, q_hi, q_lo, q_cls)
    acc, new_table = _witness_record_impl(
        table, q_hi, q_lo, jnp.asarray(q_cls), valid, interpret, tile_sets
    )
    return acc[:B], new_table


def witness_record_seq(table: WitnessTable, q_hi, q_lo,
                       *, interpret: bool | None = None):
    """Pre-refactor sequential-kernel record path (whole batch = one ordered
    fori_loop).  Kept for old-vs-new benchmarking and differential tests."""
    if interpret is None:
        interpret = not _on_tpu()
    _count_dispatch()
    q_hi = jnp.asarray(q_hi, U32)
    q_lo = jnp.asarray(q_lo, U32)
    return witness_record_seq_pallas(table, q_hi, q_lo, interpret=interpret)


def witness_gc(table: WitnessTable, g_hi, g_lo,
               *, interpret: bool | None = None):
    if interpret is None:
        interpret = not _on_tpu()
    _count_dispatch()
    return witness_gc_pallas(
        table, jnp.asarray(g_hi, U32), jnp.asarray(g_lo, U32),
        interpret=interpret,
    )


def conflict_scan(w_hi, w_lo, w_valid, q_hi, q_lo, q_cls=None,
                  *, block_b: int = 256, block_u: int = 512,
                  interpret: bool | None = None):
    """Commutativity check of B queries vs a U-entry unsynced window.

    ``w_valid`` packs each window entry's merge-lattice class (0 invalid,
    else 1 + class; legacy 0/1 callers get class SET) and ``q_cls`` is the
    optional per-query class — same in-dispatch matrix consult as the
    witness record kernels.
    """
    if interpret is None:
        interpret = not _on_tpu()
    _count_dispatch()
    w_hi = jnp.asarray(w_hi, U32)
    w_lo = jnp.asarray(w_lo, U32)
    w_valid = jnp.asarray(w_valid, jnp.int32)
    q_hi = jnp.asarray(q_hi, U32)
    q_lo = jnp.asarray(q_lo, U32)
    if q_cls is None:
        q_cls = jnp.zeros(q_hi.shape, jnp.int32)
    else:
        q_cls = jnp.asarray(q_cls, jnp.int32)
    whp, u = _pad_to(w_hi, block_u)
    wlp, _ = _pad_to(w_lo, block_u)
    wvp, _ = _pad_to(w_valid, block_u)      # padding is valid=0 => no hits
    qhp, b = _pad_to(q_hi, block_b)
    qlp, _ = _pad_to(q_lo, block_b)
    qcp, _ = _pad_to(q_cls, block_b)
    out = conflict_scan_pallas(
        whp, wlp, wvp, qhp, qlp, qcp,
        block_b=block_b, block_u=block_u, interpret=interpret,
    )
    return out[:b]


# ---------------------------------------------------------------------------
# Fused fast path: hash -> route -> record -> conflict scan, one dispatch
# ---------------------------------------------------------------------------
class FastPathResult(NamedTuple):
    """Result of one fused fast-path batch (all [B], caller order)."""
    accepted: jnp.ndarray    # witness accept bit per op
    conflicts: jnp.ndarray   # master-window conflict bit per op
    shard_ids: jnp.ndarray   # keyhash2x32 placement (int32)
    q_hi: jnp.ndarray        # mixed keyhash lanes — callers extend their
    q_lo: jnp.ndarray        # unsynced window with these on accept
    table: WitnessTable      # updated witness table (donated buffers)


@functools.partial(
    jax.jit, static_argnames=("n_slots", "interpret", "tile_sets")
)
def _fastpath_impl(table, w_hi, w_lo, w_valid, k_hi, k_lo, k_cls, k_valid,
                   slot_map, n_slots: int, interpret: bool, tile_sets: int):
    # Hash: bit-exact with the keyhash2x32 Pallas kernel (same fmix32 chain);
    # inlined here so XLA fuses it with the sort/segment prep.
    qh, ql = ref_keyhash2x32(k_hi, k_lo)
    # Slot-table routing: the gather is plain XLA fused around the single
    # pallas_call; the map is a traced input, so a live slot handover (table
    # edit) never recompiles this program.
    slots = (ql % jnp.uint32(n_slots)).astype(jnp.int32)
    shard_ids = slot_map[slots]
    S, _W = table.occ.shape
    qhi_f, qlo_f, sets_f, rstart, n_rounds, perm = _setpar_prep(
        S, qh, ql, k_valid
    )
    acc_f, con_f, new_table = fastpath_record_scan_pallas(
        table, qhi_f, qlo_f, sets_f, k_cls[perm], rstart, n_rounds,
        w_hi, w_lo, w_valid, tile_sets=tile_sets, interpret=interpret,
    )
    return (_unsort(perm, acc_f), _unsort(perm, con_f), shard_ids,
            qh, ql, new_table)


def fastpath_batch(
    table: WitnessTable, key_hi, key_lo, key_cls=None,
    *, window_hi=None, window_lo=None, window_valid=None,
    n_shards: int = 1, slot_map=None, n_slots: int = DEFAULT_N_SLOTS,
    interpret: bool | None = None,
    tile_sets: int = DEFAULT_TILE_SETS,
) -> FastPathResult:
    """One fused device dispatch for a whole update batch.

    ``key_hi``/``key_lo`` are the RAW 64-bit keyhash lanes (types.keyhash
    split into uint32 halves); the op mixes them (keyhash2x32), derives shard
    placement by slot-table gather (``slot_map``, or the round-robin default
    for ``n_shards``; the map is a traced input, so live slot handovers
    never recompile), resolves witness accept/reject via the set-parallel
    kernel, and checks commutativity against the master's unsynced window —
    all in a single jitted program containing a single pallas_call.

    ``key_cls`` is the optional per-op merge-lattice class (default SET);
    it widens BOTH in-dispatch decisions — witness record and window scan —
    with the same matrix as the Python path.  The window arguments are
    MIXED lanes (as previously returned in ``FastPathResult.q_hi/q_lo``),
    with ``window_valid`` packing the entry class (0 invalid, else
    1 + class; plain 0/1 means class SET); omit them for an empty window.
    Table buffers are donated; rebind to ``result.table``.
    """
    if interpret is None:
        interpret = not _on_tpu()
    if slot_map is None:
        slot_map = default_slot_map(n_shards, n_slots)
    slot_map = np.asarray(slot_map, np.int32)
    n_slots = int(slot_map.shape[0])
    _count_dispatch()
    key_hi = np.asarray(key_hi, np.uint32)
    key_lo = np.asarray(key_lo, np.uint32)
    if window_hi is None or np.asarray(window_hi).shape[0] == 0:
        if window_lo is not None and np.asarray(window_lo).shape[0] > 0:
            raise ValueError("window_lo given without window_hi")
        w_hi = np.zeros((1,), np.uint32)
        w_lo = np.zeros((1,), np.uint32)
        w_val = np.zeros((1,), np.int32)
    else:
        if window_lo is None:
            raise ValueError("window_hi given without window_lo")
        w_hi = np.asarray(window_hi, np.uint32)
        w_lo = np.asarray(window_lo, np.uint32)
        w_val = (np.ones(w_hi.shape, np.int32) if window_valid is None
                 else np.asarray(window_valid, np.int32))
    # Bucket-pad the batch and the window (host-side): the protocol layer
    # produces arbitrary sizes per shard; padding keeps the jit cache to
    # O(log B) entries.  Padded query lanes are masked out end to end;
    # padded window lanes carry valid=0 and can never hit.
    (B,) = key_hi.shape
    key_cls = (np.zeros((B,), np.int32) if key_cls is None
               else np.asarray(key_cls, np.int32))
    key_hi, key_lo, key_cls, k_valid = _pad_valid(B, key_hi, key_lo, key_cls)
    (U,) = w_hi.shape
    pad_u = _bucket(U) - U
    if pad_u:
        w_hi = np.concatenate([w_hi, np.zeros((pad_u,), np.uint32)])
        w_lo = np.concatenate([w_lo, np.zeros((pad_u,), np.uint32)])
        w_val = np.concatenate([w_val, np.zeros((pad_u,), np.int32)])
    acc, con, shard_ids, qh, ql, new_table = _fastpath_impl(
        table, w_hi, w_lo, w_val, key_hi, key_lo, jnp.asarray(key_cls),
        k_valid, jnp.asarray(slot_map), n_slots, interpret, tile_sets,
    )
    return FastPathResult(
        acc[:B], con[:B], shard_ids[:B], qh[:B], ql[:B], new_table
    )


# ---------------------------------------------------------------------------
# Transactional probe: all-or-nothing multi-key record in ONE dispatch
# ---------------------------------------------------------------------------
class TxnProbeResult(NamedTuple):
    """Result of one all-or-nothing multi-key record (ONE dispatch)."""
    accepted: bool           # the whole op accepted (all keys placed/hit)
    hit: jnp.ndarray         # [K] same-key table hit per key (caller order)
    q_hi: jnp.ndarray        # mixed keyhash lanes of the op's keys — callers
    q_lo: jnp.ndarray        # gc with these, extend windows on accept
    table: WitnessTable      # updated iff accepted; bit-identical otherwise


@functools.partial(jax.jit, static_argnames=("interpret",))
def _txn_probe_impl(table, k_hi, k_lo, own, valid, interpret: bool):
    qh, ql = ref_keyhash2x32(k_hi, k_lo)    # fuses with the probe's jit
    acc, hit, new_table = witness_record_txn_pallas(
        table, qh, ql, own, valid, interpret=interpret
    )
    return acc, hit, qh, ql, new_table


def txn_probe(table: WitnessTable, key_hi, key_lo, own=None,
              *, interpret: bool | None = None) -> TxnProbeResult:
    """All-or-nothing record of ONE multi-key op — a single device dispatch
    on BOTH the accept and the reject path (the record-then-rollback scheme
    this replaces paid a second gc dispatch on reject).

    ``key_hi``/``key_lo`` are the RAW 64-bit keyhash lanes of the op's
    (deduplicated) keys; ``own[k] = 1`` marks keys the caller knows are
    already held under this op's rpc_id (idempotent retry), resolved from
    the host mirror.  The kernel leaves the table bit-identical when the op
    rejects, so callers can rebind ``result.table`` unconditionally.
    """
    if interpret is None:
        interpret = not _on_tpu()
    _count_dispatch()
    key_hi = np.asarray(key_hi, np.uint32)
    key_lo = np.asarray(key_lo, np.uint32)
    (K,) = key_hi.shape
    own_arr = (np.zeros((K,), np.int32) if own is None
               else np.asarray(own, np.int32))
    key_hi, key_lo, own_arr, valid = _pad_valid(K, key_hi, key_lo, own_arr)
    acc, hit, qh, ql, new_table = _txn_probe_impl(
        table, key_hi, key_lo, own_arr, valid, interpret
    )
    return TxnProbeResult(
        bool(np.asarray(acc)[0]), hit[:K], qh[:K], ql[:K], new_table
    )


# ---------------------------------------------------------------------------
# Gang ops: stacked witness lanes with kernel-held RIFL/gc state
# ---------------------------------------------------------------------------
# A gang stacks L witness instances (all shards x all witnesses) into one
# device table of [W, L*S] planes (sets along the vector lanes) whose slots
# carry rpc identity and gc age alongside the keyhash lanes
# (repro.kernels.ref.GangTable).  The ops below keep the
# whole serving hot loop at ONE dispatch per *cluster* batch: reason codes
# (1 insert / 2 dup / 3 conflict / 4 full) come back per op so the host
# updates stats/mirrors without consulting device state, and all outputs are
# materialized to numpy HERE — callers slice/index host-side for free instead
# of paying one device program per jnp ``__getitem__``.

class GangRecordResult(NamedTuple):
    """Result of one grouped gang record (all caller order)."""
    reasons: np.ndarray      # [G] reason code per group
    q_hi: np.ndarray         # [G, K] mixed lanes of every key (padding = 0)
    q_lo: np.ndarray         # [G, K]
    table: GangTable         # updated gang table (donated buffers)


def _key_rows(lanes, raw_lo, n_sets: int):
    """Global gang row of each key: ``lane * S`` plus the set picked by the
    RAW low key lane, i.e. the Python witness's ``kh % n_sets`` placement.
    (The MIXED low lane also picks the key's shard slot, so a set taken
    from it would confine each shard's keys to 1/n_shards of the sets.)"""
    return lanes * n_sets + (raw_lo & jnp.uint32(n_sets - 1)).astype(
        jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_sets", "interpret"))
def _gang_groups_impl(table, k_hi, k_lo, k_cls, k_valid, lanes, r_hi, r_lo,
                      g_valid, n_sets: int, interpret: bool):
    G, K = k_hi.shape
    qh, ql = ref_keyhash2x32(k_hi.reshape(-1), k_lo.reshape(-1))
    qh = qh.reshape(G, K)
    ql = ql.reshape(G, K)
    rows = _key_rows(lanes[:, None], k_lo, n_sets)
    rsn, new_table = gang_record_pallas(
        table, qh, ql, rows, k_valid, k_cls, r_hi, r_lo, g_valid,
        n_sets=n_sets, interpret=interpret,
    )
    return rsn, qh, ql, new_table


def gang_record_groups(
    table: GangTable, n_sets: int,
    key_hi, key_lo, key_valid, lanes, rpc_hi, rpc_lo, key_cls=None,
    *, interpret: bool | None = None,
) -> GangRecordResult:
    """Batched per-group all-or-nothing record: ONE dispatch for a whole
    batch of (possibly multi-key) ops.

    ``key_hi``/``key_lo``/``key_valid`` are [G, K] RAW keyhash lanes padded
    to a common key count; ``key_cls`` is the optional [G, K] merge-lattice
    class per key (default SET); ``lanes``/``rpc_hi``/``rpc_lo`` are [G]
    (target witness lane, rpc identity).  Groups resolve sequentially in
    index order with the Python reference's exact placement semantics; dup/
    conflict decisions use the kernel-held rpc lanes (no host mirror
    input).  Rebind ``result.table``.
    """
    if interpret is None:
        interpret = not _on_tpu()
    _count_dispatch()
    key_hi = np.asarray(key_hi, np.uint32)
    key_lo = np.asarray(key_lo, np.uint32)
    key_valid = np.asarray(key_valid, np.int32)
    G, K = key_hi.shape
    key_cls = (np.zeros((G, K), np.int32) if key_cls is None
               else np.asarray(key_cls, np.int32))
    Gp, Kp = _bucket(G, lo=4), _bucket(K, lo=2)
    pad2 = ((0, Gp - G), (0, Kp - K))
    key_hi = np.pad(key_hi, pad2)
    key_lo = np.pad(key_lo, pad2)
    key_valid = np.pad(key_valid, pad2)
    key_cls = np.pad(key_cls, pad2)
    lanes = np.pad(np.asarray(lanes, np.int32), (0, Gp - G))
    rpc_hi = np.pad(np.asarray(rpc_hi, np.uint32), (0, Gp - G))
    rpc_lo = np.pad(np.asarray(rpc_lo, np.uint32), (0, Gp - G))
    g_valid = np.zeros((Gp,), np.int32)
    g_valid[:G] = 1
    rsn, qh, ql, new_table = _gang_groups_impl(
        table, key_hi, key_lo, jnp.asarray(key_cls), key_valid, lanes,
        rpc_hi, rpc_lo, jnp.asarray(g_valid), n_sets, interpret,
    )
    return GangRecordResult(
        np.asarray(rsn)[:G], np.asarray(qh)[:G, :K], np.asarray(ql)[:G, :K],
        new_table,
    )


def _single_key_record(table, qh, ql, raw_lo, k_cls, valid, lanes, r_hi,
                       r_lo, n_sets: int, interpret: bool):
    """[B] single-key records as B groups of one key (traced helper)."""
    rows = _key_rows(lanes, raw_lo, n_sets)
    col = lambda x: x[:, None]
    return gang_record_pallas(
        table, col(qh), col(ql), col(rows), col(valid), col(k_cls),
        r_hi, r_lo, valid, n_sets=n_sets, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("n_sets", "interpret"))
def _gang_record_impl(table, k_hi, k_lo, k_cls, k_valid, lanes, r_hi, r_lo,
                      n_sets: int, interpret: bool):
    qh, ql = ref_keyhash2x32(k_hi, k_lo)
    rsn, new_table = _single_key_record(
        table, qh, ql, k_lo, k_cls, k_valid, lanes, r_hi, r_lo,
        n_sets, interpret,
    )
    return rsn, qh, ql, new_table


def gang_record(
    table: GangTable, n_sets: int, key_hi, key_lo, lanes, rpc_hi, rpc_lo,
    key_cls=None,
    *, interpret: bool | None = None,
):
    """Single-key record over the gang: ONE dispatch for a batch of [B]
    single-key ops (each with its own lane + rpc identity).
    ``key_cls`` is the optional [B] merge-lattice class lane (default SET).

    Returns (reasons [B], q_hi [B], q_lo [B], table) — numpy outputs,
    caller order, same reason codes as ``gang_record_groups``.
    """
    if interpret is None:
        interpret = not _on_tpu()
    _count_dispatch()
    key_hi = np.asarray(key_hi, np.uint32)
    key_lo = np.asarray(key_lo, np.uint32)
    (B,) = key_hi.shape
    key_cls = (np.zeros((B,), np.int32) if key_cls is None
               else np.asarray(key_cls, np.int32))
    key_hi, key_lo, key_cls, lanes, rpc_hi, rpc_lo, valid = _pad_valid(
        B, key_hi, key_lo, key_cls,
        np.asarray(lanes, np.int32),
        np.asarray(rpc_hi, np.uint32), np.asarray(rpc_lo, np.uint32),
    )
    rsn, qh, ql, new_table = _gang_record_impl(
        table, key_hi, key_lo, jnp.asarray(key_cls), valid, lanes,
        rpc_hi, rpc_lo, n_sets, interpret,
    )
    return (np.asarray(rsn)[:B], np.asarray(qh)[:B], np.asarray(ql)[:B],
            new_table)


@functools.partial(jax.jit, static_argnames=("n_sets", "do_age", "interpret"))
def _gang_gc_impl(table, g_hi, g_lo, g_rh, g_rl, g_lane, g_valid, aged_lanes,
                  n_sets: int, do_age: bool, interpret: bool):
    rows = _key_rows(g_lane, g_lo, n_sets)
    g_hi, g_lo = ref_keyhash2x32(g_hi, g_lo)
    aged_rows = jnp.repeat(aged_lanes.astype(jnp.int32), n_sets)
    R = table.occ.shape[1]
    aged_rows = jnp.pad(aged_rows, (0, R - aged_rows.shape[0]))
    return gang_gc_pallas(
        table, g_hi, g_lo, g_rh, g_rl, rows, g_valid, aged_rows,
        n_sets=n_sets, do_age=do_age, interpret=interpret,
    )


def gang_gc(
    table: GangTable, n_sets: int,
    g_hi, g_lo, g_rpc_hi, g_rpc_lo, g_lane, aged_lanes,
    *, do_age: bool = True, interpret: bool | None = None,
):
    """Gang gc, ONE dispatch: rpc-matched clears + in-kernel aging.

    Entry lanes are RAW key lanes (split 64-bit key hashes, as the record
    ops take them) plus the recording rpc identity and target lane; a slot
    clears only on a full (key, rpc, lane) match, so a stale gc entry never
    drops a newer same-key record.  ``aged_lanes`` is an [L] 0/1 mask of lanes whose
    survivors age this round (§4.5); ``do_age=False`` is the rollback
    variant.  Returns (cleared [G] numpy bit per entry, new table).
    """
    if interpret is None:
        interpret = not _on_tpu()
    _count_dispatch()
    g_hi = np.asarray(g_hi, np.uint32)
    (G,) = g_hi.shape
    g_hi, g_lo, g_rh, g_rl, g_lane, valid = _pad_valid(
        G, g_hi, np.asarray(g_lo, np.uint32),
        np.asarray(g_rpc_hi, np.uint32), np.asarray(g_rpc_lo, np.uint32),
        np.asarray(g_lane, np.int32),
    )
    clr, new_table = _gang_gc_impl(
        table, g_hi, g_lo, g_rh, g_rl, g_lane, valid,
        jnp.asarray(np.asarray(aged_lanes, np.int32)),
        n_sets, do_age, interpret,
    )
    return np.asarray(clr)[:G], new_table


# ---------------------------------------------------------------------------
# Fused gang fast path: ONE dispatch for a routed multi-shard batch
# ---------------------------------------------------------------------------
class GangFastPathResult(NamedTuple):
    """Result of one fused cluster-batch dispatch (all caller order)."""
    reasons: np.ndarray      # [B, f] reason code per op per witness copy
    conflicts: np.ndarray    # [B] device master-window conflict bit
    shard_ids: np.ndarray    # [B] slot-table placement
    q_hi: np.ndarray         # [B] mixed keyhash lanes
    q_lo: np.ndarray         # [B]
    table: GangTable         # updated gang table (donated buffers)
    ring_hi: jnp.ndarray     # [NS, CAP] updated unsynced-window rings
    ring_lo: jnp.ndarray     # [NS, CAP]
    counts: np.ndarray       # [NS] post-append live-entry count per ring
    ring_cls: jnp.ndarray    # [NS, CAP] merge-lattice class per ring entry


@functools.partial(jax.jit, static_argnames=("n_slots", "n_sets", "f",
                                             "interpret"))
def _gang_fastpath_impl(table, k_hi, k_lo, k_cls, k_valid, r_hi, r_lo,
                        exec_pred, slot_map, lane_map, ring_hi, ring_lo,
                        ring_cls, tail_slot, count,
                        n_slots: int, n_sets: int, f: int, interpret: bool):
    (B,) = k_hi.shape
    NS, CAP = ring_hi.shape
    qh, ql = ref_keyhash2x32(k_hi, k_lo)
    slots = (ql % jnp.uint32(n_slots)).astype(jnp.int32)
    shard = slot_map[slots]                                        # [B]
    valid = k_valid.astype(jnp.int32)
    qcls = k_cls.astype(jnp.int32)
    mrow = matrix_rows(qcls)                                       # [B]
    # --- device-resident master window: ring conflict scan -----------------
    rhi_b = ring_hi[shard]                                         # [B, CAP]
    rlo_b = ring_lo[shard]
    rcls_b = ring_cls[shard]                                       # [B, CAP]
    c_iota = jax.lax.iota(jnp.int32, CAP)[None, :]
    live = ((c_iota - tail_slot[shard][:, None]) % CAP) < count[shard][:, None]
    ring_hit = jnp.any(
        live & (rhi_b == qh[:, None]) & (rlo_b == ql[:, None])
        & (((mrow[:, None] >> rcls_b) & 1) == 1), axis=1
    )
    # Intra-batch window growth: op i also conflicts with any EARLIER op j
    # of the same shard and key that will itself enter the window — unless
    # the merge lattice says their classes commute (e.g. INCR over INCR).
    app = (exec_pred == 1) & (valid == 1)                          # [B]
    b_iota = jax.lax.iota(jnp.int32, B)
    earlier = b_iota[:, None] > b_iota[None, :]
    same = (
        (qh[:, None] == qh[None, :])
        & (ql[:, None] == ql[None, :])
        & (shard[:, None] == shard[None, :])
        & (((mrow[:, None] >> qcls[None, :]) & 1) == 1)
        & earlier & app[None, :]
    )
    intra_hit = jnp.any(same, axis=1)
    conflicts = ((ring_hit | intra_hit) & (valid == 1)).astype(jnp.int32)
    # --- ring append (executed ops only, in batch order per shard) ---------
    shard_eq = shard[:, None] == shard[None, :]
    rank = jnp.sum(shard_eq & earlier & app[None, :], axis=1)
    slot_pos = (tail_slot[shard] + count[shard] + rank) % CAP
    srow = jnp.where(app, shard, NS)
    ring_hi = ring_hi.at[srow, slot_pos].set(qh, mode="drop")
    ring_lo = ring_lo.at[srow, slot_pos].set(ql, mode="drop")
    ring_cls = ring_cls.at[srow, slot_pos].set(qcls, mode="drop")
    new_count = count + jnp.zeros((NS,), jnp.int32).at[shard].add(
        app.astype(jnp.int32)
    )
    # --- witness record, expanded to every shard's f witness lanes ---------
    lanes_e = lane_map[shard].reshape(-1)                          # [B*f]
    rep = lambda x: jnp.repeat(x, f)
    rsn_flat, new_table = _single_key_record(                      # [B*f]
        table, rep(qh), rep(ql), rep(k_lo), rep(qcls), rep(valid), lanes_e,
        rep(r_hi), rep(r_lo), n_sets, interpret,
    )
    reasons = rsn_flat.reshape(B, f)
    return (reasons, conflicts, shard, qh, ql, new_table,
            ring_hi, ring_lo, new_count, ring_cls)


def gang_fastpath_batch(
    table: GangTable, n_sets: int,
    key_hi, key_lo, rpc_hi, rpc_lo, exec_pred,
    slot_map, lane_map,
    ring_hi, ring_lo, tail_slot, count,
    *, key_cls=None, ring_cls=None, interpret: bool | None = None,
) -> GangFastPathResult:
    """The whole cluster-batch hot loop in ONE device dispatch:

        hash -> slot route -> ring conflict scan (device-resident master
        window, incl. intra-batch growth) -> ring append -> record at every
        target shard's f witness lanes (stacked gang, rpc/age held
        in-kernel)

    ``lane_map`` is [NS, f] (gang lane of witness j of shard s);
    ``ring_hi/ring_lo`` are the [NS, CAP] per-shard unsynced-keyhash rings
    with ``tail_slot``/``count`` the live span (count + appends must fit
    CAP — callers drain first).  ``exec_pred[b]=1`` marks ops that will
    execute at their master (RIFL duplicates don't re-enter the window).
    ``key_cls`` ([B]) and ``ring_cls`` ([NS, CAP]) carry the merge-lattice
    op classes for queries and ring entries (default SET = conflict with
    everything, the legacy behaviour).  Reasons/conflicts come back per op
    as numpy; ring buffers and table stay on device.  Rebind table and
    ring state (including ``ring_cls``) from the result.
    """
    if interpret is None:
        interpret = not _on_tpu()
    slot_map = np.asarray(slot_map, np.int32)
    n_slots = int(slot_map.shape[0])
    lane_map = np.asarray(lane_map, np.int32)
    NS, f = lane_map.shape
    _count_dispatch()
    key_hi = np.asarray(key_hi, np.uint32)
    (B,) = key_hi.shape
    key_cls = (np.zeros((B,), np.int32) if key_cls is None
               else np.asarray(key_cls, np.int32))
    if ring_cls is None:
        ring_cls = jnp.zeros(ring_hi.shape, jnp.int32)
    key_hi, key_lo, key_cls, rpc_hi, rpc_lo, exec_pred, valid = _pad_valid(
        B, key_hi, np.asarray(key_lo, np.uint32), key_cls,
        np.asarray(rpc_hi, np.uint32), np.asarray(rpc_lo, np.uint32),
        np.asarray(exec_pred, np.int32),
    )
    out = _gang_fastpath_impl(
        table, key_hi, key_lo, jnp.asarray(key_cls), valid, rpc_hi, rpc_lo,
        exec_pred, jnp.asarray(slot_map), jnp.asarray(lane_map),
        ring_hi, ring_lo, ring_cls,
        jnp.asarray(np.asarray(tail_slot, np.int32)),
        jnp.asarray(np.asarray(count, np.int32)),
        n_slots, n_sets, f, interpret,
    )
    (reasons, conflicts, shard, qh, ql, new_table, rh, rl, new_count,
     rcls) = out
    return GangFastPathResult(
        np.asarray(reasons)[:B], np.asarray(conflicts)[:B],
        np.asarray(shard)[:B], np.asarray(qh)[:B], np.asarray(ql)[:B],
        new_table, rh, rl, np.asarray(new_count), rcls,
    )


__all__ = [
    "WitnessTable", "FastPathResult", "TxnProbeResult", "keyhash2x32",
    "DEFAULT_N_SLOTS", "default_slot_map",
    "shard_route", "witness_record", "witness_record_seq", "witness_gc",
    "conflict_scan", "fastpath_batch", "txn_probe", "dispatch_count",
    "reset_dispatch_count", "ref_keyhash2x32", "ref_witness_record",
    "ref_witness_gc", "ref_conflict_scan", "ref_witness_record_txn",
    "GangTable", "GangRecordResult", "GangFastPathResult",
    "gang_record", "gang_record_groups", "gang_gc", "gang_fastpath_batch",
    "np_keyhash2x32", "ref_gang_record", "ref_gang_gc", "gang_rows",
    "matrix_rows", "conflict_matrix_np",
]
