"""jit'd public wrappers around the witness-gang Pallas kernels.

Each op pads its inputs to a power-of-two bucket on the host (so the jit
cache stays O(log B) across the batch sizes the protocol layer produces),
picks interpret mode automatically (interpret on the CPU; the kernels target
the TPU), launches exactly one jitted program, and returns numpy outputs in
caller order.  The ops, each ONE dispatch:

  * ``gang_record`` — a batch of single-key records, each at its own gang
    lane under its own rpc identity;
  * ``gang_record_groups`` — a batch of per-group all-or-nothing multi-key
    records (MSET, HMSET pairs, transaction legs);
  * ``gang_gc`` — rpc-matched clears plus §4.5 aging of whole lanes;
  * ``gang_fastpath_batch`` — the fused cluster batch: hash -> slot route
    -> ring conflict scan -> ring append -> record at every target shard's
    f witness lanes.

Keys arrive as RAW 64-bit keyhash lanes and are mixed in the jit
(``ref.ref_keyhash2x32``); the host mirrors the same mix with
``ref.np_keyhash2x32``.  See witness_record.py for the kernels' layout and
README.md next to this file for how the protocol layer drives them.
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .ref import (
    GangTable,
    conflict_matrix_np,
    gang_rows,
    matrix_rows,
    np_keyhash2x32,
    ref_conflict_scan,
    ref_gang_gc,
    ref_gang_record,
    ref_keyhash2x32,
)
from .witness_record import gang_gc_pallas, gang_record_pallas, smem_fits

# ---------------------------------------------------------------------------
# Host-side dispatch accounting
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
    counter increments once per wrapper call.  The benchmark's
    ``dispatches_per_batch`` and the one-dispatch tests read it.  It does
    not see launches made outside this module, nor would it catch a second
    pallas_call added inside an impl (the parity tests pin the impl's
    behavior instead).
    """
    from repro.core.telemetry import registry

    return registry().counter(_DISPATCH_COUNTER).value


def reset_dispatch_count() -> None:
    from repro.core.telemetry import registry

    registry().counter(_DISPATCH_COUNTER).reset()


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# Keys hash to one of DEFAULT_N_SLOTS slots (mixed low lane mod n_slots) and
# a slot -> shard table names the owner; ``gang_fastpath_batch`` takes the
# table as a traced input, so a live slot handover never recompiles.  Must
# match repro.core.shard.N_SLOTS (the pure-Python mirror).
DEFAULT_N_SLOTS = 256


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
    Kp = _bucket(K, lo=2)
    Gp = _groups_bucket(G, Kp)
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


def _group_words(G: int, K: int) -> List[int]:
    """The SMEM arrays of a grouped record dispatch (``check_smem``)."""
    return [G * K] * 4 + [G] * 4


#: Groups of this many padded keys or more are wide: the record kernel's
#: body is unrolled over a group's keys, so each wide shape takes seconds to
#: trace and lower.
WIDE_K = 8


@functools.lru_cache(maxsize=None)
def _most_groups(Kp: int) -> int:
    """The most groups, a power of two, of ``Kp`` keys that fit SMEM."""
    g = 4
    while smem_fits(_group_words(2 * g, Kp)):
        g *= 2
    return g


def _groups_bucket(G: int, Kp: int) -> int:
    """Padded group count of a grouped record: the next power of two, or,
    for wide groups, the most that fit SMEM at that width, so each wide
    width compiles one shape.  Padded groups cost the kernel nothing (they
    sort past the last tile)."""
    Gp = _bucket(G, lo=4)
    if Kp >= WIDE_K and Gp <= _most_groups(Kp):
        return _most_groups(Kp)
    return Gp


def record_fits(G: int, K: int) -> bool:
    """Whether one record dispatch of ``G`` groups of at most ``K`` keys
    fits v5e's SMEM after its padding: ``gang_record`` for one key per
    group, ``gang_record_groups`` otherwise (the model ``check_smem``
    raises on)."""
    if K == 1:
        Gp = _bucket(G)
        return smem_fits([Gp] * 8)
    Kp = _bucket(K, lo=2)
    return smem_fits(_group_words(_groups_bucket(G, Kp), Kp))


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
    drops a newer same-key record.  ``aged_lanes`` is an [L] 0/1 mask of
    lanes whose survivors age this round (§4.5); ``do_age=False`` skips the
    aging.  Returns (cleared [G] numpy bit per entry, new table).
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
    "DEFAULT_N_SLOTS", "dispatch_count", "reset_dispatch_count",
    "GangTable", "GangRecordResult", "GangFastPathResult",
    "gang_record", "gang_record_groups", "gang_gc", "gang_fastpath_batch",
    "record_fits", "ref_keyhash2x32", "np_keyhash2x32", "ref_conflict_scan",
    "ref_gang_record", "ref_gang_gc", "gang_rows", "matrix_rows",
    "conflict_matrix_np",
]
