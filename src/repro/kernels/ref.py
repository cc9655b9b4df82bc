"""Oracles for the witness-gang Pallas kernels, and the hashes they share.

The protocol's hot spots (DESIGN.md §4) are integer data-structure ops, not
GEMMs, so the TPU adaptation swaps 64-bit scalar code for 32-bit vector-lane
math (TPU VPU lanes are 32-bit):

  * keyhash2x32 — a 64-bit-equivalent key hash carried as (hi, lo) uint32
    lanes, built from two murmur3 fmix32 finalizers with cross-lane mixing.
    ``ref_keyhash2x32`` is the in-jit hash of every gang op and
    ``np_keyhash2x32`` its bit-exact host mirror (``record_many``,
    ``gc_many``, ``FusedBatchDriver``).
  * ``ref_gang_record`` / ``ref_gang_gc`` — pure-Python transcriptions of
    ``repro.core.witness.Witness`` over a ``GangTable``: the oracles the
    record and gc kernels are tested against.
  * ``ref_conflict_scan`` — master-side commutativity check (§4.3): B
    queries vs a U-entry unsynced window -> conflict bitmap; the oracle of
    ``gang_fastpath_batch``'s ring scan.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax.numpy as jnp
import numpy as np

U32 = jnp.uint32
# numpy scalars: they inline as literals (Pallas kernels may not close over
# traced jnp constants).
_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)
_GOLD = np.uint32(0x9E3779B9)
_MIX5 = np.uint32(5)
_MIXC = np.uint32(0xE6546B64)


# ---------------------------------------------------------------------------
# Merge-lattice conflict matrix (repro.core.merge), kernel-consumable form.
#
# Occupancy packs the held op class: occ == 0 is empty, occ == 1 + class is
# occupied (class SET == 0, so legacy all-SET tables are bit-identical with
# the old 0/1 encoding).  A query carries its class as a separate q_cls lane;
# conflict against an occupied way is then ONE bit test:
#     ((CONFLICT_MATRIX[q_cls] >> (occ - 1)) & 1) == 1
# ---------------------------------------------------------------------------
def conflict_matrix_np() -> np.ndarray:
    """The merge-lattice matrix as int32 bitmask rows.  Imported lazily:
    repro.core imports repro.kernels at package init, so a module-level
    back-edge from here into repro.core would cycle."""
    from repro.core.merge import CONFLICT_MATRIX

    return np.asarray(CONFLICT_MATRIX, np.int32)


def matrix_rows(q_cls: jnp.ndarray) -> jnp.ndarray:
    """``mrow[i] = CONFLICT_MATRIX[q_cls[i]]`` without a gather.

    The matrix is a static Python constant, so the lookup unrolls to a
    16-way where-sum over scalar literals — legal inside a Pallas kernel
    body (no dynamic indexing of traced constants) and trivially fused by
    XLA on the jnp oracle path.  Shared by oracles AND kernels so both
    consult the exact same matrix.
    """
    rows = conflict_matrix_np()
    q_cls = q_cls.astype(jnp.int32)
    mrow = jnp.zeros(q_cls.shape, jnp.int32)
    for c in range(rows.shape[0]):
        mrow = mrow + jnp.where(q_cls == c, np.int32(rows[c]), np.int32(0))
    return mrow


def fmix32(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 32-bit finalizer (full avalanche)."""
    x = x.astype(U32)
    x = x ^ (x >> 16)
    x = x * _C1
    x = x ^ (x >> 13)
    x = x * _C2
    x = x ^ (x >> 16)
    return x


def ref_keyhash2x32(hi: jnp.ndarray, lo: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """64-bit-equivalent hash as two cross-mixed 32-bit lanes."""
    hi = hi.astype(U32)
    lo = lo.astype(U32)
    h1 = fmix32(lo + _GOLD)
    h2 = fmix32(hi ^ h1)
    h3 = fmix32(h1 + h2 * _MIX5 + _MIXC)
    return h2, h3


def ref_conflict_scan(
    w_hi: jnp.ndarray, w_lo: jnp.ndarray, w_valid: jnp.ndarray,
    q_hi: jnp.ndarray, q_lo: jnp.ndarray, q_cls: jnp.ndarray = None,
) -> jnp.ndarray:
    """conflicts[b] = any_u(valid[u] & w[u] == q[b] & classes conflict).

    ``w_valid`` packs the window entry's op class (0 = invalid, else
    1 + class; legacy callers passing 0/1 get class SET, which conflicts
    with everything — the original key-equality scan).  ``q_cls`` is the
    per-query class (default SET).  [B] int32.
    """
    if q_cls is None:
        q_cls = jnp.zeros(q_hi.shape, jnp.int32)
    w_valid = w_valid.astype(jnp.int32)
    wcls = jnp.maximum(w_valid - 1, 0)
    mrow = matrix_rows(q_cls)
    eq = (
        (w_hi[None, :] == q_hi[:, None].astype(U32))
        & (w_lo[None, :] == q_lo[:, None].astype(U32))
        & (w_valid[None, :] > 0)
        & (((mrow[:, None] >> wcls[None, :]) & 1) == 1)
    )
    return jnp.any(eq, axis=1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Host-side keyhash mix (numpy): bit-exact with ref_keyhash2x32, zero device
# dispatches.  The protocol layer uses this to mix gc entries / mirror keys.
# ---------------------------------------------------------------------------
def np_fmix32(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32)
    x = x ^ (x >> np.uint32(16))
    x = x * _C1
    x = x ^ (x >> np.uint32(13))
    x = x * _C2
    x = x ^ (x >> np.uint32(16))
    return x


def np_keyhash2x32(hi: np.ndarray, lo: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized numpy mirror of ``ref_keyhash2x32`` (same fmix32 chain)."""
    old = np.seterr(over="ignore")
    try:
        hi = np.asarray(hi, np.uint32)
        lo = np.asarray(lo, np.uint32)
        h1 = np_fmix32(lo + _GOLD)
        h2 = np_fmix32(hi ^ h1)
        h3 = np_fmix32(h1 + h2 * _MIX5 + _MIXC)
    finally:
        np.seterr(**old)
    return h2, h3


# ---------------------------------------------------------------------------
# Gang table: many witness instances stacked into one device-resident array
# ---------------------------------------------------------------------------
# Per-slot reason codes emitted by the gang record kernels.  ACCEPT_* both
# mean RecordStatus.ACCEPTED at the protocol layer; the split keeps host
# stats exact without consulting the host mirror.
REASON_NONE = 0        # padding lane / not processed
REASON_INSERT = 1      # accepted: inserted into a free way
REASON_DUP = 2         # accepted: idempotent duplicate (same key, same rpc)
REASON_CONFLICT = 3    # rejected: same key held under a foreign rpc
REASON_FULL = 4        # rejected: probed set is out of ways


class GangTable(NamedTuple):
    """L stacked witness tables sharing ONE row axis (global set row =
    lane * S + (q_lo & (S-1))), every plane laid out ``[W, R]`` — ways along
    sublanes, rows (sets) along the 128 vector lanes, so a TPU vreg holds 128
    sets instead of one padded 4-way row.  ``R`` is ``L*S`` rounded up to a
    whole 128-lane chunk (``gang_rows``); rows past ``L*S`` stay empty.

    Beside the key lanes and the packed class (``occ``), every slot carries
    the recording op's RIFL identity (rpc_hi = client id, rpc_lo = seq) and a
    §4.5 gc-age counter, so duplicate-retry acceptance, stale-gc
    suppression, and garbage suspicion resolve in-kernel.
    """
    keys_hi: jnp.ndarray   # [W, R] uint32
    keys_lo: jnp.ndarray   # [W, R] uint32
    occ: jnp.ndarray       # [W, R] int32 (0 = empty, else 1 + op class)
    rpc_hi: jnp.ndarray    # [W, R] uint32 (client id)
    rpc_lo: jnp.ndarray    # [W, R] uint32 (sequence number)
    age: jnp.ndarray       # [W, R] int32 (gc rounds survived)

    @staticmethod
    def empty(n_sets: int, n_ways: int, n_lanes: int = 1) -> "GangTable":
        assert n_sets & (n_sets - 1) == 0, "n_sets must be a power of two"
        shape = (n_ways, gang_rows(n_lanes * n_sets))
        return GangTable(*(jnp.zeros(shape, U32 if dt == "u" else jnp.int32)
                           for dt in "uuiuui"))


def gang_rows(n_rows: int) -> int:
    """Row extent of a gang plane: ``n_rows`` padded to whole 128-lane
    chunks."""
    return max(128, -(-n_rows // 128) * 128)


def _gang_np(table: GangTable):
    """Host copies of the planes, row-major ``[R, W]`` for the oracles."""
    return tuple(np.array(np.asarray(a).T) for a in table)


def _gang_jnp(planes) -> GangTable:
    return GangTable(*(jnp.asarray(np.ascontiguousarray(a.T)) for a in planes))


def ref_gang_record(table: GangTable, n_sets: int, groups):
    """Pure-Python oracle for the gang record kernels.

    ``groups`` is a sequence of ``(lane, (rpc_hi, rpc_lo), keys)`` where
    ``keys`` is a list of RAW ``(k_hi, k_lo)`` or ``(k_hi, k_lo, cls)`` lane
    triples (``cls`` defaults to SET) — ONE group is one op (single-key ops
    are groups of size 1).  A key sits at row ``lane * n_sets + (k_lo &
    (n_sets-1))`` and the table holds its keyhash2x32-mixed lanes.  Semantics transcribe
    ``repro.core.witness.Witness.record`` exactly: a same-key hit under a
    foreign rpc conflicts only when the merge lattice says the classes
    conflict, and free ways are RESERVED as the placement loop claims them,
    so two same-set keys of one op take distinct ways.

    Returns (reasons per group, new GangTable) with numpy state.
    """
    khi, klo, occ, rhi, rlo, age = _gang_np(table)
    matrix = conflict_matrix_np()
    W = occ.shape[1]
    reasons = []
    for lane, (rc, rs), keys in groups:
        rc, rs = np.uint32(rc), np.uint32(rs)
        placements = []
        claimed = set()
        reason = None
        for entry in keys:
            kh, kl, cls = entry if len(entry) == 3 else (*entry, 0)
            row = lane * n_sets + (int(kl) & (n_sets - 1))
            qh, ql = (x[0] for x in np_keyhash2x32([kh], [kl]))
            free_way = None
            conflicted = False
            for w in range(W):
                if occ[row, w] > 0:
                    same = khi[row, w] == qh and klo[row, w] == ql
                    if same and rhi[row, w] == rc and rlo[row, w] == rs:
                        free_way = w           # idempotent duplicate hit
                        break
                    if same and (int(matrix[cls]) >> (int(occ[row, w]) - 1)) & 1:
                        conflicted = True
                        break
                elif free_way is None and (row, w) not in claimed:
                    free_way = w
            if conflicted:
                reason = REASON_CONFLICT
                break
            if free_way is None:
                reason = REASON_FULL
                break
            claimed.add((row, free_way))
            placements.append((row, free_way, qh, ql, cls,
                               occ[row, free_way] > 0))
        if reason is None:
            all_dup = all(p[5] for p in placements) and len(placements) > 0
            reason = REASON_DUP if all_dup else REASON_INSERT
            for row, w, qh, ql, cls, _dup in placements:
                khi[row, w] = qh
                klo[row, w] = ql
                occ[row, w] = 1 + cls
                rhi[row, w] = rc
                rlo[row, w] = rs
                age[row, w] = 0
        reasons.append(reason)
    return reasons, _gang_jnp((khi, klo, occ, rhi, rlo, age))


def ref_gang_gc(table: GangTable, n_sets: int, entries, aged_lanes):
    """Oracle for the gang gc kernel.

    ``entries`` is a sequence of ``(lane, (k_hi, k_lo), (rpc_hi, rpc_lo))``
    with RAW key lanes (placed and mixed as in ``ref_gang_record``);
    a slot is cleared only when key AND rpc match (stale-gc suppression
    in-kernel).  Every occupied survivor in ``aged_lanes`` then ages by one
    round (§4.5).  Returns (cleared bit per entry, new GangTable).
    """
    khi, klo, occ, rhi, rlo, age = _gang_np(table)
    W = occ.shape[1]
    cleared = []
    for lane, (kh, kl), (rc, rs) in entries:
        row = lane * n_sets + (int(kl) & (n_sets - 1))
        qh, ql = (x[0] for x in np_keyhash2x32([kh], [kl]))
        hit = False
        for w in range(W):
            if (occ[row, w] > 0 and khi[row, w] == qh and klo[row, w] == ql
                    and rhi[row, w] == np.uint32(rc)
                    and rlo[row, w] == np.uint32(rs)):
                occ[row, w] = 0
                age[row, w] = 0
                hit = True
        cleared.append(hit)
    for lane in aged_lanes:
        rows = slice(lane * n_sets, (lane + 1) * n_sets)
        age[rows] = np.where(occ[rows] > 0, age[rows] + 1, 0)
    return cleared, _gang_jnp((khi, klo, occ, rhi, rlo, age))
