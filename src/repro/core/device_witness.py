"""Kernel-backed CURP witness: the accept/reject hot path runs on device.

``DeviceWitness`` is a drop-in for :class:`repro.core.witness.Witness` whose
conflict/capacity decisions come from the Pallas witness kernels
(repro.kernels).  Since the gang refactor the kernel table holds MORE than
the keyhash lanes: every slot carries the recording op's RIFL identity
(rpc_hi/rpc_lo) and a §4.5 gc-age counter, so

  * duplicate record retries (same rpc_id, same key) are accepted
    idempotently IN-KERNEL (reason code 2),
  * gc entries whose rpc_id doesn't match the held record are ignored
    IN-KERNEL (the clear requires key AND rpc to match), so a stale gc can
    never drop a newer same-key record,
  * survivors age in-kernel per gc round.

The host mirror (mixed keyhash lanes -> (rpc_id, Op)) is demoted to a
RECOVERY-TIME VIEW: it stores the Op objects the device cannot hold (replay
data for ``get_recovery_data``), answers ``commutes_with_all`` for backup
reads, and carries the suspect ages reported to the master — it is never
consulted to decide accept/reject/gc outcomes on the hot path.

Many witness instances share one device-resident **gang**
(:class:`WitnessGang`): all shards' x all witnesses' tables stacked into a
single [n_lanes*S, W] array, so a routed cross-shard batch records at every
target lane in ONE dispatch (repro.kernels.ops.gang_fastpath_batch), a batch
the fused path declines records at every witness of every master in ONE
dispatch (``record_many``), and a drain wave gc's every witness of every
master that synced in it in ONE dispatch (``gc_many``).

Set placement equals the Python witness's: ``kh % n_sets`` on the raw 64-bit
key hash (its low lane masked by S-1), so both backends fill the same sets
and even capacity rejects agree.  The stored key lanes are the
keyhash2x32-mixed ones.

Multi-key ops resolve all-or-nothing through the grouped record kernel
(repro.kernels.gang_record_groups): every key's conflict/capacity verdict is
computed against the pre-op table and writes happen only when the whole op
accepted — ONE dispatch whether the op accepts or rejects, for a whole batch
of multi-key ops at once.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import telemetry
from .merge import CLS_OTHER, conflicts
from .types import GcResp, Op, RecordStatus, RpcId, WitnessMode

_M32 = 0xFFFFFFFF

# Reason codes emitted by the gang kernels (repro.kernels.ref).
_R_INSERT = 1
_R_DUP = 2
_R_CONFLICT = 3
_R_FULL = 4

_REASON_STAT = {
    _R_INSERT: "reason_insert",
    _R_DUP: "reason_dup",
    _R_CONFLICT: "reason_conflict",
    _R_FULL: "reason_full",
}


@dataclass
class _Held:
    rpc_id: RpcId
    request: Op
    gc_age: int = 0
    op_class: int = 0


def _op_pairs(key_hashes, request: Optional[Op]):
    """The (key_hash, class) pairs to place — same derivation rule as
    ``Witness._pairs``: trust the request's lattice expansion only when the
    caller passed its own routing hashes; bare hash lists get the
    conservative OTHER class (un-widened CURP check)."""
    if request is not None and tuple(request.key_hashes()) == tuple(key_hashes):
        return request.hash_classes()
    return tuple((kh, CLS_OTHER) for kh in key_hashes)


def _lanes(khs) -> Tuple[np.ndarray, np.ndarray]:
    hi = np.fromiter(((kh >> 32) & _M32 for kh in khs), np.uint32, len(khs))
    lo = np.fromiter((kh & _M32 for kh in khs), np.uint32, len(khs))
    return hi, lo


def _rpc_lanes(rpc_ids: Sequence[RpcId]) -> Tuple[np.ndarray, np.ndarray]:
    hi = np.fromiter((r[0] & _M32 for r in rpc_ids), np.uint32, len(rpc_ids))
    lo = np.fromiter((r[1] & _M32 for r in rpc_ids), np.uint32, len(rpc_ids))
    return hi, lo


class WitnessGang:
    """Device-resident stack of witness tables (one lane per instance).

    Owns the single :class:`repro.kernels.GangTable` that every attached
    ``DeviceWitness`` records into; lanes are allocated on ``start`` and
    recycled on ``end``.  The lane count grows by doubling (a host-side
    concat of zero rows) so the row space stays a power of two — the gang
    kernels' row tiling requirement.
    """

    def __init__(self, n_sets: int = 1024, n_ways: int = 4,
                 n_lanes: int = 4) -> None:
        from repro.kernels import GangTable  # deferred: keeps jax import lazy

        assert n_lanes & (n_lanes - 1) == 0, "n_lanes must be a power of two"
        self.n_sets = n_sets
        self.n_ways = n_ways
        self.n_lanes = n_lanes
        self.table = GangTable.empty(n_sets, n_ways, n_lanes)
        self._free = list(range(n_lanes - 1, -1, -1))
        self._dirty: set = set()

    def alloc(self) -> int:
        if not self._free:
            self._grow()
        lane = self._free.pop()
        if lane in self._dirty:
            self._zero(lane)
            self._dirty.discard(lane)
        return lane

    def free(self, lane: int) -> None:
        self._dirty.add(lane)
        self._free.append(lane)

    def _grow(self) -> None:
        import jax.numpy as jnp

        from repro.kernels import GangTable, gang_rows

        old = self.n_lanes
        self.n_lanes = old * 2
        rows = gang_rows(self.n_lanes * self.n_sets)
        self.table = GangTable(*(
            jnp.asarray(np.pad(np.asarray(a),
                               ((0, 0), (0, rows - a.shape[1]))))
            for a in self.table
        ))
        self._free.extend(range(self.n_lanes - 1, old - 1, -1))

    def _zero(self, lane: int) -> None:
        # Only occupancy and age gate kernel decisions; stale key/rpc lanes
        # under occ == 0 are never read.
        import jax.numpy as jnp

        occ = np.asarray(self.table.occ).copy()
        age = np.asarray(self.table.age).copy()
        rows = slice(lane * self.n_sets, (lane + 1) * self.n_sets)
        occ[:, rows] = 0
        age[:, rows] = 0
        self.table = self.table._replace(
            occ=jnp.asarray(occ), age=jnp.asarray(age)
        )


class DeviceWitness:
    """One witness instance serving one master; table state lives in one
    lane of a (possibly shared) device-resident gang."""

    SUSPECT_AGE = 3

    def __init__(self, n_sets: int = 1024, n_ways: int = 4,
                 gang: Optional[WitnessGang] = None) -> None:
        self.n_sets = n_sets
        self.n_ways = n_ways
        self.mode = WitnessMode.ENDED
        self.master_id: Optional[int] = None
        self.gang = gang          # shared gang, or private (made on start)
        self.lane: Optional[int] = None
        # mixed (q_hi, q_lo) -> {rpc_id -> metadata}: the recovery-time
        # view.  Nested because the merge lattice lets several MERGEABLE
        # records of one key coexist (one device slot each, one rpc each).
        self._held: Dict[Tuple[int, int], Dict[RpcId, _Held]] = {}
        self.stats = {"accepts": 0, "rejects_conflict": 0, "rejects_full": 0,
                      "rejects_mode": 0, "gc_drops": 0, "kernel_batches": 0,
                      # One count per settled outcome, by kernel reason code.
                      "reason_insert": 0, "reason_dup": 0,
                      "reason_conflict": 0, "reason_full": 0}

    # -- lifecycle (Fig. 4: coordinator -> witness) ---------------------------
    def start(self, master_id: int) -> bool:
        if self.gang is None:
            self.gang = WitnessGang(self.n_sets, self.n_ways, n_lanes=1)
        elif (self.gang.n_sets, self.gang.n_ways) != (self.n_sets,
                                                      self.n_ways):
            raise ValueError("witness geometry does not match its gang")
        if self.lane is None:
            self.lane = self.gang.alloc()
        self.master_id = master_id
        self.mode = WitnessMode.NORMAL
        self._held = {}
        return True

    def end(self) -> None:
        self.mode = WitnessMode.ENDED
        self.master_id = None
        if self.lane is not None:
            self.gang.free(self.lane)
            self.lane = None
        self._held = {}

    # -- client -> witness ----------------------------------------------------
    def record(
        self, master_id: int, key_hashes: Tuple[int, ...], rpc_id: RpcId,
        request: Op,
    ) -> RecordStatus:
        """Single-op record: a group of one through the grouped kernel."""
        if self.mode is not WitnessMode.NORMAL or master_id != self.master_id:
            self.stats["rejects_mode"] += 1
            return RecordStatus.REJECTED
        return self._record_keys(key_hashes, rpc_id, request)

    def record_batch(self, master_id: int, ops: List[Op]) -> List[RecordStatus]:
        """Whole-batch record, ONE kernel dispatch, any mix of group sizes:
        ``record_many`` with this witness alone."""
        return record_many([(self, master_id, ops)])[0]

    def _settle(self, reason: int, keys: List[Tuple[int, int]],
                rpc_id: RpcId, request: Op,
                classes: List[int]) -> RecordStatus:
        """Fold a kernel reason code into protocol status + mirror + stats.

        The mirror write mirrors the Python reference's slot overwrite: on
        any accept (fresh insert or idempotent dup) every key's entry is
        re-stamped with age 0.  Entries nest per rpc so mergeable same-key
        records (each holding its own device slot) coexist in the mirror."""
        self.stats[_REASON_STAT[reason]] += 1
        if reason in (_R_INSERT, _R_DUP):
            for key, cls in zip(keys, classes):
                self._held.setdefault(key, {})[rpc_id] = _Held(
                    rpc_id, request, op_class=cls
                )
            self.stats["accepts"] += 1
            return RecordStatus.ACCEPTED
        if reason == _R_CONFLICT:
            self.stats["rejects_conflict"] += 1
        else:
            self.stats["rejects_full"] += 1
        return RecordStatus.REJECTED

    def _record_keys(self, key_hashes: Tuple[int, ...], rpc_id: RpcId,
                     request: Op) -> RecordStatus:
        """All-or-nothing multi-pair record: ONE grouped-kernel dispatch
        whether the op accepts or rejects (the kernel leaves the table
        bit-identical on reject, so no rollback gc).  Dup/conflict verdicts
        come from the kernel-held rpc lanes — no host mirror input."""
        from repro.kernels import gang_record_groups

        pairs = _op_pairs(key_hashes, request)
        hi, lo = _lanes([kh for kh, _c in pairs])
        kcls = np.fromiter((c for _kh, c in pairs), np.int32, len(pairs))
        res = gang_record_groups(
            self.gang.table, self.n_sets,
            hi[None, :], lo[None, :], np.ones((1, len(pairs)), np.int32),
            np.array([self.lane], np.int32),
            np.array([rpc_id[0] & _M32], np.uint32),
            np.array([rpc_id[1] & _M32], np.uint32),
            kcls[None, :],
        )
        self.gang.table = res.table
        self.stats["kernel_batches"] += 1
        keys = [(int(res.q_hi[0, k]), int(res.q_lo[0, k]))
                for k in range(len(pairs))]
        return self._settle(int(res.reasons[0]), keys, rpc_id, request,
                            [c for _kh, c in pairs])

    # -- master -> witness ----------------------------------------------------
    def gc(self, entries: Tuple[Tuple[int, RpcId], ...]) -> GcResp:
        """Drop synced records (one gang gc dispatch); report suspects."""
        if self.mode is not WitnessMode.NORMAL:
            return GcResp(stale_requests=())
        return gc_many([([self], entries)])[0][0]

    def _apply_gc(self, keys: List[Tuple[int, int]],
                  rpc_ids: List[RpcId], cleared) -> GcResp:
        """Fold per-entry cleared bits into mirror + stats; age survivors
        host-side for suspect reporting (the kernel ages its lanes too —
        that state drives device-side suspicion on TPU)."""
        for (key, rpc_id, clr) in zip(keys, rpc_ids, cleared):
            if not clr:
                continue
            by_rpc = self._held.get(key)
            if by_rpc is not None and rpc_id in by_rpc:
                del by_rpc[rpc_id]
                if not by_rpc:
                    del self._held[key]
            self.stats["gc_drops"] += 1
        stale: List[Op] = []
        seen: set = set()
        for by_rpc in self._held.values():
            for held in by_rpc.values():
                held.gc_age += 1
                if held.gc_age >= self.SUSPECT_AGE and held.rpc_id not in seen:
                    seen.add(held.rpc_id)
                    stale.append(held.request)
        return GcResp(stale_requests=tuple(stale))

    def get_recovery_data(self, master_id: int) -> Tuple[Op, ...]:
        """Irreversibly freeze (recovery mode) and return all held requests."""
        if self.master_id != master_id or self.mode is WitnessMode.ENDED:
            return ()
        self.mode = WitnessMode.RECOVERY
        out: Dict[RpcId, Op] = {}
        for by_rpc in self._held.values():
            for held in by_rpc.values():
                out[held.rpc_id] = held.request  # dedupe multi-key entries
        return tuple(out.values())

    # -- §A.1 consistent reads from backups ------------------------------------
    def commutes_with_all(self, key_hashes: Tuple[int, ...],
                          classes: Optional[Tuple[int, ...]] = None) -> bool:
        """True iff no held record CONFLICTS with any query pair under the
        merge lattice.  Without ``classes`` the query is the conservative
        OTHER class (conflicts with every held class) — the original "no
        held request touches these keys" read check."""
        if self.mode is not WitnessMode.NORMAL:
            return False
        if not key_hashes:
            return True
        from repro.kernels import np_keyhash2x32

        if classes is None:
            classes = (CLS_OTHER,) * len(key_hashes)
        hi, lo = _lanes(list(key_hashes))
        qh, ql = np_keyhash2x32(hi, lo)
        for i, cls in enumerate(classes):
            by_rpc = self._held.get((int(qh[i]), int(ql[i])))
            if by_rpc and any(
                conflicts(h.op_class, cls) for h in by_rpc.values()
            ):
                return False
        return True

    @property
    def occupancy(self) -> int:
        return sum(len(by_rpc) for by_rpc in self._held.values())


# Most gc entries one dispatch takes: the largest power-of-two bucket
# (``gang_gc`` pads to one) under the 43008 that fit v5e's SMEM.
GC_CHUNK = 32768


def gc_many(
    jobs: Sequence[Tuple[Sequence[DeviceWitness],
                         Tuple[Tuple[int, RpcId], ...]]],
) -> List[List[GcResp]]:
    """Gc each job's sync batch at its witnesses, every job of one gang in
    ONE dispatch when the entries fit.

    ``jobs`` are (witnesses, entries): one master's sync round and its
    witnesses, all in NORMAL mode on one gang.  A job's entries are
    deduplicated per (key, rpc) — the Python reference clears a slot once
    however many times the pair appears — and lane-expanded, every witness
    getting its own copy targeting its lane.  Lanes never overlap, so one
    stacked dispatch makes the decisions one dispatch per job would.  Aging
    covers exactly the lanes of jobs with entries; a job without entries
    ages its mirrors on the host only, as its own round would.  More than
    ``GC_CHUNK`` entries split into the fewest dispatches that fit, each a
    contiguous run of the stacked entries; lanes age in the last.  Returns
    one GcResp list per job, in its witnesses' order.
    """
    from repro.kernels import gang_gc, np_keyhash2x32

    gang = jobs[0][0][0].gang
    assert all(w.gang is gang and w.mode is WitnessMode.NORMAL
               for ws, _e in jobs for w in ws), \
        "witnesses must share a gang and be NORMAL"
    uniqs = [list(dict.fromkeys(entries)) for _ws, entries in jobs]
    keys = [_lanes([kh for kh, _rpc in uniq]) for uniq in uniqs]
    rpcs = [_rpc_lanes([rpc for _kh, rpc in uniq]) for uniq in uniqs]
    stacked = [[np.tile(a, len(ws)) for a in (hi, lo, rhi, rlo)]
               + [np.repeat(np.fromiter((w.lane for w in ws), np.int32,
                                        len(ws)), len(hi))]
               for (ws, _e), (hi, lo), (rhi, rlo) in zip(jobs, keys, rpcs)]
    g_hi, g_lo, g_rh, g_rl, g_lane = (
        np.concatenate([s[i] for s in stacked]) for i in range(5))
    N = len(g_hi)
    cleared = []
    if N:
        reg = telemetry.registry()
        aged = np.zeros(gang.n_lanes, np.int32)
        aged[g_lane] = 1
        n = -(-N // GC_CHUNK)
        for c in range(n):
            a, b = N * c // n, N * (c + 1) // n
            clr, gang.table = gang_gc(
                gang.table, gang.n_sets, g_hi[a:b], g_lo[a:b], g_rh[a:b],
                g_rl[a:b], g_lane[a:b],
                aged if c == n - 1 else np.zeros_like(aged))
            cleared.extend(clr.astype(bool).tolist())
            reg.counter("witness.gc_dispatches").inc()
        reg.counter("witness.stacked_gcs").inc()
        reg.counter("witness.stacked_gc_lanes").inc(int(aged.sum()))
    resps: List[List[GcResp]] = []
    at = 0
    for (ws, _e), uniq, (hi, lo) in zip(jobs, uniqs, keys):
        E = len(uniq)
        qh, ql = np_keyhash2x32(hi, lo)
        mixed = list(zip(qh.tolist(), ql.tolist()))
        ids = [rpc for _kh, rpc in uniq]
        job = []
        for w in ws:
            if E:
                w.stats["kernel_batches"] += 1
            job.append(w._apply_gc(mixed, ids, cleared[at:at + E]))
            at += E
        resps.append(job)
    return resps


def _pack(ops: Sequence[Op], pairs, K: int):
    """[G, K] key lanes, validity and classes plus [G] rpc lanes of one op
    list, each op a group of its (key_hash, class) pairs."""
    G = len(ops)
    counts = np.fromiter(map(len, pairs), np.int64, G)
    flat = [pc for p in pairs for pc in p]
    hi, lo = _lanes([kh for kh, _c in flat])
    g = np.repeat(np.arange(G), counts)
    k = np.arange(len(flat)) - np.repeat(np.cumsum(counts) - counts, counts)
    khi = np.zeros((G, K), np.uint32)
    klo = np.zeros((G, K), np.uint32)
    kval = np.zeros((G, K), np.int32)
    kcls = np.zeros((G, K), np.int32)
    khi[g, k] = hi
    klo[g, k] = lo
    kval[g, k] = 1
    kcls[g, k] = np.fromiter((c for _kh, c in flat), np.int32, len(flat))
    rhi, rlo = _rpc_lanes([op.rpc_id for op in ops])
    return khi, klo, kval, kcls, rhi, rlo


def _split(sizes: Sequence[int], widths: Sequence[np.ndarray],
           fits) -> int:
    """The fewest dispatches ``n`` into which the jobs' op lists (``sizes``,
    per-op pair counts ``widths``) split so that every dispatch fits:
    dispatch ``c`` takes ops ``[len*c//n, len*(c+1)//n)`` of every list,
    which keeps each witness's ops in order."""
    total = sum(sizes)
    n = 1
    while True:
        ok = True
        for c in range(n):
            g = k = 0
            for size, wid in zip(sizes, widths):
                a, b = size * c // n, size * (c + 1) // n
                if b > a:
                    g += b - a
                    k = max(k, int(wid[a:b].max()))
            if g and not fits(g, k):
                ok = False
                break
        if ok or n >= total:
            return n
        n += 1


def record_many(
    jobs: Sequence[Tuple[DeviceWitness, int, List[Op]]],
) -> List[List[RecordStatus]]:
    """Record each job's ops at its witness, every job in ONE dispatch when
    it fits the kernel's SMEM.

    ``jobs`` are (witness, master_id, ops) whose witnesses share one gang;
    ops resolve all-or-nothing per op, in op order within each witness.
    Witness lanes never overlap, so one stacked dispatch makes the decisions
    one dispatch per job would.  Each distinct op list is packed once and
    repeated across its witnesses; a dispatch of single-pair ops goes
    through ``gang_record`` with per-item lanes, one with any multi-pair op
    through ``gang_record_groups`` padded to its own widest op.  A batch
    whose work items overflow SMEM (``repro.kernels.record_fits``) splits
    into the fewest dispatches that fit, each taking a contiguous run of
    every job's ops, so every witness still records its ops in order and
    the statuses and table equal one dispatch's.  A witness whose mode or
    master does not match rejects its ops (``rejects_mode``) and takes no
    part.  Packing through the kernels' results is one ``record`` span, the
    fold into statuses one ``settle`` span.  Returns statuses per job, in
    order.
    """
    from repro.kernels import gang_record, gang_record_groups, record_fits

    out: List[Optional[List[RecordStatus]]] = []
    live = []
    for w, master_id, ops in jobs:
        if w.mode is not WitnessMode.NORMAL or master_id != w.master_id:
            w.stats["rejects_mode"] += len(ops)
            out.append([RecordStatus.REJECTED] * len(ops))
        elif not ops:
            out.append([])
        else:
            live.append((len(out), w, ops))
            out.append(None)
    if not live:
        return out  # type: ignore[return-value]
    gang = live[0][1].gang
    assert all(w.gang is gang for _j, w, _ops in live), \
        "witnesses must share a gang"
    reg = telemetry.registry()
    with telemetry.span("record"):
        lists = {id(ops): ops for _j, _w, ops in live}
        pairs = {key: [op.hash_classes() for op in ops]
                 for key, ops in lists.items()}
        widths = {key: np.fromiter(map(len, ps), np.int64, len(ps))
                  for key, ps in pairs.items()}
        K = max(int(wd.max()) for wd in widths.values())
        packed = {key: _pack(ops, pairs[key], K)
                  for key, ops in lists.items()}
        sizes = [len(ops) for _j, _w, ops in live]
        n = _split(sizes, [widths[id(ops)] for _j, _w, ops in live],
                   record_fits)
        chunks = []
        for c in range(n):
            runs = [(size * c // n, size * (c + 1) // n) for size in sizes]
            if all(b == a for a, b in runs):
                continue
            kc = max(int(widths[id(ops)][a:b].max())
                     for (_j, _w, ops), (a, b) in zip(live, runs) if b > a)
            khi, klo, kval, kcls, rhi, rlo = (
                np.concatenate([packed[id(ops)][i][a:b]
                                for (_j, _w, ops), (a, b) in zip(live, runs)])
                for i in range(6))
            lanes = np.repeat(
                np.fromiter((w.lane for _j, w, _ops in live), np.int32,
                            len(live)),
                [b - a for a, b in runs])
            if kc == 1:
                rsn, qh, ql, table = gang_record(
                    gang.table, gang.n_sets, khi[:, 0], klo[:, 0], lanes,
                    rhi, rlo, kcls[:, 0])
                qh, ql = qh[:, None], ql[:, None]
            else:
                rsn, qh, ql, table = gang_record_groups(
                    gang.table, gang.n_sets, khi[:, :kc], klo[:, :kc],
                    kval[:, :kc], lanes, rhi, rlo, kcls[:, :kc])
            gang.table = table
            reg.counter("witness.record_dispatches").inc()
            chunks.append((runs, rsn, qh, ql))
        reg.counter("witness.stacked_records").inc()
        reg.counter("witness.stacked_lanes").inc(len(live))
    with telemetry.span("settle"):
        for _j, w, _ops in live:
            w.stats["kernel_batches"] += 1
        done: Dict[int, List[RecordStatus]] = {j: [] for j, _w, _o in live}
        for runs, rsn, qh, ql in chunks:
            rsn, qh, ql = rsn.tolist(), qh.tolist(), ql.tolist()
            at = 0
            for (j, w, ops), (a, b) in zip(live, runs):
                ps = pairs[id(ops)]
                done[j].extend(
                    w._settle(rsn[at + g - a],
                              list(zip(qh[at + g - a][:len(ps[g])],
                                       ql[at + g - a][:len(ps[g])])),
                              ops[g].rpc_id, ops[g], [c for _kh, c in ps[g]])
                    for g in range(a, b))
                at += b - a
        for j, statuses in done.items():
            out[j] = statuses
    return out  # type: ignore[return-value]
