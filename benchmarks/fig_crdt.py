"""fig_crdt: fast-path fraction vs hot-key skew under the CRDT-CURP merge
lattice.

Classic CURP treats any same-key pair of updates as a conflict, so a
contended counter (every client INCRing one hot key) collapses onto the
2-RTT sync path exactly when the fast path matters most.  The merge lattice
(repro.core.merge) widens commutativity per op CLASS: INCR/INCR, SADD/SADD,
APPEND/APPEND, MAX/MAX, and HMSETs on disjoint fields merge deterministically
and therefore keep the 1-RTT fast path, while SET/anything still conflicts.

Scenarios (every history runs through the merge-aware STRICT Wing&Gong
checker — widening commutativity must not widen observable behaviour):

  * skew sweep — fast-path fraction vs probability of hitting the ONE hot
    key, INCR (mergeable) vs SET (plain).  At skew=1.0 the INCR series must
    keep >=0.95 fast-path while plain SET collapses to <=0.2.
  * merge classes — SADD / APPEND / MAX hot-key workloads at skew=1.0 stay
    fast for the same reason.
  * HMSET fields — per-field subkeys make disjoint-field HMSETs on one key
    commute (fast) while same-field HMSETs conflict (slow): commutativity is
    decided at field granularity, not key granularity.
  * parity — the Pallas conflict decision is bit-exact with Python:
    CONFLICT_MATRIX rows == scalar ``conflicts`` over all 16x16 class pairs,
    and the served record kernel (``gang_record``) matches the
    ``ref_gang_record`` oracle on a collision-heavy batch mixing INCR/INCR
    stacks with SET/INCR conflicts (reason codes AND resulting table planes
    compared bit-for-bit).
"""
from __future__ import annotations

import sys

import numpy as np

from repro.core.client import ClientSession
from repro.core.merge import N_CLASSES, conflicts
from repro.core.types import Op
from repro.kernels import (
    GangTable,
    conflict_matrix_np,
    gang_record,
    ref_gang_record,
)
from repro.sim import HotKeyWorkload, SimParams, check_linearizable_strict, run_scenario

from .common import emit

# Small sync batches + deep ways: the adversarial regime where mergeable
# records must STACK in one witness set between syncs (a shallow table would
# mask lattice rejects behind capacity rejects).
CRDT = SimParams(sync_batch=8, witness_ways=16)

SKEWS = (0.0, 0.5, 0.9, 1.0)


def _run(kind: str, skew: float, n_ops: int, seed: int):
    r = run_scenario(mode="curp", f=1, n_clients=4, n_ops=n_ops, params=CRDT,
                     op_factory=HotKeyWorkload(skew=skew, kind=kind, seed=seed),
                     seed=seed)
    ok, blame = check_linearizable_strict(r.history)
    assert ok, (
        f"fig_crdt {kind}@skew={skew}: merge-aware strict checker failed "
        f"(key={blame!r}) — deterministic merge diverged from a legal "
        f"linearization"
    )
    return r


def hmset_factory(disjoint: bool):
    """Every client HMSETs the SAME key; ``disjoint`` writes a fresh field
    per op (all ops commute via field subkeys even though the key is shared
    — key-granular CURP would serialize every one of them) vs one shared
    field, where same-field last-wins order makes every pair conflict."""
    seq = [0]

    def factory(session: ClientSession) -> Op:
        seq[0] += 1
        field = f"f{session.client_id}_{seq[0]}" if disjoint else "f0"
        return session.op_hmset("hobj", [(field, "x" * 8)])

    return factory


def _run_factory(label: str, factory, n_ops: int, seed: int):
    r = run_scenario(mode="curp", f=1, n_clients=4, n_ops=n_ops, params=CRDT,
                     op_factory=factory, seed=seed)
    ok, blame = check_linearizable_strict(r.history)
    assert ok, f"fig_crdt {label}: strict checker failed (key={blame!r})"
    return r


def check_parity(n_queries: int = 256, seed: int = 7) -> int:
    """Python<->Pallas conflict-decision parity, bit-exact.

    1. matrix encoding: every (a, b) of the 16x16 CONFLICT_MATRIX row plane
       must equal the scalar ``conflicts`` predicate the Python witness uses.
    2. record kernel: ``gang_record`` vs the ``ref_gang_record`` oracle on
       a collision-heavy classed batch (8 hot keys, 64 sets, INCR stacks +
       SET/INCR mixes, one rpc per record) — reason codes and all six table
       planes equal.
    """
    rows = conflict_matrix_np()
    for a in range(N_CLASSES):
        for b in range(N_CLASSES):
            assert bool((int(rows[a]) >> b) & 1) == conflicts(a, b), (
                f"matrix/scalar divergence at classes ({a}, {b})"
            )

    rng = np.random.default_rng(seed)
    # 8 distinct (hi, lo) pairs -> heavy same-set collisions at 64 sets.
    base_hi = rng.integers(0, 2 ** 32, size=8, dtype=np.uint32)
    base_lo = rng.integers(0, 2 ** 32, size=8, dtype=np.uint32)
    pick = rng.integers(0, 8, size=n_queries)
    q_hi = base_hi[pick]
    q_lo = base_lo[pick]
    # Mix mergeable INCR runs with plain SETs and DELs on the same keys.
    q_cls = rng.choice(np.array([0, 1, 2, 2, 2, 5], dtype=np.int32),
                       size=n_queries)
    table = GangTable.empty(64, 16)
    rpc_lo = np.arange(1, n_queries + 1, dtype=np.uint32)
    rsn_ref, t_ref = ref_gang_record(table, 64, [
        (0, (0, int(rpc_lo[i])), [(int(q_hi[i]), int(q_lo[i]), int(q_cls[i]))])
        for i in range(n_queries)])
    rsn_dev, _qh, _ql, t_dev = gang_record(
        table, 64, q_hi, q_lo, np.zeros(n_queries, np.int32),
        np.zeros(n_queries, np.uint32), rpc_lo, q_cls)
    assert np.array_equal(np.asarray(rsn_ref), np.asarray(rsn_dev)), (
        "reason codes diverge: Pallas record kernel vs oracle"
    )
    for name, a, b in zip(GangTable._fields, t_ref, t_dev):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            f"table plane {name} diverges"
    n_acc = int(np.isin(np.asarray(rsn_ref), (1, 2)).sum())
    # The batch is built to exercise both verdicts; an all-accept or
    # all-reject run means the collision setup regressed.
    assert 0 < n_acc < n_queries, (
        f"degenerate parity batch: {n_acc}/{n_queries} accepted"
    )
    return n_acc


def main(n_ops: int = 300) -> dict:
    rows = []
    derived = {}

    for kind in ("INCR", "SET"):
        for skew in SKEWS:
            r = _run(kind, skew, n_ops, seed=11)
            ff = r.fast_fraction
            rows.append({"kind": kind, "skew": skew, "fast_frac": round(ff, 4)})
            derived[f"{kind.lower()}_fastfrac_skew{skew:g}"] = ff
    for kind in ("SADD", "APPEND", "MAX"):
        r = _run(kind, 1.0, n_ops, seed=13)
        ff = r.fast_fraction
        rows.append({"kind": kind, "skew": 1.0, "fast_frac": round(ff, 4)})
        derived[f"{kind.lower()}_fastfrac_skew1"] = ff
    for label, disjoint in (("hmset_disjoint", True), ("hmset_samefield", False)):
        r = _run_factory(label, hmset_factory(disjoint), n_ops, seed=17)
        ff = r.fast_fraction
        rows.append({"kind": label, "skew": 1.0, "fast_frac": round(ff, 4)})
        derived[f"{label}_fastfrac"] = ff

    derived["parity_accepted"] = check_parity()
    derived["parity_ok"] = 1

    # The tentpole claim: the merge lattice keeps the hot counter on the
    # 1-RTT fast path where classic (SET-conflict) CURP collapses.
    incr1 = derived["incr_fastfrac_skew1"]
    set1 = derived["set_fastfrac_skew1"]
    assert incr1 >= 0.95, f"hot INCR counter fell off the fast path: {incr1}"
    assert set1 <= 0.2, f"plain SET should collapse at skew 1.0: {set1}"
    for kind in ("sadd", "append", "max"):
        v = derived[f"{kind}_fastfrac_skew1"]
        assert v >= 0.9, f"merge class {kind} fell off the fast path: {v}"
    hd = derived["hmset_disjoint_fastfrac"]
    hs = derived["hmset_samefield_fastfrac"]
    assert hd >= 0.9, f"disjoint-field HMSETs should commute: {hd}"
    assert hs <= 0.35, f"same-field HMSETs should conflict: {hs}"
    # Widening must be monotone in skew for the mergeable series: more
    # contention must NOT lose fast-path share (that is the whole point).
    assert incr1 >= derived["incr_fastfrac_skew0"] - 0.05, (
        "INCR fast fraction degraded with skew"
    )

    emit(rows, "fig_crdt: fast-path fraction vs hot-key skew")
    print("derived:", {k: (round(v, 4) if isinstance(v, float) else v)
                       for k, v in derived.items()})
    return derived


if __name__ == "__main__":
    main(n_ops=60 if "--smoke" in sys.argv[1:] else 300)
