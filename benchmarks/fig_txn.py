"""Transaction figure: the RIFL-identified mini-transaction subsystem
(repro.core.txn) over the per-shard CURP fast paths.

Three claims, measured (the first two asserted, not just reported):

  1. **Atomicity under crashes** — coordinator crashes injected at every
     2PC message stage (prepare-sent / prepared / commit-sent), with and
     without a follow-on participant-master crash: the strict multi-key
     linearizability checker passes and no undecided intent survives
     recovery (run_txn_crash_scenario).
  2. **Single-shard short-circuit** — transactions whose keys land on one
     shard keep the 1-RTT fast path: their fast-path ratio matches the
     fig_scaling level (~1.0 on an uncontended workload), while cross-shard
     transactions pay exactly one extra decide round.
  3. **Abort rate vs contention** — interleaved coordinators over a shrinking
     hot keyset: the intent-lock abort rate rises with contention (reported
     as a sweep).

Throughput view: wall-clock txns/s of all-single-shard vs all-cross-shard
transaction streams (the price of the second round).
"""
from __future__ import annotations

import argparse
import time

from repro.core import ShardedCluster, TxnStatus
from repro.core.txn import abort_op, commit_op, prepare_op
from repro.sim import TXN_CRASH_STAGES, TxnWorkload, run_txn_crash_scenario

from .common import emit


# ---------------------------------------------------------------------------
# 1. atomicity under injected crashes (assertion)
# ---------------------------------------------------------------------------
def check_crash_atomicity(n_txns: int = 12, n_shards: int = 3) -> int:
    """Every 2PC stage x {lazy resolution, participant crash}: strict
    checker green, zero leaked intents.  Raises on violation; returns the
    number of scenarios."""
    cases = 0
    for stage in TXN_CRASH_STAGES:
        for participant_crash in (False, True):
            r = run_txn_crash_scenario(
                stage=stage, n_shards=n_shards, n_txns=n_txns,
                participant_crash=participant_crash, seed=11 + cases,
            )
            assert r.intents_after == 0, \
                f"{stage}: {r.intents_after} intents leaked past recovery"
            assert r.history_ok, \
                f"{stage}: strict checker violation on {r.offending_key}"
            assert r.crashed_decision in ("COMMITTED", "ABORTED"), r
            cases += 1
    return cases


# ---------------------------------------------------------------------------
# 2+throughput. single- vs multi-shard transaction streams
# ---------------------------------------------------------------------------
def txn_throughput(n_txns: int = 200, n_shards: int = 4) -> dict:
    rows = []
    out = {}
    for label, cross in (("single", 0.0), ("cross", 1.0)):
        cluster = ShardedCluster(n_shards=n_shards, f=3, seed=5)
        session = cluster.new_client()
        wl = TxnWorkload(n_shards=n_shards, cross_shard_frac=cross,
                         keys_per_txn=2, seed=9)
        fast = rounds = 0
        t0 = time.perf_counter()
        for _ in range(n_txns):
            writes, reads = wl.next_txn()
            o = cluster.txn(session, writes, reads)
            assert o.status is TxnStatus.COMMITTED
            fast += int(o.fast_path)
            rounds += o.rtts
        wall = time.perf_counter() - t0
        rows.append({
            "stream": label, "txns": n_txns,
            "ktxn_per_s": n_txns / wall / 1e3,
            "mean_rounds": rounds / n_txns,
            "fast_frac": fast / n_txns,
        })
        out[f"{label}_ktxn_per_s"] = n_txns / wall / 1e3
        out[f"{label}_fast_frac"] = fast / n_txns
        out[f"{label}_mean_rounds"] = rounds / n_txns
    emit(rows, "fig_txn: single- vs cross-shard transaction streams")
    return out


# ---------------------------------------------------------------------------
# 3. abort rate vs contention (interleaved coordinators), per conflict policy
# ---------------------------------------------------------------------------
def _interleaved_round(cluster, pairs, policy: str) -> int:
    """Run N coordinators' transactions with prepare legs INTERLEAVED
    leg-by-leg (leg 0 of every txn, then leg 1, ...), under one of two
    intent-conflict policies:

      * ``vote-no``    — any foreign intent refuses the prepare outright
                         (the pre-policy behavior): the txn aborts.
      * ``wound-wait`` — deterministic ordering by txn_id (repro.core.txn):
                         an OLDER (lower-id) txn wounds the younger holder
                         via the safe resolve primitive and retries; a
                         YOUNGER txn parks the leg and retries it after the
                         older holders decide (wait-by-retry).

    Decides in txn_id order (lower first — the deterministic winner), then
    retries parked legs.  Returns the number of aborted transactions.
    """
    from repro.core.txn import resolve_txn

    txns = [
        {"spec": spec, "sess": sess, "votes": {}, "parked": [],
         "dead": False}
        for spec, sess in pairs
    ]
    max_legs = max(len(t["spec"].parts) for t in txns)
    for leg in range(max_legs):
        for t in txns:
            if t["dead"] or leg >= len(t["spec"].parts):
                continue
            part = t["spec"].parts[leg]
            vote = cluster.shards[part.shard_id].txn_prepare(
                t["sess"].session_for(part.shard_id),
                prepare_op(t["spec"], part))
            if vote.granted:
                t["votes"][part.shard_id] = vote
            elif policy == "wound-wait" and vote.error == "TXN_LOCKED" \
                    and vote.blocking is not None:
                if t["spec"].txn_id < vote.blocking.txn_id:
                    # Older: wound the younger holder, retry immediately.
                    resolve_txn(cluster, vote.blocking)
                    vote = cluster.shards[part.shard_id].txn_prepare(
                        t["sess"].session_for(part.shard_id),
                        prepare_op(t["spec"], part))
                    if vote.granted:
                        t["votes"][part.shard_id] = vote
                    else:
                        t["dead"] = True
                else:
                    # Younger: wait-by-retry after the older txns decide.
                    t["parked"].append(part)
            else:
                t["dead"] = True
    aborted = 0
    for t in sorted(txns, key=lambda t: t["spec"].txn_id):
        spec, sess = t["spec"], t["sess"]
        for part in t["parked"]:         # the blockers have decided by now
            vote = cluster.shards[part.shard_id].txn_prepare(
                sess.session_for(part.shard_id), prepare_op(spec, part))
            if vote.granted:
                t["votes"][part.shard_id] = vote
            else:
                t["dead"] = True
        commit = (not t["dead"]
                  and len(t["votes"]) == len(spec.parts)
                  and all(v.granted for v in t["votes"].values()))
        for p in spec.parts:
            op = commit_op(spec, p) if commit else abort_op(spec, p)
            cluster.shards[p.shard_id].txn_decide(
                op, sess.session_for(p.shard_id))
        if not commit:
            aborted += 1
    return aborted


def abort_sweep(n_rounds: int = 40, n_shards: int = 4,
                hot_fracs=(0.0, 0.5, 0.9)) -> tuple:
    """Two coordinators per round with leg-interleaved prepares, swept over
    keyset hotness AND conflict policy: the wound/wait ordering (lower
    txn_id wins, higher waits-by-retry) must cut the abort rate vs the old
    vote-NO-on-any-foreign-intent behavior (ROADMAP follow-on)."""
    rows = []
    rates = {"vote-no": {}, "wound-wait": {}}
    for hot in hot_fracs:
        for policy in ("vote-no", "wound-wait"):
            cluster = ShardedCluster(n_shards=n_shards, f=3, seed=2)
            sa = cluster.new_client()
            sb = cluster.new_client()
            wl = TxnWorkload(n_shards=n_shards, cross_shard_frac=1.0,
                             keys_per_txn=2, hot_frac=hot, hot_items=2,
                             seed=3)
            aborted = 0
            for _ in range(n_rounds):
                wa, _ = wl.next_txn()
                wb, _ = wl.next_txn()
                aborted += _interleaved_round(
                    cluster,
                    [(sa.txn_spec(wa), sa), (sb.txn_spec(wb), sb)],
                    policy,
                )
            assert not any(g.master.store.txn_intents()
                           for g in cluster.shards)
            rate = aborted / (2 * n_rounds)
            rates[policy][hot] = rate
            rows.append({"policy": policy, "hot_frac": hot,
                         "rounds": n_rounds, "abort_rate": rate})
    emit(rows, "fig_txn: abort rate vs contention (interleaved 2PCs, "
               "vote-no vs wound-wait)")
    return rows, rates


# ---------------------------------------------------------------------------
# 4. timed 2PC: concurrent prepare fan-out vs sequential vs per-shard mset
# ---------------------------------------------------------------------------
def timed_rounds(n_txns: int = 60, span: int = 3) -> dict:
    """True 2-round latency in the discrete-event transport: the fan-out
    coordinator (prepare legs concurrent) must beat the sequential baseline
    and cost ~one extra round over the non-atomic per-shard mset."""
    from repro.sim import run_timed_txn_scenario

    out = {}
    rows = []
    for mode in ("mset", "fanout", "sequential"):
        t = run_timed_txn_scenario(mode=mode, n_shards=4, span=span,
                                   n_txns=n_txns, n_clients=2, seed=6)
        rows.append({"mode": mode, "span": span, "mean_us": t.mean_us,
                     "p50_us": t.p50_us, "p99_us": t.p99_us,
                     "committed": t.committed, "aborted": t.aborted})
        out[f"timed_{mode}_us"] = t.mean_us
    emit(rows, "fig_txn: timed 2PC latency (fan-out vs sequential vs mset)")
    out["fanout_speedup_vs_seq"] = (out["timed_sequential_us"]
                                    / max(1e-9, out["timed_fanout_us"]))
    return out


def main(smoke: bool = False) -> dict:
    crash_cases = check_crash_atomicity(n_txns=8 if smoke else 12)
    thr = txn_throughput(n_txns=40 if smoke else 200)
    # Acceptance: single-shard txns keep the 1-RTT fast-path ratio
    # fig_scaling shows for uncontended uniform writes (~1.0).
    assert thr["single_fast_frac"] >= 0.95, thr
    assert thr["single_mean_rounds"] <= 1.05, thr
    assert thr["cross_mean_rounds"] >= 2.0, thr

    timed = timed_rounds(n_txns=20 if smoke else 60)
    # The fan-out coordinator's prepare round is concurrent: a 3-leg txn
    # must be well under the sequential per-leg baseline.
    assert timed["timed_fanout_us"] < timed["timed_sequential_us"], timed

    _rows, rates = abort_sweep(n_rounds=12 if smoke else 40)
    hots = sorted(rates["vote-no"])
    hottest = hots[-1]
    # Wound/wait must not abort MORE at any contention level, and must
    # strictly cut aborts at the hottest setting.
    for h in hots:
        assert rates["wound-wait"][h] <= rates["vote-no"][h], rates
    assert rates["wound-wait"][hottest] < rates["vote-no"][hottest], rates
    derived = {
        "crash_cases": crash_cases,
        **thr,
        **timed,
        **{f"abort_rate_hot{h}": rates["vote-no"][h] for h in hots},
        **{f"ww_abort_rate_hot{h}": rates["wound-wait"][h] for h in hots},
        "abort_monotone": int(rates["vote-no"][hots[0]]
                              <= rates["vote-no"][hottest]),
        "ww_abort_cut": (rates["vote-no"][hottest]
                         - rates["wound-wait"][hottest]),
    }
    print("derived:", derived)
    return derived


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny counts (CI wiring + atomicity "
                         "assertions, not a measurement)")
    args = ap.parse_args()
    d = main(smoke=args.smoke)
    if not args.smoke:
        assert d["abort_monotone"] == 1, \
            f"abort rate not monotone in contention: {d}"
