"""Live-reconfiguration figure: slot-based routing + online shard migration
(repro.core.migration) and the hot-shard auto-split.

Four claims, the first three asserted:

  1. **Zero lost/duplicated writes under a live reshard** — a 2 -> 4 shard
     slot handover runs under continuous client traffic (plus donor- and
     receiver-crash mid-handover variants): a shadow map catches any
     lost/duplicated write, every redirected (SlotMoving) write lands on
     re-issue, and the STRICT multi-key linearizability checker passes over
     the full history (run_migration_scenario).
  2. **Untouched slots never leave the 1-RTT fast path** — the fast-path
     ratio of ops on non-moving slots during the migration stays within 5%
     of the pre-reshard steady state (per-window timeline reported).
  3. **Routing parity** — the device's slot-table gather (the routing
     stage of ``gang_fastpath_batch``) matches the Python ``SlotRouter``
     bit-for-bit on random slot maps (including mid-migration-shaped ones),
     and the round-robin default map matches the legacy mod-N placement for
     power-of-two shard counts.
  4. **Hot-shard auto-split beats the static skew80 line** — per-slot op
     counters from a skewed instant-cluster run feed ``rebalance``; the
     rebalanced slot map re-runs fig_scaling's skew80 scenario in the timed
     sim and must beat the static-placement throughput (the scaling cap the
     ROADMAP called out).
"""
from __future__ import annotations

import argparse

import jax.numpy as jnp
import numpy as np

from repro.core import ShardedCluster, SlotRouter
from repro.core.types import keyhash
from repro.kernels import GangTable, gang_fastpath_batch
from repro.sim import (
    ShardSkewedWorkload,
    run_migration_scenario,
    run_sharded_scenario,
)

from .common import emit


# ---------------------------------------------------------------------------
# 3. routing parity on random slot maps (assertion)
# ---------------------------------------------------------------------------
def device_route(hi, lo, slot_map, max_shards: int = 8) -> np.ndarray:
    """Shard of each RAW key by ``gang_fastpath_batch``'s slot gather, on a
    gang of one 16-set witness lane per shard; no op is predicted to
    execute, so nothing enters the (empty) rings."""
    n = len(hi)
    ring = jnp.zeros((max_shards, 16), jnp.uint32)
    empty = np.zeros(max_shards, np.int32)
    res = gang_fastpath_batch(
        GangTable.empty(16, 4, max_shards), 16, hi, lo,
        np.zeros(n, np.uint32), np.arange(1, n + 1, dtype=np.uint32),
        np.zeros(n, np.int32), slot_map,
        np.arange(max_shards, dtype=np.int32)[:, None], ring, ring, empty,
        empty)
    return res.shard_ids


def check_route_parity(n_keys: int = 400, seed: int = 5) -> int:
    """SlotRouter <-> device slot gather bit-exactness on random slot
    maps."""
    r = np.random.default_rng(seed)
    keys = [f"user{i}" for i in range(n_keys)] + list(range(64))
    khs = [keyhash(k) for k in keys]
    hi = np.array([(h >> 32) & 0xFFFFFFFF for h in khs], np.uint32)
    lo = np.array([h & 0xFFFFFFFF for h in khs], np.uint32)
    cases = 0
    for n_slots in (64, 256):
        for n_shards in (2, 3, 4, 7):
            slot_map = r.integers(0, n_shards, n_slots).astype(np.int32)
            router = SlotRouter(list(slot_map), n_shards=n_shards)
            dev = device_route(hi, lo, slot_map)
            py = np.array([router.shard_of(k) for k in keys])
            np.testing.assert_array_equal(dev, py)
            cases += 1
    # The round-robin default map == legacy mod-N for pow2 shard counts
    # (the pre-slot-map placement this change must not disturb).
    legacy_low = np.array([_mix_low(h) for h in khs], np.uint64)
    for n_shards in (1, 2, 4):
        dev = device_route(hi, lo, SlotRouter.uniform(n_shards).slot_map)
        np.testing.assert_array_equal(dev, (legacy_low % n_shards)
                                      .astype(np.int32))
        cases += 1
    return cases


def _mix_low(kh64: int) -> int:
    from repro.core.shard import _M32, mix2x32

    _, h3 = mix2x32((kh64 >> 32) & _M32, kh64 & _M32)
    return h3


# ---------------------------------------------------------------------------
# 1+2. live reshard timeline under continuous traffic (assertions)
# ---------------------------------------------------------------------------
def live_reshard(smoke: bool = False) -> dict:
    ops = 16 if smoke else 30
    keys = 80 if smoke else 160
    out = {}
    rows = []
    for crash in (None, "donor", "receiver"):
        r = run_migration_scenario(
            n_shards_before=2, n_shards_after=4, n_slots=64,
            ops_per_window=ops, n_keys=keys, crash=crash,
            seed=3 if crash is None else 7,
        )
        tag = crash or "clean"
        assert r.mismatches == 0, f"{tag}: {r.mismatches} lost/dup writes"
        assert r.history_ok, \
            f"{tag}: strict checker violation on {r.offending_key}"
        if crash is not None:
            assert r.resumed >= 1, f"{tag}: crash never hit the handover"
        drop = r.steady_fast - r.migration_fast_untouched
        assert drop <= 0.05, \
            f"{tag}: untouched-slot fast ratio dropped {drop:.3f} (>5%)"
        out[f"{tag}_redirects"] = r.redirects
        out[f"{tag}_fast_drop"] = drop
        if crash is None:
            out["steady_fast"] = r.steady_fast
            out["migration_fast_untouched"] = r.migration_fast_untouched
            out["keys_moved"] = sum(rep.keys_moved for rep in r.reports)
            out["rifl_moved"] = sum(rep.rifl_moved for rep in r.reports)
            rows = [
                {"phase": w["phase"], "t": w["t"], "ops": w["ops"],
                 "fast": (f"{w['fast_frac']:.2f}"
                          if w["fast_frac"] is not None else "-"),
                 "fast_untouched": (f"{w['fast_frac_untouched']:.2f}"
                                    if w["fast_frac_untouched"] is not None
                                    else "-"),
                 "redirects": w["redirects"]}
                for w in r.windows
            ]
    emit(rows, "fig_migration: live 2->4 reshard timeline (clean run)")
    return out


# ---------------------------------------------------------------------------
# 4. hot-shard auto-split vs the static skew80 line (assertion)
# ---------------------------------------------------------------------------
def skew_rebalance(smoke: bool = False) -> dict:
    n_shards = 4
    # Feed the per-slot counters with the SAME skewed workload fig_scaling
    # uses, through a real instant cluster, then auto-rebalance.
    cluster = ShardedCluster(n_shards=n_shards, f=3, seed=7)
    wl = ShardSkewedWorkload(n_shards=n_shards, hot_frac=0.8,
                             n_items=max(4000, 1000 * n_shards), seed=2)
    session = cluster.new_client()
    for _ in range(200 if smoke else 1200):
        cluster.update(session, wl(session))
    loads = cluster.slot_loads()
    hot_share_before = sum(
        loads[s] for s in cluster.router.slots_of_shard(0)
    ) / max(1, sum(loads))
    plan = cluster.rebalance(max_moves=128)
    moved = sum(len(v) for v in plan["moves"].values())
    rebalanced = SlotRouter(list(cluster.router.slot_map),
                            n_shards=n_shards)

    # Timed sim: fig_scaling's skew80 parameters, static vs rebalanced map.
    n_ops, n_clients = (120, 8) if smoke else (1200, 16)
    common = dict(
        n_shards=n_shards, mode="curp", f=3, n_clients=n_clients,
        n_ops=n_ops, seed=7,
        op_factory=ShardSkewedWorkload(
            n_shards=n_shards, hot_frac=0.8,
            n_items=max(4000, 1000 * n_shards), seed=2,
        ),
    )
    static = run_sharded_scenario(**common)
    common["op_factory"] = ShardSkewedWorkload(
        n_shards=n_shards, hot_frac=0.8,
        n_items=max(4000, 1000 * n_shards), seed=2,
    )
    rebal = run_sharded_scenario(router=rebalanced, **common)
    emit([
        {"placement": "static skew80", "kops_per_s":
            static.throughput_ops_per_sec / 1e3,
         "fast_frac": static.fast_fraction},
        {"placement": "auto-rebalanced", "kops_per_s":
            rebal.throughput_ops_per_sec / 1e3,
         "fast_frac": rebal.fast_fraction},
    ], "fig_migration: skew80 throughput, static vs auto-rebalanced slots")
    return {
        "slots_moved": moved,
        "hot_share_before": hot_share_before,
        "skew_static_kops": static.throughput_ops_per_sec / 1e3,
        "skew_rebal_kops": rebal.throughput_ops_per_sec / 1e3,
        "rebal_speedup": (rebal.throughput_ops_per_sec /
                          max(1e-9, static.throughput_ops_per_sec)),
    }


def main(smoke: bool = False) -> dict:
    parity_cases = check_route_parity()
    reshard = live_reshard(smoke=smoke)
    skew = skew_rebalance(smoke=smoke)
    assert skew["slots_moved"] > 0, skew
    assert skew["rebal_speedup"] > 1.0, \
        f"rebalance did not beat static skew80: {skew}"
    derived = {"parity_cases": parity_cases, **reshard, **skew}
    print("derived:", derived)
    return derived


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny counts (CI wiring + atomicity/parity/"
                         "fast-ratio assertions, not a measurement)")
    args = ap.parse_args()
    main(smoke=args.smoke)
