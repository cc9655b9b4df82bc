"""Stacked drain waves against a shard-by-shard drain.

``repro.core.shard._drain_many`` syncs every master that needs it, then gc's
the witnesses of all of them in ONE stacked ``gc_many`` dispatch, then runs
each master's §4.5 stale retries.  Here each case is served twice on device
witnesses (Pallas kernels in interpret mode): once as the program does it,
and once with every group drained alone, as before stacking.  The outcome
rows, all six gang planes, every witness's cleared bits and reported stale
requests, each witness's held mirror, the masters' stores and stats and the
backups' logs must be equal.  Cases: fused-path SET batches, per-shard
one-field HMSET batches, a dropped witness, a wave split into several
dispatches (the chunk limit patched small), and TPC-C transaction batches.
"""
from __future__ import annotations

import json
import random
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import repro.core.device_witness as dwm  # noqa: E402
import repro.core.fastbatch as fastbatch  # noqa: E402
import repro.core.shard as shard  # noqa: E402
from repro.core import ShardedCluster, WitnessGeometry, telemetry  # noqa: E402

_drain_many = shard._drain_many
COUNTERS = ("witness.gc_dispatches", "witness.stacked_gcs",
            "witness.stacked_gc_lanes")


def _one_by_one(groups):
    """The drain before stacking: each group's whole drain by itself."""
    for g in groups:
        _drain_many([g])


def _kv_batches(c, s, hmset: bool):
    """Seven batches over 4 masters with repeated keys (conflict syncs), a
    RIFL retry of an op whose witness records were already gc'd (a record
    no sync collects, so it ages into a §4.5 suspect), and enough later
    batches for it to be reported."""
    rng = random.Random(5)
    seen, rows = [], []
    for r in range(7):
        ops = []
        for _ in range(24):
            k = f"k{rng.randrange(40)}"
            ops.append(s.op_hmset(k, ((f"f{rng.randrange(3)}", r),))
                       if hmset else s.op_set(k, f"v{r}"))
        if r == 2:
            ops[1] = seen[3]
        seen.extend(ops)
        rows += [(o.value, o.rtts, o.fast_path, o.synced_path,
                  o.witness_accepts) for o in c.update_batch(s, ops)]
    return rows


def _kv(hmset: bool, dropped: bool = False):
    def drive():
        c = ShardedCluster(n_shards=4, f=3, witness_backend="device", seed=7,
                           sync_batch=4, geometry=WitnessGeometry(64, 4))
        if dropped:
            c.shards[2].witness_drop(1)
        s = c.new_client()
        return c, _kv_batches(c, s, hmset)
    return drive


def _tpcc():
    from chipbench import loops
    from chipbench.kinds import tpcc as kind_mod
    from chipbench.traffic.tpcc import Generator

    cfg = json.loads((ROOT / "chipbench/configs/tpcc-16w-f3.json").read_text())
    cfg = dict(cfg, masters=4, warehouses=2, witness={"sets": 32, "ways": 4},
               scale={"districts": 3, "customers": 30, "items": 200,
                      "d_next_o_id": 3001})
    tr = json.loads(
        (ROOT / "chipbench/traffic/neworder_payment.closed.json").read_text())
    # Orders of 1-2 lines keep every record group within 8 pairs, which the
    # interpreted record kernel compiles in seconds.
    tr = dict(tr, batch=16, ol_cnt=[1, 2], remote_line=0.15,
              remote_payment=0.3)
    gen = Generator(tr, cfg, 14)
    kind = kind_mod.Kind()
    w = cfg["witness"]
    c = ShardedCluster(n_shards=cfg["masters"], f=cfg["f"],
                       sync_batch=cfg["sync_batch"], witness_backend="device",
                       n_slots=cfg["slots"],
                       geometry=WitnessGeometry(w["sets"], w["ways"]))
    keys, values = gen.snapshot()
    kind.snapshot(c, cfg, keys, values)
    server = loops.Server(c, kind, loops.no_span)
    win = loops.Window()
    for i in range(2):
        server.updates(win, gen.batch(i), 0.0)
    return c, [row for act in win.actions for row in act[2]]


CASES = {
    "fused-set": (_kv(hmset=False), None),
    "per-shard-hmset": (_kv(hmset=True), None),
    "dropped-witness": (_kv(hmset=True, dropped=True), None),
    "chunked": (_kv(hmset=True), 40),
    "tpcc": (_tpcc, None),
}


def _serve(drive, stacked: bool, chunk):
    """Serve one case; returns what must match, the registry's gc counters
    over the run, and the cluster."""
    gcs = {}
    real_apply = dwm.DeviceWitness._apply_gc

    def apply(w, keys, rpcs, cleared):
        resp = real_apply(w, keys, rpcs, cleared)
        gcs.setdefault(w.lane, []).append(
            (list(keys), list(rpcs), list(cleared),
             [op.rpc_id for op in resp.stale_requests]))
        return resp

    reg = telemetry.registry()
    before = [reg.counter(n).value for n in COUNTERS]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(dwm.DeviceWitness, "_apply_gc", apply)
        if not stacked:
            mp.setattr(shard, "_drain_many", _one_by_one)
            mp.setattr(fastbatch, "_drain_many", _one_by_one)
        if chunk is not None:
            mp.setattr(dwm, "GC_CHUNK", chunk)
        c, rows = drive()
        c.sync_all()
    counts = [reg.counter(n).value - b for n, b in zip(COUNTERS, before)]
    planes = [np.asarray(a) for a in c.gang.table]
    mirrors = {w.lane: {k: {r: (h.gc_age, h.op_class, h.request.rpc_id)
                            for r, h in by.items()}
                        for k, by in w._held.items()}
               for g in c.shards for w in g.witnesses}
    stores = [(g.master.store.snapshot(), dict(g.master.stats))
              for g in c.shards]
    logs = [[[(e.op.rpc_id, repr(e.result)) for e in b.log]
             for b in g.backups] for g in c.shards]
    return (rows, planes, gcs, mirrors, stores, logs), counts, c


@pytest.mark.parametrize("case", list(CASES))
def test_stacked_drain_matches_shard_by_shard(case):
    drive, chunk = CASES[case]
    got, counts, c = _serve(drive, True, chunk)
    want, _counts, _c = _serve(drive, False, None)
    rows, planes, gcs, mirrors, stores, logs = got
    assert rows == want[0]
    assert len(planes) == 6
    for a, b in zip(planes, want[1]):
        np.testing.assert_array_equal(a, b)
    assert gcs == want[2]
    assert mirrors == want[3]
    assert stores == want[4]
    assert logs == want[5]

    # The cases reach what they are meant to: clears, stacked waves of
    # several masters, and (kv cases) a reported §4.5 suspect.
    assert any(any(clr) for runs in gcs.values() for _k, _r, clr, _s in runs)
    dispatches, waves, lanes = counts
    assert lanes > waves > 0
    if case == "fused-set":
        assert c._fused.stats["fused_batches"] > 0
    if case != "tpcc":
        assert any(stale for runs in gcs.values() for *_x, stale in runs)
    if case == "dropped-witness":
        assert c.shards[2].witnesses[1].lane not in gcs
    if case == "chunked":
        assert dispatches > waves
    else:
        assert dispatches == waves
