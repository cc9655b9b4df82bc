"""The record kind ``tpcc`` (``chipbench/kinds/tpcc.py``) at test size on
the CPU: the served window comes out ``correct``, and each of these planted
faults makes it not correct:

* one acknowledgement altered after the program sent it;
* one backup missing the log entry of a New-Order that took stock;
* ``D_NEXT`` declared READ by the program, so New-Orders of one district
  commute where the reference says they conflict;

and the control (``control.py``: the reference with masters that never
start a sync) is not correct on the cell's own traffic."""
from __future__ import annotations

import dataclasses

import pytest

from chipbench import catalog, check, control, deploy, loops

SEED = 2_147_483_659


def tiny(**traffic):
    cell = catalog.Catalog().cell("tpcc16.neworder_payment.closed")
    cfg = dict(cell.cfg, masters=4, warehouses=3,
               witness={"sets": 64, "ways": 4},
               scale={"districts": 3, "customers": 30, "items": 200,
                      "d_next_o_id": 3001})
    return dataclasses.replace(cell, cfg=cfg,
                               traffic=dict(cell.traffic, batch=48, **traffic))


def python_cluster(cfg):
    from repro.core import ShardedCluster, WitnessGeometry

    w = cfg["witness"]
    return ShardedCluster(
        n_shards=cfg["masters"], f=cfg["f"], sync_batch=cfg["sync_batch"],
        witness_backend="python", n_slots=cfg["slots"],
        geometry=WitnessGeometry(w["sets"], w["ways"]))


def serve(cell, cluster, turns=3):
    """The harness's window, its turns counted instead of timed; returns
    what the comparison needs."""
    cfg, kind = cell.cfg, cell.kind
    gen = cell.generator(cell.traffic, cfg, SEED)
    keys, values = gen.snapshot()
    kind.snapshot(cluster, cfg, keys, values)
    base = dict(zip(keys, values))
    server = loops.Server(cluster, kind, loops.no_span)
    w = loops.Window()
    for i in range(turns):
        reqs = gen.batch(i)
        w.attempted += len(reqs)
        server.updates(w, reqs, 0.0)
    cluster.sync_all()
    return w, base


def judge(cell, cluster, w, base):
    kind, cfg = cell.kind, cell.cfg
    written = check.written(kind, w.actions)
    replicas = kind.read_back(cluster, cfg, sorted(written), base)
    ref = check.replay(kind, cfg, base, w.actions)
    return check.compare(ref, w.actions, replicas, written, w.attempted,
                         w.acknowledged)


def counts(nums):
    return {k: v for k, (v, _lim) in nums.items()}


def test_device_window_is_correct():
    """As the harness builds it: the device witnesses (interpreted here),
    with orders of 1-2 lines so the interpreted kernels compile quickly."""
    cell = tiny(ol_cnt=[1, 2])
    cluster = deploy.build(cell.cfg)
    w, base = serve(cell, cluster, turns=2)
    nums = judge(cell, cluster, w, base)
    assert check.passed(nums), counts(nums)


@pytest.fixture(scope="module")
def served():
    cell = tiny()
    cluster = python_cluster(cell.cfg)
    w, base = serve(cell, cluster)
    return cell, cluster, w, base


def test_python_window_is_correct(served):
    nums = judge(*served)
    assert check.passed(nums), counts(nums)


def test_altered_acknowledgement_is_caught(served):
    cell, cluster, w, base = served
    batch = w.actions[1]
    rows = list(batch[2])
    fast, synced, rtts, accepts, value = rows[5]
    rows[5] = (fast, synced, rtts + 1, accepts, value)
    actions = list(w.actions)
    actions[1] = (batch[0], batch[1], rows)
    nums = judge(cell, cluster, dataclasses.replace(w, actions=actions), base)
    assert counts(nums) == {"outcome_mismatches": 1, "read_mismatches": 0,
                            "replica_mismatches": 0, "unacknowledged": 0}


def test_dropped_stock_decrement_at_one_backup_is_caught(served):
    cell, cluster, w, base = served
    at = next(g for g in cluster.shards
              if any(":S:" in k for e in g.backups[1].log[1:]
                     for k in e.op.keys))
    log = at.backups[1].log
    i = next(i for i, e in enumerate(log) if i
             and e.op.op_type.name in ("TXN", "TXN_COMMIT")
             and any(":S:" in k for k in e.op.keys))
    stock = [k for k in log[i].op.keys if ":S:" in k]
    del log[i]
    nums = judge(cell, cluster, w, base)
    assert counts(nums)["replica_mismatches"] > 0
    assert counts(nums)["outcome_mismatches"] == 0
    replicas = cell.kind.read_back(cluster, cell.cfg, stock, base)
    assert any(vals[2] != vals[0] for vals in replicas.values())


def test_d_next_declared_read_is_caught(monkeypatch):
    from repro.apps import tpcc
    from repro.core.merge import CLS_READ

    real = tpcc._part

    def read_d_next(sid, proc, args, decl):
        decl = [(k, CLS_READ if ":D_NEXT:" in k else c) for k, c in decl]
        return real(sid, proc, args, decl)

    monkeypatch.setattr(tpcc, "_part", read_d_next)
    cell = tiny()
    cluster = python_cluster(cell.cfg)
    w, base = serve(cell, cluster)
    nums = counts(judge(cell, cluster, w, base))
    assert nums["outcome_mismatches"] > 0


def test_control_is_not_correct():
    out = control.run(tiny(), SEED, 1.0)
    assert not out["correct"]
    assert out["checks"]["outcome_mismatches"] > 0
