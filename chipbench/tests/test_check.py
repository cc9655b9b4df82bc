"""``correct`` on the CPU at a size a test run holds: the harness's whole run
(warm-up, window, sync, read-back, reference replay) with the Pallas kernels
in interpret mode, the look for a chip skipped.  A sound run is correct; the
control and every fault the cells can have make it not correct."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from chipbench import catalog, check, control
from chipbench.run import Compiles, run_cell

SEED = 2**31 + 77
PEAKS = {"hbm_bytes_per_s": 819e9}


def tiny(pair):
    cell = catalog.Catalog().pair(*pair)
    cfg = dict(cell.cfg, masters=4, records=3000,
               witness={"sets": 64, "ways": 4})
    traffic = dict(cell.traffic, batch=64, rate=400)
    return dataclasses.replace(cell, cfg=cfg, traffic=traffic)


@pytest.fixture(scope="module")
def compiles():
    return Compiles()


def run(name, compiles, seconds=1.0):
    import time

    return run_cell(tiny(name), SEED, seconds, False,
                    t_start=time.perf_counter(), compiles=compiles,
                    peaks=PEAKS)


# (configuration, traffic) of each cell, and of the open mix, whose cell
# waits for its knee (PERF.md).
CELLS = [("ramcloud-16m-f3", "write_uniform.closed"),
         ("ramcloud-16m-f3", "ycsb_a.open80"),
         ("redis-16m-f2", "write_uniform.closed")]


@pytest.mark.parametrize("name", CELLS, ids="{0[0]}.{0[1]}".format)
def test_sound_run_is_correct(name, compiles):
    res = run(name, compiles)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert {"setup_s", "fast_path_share"} <= set(res["metrics"])


@pytest.mark.parametrize("name", CELLS, ids="{0[0]}.{0[1]}".format)
def test_control_is_not_correct(name):
    out = control.run(tiny(name), SEED, 1.0)
    assert not out["correct"], out
    assert out["checks"]["outcome_mismatches"] > 0


def _state_unchanged(monkeypatch):
    """The record step hands back the witness table it was given."""
    import repro.kernels as K

    for fn in ("gang_fastpath_batch", "gang_record_groups"):
        real = getattr(K, fn)

        def frozen(table, *a, _real=real, **kw):
            return _real(table, *a, **kw)._replace(table=table)
        monkeypatch.setattr(K, fn, frozen)


def _half_batch(monkeypatch):
    """Half of every batch is left out; its outcomes are copied from the
    half that ran."""
    from repro.core.shard import ShardedCluster

    real = ShardedCluster.update_batch

    def half(self, session, ops, now=0.0):
        ran = real(self, session, ops[:(len(ops) + 1) // 2], now)
        return ran + ran[:len(ops) - len(ran)]
    monkeypatch.setattr(ShardedCluster, "update_batch", half)


def _answer_altered(monkeypatch):
    """One acknowledgement of every batch says it took the fast path when it
    did not, or the other way round."""
    from repro.core.shard import ShardedCluster

    real = ShardedCluster.update_batch

    def altered(self, session, ops, now=0.0):
        out = real(self, session, ops, now)
        if out:
            out[0] = dataclasses.replace(out[0],
                                         fast_path=not out[0].fast_path)
        return out
    monkeypatch.setattr(ShardedCluster, "update_batch", altered)


def _backup_corrupted(monkeypatch):
    """After the closing sync, one backup holds a wrong value for one
    acknowledged update."""
    from repro.core.backup import LogEntry
    from repro.core.shard import ShardedCluster
    from repro.core.types import Op

    real = ShardedCluster.sync_all

    def corrupt(self):
        real(self)
        for g in self.shards:
            log = g.backups[-1].log
            if len(log) > 1:
                e = log[-1]
                args = ((("field0", "corrupt"),),) if isinstance(
                    e.op.args[0], tuple) else ("corrupt",)
                log[-1] = LogEntry(Op(e.op.op_type, e.op.keys, args,
                                      e.op.rpc_id), e.result)
                return
    monkeypatch.setattr(ShardedCluster, "sync_all", corrupt)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered,
          "backup_corrupted": _backup_corrupted}


@pytest.mark.parametrize("name", CELLS, ids="{0[0]}.{0[1]}".format)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(name, fault, compiles, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = run(name, compiles)
    assert not res["correct"], (fault, res["checks"])


def test_compare_counts_each_disagreement():
    cell = tiny(CELLS[0])
    cfg, kind = cell.cfg, cell.kind
    actions = [("batch", [("update", "user0000001", None, "a"),
                          ("update", "user0000001", None, "b")],
                [(True, False, 1, 3, "OK"), (False, True, 2, 0, "OK")]),
               ("read", "user0000001", "b")]
    ref = check.replay(kind, cfg, {}, actions)
    keys = check.written(kind, actions)
    assert keys == {"user0000001"}
    replicas = {"user0000001": ["b"] * 4}
    good = check.compare(ref, actions, replicas, keys, 3, 3)
    assert check.passed(good), good
    bad_row = [(True, False, 1, 3, "OK"), (True, False, 1, 3, "OK")]
    bad = check.compare(ref, [(actions[0][0], actions[0][1], bad_row),
                              actions[1]], replicas, keys, 3, 3)
    assert bad["outcome_mismatches"][0] == 1
    bad = check.compare(ref, actions, {"user0000001": ["b", "b", "a", "b"]},
                        keys, 3, 3)
    assert bad["replica_mismatches"][0] == 1
    bad = check.compare(ref, actions, {}, keys, 3, 3)
    assert bad["replica_mismatches"][0] == 1
    bad = check.compare(ref, [actions[0], ("read", "user0000001", "a")],
                        replicas, keys, 3, 2)
    assert bad["read_mismatches"][0] == 1 and bad["unacknowledged"][0] == 1
    assert np.isfinite(sum(v for v, _l in bad.values()))
