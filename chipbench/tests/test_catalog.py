"""The harness finds every cell, configuration, record kind, traffic kind and
metric of BENCHMARK.json by name, refuses unknown names, and takes a new
cell, mix, metric or record kind from new files and entries alone."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
import sys
import time

import pytest

from chipbench import catalog


def test_every_cell_resolves():
    cat = catalog.Catalog()
    bench = cat.bench
    assert cat.cells() == [w["name"] for w in bench["workloads"]]
    for name in cat.cells():
        cell = cat.cell(name)
        assert cell.cfg["name"] == next(w["config"] for w in bench["workloads"]
                                        if w["name"] == name)
        assert hasattr(cell.generator, "batch")
        assert cell.kind.Reference.__module__ == f"kind_{cell.cfg['record']['kind']}"
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer, name
        for m in cell.per_layer:
            assert m["moves"] in e2e, (name, m["name"])
        for trace in (False, True):
            assert all(callable(r) for r in cell.readers(trace).values())


def test_contract_shape():
    bench = catalog.Catalog().bench
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert all(w["chips"] == 1 for w in bench["workloads"])


@pytest.mark.parametrize("kind,name", [("workload", "nope.cell"),
                                       ("traffic", "nope.mix"),
                                       ("config", "nope-config"),
                                       ("metric", "nope_metric"),
                                       ("record", "nope_kind")])
def test_unknown_names_fail(tmp_path, kind, name):
    cat = catalog.Catalog()
    if kind == "workload":
        with pytest.raises(KeyError):
            cat.cell(name)
        return
    if kind == "metric":
        with pytest.raises(FileNotFoundError):
            catalog.reader(name)
        return
    if kind == "record":
        cfg = json.loads((catalog.HERE / "configs" / "ramcloud-16m-f3.json")
                         .read_text())
        cfg["record"]["kind"] = name
        (tmp_path / "nope.json").write_text(json.dumps(cfg))
        cat.bench["configs"][0] = dict(cat.bench["configs"][0],
                                       file=str(tmp_path / "nope.json"))
        with pytest.raises(FileNotFoundError):
            cat.cell(cat.cells()[0])
        return
    w = dict(cat.bench["workloads"][0], name="x.y")
    w["traffic" if kind == "traffic" else "config"] = name
    cat.bench["workloads"].append(w)
    with pytest.raises((KeyError, FileNotFoundError)):
        cat.cell("x.y")


def test_new_cell_mix_and_metric_from_files_alone(tmp_path, monkeypatch):
    """Copy the harness, add a traffic file, a metric reader and entries in
    BENCHMARK.json, and edit nothing else: the new cell resolves."""
    root = tmp_path / "checkout"
    shutil.copytree(catalog.HERE, root / "chipbench")
    bench = json.loads((catalog.ROOT / "BENCHMARK.json").read_text())
    mix = json.loads((catalog.HERE / "traffic" / "ycsb_a.open80.json").read_text())
    (root / "chipbench" / "traffic" / "ycsb_b.open80.json").write_text(
        json.dumps(dict(mix, read_share=0.95)))
    (root / "chipbench" / "metrics" / "reads_per_s.py").write_text(
        "def read(run):\n    return run.window.reads / run.window.seconds\n")
    bench["workloads"].append({"name": "ramcloud16.ycsb_b.open80",
                               "config": "ramcloud-16m-f3",
                               "traffic": "ycsb_b.open80", "chips": 1,
                               "why": "read-heavy control"})
    bench["per_layer"].append({"name": "reads_per_s", "unit": "reads/s",
                               "better": "higher", "source": "host_clock",
                               "layer": "client batch",
                               "moves": "fast_path_share",
                               "workloads": ["ramcloud16.ycsb_b.open80"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = importlib.util.spec_from_file_location(
        "catalog_copy", root / "chipbench" / "catalog.py")
    copy = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, "catalog_copy", copy)
    spec.loader.exec_module(copy)
    cell = copy.Catalog(root).cell("ramcloud16.ycsb_b.open80")
    assert cell.traffic["read_share"] == 0.95
    assert "reads_per_s" in cell.readers(True)
    assert {m["name"] for m in cell.end_to_end} >= {"fast_path_share", "setup_s"}


def test_pair_resolves_a_mix_that_no_cell_names():
    """A knee sweep runs a configuration under a mix before its cell
    exists."""
    cat = catalog.Catalog()
    cell = cat.pair("ramcloud-16m-f3", "ycsb_a.open80")
    assert cell.traffic["loop"] == "open" and cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "fast_path_share"}
    with pytest.raises(KeyError):
        cat.pair("nope-config", "ycsb_a.open80")
    with pytest.raises(FileNotFoundError):
        cat.pair("ramcloud-16m-f3", "nope.mix")


COUNTER_KIND = '''"""Record kind ``counter``: a record is an integer, an update an INCR by
the request's value read as a hex number (merge class 2: INCRs of one key
commute)."""
from chipbench import reference
from chipbench.kinds.object import Kind as Whole

CLS_INCR = 2


def delta(value):
    return int(value[:4], 16)


class Reference(reference.Reference):
    def pairs(self, req):
        return ((self._hash(req[1]), CLS_INCR),)

    def apply(self, req):
        _op, key, _field, value = req
        self.values[key] = self.values.get(key, 0) + delta(value)
        return self.values[key]


class Kind(Whole):
    Reference = Reference

    def op(self, session, req):
        _op, key, _field, value = req
        return session.op_incr(key, delta(value))

    def logged(self, cur, op):
        if op.op_type.name == "INCR":
            return (cur or 0) + op.args[0]
        return super().logged(cur, op)
'''


@pytest.fixture(scope="module")
def counter_cell(tmp_path_factory):
    """Copy the harness and add a record kind, a configuration that names it
    and a cell: new files and entries, no edit to a file the harness has."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(catalog.HERE, root / "chipbench")
    (root / "chipbench" / "kinds" / "counter.py").write_text(COUNTER_KIND)
    cfg = json.loads((catalog.HERE / "configs" / "ramcloud-16m-f3.json")
                     .read_text())
    cfg.update(name="counter-16m-f3", record={"kind": "counter",
                                              "value_bytes": 100})
    (root / "chipbench" / "configs" / "counter-16m-f3.json").write_text(
        json.dumps(cfg))
    bench = json.loads((catalog.ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "counter-16m-f3",
                             "source": "https://arxiv.org/abs/1710.09921",
                             "file": "chipbench/configs/counter-16m-f3.json",
                             "reduced": [], "why": "INCR counters"})
    bench["workloads"].append({"name": "counter16.write_uniform.closed",
                               "config": "counter-16m-f3",
                               "traffic": "write_uniform.closed", "chips": 1,
                               "why": "INCRs of uniform keys"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = importlib.util.spec_from_file_location(
        "catalog_counter", root / "chipbench" / "catalog.py")
    copy = importlib.util.module_from_spec(spec)
    sys.modules["catalog_counter"] = copy
    try:
        spec.loader.exec_module(copy)
        cell = copy.Catalog(root).cell("counter16.write_uniform.closed")
    finally:
        del sys.modules["catalog_counter"]
    cfg = dict(cell.cfg, masters=4, records=3000,
               witness={"sets": 64, "ways": 4})
    return dataclasses.replace(cell, cfg=cfg,
                               traffic=dict(cell.traffic, batch=64))


def _answer_altered(monkeypatch):
    """One acknowledgement of every batch says the wrong fast path."""
    from repro.core.shard import ShardedCluster

    real = ShardedCluster.update_batch

    def altered(self, session, ops, now=0.0):
        out = real(self, session, ops, now)
        if out:
            out[0] = dataclasses.replace(out[0],
                                         fast_path=not out[0].fast_path)
        return out
    monkeypatch.setattr(ShardedCluster, "update_batch", altered)


@pytest.mark.parametrize("fault", [None, "answer_altered"])
def test_new_record_kind_from_files_alone(counter_cell, fault, monkeypatch):
    """A record kind with its own ops and reference, added as a file, runs
    ``correct`` through the harness's whole run on the CPU, and a run with
    one acknowledgement altered is not correct."""
    from chipbench.run import Compiles, run_cell

    assert counter_cell.kind.Reference.__module__ == "kind_counter"
    if fault:
        _answer_altered(monkeypatch)
    res = run_cell(counter_cell, 2**31 + 91, 1.0, False,
                   t_start=time.perf_counter(), compiles=Compiles(),
                   peaks={"hbm_bytes_per_s": 819e9})
    assert res["correct"] is (fault is None), res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    if fault:
        assert res["checks"]["outcome_mismatches"]["value"] > 0
