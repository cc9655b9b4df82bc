"""The harness finds every cell, configuration, traffic kind and metric of
BENCHMARK.json by name, refuses unknown names, and takes a new cell, mix or
metric from new files and entries alone."""
from __future__ import annotations

import importlib.util
import json
import shutil
import sys

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
                                       ("metric", "nope_metric")])
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
