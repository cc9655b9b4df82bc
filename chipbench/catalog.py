"""Resolve a cell of ``BENCHMARK.json`` by name: its configuration file, its
record kind, its traffic file, the generator its traffic names, and the
reader of every metric it reports.  Everything is found by name, so a new
configuration, record kind, traffic mix or metric is new files plus new
entries, and no edit here.

* configuration: the file ``configs[].file`` names;
* record kind ``<kind>`` (the configuration's ``record.kind``):
  ``chipbench/kinds/<kind>.py`` (a ``Kind`` class: what a request is, how it
  is loaded, warmed up, sent, read back and replayed by the reference, and
  the pairs it records at each witness);
* traffic ``<mix>``: ``chipbench/traffic/<mix>.json``, whose ``kind`` names
  the generator ``chipbench/traffic/<kind>.py`` (a ``Generator`` class);
* metric ``<name>``: ``chipbench/metrics/<name>.py`` (a ``read(run)``
  function returning the number, or None where it finds nothing to read).
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    kind: object
    traffic: dict
    generator: type
    end_to_end: List[dict]
    per_layer: List[dict]

    def readers(self, trace: bool) -> Dict[str, Callable]:
        return {m["name"]: reader(m["name"])
                for m in (self.per_layer if trace else self.end_to_end)}


def reader(metric: str) -> Callable:
    return _module(HERE / "metrics" / f"{metric}.py", f"metric_{metric}").read


class Catalog:
    def __init__(self, root: Path = ROOT) -> None:
        self.root = Path(root)
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> Cell:
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        w = cells[name]
        return self._build(name, w["chips"], w["config"], w["traffic"])

    def pair(self, config: str, traffic: str) -> Cell:
        """A configuration under a traffic mix that no cell names yet, on one
        chip, with no metrics: what a knee sweep runs before its cell
        exists."""
        return self._build(f"{config}.{traffic}", 1, config, traffic)

    def _build(self, name: str, chips: int, config: str, mix: str) -> Cell:
        configs = {c["name"]: c for c in self.bench["configs"]}
        if config not in configs:
            raise KeyError(f"no configuration {config!r} in BENCHMARK.json")
        cfg = json.loads((self.root / configs[config]["file"]).read_text())
        rk = cfg["record"]["kind"]
        kind = _module(HERE / "kinds" / f"{rk}.py", f"kind_{rk}").Kind()
        traffic_file = HERE / "traffic" / f"{mix}.json"
        if not traffic_file.is_file():
            raise FileNotFoundError(f"no traffic file for {mix!r}")
        traffic = json.loads(traffic_file.read_text())
        gen = _module(HERE / "traffic" / f"{traffic['kind']}.py",
                      f"traffic_{traffic['kind']}").Generator
        e2e = [m for m in self.bench["end_to_end"]
               if name in m.get("workloads", [name])]
        reported = {m["name"] for m in e2e}
        layer = [m for m in self.bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
        return Cell(name, chips, cfg, kind, traffic, gen, e2e, layer)

    def cells(self) -> List[str]:
        return [w["name"] for w in self.bench["workloads"]]
