#!/usr/bin/env python3
"""The host phases of ``update_batch`` from a profiler trace.

The program marks each phase of its served update path with a span
``curp.<phase>`` on the JAX profiler's timeline (``repro.core.telemetry.
span``): the root ``curp.update_batch`` and, nested in it, ``preflight``,
``record``, ``settle``, ``master``, ``sync`` and ``gc``.  ``trace.py`` keeps
only the benchmark's own ``bench.*`` spans, so this module reads the
program's spans from the same ``.xplane.pb`` and splits each call's host
time by phase.

A span's *self time* is its length, less the time that program spans nested
in it cover, less the device busy time in what remains.  Over one
``update_batch`` call the self times of its spans add up to the call's
length less the device busy time in it.  Every number below is per
``update_batch`` call: the summed self time over the traced window's calls.

    python3 chipbench/phases.py --workload <cell> --seed <n> --seconds <s> [--keep <file>]

runs the cell once with ``--trace 1`` as ``run.py`` does and prints its
result line with a ``phases`` key: the split in ms per call, the sync
rounds per call, the benchmark's ``host_ms_per_batch`` for comparison, the
traced run's ``updates_per_s`` and the longest idle gaps of the device, each
named ``<bench span>/<program phase>`` by the phase whose self time covers
most of it.  ``--keep`` copies the raw trace to a file.
"""
from __future__ import annotations

import argparse
import bisect
import json
import shutil
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

PREFIX = "curp."
ROOT_SPAN = "update_batch"
PHASES = ("preflight", "record", "settle", "master", "sync", "gc")

Interval = Tuple[float, float]


@dataclass
class Span:
    name: str            # without the prefix
    start: float
    end: float
    thread: str          # host line the span lies on
    parent: Optional[int] = None          # index of the enclosing span
    pieces: Tuple[Interval, ...] = ()     # [start, end] less nested spans


def read(pd, t) -> List[Span]:
    """The program's spans inside the reduced trace ``t``'s window, sorted,
    with their nesting and self pieces.  Empty for a program without
    spans."""
    spans: List[Span] = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            thread = f"{plane.name}#{k}"
            spans += [Span(e.name[len(PREFIX):], e.start_ns, e.end_ns, thread)
                      for e in line.events
                      if e.name.startswith(PREFIX)
                      and t.start <= e.start_ns and e.end_ns <= t.end]
    spans.sort(key=lambda s: (s.thread, s.start, -s.end))
    stack: List[int] = []
    kids: Dict[int, List[Interval]] = {}
    for i, s in enumerate(spans):
        while stack and (spans[stack[-1]].thread != s.thread
                         or spans[stack[-1]].end <= s.start):
            stack.pop()
        if stack:
            p = stack[-1]
            if s.end > spans[p].end:
                raise ValueError(f"span {s.name} overlaps {spans[p].name} "
                                 "without nesting in it")
            s.parent = p
            kids.setdefault(p, []).append((s.start, s.end))
        stack.append(i)
    for i, s in enumerate(spans):
        pieces, at = [], s.start
        for a, b in kids.get(i, ()):
            if a > at:
                pieces.append((at, a))
            at = max(at, b)
        if s.end > at:
            pieces.append((at, s.end))
        s.pieces = tuple(pieces)
    return spans


def self_ns(s: Span, t) -> float:
    """The span's length less nested spans less device busy time."""
    return sum((b - a) - t.busy_in(a, b) for a, b in s.pieces)


def in_calls(spans: List[Span]) -> List[Span]:
    """The spans inside an ``update_batch`` call: a sync that a read sets
    off, outside any call, is left out."""
    inside: List[bool] = []
    for s in spans:          # sorted, so a parent precedes its children
        inside.append(s.name == ROOT_SPAN if s.parent is None
                      else inside[s.parent])
    return [s for s, keep in zip(spans, inside) if keep]


def split(spans: List[Span], t) -> Optional[dict]:
    """Per ``update_batch`` call: ms of self time of each phase and of the
    root (``unspanned``), and sync rounds.  None when the program has no
    spans (or the trace no calls)."""
    calls = len(t.span_list(ROOT_SPAN))
    if not spans or not calls:
        return None
    spans = in_calls(spans)
    ms: Dict[str, float] = {p: 0.0 for p in PHASES + (ROOT_SPAN,)}
    for s in spans:
        ms[s.name] = ms.get(s.name, 0.0) + self_ns(s, t)
    out = {f"{p}_ms": ms.pop(p) / calls / 1e6 for p in PHASES}
    out["unspanned_ms"] = ms.pop(ROOT_SPAN) / calls / 1e6
    out.update({f"{p}_ms": v / calls / 1e6 for p, v in ms.items()})
    out["syncs_per_batch"] = sum(s.name == "sync" for s in spans) / calls
    return out


def gaps(t) -> List[Interval]:
    """Gaps between the first chip's operations inside the window, as
    ``trace.Trace.idle_gaps`` finds them, longest first."""
    edges = [t.start] + [x for iv in t.busy[0] for x in iv] + [t.end]
    found = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
             if edges[i + 1] > edges[i]]
    return sorted(found, key=lambda g: g[0] - g[1])


def named_gaps(spans: List[Span], t, top: int = 10) -> List[list]:
    """The ``top`` longest idle gaps, each named by the benchmark span that
    covers most of it (as ``trace.py`` names it) and ``/`` the program span
    whose self pieces cover most of it, if any does."""
    by_thread: Dict[int, List[Tuple[float, float, str]]] = {}
    for s in spans:
        by_thread.setdefault(s.thread, []).extend(
            (a, b, s.name) for a, b in s.pieces)
    for pieces in by_thread.values():
        pieces.sort()
    starts = {k: [p[0] for p in v] for k, v in by_thread.items()}
    out = []
    for a, b in gaps(t)[:top]:
        bench, cover = "no span", 0.0
        for n, s, e in t.spans:
            c = min(b, e) - max(a, s)
            if c > cover:
                bench, cover = n, c
        phase: Dict[str, float] = {}
        for k, pieces in by_thread.items():
            i = max(0, bisect.bisect_right(starts[k], a) - 1)
            for s, e, n in pieces[i:]:
                if s >= b:
                    break
                c = min(b, e) - max(a, s)
                if c > 0:
                    phase[n] = phase.get(n, 0.0) + c
        name = bench
        if phase:
            name += "/" + max(phase, key=phase.get)
        out.append([name, (b - a) / 1e9])
    return out


class _Keep:
    """Stands in for ``chipbench.trace`` inside ``run.py``: reduces the
    trace as it does and reads the program's spans before the run deletes
    the trace."""

    def __init__(self, keep: Optional[str]) -> None:
        self.keep = keep
        self.spans: List[Span] = []
        self.trace = None

    def load(self, trace_dir):
        from jax.profiler import ProfileData

        from chipbench import trace

        (path,) = Path(trace_dir).rglob("*.xplane.pb")
        if self.keep:
            shutil.copyfile(path, self.keep)
        pd = ProfileData.from_file(str(path))
        self.trace = trace.reduce(pd)
        self.spans = read(pd, self.trace)
        return self.trace


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--keep", default=None,
                   help="copy the raw .xplane.pb to this file")
    args = p.parse_args(argv)

    from chipbench import catalog, layers, run

    cell = catalog.Catalog().cell(args.workload)
    run.enable_cache()
    dev = run.device_line()
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        run.log(f"phases: cell {cell.name} needs {cell.chips} TPU chip(s); "
                f"JAX found {dev['count']} {dev['platform']} device(s)")
        return 2
    peaks = json.loads((catalog.HERE / "peaks.json").read_text())[dev["kind"]]
    keep = _Keep(args.keep)
    records = []
    record_cls = run.RunRecord

    def capture(*a):
        records.append(record_cls(*a))
        return records[-1]

    run.trace_mod, run.RunRecord = keep, capture
    res = run.run_cell(cell, args.seed, args.seconds, True,
                       t_start=run.T_START, compiles=run.Compiles(),
                       peaks=peaks)
    rec = records[-1]
    phases = split(keep.spans, keep.trace) or {}
    phases["host_ms_per_batch"] = layers.host_ms_per_batch(rec)
    phases["updates_per_s"] = catalog.reader("updates_per_s")(rec)
    phases["batches"] = layers.batches(rec)
    phases["idle_gaps"] = named_gaps(keep.spans, keep.trace)
    res["phases"] = phases
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
