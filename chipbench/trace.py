"""The one reduction from a profiler trace (``.xplane.pb``) to device busy
intervals, per-module kernel and prep time, and the benchmark's host spans.

Device operations are classified by the HLO module they run in:
``jit__gang_fastpath_impl`` (the fused batch), ``jit__gang_record_impl`` and
``jit__gang_groups_impl`` (per-master records) and ``jit__gang_gc_impl``
(witness gc).  Within a module the ``tpu_custom_call`` operations are the
Pallas kernel and the rest is XLA prep: both Pallas kernels carry the same
generic name inside the program, so the module is what tells them apart.

All times are nanoseconds on the trace's one timeline, on which the host's
spans and the device's operations both lie.
"""
from __future__ import annotations

import bisect
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Tuple

FUSED_MODULES = ("jit__gang_fastpath_impl",)
RECORD_MODULES = ("jit__gang_fastpath_impl", "jit__gang_record_impl",
                  "jit__gang_groups_impl")
GC_MODULES = ("jit__gang_gc_impl",)
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
SPAN_PREFIX = "bench."

Interval = Tuple[float, float]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclass
class Trace:
    start: float
    end: float
    chips: int
    busy: List[List[Interval]]                 # per chip: merged, clipped
    spans: List[Tuple[str, float, float]]      # the benchmark's host spans
    kernel_ns: Dict[str, float] = field(default_factory=dict)   # per module
    prep_ns: Dict[str, float] = field(default_factory=dict)     # per module
    module_runs: Dict[str, int] = field(default_factory=dict)
    op_ns: Counter = field(default_factory=Counter)             # per op name

    @property
    def window_s(self) -> float:
        return (self.end - self.start) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(b - a for chip in self.busy for a, b in chip) / 1e9 / self.chips

    def busy_in(self, a: float, b: float) -> float:
        """Nanoseconds of device busy time inside [a, b], averaged over the
        chips."""
        tot = 0.0
        for chip in self.busy:
            i = max(0, bisect.bisect_right(chip, (a, float("inf"))) - 1)
            for s, e in chip[i:]:
                if s >= b:
                    break
                tot += max(0.0, min(e, b) - max(s, a))
        return tot / self.chips

    def span_list(self, name: str) -> List[Interval]:
        return [(a, b) for n, a, b in self.spans if n == name]

    def host_self_ns(self, name: str) -> List[float]:
        """Per span of ``name``: its length less the device busy time in it."""
        return [(b - a) - self.busy_in(a, b) for a, b in self.span_list(name)]

    def kernel(self, modules) -> float:
        return sum(self.kernel_ns.get(m, 0.0) for m in modules)

    def prep(self, modules) -> float:
        return sum(self.prep_ns.get(m, 0.0) for m in modules)

    def runs(self, modules) -> int:
        return sum(self.module_runs.get(m, 0) for m in modules)

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Gaps between the first chip's operations inside the window, each
        named by the benchmark span that covers most of it, longest first."""
        edges = [self.start] + [x for iv in self.busy[0] for x in iv] + [self.end]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        named = []
        for a, b in gaps:
            best, cover = "no span", 0.0
            for n, s, e in self.spans:
                c = min(b, e) - max(a, s)
                if c > cover:
                    best, cover = n, c
            named.append((best, (b - a) / 1e9))
        return sorted(named, key=lambda x: -x[1])

    def breakdown(self, top: int = 10) -> dict:
        return {
            "device_ops": [[n, ns / 1e9] for n, ns in self.op_ns.most_common(top)],
            "idle_gaps": [[n, s] for n, s in self.idle_gaps()[:top]],
        }


def load(trace_dir) -> Trace:
    """Reduce the one ``.xplane.pb`` under ``trace_dir`` (or that file)."""
    from jax.profiler import ProfileData

    path = Path(trace_dir)
    if path.is_dir():
        found = sorted(path.rglob("*.xplane.pb"))
        if len(found) != 1:
            raise FileNotFoundError(f"{len(found)} .xplane.pb under {path}")
        path = found[0]
    return reduce(ProfileData.from_file(str(path)))


def reduce(pd) -> Trace:
    spans: List[Tuple[str, float, float]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):],
                                      e.start_ns, e.end_ns))
    if not spans:
        raise ValueError("the trace holds none of the benchmark's spans")
    start = min(a for _n, a, _b in spans)
    end = max(b for _n, _a, b in spans)
    kernel: Dict[str, float] = defaultdict(float)
    prep: Dict[str, float] = defaultdict(float)
    runs: Dict[str, int] = defaultdict(int)
    op_ns: Counter = Counter()
    busy: List[List[Interval]] = []
    for plane in devices:
        chip: List[Interval] = []
        lines = {ln.name: ln for ln in plane.lines}
        mods = sorted(((e.start_ns, e.end_ns, e.name.split("(")[0])
                       for e in lines["XLA Modules"].events
                       if start <= e.start_ns < end) if "XLA Modules" in lines
                      else [])
        starts = [m[0] for m in mods]
        for _a, _b, name in mods:
            runs[name] += 1
        ops = lines["XLA Ops"].events if "XLA Ops" in lines else []
        per_run: Dict[int, List[Interval]] = defaultdict(list)
        for e in ops:
            a, b = e.start_ns, e.end_ns
            if b <= start or a >= end:
                continue
            chip.append((max(a, start), min(b, end)))
            i = bisect.bisect_right(starts, a) - 1
            inside = i >= 0 and a < mods[i][1]
            mod = mods[i][2] if inside else "other"
            op = e.name.split(" = ")[0].lstrip("%")
            op_ns[f"{mod}:{op}"] += b - a
            if KERNEL_MARK in e.name:
                kernel[mod] += b - a
            if inside:
                per_run[i].append((a, b))
        # Operations nest (a loop and its body), so a module run's prep time
        # is the union of its operations less its kernel time.
        for i, ivs in per_run.items():
            prep[mods[i][2]] += sum(b - a for a, b in merge(ivs))
        busy.append(merge(chip))
    for mod in list(prep):
        prep[mod] = max(0.0, prep[mod] - kernel.get(mod, 0.0))
    if not busy:
        raise ValueError("the trace holds no TPU device")
    return Trace(start=start, end=end, chips=max(1, len(devices)),
                 busy=busy, spans=spans, kernel_ns=dict(kernel),
                 prep_ns=dict(prep), module_runs=dict(runs), op_ns=op_ns)
