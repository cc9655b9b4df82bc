#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a deployment and a traffic mix; the
deployment's record kind (``chipbench/kinds/``) says what a request is.  The
run builds the deployment's ``ShardedCluster`` on the device witness backend,
warms up the shapes the traffic reaches on a scratch cluster of the same
deployment, installs the loaded records where the traffic needs them, then
drives the served path (the kind's ``send``, ``read``) for ``--seconds``.
With ``--trace 1`` the window runs under the profiler and the run reports the
cell's per-layer metrics; otherwise its end-to-end metrics.

After the window it syncs every master, reads every acknowledged key back
from its master and backups, and replays the window through the kind's plain
reference (``reference.py``); ``correct`` holds when every number compared is
within its limit (``check.py``).  Those numbers are the last lines on
standard error and the last key of the result line, the last line on
standard output.

It exits non-zero and prints no result when JAX finds no TPU or fewer chips
than the cell asks for: it never falls back to the CPU or the interpreter.
The persistent compile cache lives at ``.chipbench_cache/jax`` inside the
checkout, or where ``JAX_COMPILATION_CACHE_DIR`` points when it is set.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import catalog, check, deploy, loops  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402

CACHE_DIR = ROOT / ".chipbench_cache" / "jax"


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


@dataclass
class RunRecord:
    """What a metric reader reads."""
    cfg: dict
    kind: Any
    traffic: dict
    window: Any
    setup_s: float
    dispatches: int
    trace: Optional[Any]
    peaks: dict


class Compiles:
    """Counts backend compiles (JAX reports persistent-cache hits as
    compiles too) and persistent-cache hits and writes."""

    def __init__(self) -> None:
        from jax import monitoring

        self.compiles = self.hits = self.writes = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1


def enable_cache() -> None:
    """The persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` where it
    is set, else the fixed ``.chipbench_cache/jax`` inside the checkout."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR))
    # JAX writes only compiles of a second or more by default, which keeps
    # every program of this system out of the cache.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_line() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_memory() -> Optional[int]:
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_cell(cell, seed: int, seconds: float, traced: bool, *,
             t_start: float, compiles: Compiles, peaks: dict) -> dict:
    """Set up, measure, check and reduce one run; returns the result dict
    (less ``device``)."""
    import jax

    from repro.kernels import dispatch_count

    cfg, traffic, kind = cell.cfg, cell.traffic, cell.kind
    gen = cell.generator(traffic, cfg, seed)
    warm = kind.warm_up(cfg, traffic, gen)
    cluster = deploy.build(cfg)
    base = {}
    if traffic["load"]:
        keys, values = gen.snapshot()
        kind.snapshot(cluster, cfg, keys, values)
        base = dict(zip(keys, values))
        del keys, values
    if traffic["loop"] == "open":
        due, reqs = gen.schedule(seconds)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    span = jax.profiler.TraceAnnotation if traced else loops.no_span
    server = loops.Server(cluster, kind, span)
    gc.collect()
    setup_s = time.perf_counter() - t_start
    log(f"setup: {setup_s:.3f} s ({warm} warm-up updates; compile cache "
        f"{compiles.hits} hits, {compiles.writes} writes, {compiles.compiles}"
        " backend compiles)")

    compiles_before, dispatches_before = compiles.compiles, dispatch_count()
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    if traffic["loop"] == "closed":
        window = loops.closed_loop(server, gen, seconds)
    else:
        window = loops.open_loop(server, due, reqs, traffic["batch"])
    jax.block_until_ready(cluster.gang.table)
    if traced:
        jax.profiler.stop_trace()
    in_window = compiles.compiles - compiles_before
    dispatches = dispatch_count() - dispatches_before
    memory_peak = peak_memory()
    log(f"window: {window.seconds:.3f} s, {window.acknowledged} of "
        f"{window.attempted} requests acknowledged, "
        f"{len(window.batch_spans)} update batches, {window.reads} reads, "
        f"mean arrival-to-take lateness {window.lateness_s * 1e3:.3f} ms")
    log(f"compiles inside the window: {in_window}")

    written = check.written(kind, window.actions)
    cluster.sync_all()
    replicas = kind.read_back(cluster, cfg, sorted(written), base)
    del cluster, server
    gc.collect()
    t = time.perf_counter()
    ref = check.replay(kind, cfg, base, window.actions)
    nums = check.compare(ref, window.actions, replicas, written,
                         window.attempted, window.acknowledged)
    log(f"reference replay and comparison: {time.perf_counter() - t:.1f} s")

    trace = None
    if traced:
        trace = trace_mod.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    rec = RunRecord(cfg, kind, traffic, window, setup_s, dispatches, trace,
                    peaks)
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = catalog.reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": check.passed(nums), "attempted": window.attempted,
           "failed": window.attempted - window.acknowledged,
           "metrics": metrics, "memory_peak_bytes": memory_peak}
    if trace is not None:
        out["busy_s"] = trace.busy_s
        out["window_s"] = trace.window_s
        out["breakdown"] = trace.breakdown()
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in nums.items()}
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    cell = catalog.Catalog().cell(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"chipbench: no system under test at {ROOT / 'src' / 'repro'}")
        return 2
    enable_cache()
    dev = device_line()
    log(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        log(f"chipbench: cell {cell.name} needs {cell.chips} TPU chip(s); "
            f"JAX found {dev['count']} {dev['platform']} device(s). No CPU or "
            "interpreter fallback.")
        return 2
    peaks_all = json.loads((catalog.HERE / "peaks.json").read_text())
    if dev["kind"] not in peaks_all:
        log(f"chipbench: no peaks for device kind {dev['kind']!r}")
        return 2
    res = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_start=T_START, compiles=Compiles(),
                   peaks=peaks_all[dev["kind"]])
    device = dict(dev, memory_peak_bytes=res.pop("memory_peak_bytes"))
    for k in ("busy_s", "window_s"):
        if k in res:
            device[k] = res.pop(k)
    checks = res.pop("checks")
    line = dict(res, device=device)
    line["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
