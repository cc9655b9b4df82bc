"""Benchmark harness entry point: one benchmark per paper table/figure.
Prints ``name,us_per_call,derived`` CSV at the end (per the grading
contract), after each figure's own detailed tables, and writes the same
numbers to ``BENCH_curp.json`` at the repo root so the perf trajectory is
machine-readable across PRs.
"""
from __future__ import annotations

import json
import pathlib
import time

BENCH_DIR = pathlib.Path(__file__).resolve().parent
BENCH_JSON = BENCH_DIR.parent / "BENCH_curp.json"
BENCH_HISTORY = BENCH_DIR.parent / "BENCH_history.jsonl"


def _jsonable(v):
    try:
        json.dumps(v)
        return v
    except TypeError:
        return float(v)


def write_bench_json(results, path: pathlib.Path = BENCH_JSON) -> None:
    """Persist every figure's derived metrics (the summary CSV, structured).

    Schema: {"schema": 1, "unix_time": ..., "figures": {name:
    {"us_per_call": ..., "derived": {...}}}} — stable keys so a driver can
    diff BENCH_curp.json between PRs.

    MERGES into an existing file instead of overwriting it: figures run now
    replace their own entries, figures not in ``results`` keep their prior
    numbers — so a partial run (or a PR that adds a new figure) never drops
    the rest of the perf trajectory.
    """
    figures = {}
    prior_time = None
    if path.exists():
        try:
            prior = json.loads(path.read_text())
            figures = dict(prior.get("figures", {}))
            prior_time = prior.get("unix_time")
        except (json.JSONDecodeError, OSError):
            figures = {}
    # Backfill: entries written before per-figure stamping landed carry no
    # unix_time, so the staleness guard below can never fire for them.
    # Stamp them with the file-level time (the best known lower bound on
    # when they last ran) so every preserved entry is staleness-checkable.
    for entry in figures.values():
        if "unix_time" not in entry:
            entry["unix_time"] = prior_time if prior_time is not None \
                else time.time()
    # Perf trajectory: for every numeric metric that already had a recorded
    # value, keep the previous number next to the new one so a driver can
    # read deltas across PRs without diffing git history.
    deltas = {}
    for name, _dt, derived in results:
        prev = figures.get(name, {}).get("derived", {})
        moved = {
            k: {"prev": prev[k], "now": _jsonable(v)}
            for k, v in derived.items()
            if k in prev and isinstance(prev[k], (int, float))
            and isinstance(_jsonable(v), (int, float))
            and _jsonable(v) != prev[k]
        }
        if moved:
            deltas[name] = moved
    now = time.time()
    figures.update({
        name: {
            "us_per_call": dt,
            "unix_time": now,
            "derived": {k: _jsonable(v) for k, v in derived.items()},
        }
        for name, dt, derived in results
    })
    # Staleness guard: a figure carried over from the prior file whose
    # benchmark module was edited AFTER the figure last ran is showing
    # numbers the current code may no longer produce (how fig10's recorded
    # medians survived a cost-model change unnoticed).  Warn, don't fail —
    # partial runs are legitimate; the warning says which job to re-run.
    ran = {name for name, _dt, _d in results}
    for name, entry in sorted(figures.items()):
        if name in ran:
            continue
        mod = BENCH_DIR / f"{name}.py"
        stamp = entry.get("unix_time", prior_time)
        if mod.exists() and stamp is not None and mod.stat().st_mtime > stamp:
            print(f"WARNING: {path.name} entry '{name}' predates "
                  f"benchmarks/{mod.name} (module edited since that figure "
                  f"last ran) — stale numbers; re-run it")
    payload = {
        "schema": 1,
        "unix_time": time.time(),
        "figures": figures,
        "deltas": deltas,
    }
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    print(f"wrote {path} ({len(results)} updated, "
          f"{len(figures) - len(results)} preserved)")
    # Append-only trajectory: one line per merge, so the perf history
    # survives BENCH_curp.json's in-place updates (scripts/bench_gate.py
    # gates on the latest deltas; the jsonl is the long view).
    hist_line = {
        "unix_time": payload["unix_time"],
        "updated": sorted(ran),
        "deltas": deltas,
        "figures": {
            name: {"us_per_call": dt,
                   "derived": {k: _jsonable(v) for k, v in derived.items()
                               if isinstance(_jsonable(v), (int, float))}}
            for name, dt, derived in results
        },
    }
    hist_path = path.parent / BENCH_HISTORY.name
    with hist_path.open("a") as fh:
        fh.write(json.dumps(hist_line, sort_keys=True) + "\n")
    print(f"appended {hist_path.name} ({len(results)} figures)")


def main() -> None:
    from . import (
        fig5_latency_cdf,
        fig6_throughput,
        fig7_ycsb,
        fig8_redis,
        fig10_ops,
        fig11_witness_capacity,
        fig12_batchsize,
        fig_crdt,
        fig_migration,
        fig_obs,
        fig_scaling,
        fig_slo,
        fig_txn,
        fig_watchdog,
        roofline_table,
    )

    jobs = [
        ("fig5_latency_cdf", fig5_latency_cdf.main),
        ("fig6_throughput", fig6_throughput.main),
        ("fig7_ycsb", fig7_ycsb.main),
        ("fig8_redis", fig8_redis.main),
        ("fig10_ops", fig10_ops.main),
        ("fig11_witness_capacity", fig11_witness_capacity.main),
        ("fig12_batchsize", fig12_batchsize.main),
        ("fig_scaling", fig_scaling.main),
        ("fig_txn", fig_txn.main),
        ("fig_migration", fig_migration.main),
        ("fig_crdt", fig_crdt.main),
        ("fig_slo", fig_slo.main),
        ("fig_obs", fig_obs.main),
        ("fig_watchdog", fig_watchdog.main),
        ("roofline_table", roofline_table.main),
    ]
    results = []
    for name, fn in jobs:
        t0 = time.time()
        derived = fn()
        dt = (time.time() - t0) * 1e6
        results.append((name, dt, derived))

    print("\n== summary CSV ==")
    print("name,us_per_call,derived")
    for name, dt, derived in results:
        compact = ";".join(
            f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
            for k, v in list(derived.items())[:8]
        )
        print(f"{name},{dt:.0f},{compact}")

    write_bench_json(results)


if __name__ == "__main__":
    main()
