#!/usr/bin/env python3
"""Find the knee of an open-loop mix on a configuration: the highest offered
rate the system sustains without a growing backlog.

    python3 chipbench/sweep.py --config <config> --traffic <open mix> \
        --seed <n> --seconds <s> --rates 4000 6000 8000 ...

One process sets the pair up once (warm-up, loaded records), then offers
each rate in turn for ``--seconds`` to the same cluster, and prints one JSON
line per rate: the rate completed, the median and 99th-percentile commit
latency, how long after the last arrival the queue took to drain, and the
ratio of the mean latency of the updates due in the window's last quarter
to that of its second quarter.  A rate is sustained when that ratio stays
under 1.5 and the queue drains within a second; a growing backlog pushes
both up.  The knee is written into the traffic file by hand, with the
readings, in PERF.md: the benchmark itself never searches for a rate.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from chipbench import catalog, deploy, loops  # noqa: E402
from chipbench.run import device_line, enable_cache, log  # noqa: E402


def quarter_ratio(window) -> float:
    by_due = np.asarray(window.latency_s)[np.argsort(window.update_index)]
    q = len(by_due) // 4
    return float(by_due[3 * q:].mean() / max(1e-9, by_due[q:2 * q].mean()))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args(argv)
    cell = catalog.Catalog().pair(args.config, args.traffic)
    if cell.traffic["loop"] != "open":
        raise SystemExit(f"{args.traffic} is not an open-loop mix")
    enable_cache()
    dev = device_line()
    log(f"device: {dev}")
    if dev["platform"] != "tpu":
        return 2
    cfg, traffic = cell.cfg, cell.traffic
    gen = cell.generator(traffic, cfg, args.seed)
    cell.kind.warm_up(cfg, traffic, gen)
    cluster = deploy.build(cfg)
    if traffic["load"]:
        keys, values = gen.snapshot()
        cell.kind.snapshot(cluster, cfg, keys, values)
        del keys, values
    server = loops.Server(cluster, cell.kind, loops.no_span)
    log(f"setup {time.perf_counter() - T_START:.1f} s")
    for i, rate in enumerate(args.rates):
        g = cell.generator(dict(traffic, rate=rate), cfg, args.seed + i)
        due, reqs = g.schedule(args.seconds)
        w = loops.open_loop(server, due, reqs, traffic["batch"])
        ups = len(w.latency_s)
        print(json.dumps({
            "rate": rate, "completed_per_s": w.acknowledged / w.seconds,
            "commit_p50_ms": float(np.percentile(w.latency_s, 50)) * 1e3,
            "commit_p99_ms": float(np.percentile(w.latency_s, 99)) * 1e3,
            "drain_after_close_s": w.seconds - float(due[-1]),
            "late_quarter_ratio": quarter_ratio(w),
            "fast_path_share": sum(w.fast) / max(1, ups),
            "ops_per_batch": ups / max(1, len(w.batch_spans)),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
