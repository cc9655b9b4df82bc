#!/usr/bin/env python3
"""The control of the ``correct`` comparison: the plain reference with one of
the deployment's guarantees broken, put in the program's place, must come
out not correct.

    python3 chipbench/control.py --workload <cell> --seeds <n> [<n> ...] \
        --seconds <s>

The broken guarantee is that a master syncs when the protocol requires it
(``no_master_sync``): the master never starts a sync itself, neither before
replying to an update that conflicts with its unsynced window nor once
``sync_batch`` updates are unsynced, and syncs only when a client asks
because a witness rejected its record, or a read needs it.  That is the
later, rarer flush a change could be tempted by.  The cell's own traffic, at its own size and load, runs
through the benchmark's own loops for ``--seconds``; the replicas read back
are the control's values.  One JSON line per seed gives every number
compared; ``correct`` must be false on every seed.
"""
from __future__ import annotations

import argparse
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Any

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT)]

from chipbench import catalog, check, loops  # noqa: E402


@dataclass
class Outcome:
    fast_path: bool
    synced_path: bool
    rtts: int
    witness_accepts: int
    value: Any


class ControlCluster:
    """The cell's record kind with its reference, broken, in the program's
    place: update requests go straight to the reference, and reads answer
    through the program's client interface."""

    def __init__(self, kind, cfg: dict, snapshot) -> None:
        self.ref = kind.Reference(cfg, snapshot, fault="no_master_sync")

    def new_client(self):
        return self

    def send(self, _cluster, _session, reqs, _span):
        return self.ref.update_batch(reqs)

    def op_get(self, key):
        return key

    def read(self, _session, key):
        return Outcome(True, False, 1, 0, self.ref.read(key))


def run(cell, seed: int, seconds: float) -> dict:
    cfg, traffic = cell.cfg, cell.traffic
    gen = cell.generator(traffic, cfg, seed)
    base = {}
    if traffic["load"]:
        keys, values = gen.snapshot()
        base = dict(zip(keys, values))
    cl = ControlCluster(cell.kind, cfg, base)
    server = loops.Server(cl, cl, loops.no_span)
    if traffic["loop"] == "closed":
        w = loops.closed_loop(server, gen, seconds)
    else:
        due, reqs = gen.schedule(seconds)
        w = loops.open_loop(server, due, reqs, traffic["batch"])
    written = check.written(cell.kind, w.actions)
    replicas = {k: [cl.ref.values.get(k)] * (cfg["f"] + 1) for k in written}
    ref = check.replay(cell.kind, cfg, base, w.actions)
    nums = check.compare(ref, w.actions, replicas, written, w.attempted,
                         w.acknowledged)
    return {"seed": seed, "correct": check.passed(nums),
            "updates": len(w.fast),
            "checks": {k: v for k, (v, _lim) in nums.items()}}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    cell = catalog.Catalog().cell(args.workload)
    for seed in args.seeds:
        print(json.dumps(dict(run(cell, seed, args.seconds),
                              workload=cell.name)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
