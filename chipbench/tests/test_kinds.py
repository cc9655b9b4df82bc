"""The record kinds ``object`` and ``hash`` (``chipbench/kinds/``) drive the
program exactly as the harness did before what a request is moved into kind
modules: on one seed at test size, the same requests and outcome rows in
every turn, the same reads, the same replicas read back and the same counts
from the reference's replay and from its control.

``data/kinds_parity.json`` was made by that earlier harness (commit 44bb0e7),
driving the same turns through its own ``loops.Server``, ``deploy`` and
``check`` functions; it keeps a digest of every action and the counts."""
from __future__ import annotations

import dataclasses
import hashlib
import json
import time
from pathlib import Path

import pytest

from chipbench import catalog, check, deploy, loops

GOLDEN = json.loads((Path(__file__).parent / "data" / "kinds_parity.json")
                    .read_text())


def digest(obj) -> str:
    return hashlib.sha256(json.dumps(obj, sort_keys=True).encode()
                          ).hexdigest()[:16]


def tiny(name: str):
    config, mix = name.split(".", 1)
    cell = catalog.Catalog().pair(config, mix)
    cfg = dict(cell.cfg, masters=4, records=3000,
               witness={"sets": 64, "ways": 4})
    traffic = dict(cell.traffic, batch=GOLDEN["turn"], rate=400)
    return dataclasses.replace(cell, cfg=cfg, traffic=traffic)


def turns(traffic, gen):
    """Closed: the loop's first batches.  Open: the schedule's requests in
    arrival order, a batch's worth a turn, so no turn depends on the clock."""
    n = GOLDEN["turn"]
    if traffic["loop"] == "closed":
        return [gen.batch(i) for i in range(GOLDEN["closed_turns"])]
    _due, reqs = gen.schedule(GOLDEN["open_seconds"])
    return [reqs[i:i + n] for i in range(0, len(reqs), n)]


def drive(cell) -> dict:
    cfg, traffic, kind = cell.cfg, cell.traffic, cell.kind
    gen = cell.generator(traffic, cfg, GOLDEN["seed"])
    cluster = deploy.build(cfg)
    base = {}
    if traffic["load"]:
        keys, values = gen.snapshot()
        kind.snapshot(cluster, cfg, keys, values)
        base = dict(zip(keys, values))
    server = loops.Server(cluster, kind, loops.no_span)
    w = loops.Window()
    t0 = time.perf_counter()
    for reqs in turns(traffic, gen):
        w.attempted += len(reqs)
        server.reads(w, [r for r in reqs if r[0] == "read"])
        ups = [r for r in reqs if r[0] == "update"]
        if ups:
            server.updates(w, ups, t0)
    written = check.written(kind, w.actions)
    cluster.sync_all()
    replicas = kind.read_back(cluster, cfg, sorted(written), base)
    ref = check.replay(kind, cfg, base, w.actions)
    nums = check.compare(ref, w.actions, replicas, written, w.attempted,
                         w.acknowledged)
    ctl = kind.Reference(cfg, base, fault="no_master_sync")
    ctl.expected = [ctl.read(a[1]) if a[0] == "read" else ctl.update_batch(a[1])
                    for a in w.actions]
    ctl_nums = check.compare(ctl, w.actions, replicas, written, w.attempted,
                             w.acknowledged)
    actions, rows = [], []
    for act in w.actions:
        if act[0] == "read":
            actions.append(digest(["read", act[1], act[2]]))
        else:
            assert all(r[0] == "update" for r in act[1])
            rows += act[2]
            actions.append(digest(["batch", [list(r[1:]) for r in act[1]],
                                   [list(r) for r in act[2]]]))
    return {"actions": actions, "updates": len(rows), "reads": w.reads,
            "fast": sum(r[0] for r in rows), "synced": sum(r[1] for r in rows),
            "rtts": sum(r[2] for r in rows), "accepts": sum(r[3] for r in rows),
            "replicas": digest(sorted(replicas.items())),
            "checks": {k: v for k, (v, _l) in nums.items()},
            "control_checks": {k: v for k, (v, _l) in ctl_nums.items()}}


@pytest.mark.parametrize("name", sorted(GOLDEN["cases"]))
def test_kind_drives_as_before(name):
    got, want = drive(tiny(name)), GOLDEN["cases"][name]
    assert len(got["actions"]) == len(want["actions"])
    bad = [i for i, (a, b) in enumerate(zip(got["actions"], want["actions"]))
           if a != b]
    assert not bad, f"actions {bad} differ"
    assert got == want
    assert not any(got["checks"].values())
