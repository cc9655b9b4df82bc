"""The system under test: build a deployment's cluster, turn requests into its
ops, install a loaded snapshot, and warm up the shapes a cell's traffic uses.

This is the only module of the benchmark that calls into ``repro``; it uses
the served entry points (``ShardedCluster.update_batch``, ``read``,
``sync_all``) and the public op constructors of the client session.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

from chipbench.reference import shard_of_np


def build(cfg: dict):
    from repro.core import ShardedCluster, WitnessGeometry

    w = cfg["witness"]
    return ShardedCluster(
        n_shards=cfg["masters"], f=cfg["f"], sync_batch=cfg["sync_batch"],
        witness_backend="device", n_slots=cfg["slots"],
        geometry=WitnessGeometry(w["sets"], w["ways"]))


def update_op(session, cfg: dict, key: str, field, value):
    if cfg["record"]["kind"] == "object":
        return session.op_set(key, value)
    return session.op_hmset(key, ((field, value),))


def install_snapshot(cluster, cfg: dict, keys: Sequence[str],
                     values: Sequence[Any]) -> None:
    """Give every master and each of its backups the loaded records, as a
    cluster restored from a synced snapshot holds them: one bulk MSET entry
    per master, first (and synced) in the master's log and in each backup's
    log.  Loading through ``update_batch`` would cost minutes per run."""
    from repro.core.backup import LogEntry

    owner = shard_of_np(keys, cfg["masters"], cfg["slots"])
    loader = cluster.new_client()
    for i in range(0, len(keys), max(1, len(keys) // 997)):
        if cluster.shard_of(keys[i]) != owner[i]:
            raise RuntimeError(f"key placement of {keys[i]!r} disagrees with "
                               "the deployment's stated hash")
    order = np.argsort(owner, kind="stable")
    bounds = np.searchsorted(owner[order], np.arange(cfg["masters"] + 1))
    for sid, g in enumerate(cluster.shards):
        idx = order[bounds[sid]:bounds[sid + 1]]
        op = loader.session_for(sid).op_mset([(keys[i], values[i]) for i in idx])
        entry = LogEntry(op, "OK")
        g.master.restore_from_log([entry])
        for b in g.backups:
            b.log = [entry]


def _buckets(lo: int, hi: int) -> List[int]:
    out, b = [], lo
    while b < hi:
        out.append(b)
        b <<= 1
    return out + [hi]


def warm_up(cfg: dict, traffic: dict, gen) -> int:
    """Run the shapes the cell's traffic reaches on a scratch cluster of the
    same deployment, through the served path, so the window compiles
    nothing: the update batch sizes the loop sends, and per-master batches
    from a few up to most of a batch, which reach every sync (gc) and
    per-master record size.  Returns the number of updates sent."""
    cl = build(cfg)
    s = cl.new_client()
    cap = traffic["batch"]
    rng = np.random.default_rng([gen.seed, 9])
    pool = gen.keys(rng.integers(0, gen.n, 8 * cap))
    owner = np.array([cl.shard_of(k) for k in pool])
    own0 = [k for k, o in zip(pool, owner) if o == 0]
    rest = [k for k, o in zip(pool, owner) if o != 0]
    field = (cfg["record"]["field_format"] % 0
             if cfg["record"]["kind"] == "hash" else None)

    def send(keys: List[str]) -> None:
        vals = gen.values(rng, len(keys))
        cl.update_batch(s, [update_op(s, cfg, k, field, v)
                            for k, v in zip(keys, vals)])
        cl.sync_all()

    sizes = [cap] if traffic["loop"] == "closed" else _buckets(16, cap)
    sent = 0
    for n in sizes:
        send(rest[:n])
        sent += n
    k = 3
    while k < cap and k <= len(own0):
        send(own0[:k] + rest[:cap - k])
        sent += cap
        k *= 2
    return sent


def read_back(cluster, cfg: dict, keys: Sequence[str], base: Dict[str, Any]
              ) -> Dict[str, List[Any]]:
    """After a sync, each key's value at its master and at each backup.

    A backup holds a log; its value of a key is the snapshot's (``base``)
    with the backup's logged updates after the snapshot entry applied in
    order."""
    from repro.core.types import OpType

    hashes = cfg["record"]["kind"] == "hash"
    out: Dict[str, List[Any]] = {}
    by_shard: Dict[int, List[str]] = {}
    for k in keys:
        by_shard.setdefault(cluster.shard_of(k), []).append(k)
    for sid, ks in by_shard.items():
        g = cluster.shards[sid]
        views = []
        for b in g.backups:
            view = {k: base.get(k) for k in ks}
            for e in b.log[1 if base else 0:]:
                op = e.op
                if op.op_type is OpType.SET:
                    view[op.keys[0]] = op.args[0]
                elif op.op_type is OpType.HMSET and hashes:
                    cur = view.get(op.keys[0])
                    h = dict(cur) if isinstance(cur, dict) else {}
                    h.update(op.args[0])
                    view[op.keys[0]] = h
                else:
                    view[op.keys[0]] = ("unexpected op", op.op_type.name)
            views.append(view)
        for k in ks:
            out[k] = [g.master.store.get(k)] + [v[k] for v in views]
    return out
