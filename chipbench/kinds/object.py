"""Record kind ``object``: a record is one value, an update a SET of the whole
value (CURP on RAMCloud).  A request is the traffic generator's
``(op, key, field, value)``; ``field`` is None.

A record kind owns everything that depends on what a request is: the synced
bulk load, the warm-up's requests, turning a turn's requests into the
program's ops and serving them, the keys a window wrote, reading them back
from every replica, the reference's pairs and effect of a request, and the
pairs a request records at each witness.  ``chipbench/catalog.py`` finds the
kind ``cfg["record"]["kind"]`` names as ``chipbench/kinds/<kind>.py`` and
uses its ``Kind`` class; a new kind subclasses this one where it shares its
key-value records, or gives the same methods of its own.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence, Set, Tuple

import numpy as np

from chipbench import deploy, reference
from chipbench.loops import no_span
from chipbench.reference import CLS_SET, Row, shard_of_np


class Reference(reference.Reference):
    def pairs(self, req):
        return ((self._hash(req[1]), CLS_SET),)

    def apply(self, req):
        _op, key, _field, value = req
        self.values[key] = value
        return "OK"


def _buckets(lo: int, hi: int) -> List[int]:
    out, b = [], lo
    while b < hi:
        out.append(b)
        b <<= 1
    return out + [hi]


class Kind:
    Reference = Reference

    def op(self, session, req):
        """The program's op for one update request."""
        _op, key, _field, value = req
        return session.op_set(key, value)

    def warm_request(self, cfg: dict, key: str, value: str) -> Tuple:
        """An update request of the warm-up."""
        return ("update", key, None, value)

    def logged(self, cur: Any, op) -> Any:
        """A key's value after a backup's logged ``op`` of it, from ``cur``."""
        if op.op_type.name == "SET":
            return op.args[0]
        return ("unexpected op", op.op_type.name)

    def pairs(self, req) -> int:
        """(hash, class) pairs one request records at each witness."""
        return 1

    def written(self, reqs) -> Set[str]:
        """The keys a batch of update requests wrote."""
        return {key for _op, key, _field, _value in reqs}

    def send(self, cluster, session, reqs, span) -> List[Row]:
        """Serve one turn's update requests through one
        ``ShardedCluster.update_batch`` call; one outcome row each."""
        with span("bench.make_ops"):
            ops = [self.op(session, r) for r in reqs]
        with span("bench.update_batch"):
            out = cluster.update_batch(session, ops)
        return [(o.fast_path, o.synced_path, o.rtts, o.witness_accepts,
                 o.value) for o in out]

    def snapshot(self, cluster, cfg: dict, keys: Sequence[str],
                 values: Sequence[Any]) -> None:
        """Give every master and each of its backups the loaded records, as
        a cluster restored from a synced snapshot holds them: one bulk MSET
        entry per master, first (and synced) in the master's log and in each
        backup's log.  Loading through ``update_batch`` would cost minutes
        per run."""
        from repro.core.backup import LogEntry

        owner = shard_of_np(keys, cfg["masters"], cfg["slots"])
        loader = cluster.new_client()
        for i in range(0, len(keys), max(1, len(keys) // 997)):
            if cluster.shard_of(keys[i]) != owner[i]:
                raise RuntimeError(f"key placement of {keys[i]!r} disagrees "
                                   "with the deployment's stated hash")
        order = np.argsort(owner, kind="stable")
        bounds = np.searchsorted(owner[order], np.arange(cfg["masters"] + 1))
        for sid, g in enumerate(cluster.shards):
            idx = order[bounds[sid]:bounds[sid + 1]]
            op = loader.session_for(sid).op_mset([(keys[i], values[i])
                                                  for i in idx])
            entry = LogEntry(op, "OK")
            g.master.restore_from_log([entry])
            for b in g.backups:
                b.log = [entry]

    def warm_up(self, cfg: dict, traffic: dict, gen) -> int:
        """Run the shapes the cell's traffic reaches on a scratch cluster of
        the same deployment, through the served path, so the window compiles
        nothing: the update batch sizes the loop sends, and per-master
        batches from a few up to most of a batch, which reach every sync
        (gc) and per-master record size.  Returns the number of updates
        sent."""
        cl = deploy.build(cfg)
        s = cl.new_client()
        cap = traffic["batch"]
        rng = np.random.default_rng([gen.seed, 9])
        pool = gen.keys(rng.integers(0, gen.n, 8 * cap))
        owner = np.array([cl.shard_of(k) for k in pool])
        own0 = [k for k, o in zip(pool, owner) if o == 0]
        rest = [k for k, o in zip(pool, owner) if o != 0]

        def send(keys: List[str]) -> None:
            vals = gen.values(rng, len(keys))
            self.send(cl, s, [self.warm_request(cfg, k, v)
                              for k, v in zip(keys, vals)], no_span)
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

    def read_back(self, cluster, cfg: dict, keys: Sequence[str],
                  base: Dict[str, Any]) -> Dict[str, List[Any]]:
        """After a sync, each key's value at its master and at each backup.

        A backup holds a log; its value of a key is the snapshot's
        (``base``) with the backup's logged updates after the snapshot entry
        applied in order."""
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
                    key = e.op.keys[0]
                    view[key] = self.logged(view.get(key), e.op)
                views.append(view)
            for k in ks:
                out[k] = [g.master.store.get(k)] + [v[k] for v in views]
        return out
