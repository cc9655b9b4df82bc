"""The system under test: build a deployment's cluster.  What a request is,
and how it is loaded, sent and read back, is its record kind's
(``chipbench/kinds/``)."""
from __future__ import annotations


def build(cfg: dict):
    from repro.core import ShardedCluster, WitnessGeometry

    w = cfg["witness"]
    return ShardedCluster(
        n_shards=cfg["masters"], f=cfg["f"], sync_batch=cfg["sync_batch"],
        witness_backend="device", n_slots=cfg["slots"],
        geometry=WitnessGeometry(w["sets"], w["ways"]))
