"""Each key of an op is hashed once in the op's life.

An op's 64-bit key hashes (``Op.key_hashes``) and routing lanes
(``repro.core.shard.route_lanes``) are memoized on the op and read by every
later step: routing, the masters' ownership check, the merge pairs, witness
records and gc.  The registry counter ``keys.hashed`` counts FNV-1a passes
over a key's bytes.  Here it is read over op creation plus one
``ShardedCluster.update_batch`` call on each served path, and the outcome
rows are held to a reference: the Python witness backend on the per-shard
path for the device paths, and the TPC-C kind's plain reference for the
transactions.  The op-level route must give ``SlotRouter.slot_of``'s slots,
and must follow a slot-map flip for ops built before it.
"""
from __future__ import annotations

import pytest

from chipbench import check, loops
from chipbench.kinds import tpcc as tpcc_kind
from repro.core import ShardedCluster, WitnessGeometry, telemetry
from repro.core.shard import route_lanes
from test_tpcc import load, small


def hashed() -> int:
    return telemetry.registry().counter("keys.hashed").value


def _rows(outcomes):
    return [(o.value, o.rtts, o.fast_path, o.synced_path, o.witness_accepts)
            for o in outcomes]


def _kv_batches(backend, make):
    """Two batches of 16 updates over 4 masters, the second counted: the
    FNV passes of building and serving it, and every outcome row."""
    c = ShardedCluster(n_shards=4, f=3, witness_backend=backend, seed=7,
                       sync_batch=10, geometry=WitnessGeometry(256, 4))
    s = c.new_client()
    rows = _rows(c.update_batch(s, [make(s, f"w{i}", i) for i in range(16)]))
    before = hashed()
    ops = [make(s, f"user{i % 12:019d}", i) for i in range(16)]
    rows += _rows(c.update_batch(s, ops))
    return c, hashed() - before, rows


def _set(s, key, i):
    return s.op_set(key, f"v{i}")


def _hmset(s, key, i):
    return s.op_hmset(key, ((f"field{i % 3}", f"v{i}"),))


@pytest.mark.parametrize("case", ["set-fused", "hmset-per-shard",
                                  "tpcc-txn"])
def test_one_fnv_pass_per_key(case):
    if case == "tpcc-txn":
        cfg, tr = small(2)
        gen, kind, cluster, base = load(cfg, tr, 21, "python")
        for w in range(1, cfg["warehouses"] + 1):
            cluster.shard_of(f"{{{w}}}:W")      # the tags' hashes, cached
        server = loops.Server(cluster, kind, loops.no_span)
        win = loops.Window()
        spare = cluster.new_client()
        want = 0
        passes = 0
        for i in range(2):
            reqs = gen.batch(i)
            want += sum(len({k for p in kind.spec(spare, r).parts
                             for k in p.keys}) for r in reqs)
            before = hashed()
            server.updates(win, reqs, 0.0)
            passes += hashed() - before
        assert kind.counts["txn.batch.deferred"] > 0
        assert passes == want
        ref = check.replay(kind, cfg, base, win.actions)
        for act, exp in zip(win.actions, ref.expected):
            assert act[2] == exp
        return

    make, per_op = {"set-fused": (_set, 1),
                    "hmset-per-shard": (_hmset, 2)}[case]
    cd, passes, rows = _kv_batches("device", make)
    _cp, _passes, want = _kv_batches("python", make)
    assert passes == per_op * 16
    assert rows == want
    fused = cd._fused.stats["fused_batches"]
    assert fused == (2 if case == "set-fused" else 0)


def test_op_route_matches_key_route_and_follows_slot_flips():
    c = ShardedCluster(n_shards=4, f=1, witness_backend="python")
    s = c.new_client()
    r = c.router
    keys = ["user0000000000000000042", "{7}:W", "{7}:S:31", "a{}b", "x{y",
            42, -5]
    ops = [s.op_set(k, "v") for k in keys]
    ops.append(s.session_for(0).op_mset([(k, i) for i, k in
                                         enumerate(keys)]))
    assert len({r.shard_of(k) for k in keys}) > 1
    for op in ops:
        assert r.slots_of_op(op) == tuple(r.slot_of(k) for k in op.keys)
        for sid in range(4):
            assert r.owned_by(op, sid) == all(r.shard_of(k) == sid
                                              for k in op.keys)
    with pytest.raises(ValueError):
        c._group_for(ops[-1])

    op = s.op_set("user0000000000000000042", "after")
    lanes = route_lanes(op)
    old = c._group_for(op).shard_id
    new = (old + 1) % 4
    assert c.shards[old].master.owns(op)
    assert not c.shards[new].master.owns(op)
    r.assign([r.slot_of(op.keys[0])], new)
    assert route_lanes(op) is lanes
    assert c._group_for(op).shard_id == new
    assert c.shards[new].master.owns(op)
    assert not c.shards[old].master.owns(op)
    c.update_batch(s, [op])
    assert c.shards[new].master.store.get(op.keys[0]) == "after"
    assert c.shards[old].master.store.get(op.keys[0]) is None
