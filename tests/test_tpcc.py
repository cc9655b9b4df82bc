"""TPC-C New-Order and Payment (``repro.apps.tpcc``) served through
``ShardedCluster.update_batch``, against the benchmark's plain reference
(``chipbench/kinds/tpcc.py``: written from the specification's §2.4.2 and
§2.5.2 profiles over one dict, importing nothing of ``repro``), on seeded
random data at a small size: 2-4 warehouses on 4 masters, with districts,
customers and items scaled down.

Every outcome row, every key's value at its master and at each backup after
``sync_all``, and the ``txn.batch.*`` counters must equal the reference's,
on the Python witnesses and on the device witnesses (Pallas kernels in
interpret mode).  Also here: hash-tag placement against the reference's
stated hash, the untagged placement the tag rule must leave alone, the
fused path's refusal of tagged keys, ``record_many``'s SMEM split against
one unsplit dispatch, and recovery of a crashed master holding prepared,
undecided transaction legs.
"""
from __future__ import annotations

import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import check, loops  # noqa: E402
from chipbench.kinds import tpcc as kind_mod  # noqa: E402
from chipbench.reference import shard_of_np  # noqa: E402
from chipbench.traffic.tpcc import Generator  # noqa: E402
from repro.core import ShardedCluster, WitnessGeometry, telemetry  # noqa: E402
from repro.core.shard import SlotRouter, hash_tag  # noqa: E402
from repro.core.txn import prepare_op  # noqa: E402

CFG = json.loads((ROOT / "chipbench/configs/tpcc-16w-f3.json").read_text())
TRAFFIC = json.loads(
    (ROOT / "chipbench/traffic/neworder_payment.closed.json").read_text())
COUNTERS = ("txn.batch.rounds", "txn.batch.deferred", "txn.batch.multi_shard")


def small(W: int, *, sets: int = 64, **traffic):
    """The deployment at a small size, and its traffic with more remote
    lines, rollbacks and remote Payments than the specification's odds, so
    a few batches reach every path."""
    cfg = dict(CFG, masters=4, warehouses=W, witness={"sets": sets, "ways": 4},
               scale={"districts": 3, "customers": 30, "items": 200,
                      "d_next_o_id": 3001})
    tr = dict(TRAFFIC, batch=48, remote_line=0.15, rollback=0.1,
              remote_payment=0.3)
    tr.update(traffic)
    return cfg, tr


def build(cfg: dict, backend: str) -> ShardedCluster:
    w = cfg["witness"]
    return ShardedCluster(
        n_shards=cfg["masters"], f=cfg["f"], sync_batch=cfg["sync_batch"],
        witness_backend=backend, n_slots=cfg["slots"],
        geometry=WitnessGeometry(w["sets"], w["ways"]))


def load(cfg: dict, tr: dict, seed: int, backend: str):
    gen = Generator(tr, cfg, seed)
    kind = kind_mod.Kind()
    cluster = build(cfg, backend)
    keys, values = gen.snapshot()
    kind.snapshot(cluster, cfg, keys, values)
    return gen, kind, cluster, dict(zip(keys, values))


def every_key(cluster, ref, base):
    keys = set(base) | set(ref.values)
    for g in cluster.shards:
        keys.update(g.master.store.keys())
    return sorted(keys)


def assert_replicas_match(kind, cluster, cfg, ref, base):
    """After a sync of every master, each key's value at its master and at
    each of its backups is the reference's."""
    cluster.sync_all()
    replicas = kind.read_back(cluster, cfg, every_key(cluster, ref, base),
                              base)
    bad = {k: (vals, ref.values.get(k)) for k, vals in replicas.items()
           if any(v != ref.values.get(k) for v in vals)}
    assert not bad, f"{len(bad)} keys differ, e.g. {next(iter(bad.items()))}"
    assert len(replicas[next(iter(replicas))]) == 1 + cfg["f"]


# ------------------------------------------------- program vs reference ----
CASES = [
    # (backend, seed, warehouses, batches, traffic overrides)
    ("python", 11, 2, 4, {}),
    ("python", 12, 3, 4, {}),
    ("python", 13, 4, 4, {}),
    # The interpreted record kernel compiles in time linear in its padded
    # pair count: orders of 1-2 lines keep every group within 8 pairs.
    ("device", 14, 2, 2, {"batch": 16, "ol_cnt": [1, 2]}),
]


@pytest.mark.parametrize("backend,seed,W,turns,traffic", CASES,
                         ids=[f"{c[0]}-{c[2]}w-seed{c[1]}" for c in CASES])
def test_program_matches_reference(backend, seed, W, turns, traffic):
    cfg, tr = small(W, sets=32 if backend == "device" else 64, **traffic)
    gen, kind, cluster, base = load(cfg, tr, seed, backend)
    reg = telemetry.registry()
    before = {n: reg.counter(n).value for n in COUNTERS}
    server = loops.Server(cluster, kind, loops.no_span)
    win = loops.Window()
    for i in range(turns):
        reqs = gen.batch(i)
        win.attempted += len(reqs)
        server.updates(win, reqs, 0.0)
    got = {n: reg.counter(n).value - before[n] for n in COUNTERS}

    ref = check.replay(kind, cfg, base, win.actions)
    for act, want in zip(win.actions, ref.expected):
        for req, row, exp in zip(act[1], act[2], want):
            assert row == exp, f"{req}: program {row}, reference {exp}"
    assert win.acknowledged == win.attempted
    assert got == {n: ref.counts[n] for n in COUNTERS}
    assert kind.counts["txn.batch.rounds"] == ref.counts["txn.batch.rounds"]
    dispatches = kind.counts["witness.record_dispatches"]
    if backend == "device":
        assert dispatches >= ref.counts["txn.batch.rounds"]
    else:
        assert dispatches == 0
    assert_replicas_match(kind, cluster, cfg, ref, base)

    # The traffic reached every path the comparison is meant to cover.
    rows = [(req, row) for act in win.actions
            for req, row in zip(act[1], act[2])]
    legs = Counter((req[1], len(ref.legs(req)) > 1) for req, _row in rows)
    assert legs[("new_order", False)] and legs[("payment", False)]
    assert legs[("payment", True)] and ref.counts["txn.batch.deferred"]
    if backend == "python":
        assert legs[("new_order", True)]
        assert any(row[4] == ("ROLLBACK",) for _req, row in rows)
        assert any(req[1] == "payment" and req[8] is not None
                   for req, _row in rows)
        assert any(row[0] for _req, row in rows)
        assert any(row[1] for _req, row in rows)


# ------------------------------------------------------------ recovery ----
def _stranded(gen: Generator):
    """Four multi-master transactions a coordinator left undecided, with
    warehouse w on master w - 1: a New-Order of warehouse 1 with a line
    supplied by warehouse 2, a Payment to warehouse 2 by a customer of
    warehouse 3 selected by last name, a Payment to warehouse 1 by a
    customer of warehouse 2 selected by id, and a New-Order of the first
    one's district, which its home leg's D_NEXT lock refuses."""
    names = gen.last_name_index(3, 2)
    last = sorted(names)[0]
    by_name = names[last][(len(names[last]) - 1) // 2]
    date = 2_000_000
    return [
        ("update", "new_order", "r.0", 1, 1, 7, ((5, 1, 3), (9, 2, 4)), date),
        ("update", "payment", "r.1", 2, 1, 3, 2, by_name, last, 12345, date),
        ("update", "payment", "r.2", 1, 2, 2, 3, 11, None, 777, date),
        ("update", "new_order", "r.3", 1, 1, 8, ((6, 3, 2), (5, 1, 1)), date),
    ]


def test_recovery_resolves_batched_and_stranded_transactions():
    """Crash a master after batched transactions while multi-master legs
    are prepared and not yet synced (one of them already committed at its
    other participant), recover it from its backups and a witness, and run
    ``resolve_pending``: the state must be the reference's, with every
    prepared transaction committed by re-reading the values its legs
    forward (S_DIST_xx and S_DATA of the supplying stock, the by-name
    customer's C_ID) and the refused one aborted."""
    from repro.core.txn import commit_op, forwarded_of, resolve_pending

    cfg, tr = small(3)
    gen, kind, cluster, base = load(cfg, tr, 21, "python")
    server = loops.Server(cluster, kind, loops.no_span)
    win = loops.Window()
    for i in range(2):
        server.updates(win, gen.batch(i), 0.0)
    ref = check.replay(kind, cfg, base, win.actions)

    s = server.s
    reqs = _stranded(gen)
    specs = [kind.spec(s, r) for r in reqs]
    assert all(len(sp.parts) == 2 for sp in specs)
    votes = []
    for sp in specs:
        # The refused New-Order prepares its supplying leg first, so it
        # leaves an intent that the resolution must abort.
        parts = sorted(sp.parts, key=lambda p: p.args[0] == "home")
        votes.append({p.shard_id: cluster.shards[p.shard_id].txn_prepare(
            s.session_for(p.shard_id), prepare_op(sp, p)) for p in parts})
    assert [all(v.granted for v in vs.values()) for vs in votes] \
        == [True, True, True, False]
    # The coordinator of the first commits at its home master, then dies.
    first = specs[0]
    home = next(p for p in first.parts if p.args[0] == "home")
    fwd = forwarded_of([v.read_values for v in votes[0].values()])
    cluster.shards[home.shard_id].txn_decide(
        commit_op(first, home, fwd), s.session_for(home.shard_id))

    crashed = cluster.shard_of("{2}:W")
    assert crashed == 1 and cluster.shards[crashed].master.unsynced_count
    cluster.shards[crashed].crash_master()
    assert resolve_pending(cluster) == {"resolved": 4, "committed": 3,
                                        "aborted": 1}
    for r in reqs[:3]:
        ref.commit(r)
    assert_replicas_match(kind, cluster, cfg, ref, base)


# ----------------------------------------------------------- placement ----
@pytest.mark.parametrize("key,tag", [
    ("{7}:S:12", "7"), ("a{bc}d", "bc"), ("{}x{y}", None), ("x{y", None),
    ("{a}{b}", "a"), ("plain", None), ("}{z}", "z"), ("{{q}}", "{q"),
])
def test_hash_tag_rule(key, tag):
    """Redis Cluster's rule: the text between the first ``{`` and the next
    ``}``, when not empty; the reference states the same rule."""
    assert hash_tag(key) == tag
    assert kind_mod.tag_of(key) == tag


def test_tagged_placement_agrees_with_reference():
    """Every key of a warehouse lands on the warehouse's master, by the
    router and by the reference's stated hash and slot assignment."""
    cfg, tr = small(4)
    cfg = dict(cfg, warehouses=16, masters=16)
    cluster = build(dict(cfg, witness={"sets": 16, "ways": 4}), "python")
    kind_mod.Kind().assign(cluster, cfg)
    ref = kind_mod.Reference(cfg)
    keys = [f"{{{w}}}:{t}:{i}" for w in range(1, 17)
            for t in ("S", "I", "C", "CB", "D_NEXT") for i in range(40)]
    keys += [f"{{{w}}}:W" for w in range(1, 17)]
    for k in keys:
        w = kind_mod.warehouse_of(k)
        assert cluster.shard_of(k) == ref.shard_of(k) == w - 1, k
        assert cluster.router.slot_of(k) == cluster.router.slot_of(
            f"{{{w}}}")


def test_untagged_placement_is_unchanged():
    """Keys without a tag keep their placement: the router's slot is the
    whole key's hash, as the deployments' stated hash gives it, on 10^5
    YCSB keys."""
    from chipbench.traffic.ycsb import fnvhash64
    from repro.core.types import keyhash

    keys = [f"user{n}" for n in fnvhash64(np.arange(100_000)).tolist()]
    router = SlotRouter.uniform(16, 256)
    got = np.fromiter((router.shard_of(k) for k in keys), np.int64,
                      len(keys))
    assert np.array_equal(got, shard_of_np(keys, 16, 256))
    assert all(router.slot_of(k) == router.slot_of_hash(keyhash(k))
               for k in keys[:2000])


def test_fused_path_declines_tagged_keys():
    """The fused kernel routes by the whole key's hash, so a batch with a
    tagged key takes the per-shard path, where the router places it."""
    cl = ShardedCluster(n_shards=4, f=2, witness_backend="device",
                        geometry=WitnessGeometry(16, 4))
    s = cl.new_client()
    ops = [s.op_set("{3}:x", "v"), s.op_set("plain", "v")]
    assert cl._fused.try_update_batch(s, ops) is None
    assert cl._fused.stats["declined"] == 1


# -------------------------------------------------------- record split ----
def test_record_many_split_matches_one_dispatch(monkeypatch):
    """``record_many`` split over several dispatches (as when a batch
    overflows SMEM) makes the decisions, and leaves the table, that one
    dispatch makes, where both fit."""
    import repro.kernels as kernels
    from repro.core.device_witness import record_many

    def run(fits):
        monkeypatch.setattr(kernels, "record_fits", fits)
        cl = ShardedCluster(n_shards=2, f=2, witness_backend="device",
                            geometry=WitnessGeometry(8, 2))
        s = cl.new_client()
        jobs = []
        for sid, g in enumerate(cl.shards):
            ops = [s.session_for(sid).op_hmset(f"k{sid}.{i % 13}",
                                               ((f"f{i % 3}", "v"),))
                   for i in range(24)]
            jobs += [(w, g.master.master_id, ops) for w in g.witnesses]
        reg = telemetry.registry()
        before = reg.counter("witness.record_dispatches").value
        out = record_many(jobs)
        table = [np.asarray(p) for p in cl.gang.table]
        n = reg.counter("witness.record_dispatches").value - before
        return out, table, n

    whole, t1, n1 = run(lambda g, k: True)
    split, t2, n2 = run(lambda g, k: g <= 24)
    assert (n1, n2) == (1, 4)
    assert split == whole
    assert all(np.array_equal(a, b) for a, b in zip(t1, t2))
    statuses = {st.name for per in whole for st in per}
    assert {"ACCEPTED", "REJECTED"} <= statuses


@pytest.mark.parametrize("K,most", [(8, 4096), (16, 2048), (32, 1024),
                                    (64, 512)])
def test_wide_records_pad_to_one_shape_per_width(K, most):
    """A grouped record of 8 or more keys pads its groups to the most that
    fit SMEM at its width, so every dispatch of that width compiles one
    shape; one group more does not fit, and narrower groups keep the next
    power of two."""
    from repro.kernels import record_fits
    from repro.kernels.ops import _group_words, _groups_bucket
    from repro.kernels.witness_record import smem_fits

    assert {_groups_bucket(g, K) for g in (1, 5, most // 2 + 1, most)} \
        == {most}
    assert smem_fits(_group_words(most, K))
    assert not smem_fits(_group_words(2 * most, K))
    assert record_fits(most, K) and not record_fits(most + 1, K)
    assert [_groups_bucket(g, 4) for g in (3, 5, 600)] == [4, 8, 1024]
