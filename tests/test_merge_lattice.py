"""CRDT-CURP merge-lattice tests: matrix/scalar agreement, the multi-key
same-set placement regression (ways must be RESERVED as one op claims them),
Python Witness <-> DeviceWitness decision parity on collision-heavy classed
batches, dup-rpc retries, and §4.5 stale-gc parity.

Capacity caveat baked into the parity tests: the Python witness places at
``kh % n_sets`` while the device places at the keyhash2x32-mixed low lane,
so WHICH set a key lands in legitimately differs between backends.  Conflict
and dup decisions are placement-independent; capacity (rejects_full) is not.
Every parity scenario therefore bounds per-key load well under n_ways and
asserts ``rejects_full == 0`` on BOTH backends, which makes the
decision-parity assertions sound.
"""
from __future__ import annotations

import random

import numpy as np
import pytest

from repro.core.client import ClientSession
from repro.core.merge import (
    CLS_DEL,
    CLS_HMSET,
    CLS_INCR,
    CLS_OTHER,
    CLS_READ,
    CLS_SET,
    CONFLICT_MATRIX,
    MERGEABLE,
    N_CLASSES,
    conflicts,
    op_hash_classes,
)
from repro.core.types import Op, OpType
from repro.core.witness import RecordStatus, Witness
from repro.kernels import (
    GangTable,
    conflict_matrix_np,
    gang_record_groups,
    matrix_rows,
)

from _gang import accepted, assert_tables_equal, oracle, record


def _sessions(n=4):
    return [ClientSession(client_id=i + 1) for i in range(n)]


def _device_witness(n_sets, n_ways):
    from repro.core.device_witness import DeviceWitness

    w = DeviceWitness(n_sets=n_sets, n_ways=n_ways)
    w.start(1)
    return w


# ---------------------------------------------------------------- matrix ----


def test_matrix_matches_scalar_over_all_pairs():
    rows = conflict_matrix_np()
    assert rows.shape == (N_CLASSES,)
    for a in range(N_CLASSES):
        for b in range(N_CLASSES):
            assert bool((int(rows[a]) >> b) & 1) == conflicts(a, b)
    # symmetric: merge-commutativity has no direction
    for a in range(N_CLASSES):
        for b in range(N_CLASSES):
            assert conflicts(a, b) == conflicts(b, a)


def test_matrix_rows_helper_matches_numpy_rows():
    rows = conflict_matrix_np()
    got = np.asarray(matrix_rows(np.arange(N_CLASSES, dtype=np.int32)))
    assert np.array_equal(got, rows.astype(got.dtype))


def test_read_class_commutes_only_with_reads():
    """READ (a stored procedure's read) commutes with READ alone, and the
    rows of the classes that existed before it are bit for bit what they
    were: READ joined the MERGEABLE set, and every other class already
    conflicted with the unused class 9."""
    assert not conflicts(CLS_READ, CLS_READ)
    assert all(conflicts(CLS_READ, c) and conflicts(c, CLS_READ)
               for c in range(N_CLASSES) if c != CLS_READ)
    before = frozenset(MERGEABLE - {CLS_READ})
    for a in range(CLS_OTHER + 1):
        row = sum(1 << b for b in range(N_CLASSES)
                  if not (a == b and a in before))
        assert CONFLICT_MATRIX[a] == row, a


def test_mergeable_classes_self_commute_others_conflict():
    for cls in MERGEABLE:
        assert not conflicts(cls, cls)
        assert conflicts(cls, CLS_SET)
        assert conflicts(CLS_SET, cls)
        assert conflicts(cls, CLS_DEL)
        assert conflicts(cls, CLS_OTHER)
    assert conflicts(CLS_SET, CLS_SET)


# ------------------------------------------- multi-key placement regression ----


def test_mset_same_set_keys_both_survive_recovery():
    """Satellite regression: with EVERY key forced into one set (n_sets=1),
    a 2-key MSET must claim two distinct ways — the aliasing bug seated both
    keys in the same free way, so the second overwrote the first and one
    key's record silently vanished from recovery."""
    (s,) = _sessions(1)
    w = Witness(n_sets=1, n_ways=4)
    w.start(1)
    op = s.op_mset([("ka", "1"), ("kb", "2")])
    assert len(op.keys) == 2
    assert w.record(1, op.key_hashes(), op.rpc_id, op) is RecordStatus.ACCEPTED
    # both keys occupy their own way of set 0
    held = [slot for slot in w._slots[0] if slot.occupied]
    assert len(held) == 2
    assert {slot.key_hash for slot in held} == set(op.key_hashes())
    # each key independently defends its record: a foreign SET conflicts
    for key in ("ka", "kb"):
        probe = s.op_set(key, "x")
        assert (w.record(1, probe.key_hashes(), probe.rpc_id, probe)
                is RecordStatus.REJECTED)
    got = w.get_recovery_data(1)
    assert [o.rpc_id for o in got] == [op.rpc_id]


def test_gang_kernel_reserves_ways_for_same_set_group():
    """Kernel side of the same regression: one group carrying two DISTINCT
    keys whose placement collides into one set must occupy two ways."""
    n_sets = 8
    # brute-force two raw keyhashes placed in the same set row (the gang
    # places by the raw low lane, like the Python witness's kh % n_sets)
    target = None
    seen = {}
    for raw in range(1, 4096):
        hi, lo = np.uint32(raw * 2654435761 % 2 ** 32), np.uint32(raw)
        srow = int(lo) & (n_sets - 1)
        if srow in seen and seen[srow][:2] != (int(hi), int(lo)):
            target = (seen[srow], (int(hi), int(lo), srow))
            break
        seen.setdefault(srow, (int(hi), int(lo), srow))
    assert target is not None
    (h1, l1, srow), (h2, l2, srow2) = target
    assert srow == srow2 and (h1, l1) != (h2, l2)

    table = GangTable.empty(n_sets, 4, 1)
    res = gang_record_groups(
        table, n_sets,
        key_hi=[[h1, h2]], key_lo=[[l1, l2]], key_valid=[[1, 1]],
        lanes=[0], rpc_hi=[7], rpc_lo=[1], key_cls=[[CLS_SET, CLS_SET]],
    )
    assert int(res.reasons[0]) == 1  # REASON_INSERT: accepted
    occ_row = np.asarray(res.table.occ)[:, srow]      # planes are [W, R]
    assert int((occ_row > 0).sum()) == 2, (
        "same-set keys of one group must reserve distinct ways"
    )
    held_keys = {
        (int(np.asarray(res.table.keys_hi)[wy, srow]),
         int(np.asarray(res.table.keys_lo)[wy, srow]))
        for wy in range(4) if occ_row[wy] > 0
    }
    assert held_keys == {(int(res.q_hi[0, 0]), int(res.q_lo[0, 0])),
                         (int(res.q_hi[0, 1]), int(res.q_lo[0, 1]))}


# --------------------------------------------------- kernel/oracle parity ----


def test_record_kernel_matches_oracle_on_classed_collisions():
    _kernel_matches_oracle((CLS_SET, CLS_DEL, CLS_INCR, CLS_INCR, CLS_INCR))


def test_record_kernel_matches_oracle_with_read_class():
    _kernel_matches_oracle((CLS_SET, CLS_READ, CLS_READ, CLS_INCR, CLS_READ))


def _kernel_matches_oracle(classes):
    rng = np.random.default_rng(5)
    base_hi = rng.integers(0, 2 ** 32, size=6, dtype=np.uint32)
    base_lo = rng.integers(0, 2 ** 32, size=6, dtype=np.uint32)
    pick = rng.integers(0, 6, size=128)
    q_hi, q_lo = base_hi[pick], base_lo[pick]
    q_cls = rng.choice(np.array(classes, dtype=np.int32), size=128)
    table = GangTable.empty(32, 16)
    rsn_ref, t_ref = oracle(table, 32, q_hi, q_lo, q_cls)
    rsn_dev, t_dev = record(table, 32, q_hi, q_lo, q_cls)
    assert np.array_equal(rsn_ref, rsn_dev)
    assert_tables_equal(t_ref, t_dev)
    acc = accepted(rsn_ref)
    assert 0 < int(acc.sum()) < len(acc)


def test_record_kernel_consults_the_predicate_for_every_class_pair():
    """The kernel's conflict-row consult agrees with the Python predicate
    on every ordered pair of SET, INCR, HMSET, OTHER and READ: a key held
    under class ``a`` accepts a second record of class ``b`` iff the two
    commute."""
    classes = (CLS_SET, CLS_INCR, CLS_HMSET, CLS_OTHER, CLS_READ)
    pairs = [(a, b) for a in classes for b in classes]
    rng = np.random.default_rng(17)
    k_hi = rng.integers(0, 2 ** 32, size=len(pairs), dtype=np.uint32)
    k_lo = rng.integers(0, 2 ** 32, size=len(pairs), dtype=np.uint32)
    q_hi, q_lo = np.repeat(k_hi, 2), np.repeat(k_lo, 2)
    q_cls = np.array(pairs, dtype=np.int32).reshape(-1)
    table = GangTable.empty(64, 8)
    rsn_dev, _ = record(table, 64, q_hi, q_lo, q_cls)
    rsn_ref, _ = oracle(table, 64, q_hi, q_lo, q_cls)
    acc = accepted(rsn_dev).reshape(-1, 2)
    assert np.array_equal(accepted(rsn_ref).reshape(-1, 2), acc)
    assert acc[:, 0].all()
    assert [bool(x) for x in acc[:, 1]] == [not conflicts(a, b)
                                            for a, b in pairs]


def test_all_set_batch_keeps_legacy_occ_encoding():
    """CLS_SET == 0, so a classless (all-SET) gang must hold the same
    planes as one recorded without classes, with a 0/1 occupancy plane."""
    rng = np.random.default_rng(9)
    q_hi = rng.integers(0, 2 ** 32, size=64, dtype=np.uint32)
    q_lo = rng.integers(0, 2 ** 32, size=64, dtype=np.uint32)
    table = GangTable.empty(32, 4)
    _, t_cls = record(table, 32, q_hi, q_lo, np.zeros(64, np.int32))
    _, t_legacy = record(table, 32, q_hi, q_lo)      # key_cls defaulted
    occ = np.asarray(t_cls.occ)
    assert set(np.unique(occ)) <= {0, 1}
    assert_tables_equal(t_cls, t_legacy)


# ----------------------------------------- Witness <-> DeviceWitness parity ----


def _collision_heavy_ops(seed, n_ops=72, n_keys=8, incr_cap=6):
    """INCR/INCR stacks + SET/INCR mixes over few keys; per-key mergeable
    load stays under incr_cap so capacity never decides (see module doc)."""
    sessions = _sessions(4)
    rng = random.Random(seed)
    keys = [f"k{i}" for i in range(n_keys)]
    per_key = {k: 0 for k in keys}
    ops = []
    for _ in range(n_ops):
        s = rng.choice(sessions)
        k = rng.choice(keys)
        if rng.random() < 0.7 and per_key[k] < incr_cap:
            per_key[k] += 1
            ops.append(s.op_incr(k, 1))
        else:
            ops.append(s.op_set(k, "v"))
    return ops


@pytest.mark.parametrize("seed", [3, 11])
def test_python_vs_device_decision_parity(seed):
    pyw = Witness(n_sets=64, n_ways=16)
    pyw.start(1)
    dw = _device_witness(64, 16)
    for op in _collision_heavy_ops(seed):
        a = pyw.record(1, op.key_hashes(), op.rpc_id, op)
        b = dw.record(1, op.key_hashes(), op.rpc_id, op)
        assert a is b, f"decision diverged on {op.op_type} {op.keys}: {a}/{b}"
    assert pyw.stats["rejects_full"] == 0
    assert dw.stats["rejects_full"] == 0
    assert pyw.stats["accepts"] == dw.stats["accepts"]
    # same surviving rpc set on both sides
    pa = {o.rpc_id for o in pyw.get_recovery_data(1)}
    da = {o.rpc_id for o in dw.get_recovery_data(1)}
    assert pa == da


def test_device_batch_matches_python_sequential():
    """record_batch (one gang dispatch) must make the same decisions as the
    Python witness fed the same ops one at a time, in batch order."""
    ops = _collision_heavy_ops(seed=21, n_ops=48)
    pyw = Witness(n_sets=64, n_ways=16)
    pyw.start(1)
    dw = _device_witness(64, 16)
    want = [pyw.record(1, op.key_hashes(), op.rpc_id, op) for op in ops]
    got = dw.record_batch(1, ops)
    assert got == want
    assert pyw.stats["rejects_full"] == 0
    assert dw.stats["rejects_full"] == 0


def test_dup_rpc_retry_parity():
    """A retried rpc (same RIFL id) is idempotently ACCEPTED by both
    backends and holds exactly one record."""
    (s,) = _sessions(1)
    pyw = Witness(n_sets=16, n_ways=8)
    pyw.start(1)
    dw = _device_witness(16, 8)
    op = s.op_incr("ctr", 1)
    for w in (pyw, dw):
        assert w.record(1, op.key_hashes(), op.rpc_id, op) is RecordStatus.ACCEPTED
        assert w.record(1, op.key_hashes(), op.rpc_id, op) is RecordStatus.ACCEPTED
    assert len(pyw.get_recovery_data(1)) == 1
    assert len(dw.get_recovery_data(1)) == 1


def test_stale_gc_suspicion_parity():
    """§4.5: both backends must suspect the SAME records as uncollected
    garbage after SUSPECT_AGE unserviced gc rounds, and a gc that names the
    record must clear it on both (mergeable stacks included)."""
    sessions = _sessions(2)
    pyw = Witness(n_sets=16, n_ways=8)
    pyw.start(1)
    dw = _device_witness(16, 8)
    ops = [sessions[0].op_incr("hot", 1), sessions[1].op_incr("hot", 1),
           sessions[0].op_set("cold", "v")]
    for op in ops:
        assert pyw.record(1, op.key_hashes(), op.rpc_id, op) is RecordStatus.ACCEPTED
        assert dw.record(1, op.key_hashes(), op.rpc_id, op) is RecordStatus.ACCEPTED
    # gc away ONE of the stacked INCRs; the other two records age out
    entries = tuple((kh, ops[0].rpc_id)
                    for kh, _cls in op_hash_classes(ops[0]))
    assert pyw.gc(entries).stale_requests == ()
    assert dw.gc(entries).stale_requests == ()
    for rnd in range(Witness.SUSPECT_AGE + 1):
        p = pyw.gc(())
        d = dw.gc(())
        assert ({o.rpc_id for o in p.stale_requests}
                == {o.rpc_id for o in d.stale_requests}), f"round {rnd}"
    # the aged-out survivors are exactly the two un-gc'd ops
    assert ({o.rpc_id for o in p.stale_requests}
            == {ops[1].rpc_id, ops[2].rpc_id})


def test_mixed_set_incr_conflict_is_order_dependent_but_parity_holds():
    """SET-then-INCR and INCR-then-SET both conflict (matrix is symmetric for
    SET vs INCR), while INCR-then-INCR stacks — on both backends."""
    sessions = _sessions(3)
    for first_kind in ("SET", "INCR"):
        pyw = Witness(n_sets=16, n_ways=8)
        pyw.start(1)
        dw = _device_witness(16, 8)
        mk = {"SET": lambda s: s.op_set("k", "v"),
              "INCR": lambda s: s.op_incr("k", 1)}
        first = mk[first_kind](sessions[0])
        second = mk["INCR" if first_kind == "SET" else "SET"](sessions[1])
        third = sessions[2].op_incr("k", 1)
        for w in (pyw, dw):
            assert w.record(1, first.key_hashes(), first.rpc_id,
                            first) is RecordStatus.ACCEPTED
            assert w.record(1, second.key_hashes(), second.rpc_id,
                            second) is RecordStatus.REJECTED
            expect = (RecordStatus.ACCEPTED if first_kind == "INCR"
                      else RecordStatus.REJECTED)
            assert w.record(1, third.key_hashes(), third.rpc_id,
                            third) is expect
