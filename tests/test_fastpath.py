"""Fast-path pipeline tests: set-parallel kernel parity on adversarial
batches, the fused fastpath_batch op, buffer-donation round-trips, and the
batched client path (update_batch / commit_batch) on both witness backends.

Property tests go through the _hyp shim (skips cleanly without hypothesis);
each has a deterministic companion so the invariants stay covered either way.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st

from repro.core import (
    DeviceWitness,
    ShardedCluster,
    Witness,
    WitnessGeometry,
)
from repro.core.types import RecordStatus
from repro.kernels import (
    WitnessTable,
    dispatch_count,
    fastpath_batch,
    ref_conflict_scan,
    ref_keyhash2x32,
    ref_witness_record,
    reset_dispatch_count,
    witness_gc,
    witness_record,
    witness_record_seq,
)


def rng(seed=0):
    return np.random.default_rng(seed)


def assert_tables_equal(a: WitnessTable, b: WitnessTable):
    np.testing.assert_array_equal(np.asarray(a.occ), np.asarray(b.occ))
    np.testing.assert_array_equal(np.asarray(a.keys_hi), np.asarray(b.keys_hi))
    np.testing.assert_array_equal(np.asarray(a.keys_lo), np.asarray(b.keys_lo))


class TestSetParallelParity:
    """The set-parallel kernel is bit-exact with ref_witness_record."""

    @pytest.mark.parametrize("sets,ways,batch,kspan,span", [
        (16, 2, 200, 4, 8),          # duplicate keys, tiny keyspace
        (16, 4, 300, 6, 4),          # capacity-full sets
        (64, 4, 512, 2**32 - 1, 64),  # every set overcommitted
        (1024, 4, 1000, 2**32 - 1, 2**32 - 1),
        (128, 2, 127, 3, 3),         # odd batch (bucket-padding path)
    ])
    def test_collision_heavy_matches_oracle(self, sets, ways, batch,
                                            kspan, span):
        r = rng(sets + batch)
        t = WitnessTable.empty(sets, ways)
        qh = r.integers(0, kspan, batch).astype(np.uint32)
        ql = r.integers(0, span, batch).astype(np.uint32)
        acc_k, t_k = witness_record(t, qh, ql)
        acc_r, t_r = ref_witness_record(t, jnp.asarray(qh), jnp.asarray(ql))
        np.testing.assert_array_equal(np.asarray(acc_k), np.asarray(acc_r))
        assert_tables_equal(t_k, t_r)
        # ... and with the pre-refactor sequential kernel.
        acc_s, t_s = witness_record_seq(t, qh, ql)
        np.testing.assert_array_equal(np.asarray(acc_s), np.asarray(acc_r))
        assert_tables_equal(t_s, t_r)

    def test_duplicate_keys_single_batch(self):
        """Same key B times in one batch: exactly one accept (the first)."""
        t = WitnessTable.empty(16, 4)
        qh = np.full(9, 7, np.uint32)
        ql = np.full(9, 3, np.uint32)
        acc, t2 = witness_record(t, qh, ql)
        assert np.asarray(acc).tolist() == [1] + [0] * 8
        assert int(np.asarray(t2.occ).sum()) == 1

    def test_full_set_capacity_rejects(self):
        """W+k distinct keys probing one set: exactly W accepts, in order."""
        t = WitnessTable.empty(16, 4)
        S = 16
        qh = np.arange(7, dtype=np.uint32)           # distinct keys
        ql = np.full(7, 5, np.uint32)                # same set (5 & 15)
        acc, t2 = witness_record(t, qh, ql)
        assert np.asarray(acc).tolist() == [1, 1, 1, 1, 0, 0, 0]
        assert int(np.asarray(t2.occ)[5].sum()) == 4

    def test_cross_set_permutation_invariance(self):
        """Permuting ops of OTHER sets never changes an op's accept bit —
        the set-level independence the kernel parallelizes over."""
        r = rng(3)
        S, B = 16, 240
        t = WitnessTable.empty(S, 4)
        qh = r.integers(0, 6, B).astype(np.uint32)
        ql = r.integers(0, 64, B).astype(np.uint32)
        acc0, t0 = witness_record(t, qh, ql)
        sets = ql & (S - 1)
        # Stable-sort by set id: reorders across sets, preserves order within.
        perm = np.argsort(sets, kind="stable")
        acc1, t1 = witness_record(t, qh[perm], ql[perm])
        np.testing.assert_array_equal(np.asarray(acc0)[perm],
                                      np.asarray(acc1))
        assert_tables_equal(t0, t1)

    @pytest.mark.parametrize("tile_sets,sets", [(64, 256), (32, 128)])
    def test_multi_cell_grid_matches_oracle(self, tile_sets, sets):
        """Grids with several set-tiles (tile_sets < n_sets): the per-tile
        masking + accumulate-on-revisit accept vector must stay bit-exact."""
        r = rng(tile_sets + sets)
        t = WitnessTable.empty(sets, 4)
        qh = r.integers(0, 16, 600).astype(np.uint32)
        ql = r.integers(0, sets * 5, 600).astype(np.uint32)
        acc_k, t_k = witness_record(t, qh, ql, tile_sets=tile_sets)
        acc_r, t_r = ref_witness_record(t, jnp.asarray(qh), jnp.asarray(ql))
        np.testing.assert_array_equal(np.asarray(acc_k), np.asarray(acc_r))
        assert_tables_equal(t_k, t_r)

    def test_non_dividing_tile_rejected(self):
        t = WitnessTable.empty(256, 4)
        with pytest.raises(AssertionError):
            witness_record(t, np.zeros(4, np.uint32), np.zeros(4, np.uint32),
                           tile_sets=96)

    @settings(deadline=None, max_examples=25)
    @given(seed=st.integers(0, 10_000), sets=st.sampled_from([16, 64, 256]),
           ways=st.sampled_from([2, 4, 8]), batch=st.integers(1, 300),
           kspan=st.sampled_from([2, 5, 2**32 - 1]))
    def test_property_matches_oracle(self, seed, sets, ways, batch, kspan):
        r = rng(seed)
        t = WitnessTable.empty(sets, ways)
        qh = r.integers(0, kspan, batch).astype(np.uint32)
        ql = r.integers(0, max(2, sets * 3), batch).astype(np.uint32)
        acc_k, t_k = witness_record(t, qh, ql)
        acc_r, t_r = ref_witness_record(t, jnp.asarray(qh), jnp.asarray(ql))
        np.testing.assert_array_equal(np.asarray(acc_k), np.asarray(acc_r))
        assert_tables_equal(t_k, t_r)

    @settings(deadline=None, max_examples=15)
    @given(seed=st.integers(0, 10_000))
    def test_property_permutation_invariance(self, seed):
        r = rng(seed)
        S, B = 32, 100
        t = WitnessTable.empty(S, 2)
        qh = r.integers(0, 4, B).astype(np.uint32)
        ql = r.integers(0, 128, B).astype(np.uint32)
        acc0, _ = witness_record(t, qh, ql)
        perm = np.argsort(ql & (S - 1), kind="stable")
        acc1, _ = witness_record(t, qh[perm], ql[perm])
        np.testing.assert_array_equal(np.asarray(acc0)[perm],
                                      np.asarray(acc1))


class TestGcDonationRoundTrip:
    def test_record_gc_record_no_stale_occupancy(self):
        """record -> gc -> record round-trips: gc leaves no stale occupancy
        and a full re-record of the same keys is accepted again."""
        r = rng(9)
        t = WitnessTable.empty(64, 4)
        qh = r.integers(0, 2**32, 120).astype(np.uint32)
        ql = np.arange(120, dtype=np.uint32)       # distinct sets mod 64? no:
        acc1, t = witness_record(t, qh, ql)        # 2 rounds over 64 sets
        occupied = int(np.asarray(t.occ).sum())
        assert occupied == int(np.asarray(acc1).sum()) > 0
        t = witness_gc(t, qh, ql)
        assert int(np.asarray(t.occ).sum()) == 0   # no stale occupancy
        acc2, t = witness_record(t, qh, ql)
        np.testing.assert_array_equal(np.asarray(acc2), np.asarray(acc1))

    def test_gc_then_accept_chain_reuses_table(self):
        """Functional chain that rebinds the table each call (the donation
        pattern): many record/gc cycles stay self-consistent."""
        t = WitnessTable.empty(16, 2)
        qh = np.array([5, 6, 7], np.uint32)
        ql = np.array([1, 2, 3], np.uint32)
        for _ in range(5):
            acc, t = witness_record(t, qh, ql)
            assert np.asarray(acc).tolist() == [1, 1, 1]
            t = witness_gc(t, qh, ql)
        assert int(np.asarray(t.occ).sum()) == 0


class TestFusedFastPath:
    def test_single_dispatch_per_batch(self):
        t = WitnessTable.empty(64, 4)
        r = rng(1)
        khi = r.integers(0, 2**32, 33).astype(np.uint32)
        klo = r.integers(0, 2**32, 33).astype(np.uint32)
        fastpath_batch(t, khi, klo)            # warm
        reset_dispatch_count()
        fastpath_batch(t, khi, klo)
        assert dispatch_count() == 1
        reset_dispatch_count()

    def test_matches_unfused_pipeline(self):
        """fastpath_batch == keyhash2x32 -> record -> conflict_scan, bit for
        bit, including shard routing."""
        r = rng(5)
        t = WitnessTable.empty(128, 4)
        khi = r.integers(0, 2**32, 70).astype(np.uint32)
        klo = r.integers(0, 2**32, 70).astype(np.uint32)
        res = fastpath_batch(t, khi, klo, n_shards=4)
        qh, ql = ref_keyhash2x32(jnp.asarray(khi), jnp.asarray(klo))
        acc_r, t_r = ref_witness_record(t, qh, ql)
        np.testing.assert_array_equal(np.asarray(res.accepted),
                                      np.asarray(acc_r))
        assert_tables_equal(res.table, t_r)
        np.testing.assert_array_equal(
            np.asarray(res.shard_ids),
            np.asarray((ql % jnp.uint32(4)).astype(jnp.int32)))
        # Window conflicts against previously recorded mixed lanes.
        wv = np.ones(10, np.int32)
        res2 = fastpath_batch(res.table, khi[:20], klo[:20],
                              window_hi=res.q_hi[:10],
                              window_lo=res.q_lo[:10], window_valid=wv)
        con_r = ref_conflict_scan(res.q_hi[:10], res.q_lo[:10],
                                  jnp.asarray(wv), qh[:20], ql[:20])
        np.testing.assert_array_equal(np.asarray(res2.conflicts),
                                      np.asarray(con_r))

    def test_window_valid_defaults_to_all_live(self):
        """window_valid omitted => every window entry counts; partial window
        specs fail loudly instead of deep in jnp."""
        r = rng(8)
        t = WitnessTable.empty(64, 4)
        khi = r.integers(0, 2**32, 12).astype(np.uint32)
        klo = r.integers(0, 2**32, 12).astype(np.uint32)
        res = fastpath_batch(t, khi, klo)
        res2 = fastpath_batch(res.table, khi[:6], klo[:6],
                              window_hi=res.q_hi[:4], window_lo=res.q_lo[:4])
        con_r = ref_conflict_scan(
            res.q_hi[:4], res.q_lo[:4], jnp.ones(4, jnp.int32),
            res.q_hi[:6], res.q_lo[:6])
        np.testing.assert_array_equal(np.asarray(res2.conflicts),
                                      np.asarray(con_r))
        with pytest.raises(ValueError):
            fastpath_batch(t, khi, klo, window_hi=res.q_hi[:4])
        with pytest.raises(ValueError):
            fastpath_batch(t, khi, klo, window_lo=res.q_lo[:4])

    def test_shard_route_matches_key_router(self):
        from repro.core.shard import KeyRouter
        from repro.core.types import keyhash

        keys = [f"s{i}" for i in range(64)]
        khs = [keyhash(k) for k in keys]
        hi = np.array([(h >> 32) & 0xFFFFFFFF for h in khs], np.uint32)
        lo = np.array([h & 0xFFFFFFFF for h in khs], np.uint32)
        res = fastpath_batch(WitnessTable.empty(64, 4), hi, lo, n_shards=3)
        router = KeyRouter(3)
        np.testing.assert_array_equal(
            np.asarray(res.shard_ids),
            np.array([router.shard_of(k) for k in keys]))


class TestTxnProbe:
    """All-or-nothing multi-key record: one dispatch on accept AND reject."""

    def _oracle(self, table, hi, lo, own=None):
        from repro.kernels import ref_witness_record_txn
        from repro.kernels.ops import _pad_valid

        (K,) = np.asarray(hi).shape
        qh, ql = ref_keyhash2x32(jnp.asarray(hi, jnp.uint32),
                                 jnp.asarray(lo, jnp.uint32))
        own = np.zeros(K, np.int32) if own is None else np.asarray(own)
        qhp, qlp, ownp, valid = _pad_valid(K, np.asarray(qh), np.asarray(ql),
                                           own)
        return ref_witness_record_txn(
            table, jnp.asarray(qhp), jnp.asarray(qlp), jnp.asarray(ownp),
            jnp.asarray(valid))

    def test_accept_and_reject_single_dispatch(self):
        from repro.kernels import txn_probe

        t = WitnessTable.empty(16, 2)
        hi = np.array([1, 2, 3], np.uint32)
        lo = np.array([1, 2, 3], np.uint32)
        txn_probe(t, hi, lo)            # warm the jit cache
        reset_dispatch_count()
        res = txn_probe(t, hi, lo)
        assert res.accepted and dispatch_count() == 1
        reset_dispatch_count()
        # Conflict: same keys again (different op) — rejects, still 1 call.
        res2 = txn_probe(res.table, hi, lo)
        assert not res2.accepted and dispatch_count() == 1
        reset_dispatch_count()

    def test_reject_leaves_table_bit_identical(self):
        from repro.kernels import txn_probe

        r = rng(4)
        t = WitnessTable.empty(16, 2)
        res = txn_probe(t, np.array([7], np.uint32), np.array([7], np.uint32))
        t = res.table
        # Op with one fresh key and one conflicting key: must reject and
        # leave the table untouched (no partial insert, no rollback).
        res2 = txn_probe(t, np.array([5, 7], np.uint32),
                         np.array([5, 7], np.uint32))
        assert not res2.accepted
        assert_tables_equal(res2.table, t)

    @pytest.mark.parametrize("sets,ways,kspan", [
        (8, 2, 4), (16, 4, 6), (64, 4, 3),
    ])
    def test_matches_oracle_collision_heavy(self, sets, ways, kspan):
        from repro.kernels import txn_probe

        r = rng(sets + ways)
        table = WitnessTable.empty(sets, ways)
        oracle = WitnessTable.empty(sets, ways)
        for i in range(80):
            K = int(r.integers(1, 7))
            hi = r.integers(0, kspan, K).astype(np.uint32)
            lo = r.integers(0, kspan, K).astype(np.uint32)
            res = txn_probe(table, hi, lo)
            acc_r, hit_r, oracle = self._oracle(oracle, hi, lo)
            assert res.accepted == bool(np.asarray(acc_r)[0]), i
            np.testing.assert_array_equal(np.asarray(res.hit),
                                          np.asarray(hit_r)[:K])
            table = res.table
            assert_tables_equal(table, oracle)

    def test_own_bit_makes_retry_idempotent(self):
        from repro.kernels import txn_probe

        t = WitnessTable.empty(16, 4)
        hi = np.array([3, 4], np.uint32)
        lo = np.array([3, 4], np.uint32)
        res = txn_probe(t, hi, lo)
        assert res.accepted
        # Same op retried without own bits: same-key hits -> conflict.
        res2 = txn_probe(res.table, hi, lo)
        assert not res2.accepted
        # With own bits (the caller knows these are its keys): accepted,
        # table unchanged (keys already placed).
        res3 = txn_probe(res.table, hi, lo, own=np.array([1, 1], np.int32))
        assert res3.accepted
        assert np.asarray(res3.hit).tolist() == [1, 1]
        assert_tables_equal(res3.table, res.table)

    def test_capacity_reject_all_or_nothing(self):
        from repro.kernels import txn_probe

        t = WitnessTable.empty(1, 2)    # one set, two ways
        # Fill both ways with two separate single-key ops (keys of ONE op
        # compute placement against the pre-op state — Python Witness
        # semantics — so one 2-key op would land in a single way).
        for k in (1, 2):
            res = txn_probe(t, np.array([k], np.uint32),
                            np.array([k], np.uint32))
            assert res.accepted
            t = res.table
        assert int(np.asarray(t.occ).sum()) == 2
        res2 = txn_probe(t, np.array([9, 10], np.uint32),
                         np.array([9, 10], np.uint32))
        assert not res2.accepted        # capacity: whole op rejected
        assert_tables_equal(res2.table, t)

    def test_device_witness_multikey_one_dispatch_no_rollback(self):
        """DeviceWitness multi-key records go through the probe: 1 kernel
        dispatch whether the op accepts or rejects (the old path paid 2 on
        reject), with statuses identical to the rollback implementation."""
        from repro.core import DeviceWitness
        from repro.core.types import Op, OpType

        def fresh():
            w = DeviceWitness(64, 4)
            w.start(1)
            w.record(1, (7,), (1, 1), Op(OpType.SET, ("x",), (0,), (1, 1)))
            return w

        reject_op = Op(OpType.MSET, ("a", "b"), (1, 2), (2, 1))
        w = fresh()
        reset_dispatch_count()
        st = w._record_keys((5, 7), reject_op.rpc_id, reject_op)
        assert dispatch_count() == 1
        w2 = fresh()
        reset_dispatch_count()
        st2 = w2._record_keys_rollback((5, 7), reject_op.rpc_id, reject_op)
        assert dispatch_count() == 2
        assert st == st2
        # Mirror and stats agree with the Python reference on the reject.
        assert w.stats["rejects_conflict"] == 1
        assert w.occupancy == w2.occupancy == 1


class TestDeviceWitness:
    def test_matches_python_witness_semantics(self):
        from repro.core.client import ClientSession

        s = ClientSession(client_id=1)
        ops = [s.op_set(f"k{i % 5}", "v") for i in range(20)]
        pw = Witness(64, 4)
        dw = DeviceWitness(64, 4)
        pw.start(master_id=9)
        dw.start(master_id=9)
        st_p = pw.record_batch(9, ops)
        st_d = dw.record_batch(9, ops)
        assert st_p == st_d
        assert pw.occupancy == dw.occupancy == 5

    def test_duplicate_retry_idempotent_accept(self):
        from repro.core.client import ClientSession

        s = ClientSession(client_id=2)
        op = s.op_set("x", "v")
        dw = DeviceWitness(16, 2)
        dw.start(master_id=1)
        assert dw.record(1, op.key_hashes(), op.rpc_id, op) \
            is RecordStatus.ACCEPTED
        # Same rpc retry: idempotent accept; different rpc: conflict.
        assert dw.record(1, op.key_hashes(), op.rpc_id, op) \
            is RecordStatus.ACCEPTED
        op2 = s.op_set("x", "w")
        assert dw.record(1, op2.key_hashes(), op2.rpc_id, op2) \
            is RecordStatus.REJECTED

    def test_stale_gc_never_drops_newer_record(self):
        from repro.core.client import ClientSession

        s = ClientSession(client_id=3)
        op1 = s.op_set("k", "a")
        dw = DeviceWitness(16, 2)
        dw.start(master_id=1)
        dw.record(1, op1.key_hashes(), op1.rpc_id, op1)
        dw.gc(tuple((kh, op1.rpc_id) for kh in op1.key_hashes()))
        op2 = s.op_set("k", "b")
        assert dw.record(1, op2.key_hashes(), op2.rpc_id, op2) \
            is RecordStatus.ACCEPTED
        # gc carrying op1's (stale) rpc must NOT drop op2's record.
        dw.gc(tuple((kh, op1.rpc_id) for kh in op1.key_hashes()))
        assert dw.occupancy == 1
        assert not dw.commutes_with_all(op2.key_hashes())

    def test_mixed_batch_preserves_order_vs_python(self):
        """A batch interleaving multi-key and single-key ops must resolve in
        batch order on both backends (regression: the device path used to
        record all single-key ops first)."""
        from repro.core.client import ClientSession

        s = ClientSession(client_id=7)
        ops = [
            s.op_mset([("a", "1"), ("b", "2")]),   # takes a+b
            s.op_set("a", "3"),                    # conflicts with the mset
            s.op_set("c", "4"),
            s.op_mset([("c", "5"), ("d", "6")]),   # conflicts on c
            s.op_set("d", "7"),                    # d is free (mset rolled back)
        ]
        pw, dw = Witness(64, 4), DeviceWitness(64, 4)
        pw.start(master_id=1)
        dw.start(master_id=1)
        st_p = pw.record_batch(1, ops)
        st_d = dw.record_batch(1, ops)
        assert st_d == st_p
        assert st_p == [RecordStatus.ACCEPTED, RecordStatus.REJECTED,
                        RecordStatus.ACCEPTED, RecordStatus.REJECTED,
                        RecordStatus.ACCEPTED]

    def test_repeated_key_within_one_op_accepted(self):
        """An op listing the same key twice occupies one slot and is
        accepted — parity with the Python witness (regression)."""
        from repro.core.client import ClientSession

        s = ClientSession(client_id=11)
        op = s.op_mset([("a", "1"), ("a", "2")])
        for w in (Witness(64, 4), DeviceWitness(64, 4)):
            w.start(master_id=1)
            assert w.record(1, op.key_hashes(), op.rpc_id, op) \
                is RecordStatus.ACCEPTED
            assert w.occupancy == 1

    def test_multikey_retry_after_partial_gc_accepted(self):
        """Retrying an accepted multi-key op after one of its keys was gc'd:
        the still-held key is an idempotent hit, the gc'd key re-inserts —
        ACCEPTED on both backends (regression)."""
        from repro.core.client import ClientSession

        s = ClientSession(client_id=12)
        op = s.op_mset([("p", "1"), ("q", "2")])
        kh_p = op.key_hashes()[0]
        for w in (Witness(64, 4), DeviceWitness(64, 4)):
            w.start(master_id=1)
            assert w.record(1, op.key_hashes(), op.rpc_id, op) \
                is RecordStatus.ACCEPTED
            w.gc(((kh_p, op.rpc_id),))           # drop only key p
            assert w.record(1, op.key_hashes(), op.rpc_id, op) \
                is RecordStatus.ACCEPTED
            assert w.occupancy == 2

    def test_record_batch_wrong_master_rejected(self):
        """record_batch addressed to the wrong master must reject everything
        (same guard as the per-op path)."""
        from repro.core.client import ClientSession

        s = ClientSession(client_id=8)
        ops = [s.op_set("x", "v")]
        for w in (Witness(16, 2), DeviceWitness(16, 2)):
            w.start(master_id=42)
            assert w.record_batch(99, ops) == [RecordStatus.REJECTED]
            assert w.record_batch(42, ops) == [RecordStatus.ACCEPTED]

    def test_recovery_data_and_suspects(self):
        from repro.core.client import ClientSession

        s = ClientSession(client_id=4)
        ops = [s.op_set(f"r{i}", "v") for i in range(4)]
        dw = DeviceWitness(64, 4)
        dw.start(master_id=1)
        dw.record_batch(1, ops)
        # Age past SUSPECT_AGE with unrelated gcs -> stale reports.
        stale = ()
        for _ in range(DeviceWitness.SUSPECT_AGE):
            stale = dw.gc(()).stale_requests
        assert {o.rpc_id for o in stale} == {o.rpc_id for o in ops}
        rec = dw.get_recovery_data(1)
        assert {o.rpc_id for o in rec} == {o.rpc_id for o in ops}
        # Frozen after recovery handoff.
        op = s.op_set("z", "v")
        assert dw.record(1, op.key_hashes(), op.rpc_id, op) \
            is RecordStatus.REJECTED


class TestBatchedClientPath:
    @pytest.mark.parametrize("backend", ["python", "device"])
    def test_update_batch_accounting(self, backend):
        c = ShardedCluster(n_shards=2, f=3, witness_backend=backend,
                           geometry=WitnessGeometry(256, 4))
        s = c.new_client()
        ops = [s.op_set(f"k{i}", "v") for i in range(30)]
        outs = c.update_batch(s, ops)
        assert len(outs) == 30
        assert all(o.fast_path and o.rtts == 1 for o in outs)
        assert all(o.witness_accepts == 3 for o in outs)

    @pytest.mark.parametrize("backend", ["python", "device"])
    def test_update_batch_same_key_conflicts(self, backend):
        c = ShardedCluster(n_shards=1, f=3, witness_backend=backend)
        s = c.new_client()
        ops = [s.op_set("dup", "a"), s.op_set("dup", "b"),
               s.op_set("other", "c")]
        outs = c.update_batch(s, ops)
        assert [o.fast_path for o in outs] == [True, False, True]
        assert [o.rtts for o in outs] == [1, 2, 1]

    @pytest.mark.parametrize("backend", ["python", "device"])
    def test_update_batch_then_crash_recovers(self, backend):
        c = ShardedCluster(n_shards=2, f=3, witness_backend=backend,
                           auto_sync=False)
        s = c.new_client()
        c.update_batch(s, [s.op_set(f"k{i}", f"v{i}") for i in range(12)])
        for shard in range(2):
            c.crash_master(shard)
        for i in range(12):
            assert c.read(s, s.op_get(f"k{i}")).value == f"v{i}"

    def test_batch_matches_per_op_decisions(self):
        """Batched and per-op paths agree on fast/slow classification for a
        conflict-free workload (same keys, fresh clusters)."""
        keys = [f"q{i}" for i in range(20)]
        c1 = ShardedCluster(n_shards=2, f=3)
        s1 = c1.new_client()
        per_op = [c1.update(s1, s1.op_set(k, "v")).fast_path for k in keys]
        c2 = ShardedCluster(n_shards=2, f=3)
        s2 = c2.new_client()
        batched = [o.fast_path for o in
                   c2.update_batch(s2, [s2.op_set(k, "v") for k in keys])]
        assert per_op == batched

    def test_dropped_witness_forces_slow_path(self):
        c = ShardedCluster(n_shards=1, f=3)
        s = c.new_client()
        c.shards[0].witness_drop(0)
        outs = c.update_batch(s, [s.op_set("a", "1"), s.op_set("b", "2")])
        assert all(not o.fast_path and o.rtts == 2 for o in outs)
        assert all(o.witness_accepts == 2 for o in outs)

    def test_update_batch_rejects_cross_shard_op(self):
        c = ShardedCluster(n_shards=4, f=1)
        s = c.new_client()
        kvs = [("a", "1"), ("b", "2"), ("c", "3"), ("d", "4")]
        op = s.session_for(0).op_mset(kvs)
        with pytest.raises(ValueError):
            c.update_batch(s, [op])


class TestCommitBatch:
    @pytest.mark.parametrize("backend", ["python", "device"])
    def test_commit_batch_fast_and_recoverable(self, backend):
        from repro.serving.kvstore import CurpSessionStore, SessionState

        store = CurpSessionStore(n_shards=2, witness_backend=backend,
                                 geometry=WitnessGeometry(256, 4))
        states = [SessionState(f"s{i}", [1, 2, i]) for i in range(6)]
        store.commit_batch(states)
        assert store.fast_commits == 6 and store.slow_commits == 0
        # Second commit of each session is the one §4.4 slow commit (the
        # first update wasn't "recently updated" yet, so it stayed unsynced
        # and the re-commit conflicts); it arms the hot-key preemptive sync.
        for st_ in states:
            st_.tokens.append(9)
        store.commit_batch(states)
        assert store.fast_commits == 6 and store.slow_commits == 6
        # From the third commit on, every step stays on the 1-RTT path.
        for st_ in states:
            st_.tokens.append(11)
        store.commit_batch(states)
        assert store.fast_commits == 12 and store.slow_commits == 6
        assert sum(store.per_shard_commits()) == 18
        store.crash_and_recover()
        for i in range(6):
            got = store.load(f"s{i}")
            assert got is not None and got.tokens == [1, 2, i, 9, 11]

    def test_commit_batch_empty_noop(self):
        from repro.serving.kvstore import CurpSessionStore

        store = CurpSessionStore()
        store.commit_batch([])
        assert store.fast_commits == 0 and store.slow_commits == 0


class TestGangKernelState:
    """Kernel-held RIFL/age state: dup and stale-gc verdicts resolve on
    device; the host mirror is a recovery-time view only."""

    def test_decisions_ignore_the_host_mirror(self):
        """Wiping the mirror must not change accept/dup/conflict verdicts —
        they come from the kernel's rpc lanes, not host state."""
        from repro.core.client import ClientSession

        s = ClientSession(client_id=21)
        op = s.op_set("k", "v")
        dw = DeviceWitness(64, 4)
        dw.start(master_id=1)
        assert dw.record(1, op.key_hashes(), op.rpc_id, op) \
            is RecordStatus.ACCEPTED
        dw._held.clear()                      # corrupt the recovery view
        assert dw.record(1, op.key_hashes(), op.rpc_id, op) \
            is RecordStatus.ACCEPTED          # in-kernel dup hit
        op2 = s.op_set("k", "w")
        assert dw.record(1, op2.key_hashes(), op2.rpc_id, op2) \
            is RecordStatus.REJECTED          # in-kernel conflict
        assert dw.stats["rejects_conflict"] == 1

    def test_stale_gc_suppressed_in_kernel(self):
        """A gc entry with a superseded rpc must not clear the slot even if
        the mirror has been wiped — suppression is in-kernel."""
        from repro.core.client import ClientSession

        s = ClientSession(client_id=22)
        op1 = s.op_set("k", "a")
        dw = DeviceWitness(16, 2)
        dw.start(master_id=1)
        dw.record(1, op1.key_hashes(), op1.rpc_id, op1)
        dw.gc(tuple((kh, op1.rpc_id) for kh in op1.key_hashes()))
        op2 = s.op_set("k", "b")
        dw.record(1, op2.key_hashes(), op2.rpc_id, op2)
        drops_before = dw.stats["gc_drops"]
        dw._held.clear()
        dw.gc(tuple((kh, op1.rpc_id) for kh in op1.key_hashes()))
        assert dw.stats["gc_drops"] == drops_before
        op3 = s.op_set("k", "c")
        assert dw.record(1, op3.key_hashes(), op3.rpc_id, op3) \
            is RecordStatus.REJECTED          # op2's record survived

    def test_recovery_data_matches_python_witness(self):
        """After the same record/gc history the device recovery set equals
        the Python witness's, and both freeze irreversibly."""
        from repro.core.client import ClientSession

        s = ClientSession(client_id=23)
        ops = [s.op_set(f"k{i % 6}", f"v{i}") for i in range(14)]
        ops.append(s.op_mset([("m1", "x"), ("m2", "y")]))
        pw, dw = Witness(64, 4), DeviceWitness(64, 4)
        pw.start(master_id=1)
        dw.start(master_id=1)
        assert pw.record_batch(1, ops) == dw.record_batch(1, ops)
        gc_entries = tuple(
            (kh, ops[0].rpc_id) for kh in ops[0].key_hashes()
        ) + tuple((kh, ops[3].rpc_id) for kh in ops[3].key_hashes())
        pw.gc(gc_entries)
        dw.gc(gc_entries)
        rec_p = {o.rpc_id for o in pw.get_recovery_data(1)}
        rec_d = {o.rpc_id for o in dw.get_recovery_data(1)}
        assert rec_p == rec_d
        late = s.op_set("late", "v")
        for w in (pw, dw):
            assert w.record(1, late.key_hashes(), late.rpc_id, late) \
                is RecordStatus.REJECTED      # RECOVERY mode is frozen

    def test_shared_gang_lane_isolation(self):
        """Witnesses stacked in one gang are independent tables: the same
        key records at every lane, and gc at one lane leaves the others."""
        from repro.core.client import ClientSession
        from repro.core.device_witness import WitnessGang, gc_many

        gang = WitnessGang(64, 4, n_lanes=2)
        w1 = DeviceWitness(64, 4, gang=gang)
        w2 = DeviceWitness(64, 4, gang=gang)
        w1.start(master_id=1)
        w2.start(master_id=1)
        s = ClientSession(client_id=24)
        op = s.op_set("shared", "v")
        assert w1.record(1, op.key_hashes(), op.rpc_id, op) \
            is RecordStatus.ACCEPTED
        assert w2.record(1, op.key_hashes(), op.rpc_id, op) \
            is RecordStatus.ACCEPTED
        w1.gc(tuple((kh, op.rpc_id) for kh in op.key_hashes()))
        assert w1.occupancy == 0 and w2.occupancy == 1
        op2 = s.op_set("shared", "w")
        assert w1.record(1, op2.key_hashes(), op2.rpc_id, op2) \
            is RecordStatus.ACCEPTED          # lane 1 slot was freed
        assert w2.record(1, op2.key_hashes(), op2.rpc_id, op2) \
            is RecordStatus.REJECTED          # lane 2 still holds op

    def test_gc_many_one_dispatch_matches_per_witness(self):
        """Stacked gc: one dispatch covers every witness of the gang, with
        per-witness results equal to individual gc calls."""
        from repro.core.client import ClientSession
        from repro.core.device_witness import WitnessGang, gc_many

        def build():
            gang = WitnessGang(64, 4, n_lanes=4)
            ws = [DeviceWitness(64, 4, gang=gang) for _ in range(3)]
            for w in ws:
                w.start(master_id=1)
            s = ClientSession(client_id=25)
            ops = [s.op_set(f"g{i}", "v") for i in range(8)]
            for w in ws:
                w.record_batch(1, ops)
            return ws, ops

        ws, ops = build()
        entries = tuple((kh, op.rpc_id) for op in ops[:4]
                        for kh in op.key_hashes())
        reset_dispatch_count()
        (resps,) = gc_many([(ws, entries)])
        assert dispatch_count() == 1
        reset_dispatch_count()
        ws2, _ = build()
        resps2 = [w.gc(entries) for w in ws2]
        assert [r.stale_requests for r in resps] == \
            [r.stale_requests for r in resps2]
        assert [w.occupancy for w in ws] == [w.occupancy for w in ws2] \
            == [4, 4, 4]
        assert [w.stats["gc_drops"] for w in ws] == \
            [w.stats["gc_drops"] for w in ws2] == [4, 4, 4]

    @pytest.mark.parametrize("kind", ["single", "hmset", "mixed"])
    def test_record_many_one_dispatch_matches_per_witness(self, kind):
        """Stacked record: one dispatch covers every witness of the gang
        (two sharing one op list, one with its own, one addressed to the
        wrong master), with the statuses, mirrors and stats of one
        ``record_batch`` per witness, and the Python witness's statuses."""
        from repro.core import telemetry
        from repro.core.client import ClientSession
        from repro.core.device_witness import WitnessGang, record_many

        s = ClientSession(client_id=27)

        def op(i, tag="m"):
            k = f"{tag}{i % 7}"
            if kind == "single" or (kind == "mixed" and i % 3 == 0):
                return s.op_set(k, "v")
            if kind == "hmset" or i % 3 == 1:
                return s.op_hmset(k, ((f"f{i % 2}", "v"),))
            return s.op_hmset(k, (("f0", "v"), (f"g{i % 4}", "v")))

        warm = [op(i) for i in range(10)]
        ops_a = [op(i) for i in range(24)] + warm[:3]   # dup retries
        ops_b = [op(i, "n") for i in range(5, 21)]     # fresh keys
        masters = (1, 1, 1, 2)
        lists = (ops_a, ops_a, ops_b, ops_a)

        def build(cls):
            gang = WitnessGang(16, 2, n_lanes=4)
            ws = [cls(16, 2, gang=gang) if cls is DeviceWitness
                  else cls(16, 2) for _ in masters]
            for w, m in zip(ws, masters):
                w.start(master_id=m)
                w.record_batch(m, warm)
            return ws

        ws = build(DeviceWitness)
        reg = telemetry.registry()
        lanes0 = reg.counter("witness.stacked_lanes").value
        reset_dispatch_count()
        got = record_many([(w, 1, ops) for w, ops in zip(ws, lists)])
        assert dispatch_count() == 1
        assert reg.counter("witness.stacked_lanes").value - lanes0 == 3
        reset_dispatch_count()
        ws2 = build(DeviceWitness)
        want = [w.record_batch(1, ops) for w, ops in zip(ws2, lists)]
        py = [w.record_batch(1, ops) for w, ops in zip(build(Witness), lists)]
        assert got == want == py
        assert got[3] == [RecordStatus.REJECTED] * len(ops_a)
        flat = [st for sts in got[:3] for st in sts]
        assert RecordStatus.ACCEPTED in flat and RecordStatus.REJECTED in flat
        assert [w._held for w in ws] == [w._held for w in ws2]
        assert [w.stats for w in ws] == [w.stats for w in ws2]
        assert ws[3].stats["rejects_mode"] == len(ops_a)

    def test_gang_record_one_dispatch_and_bounded_jit_cache(self):
        """Batches of any size are ONE dispatch, and bucket padding keeps
        the jit cache logarithmic in the largest batch seen."""
        from repro.core.client import ClientSession
        from repro.kernels.ops import _gang_record_impl

        s = ClientSession(client_id=26)
        sizes = [1, 2, 3, 5, 9, 17, 33, 64, 65, 100, 127, 128]
        cache_before = _gang_record_impl._cache_size()
        for n in sizes:
            dw = DeviceWitness(1024, 4)  # fresh table: no capacity carryover
            dw.start(master_id=1)
            ops = [s.op_set(f"c{n}_{i}", "v") for i in range(n)]
            reset_dispatch_count()
            st = dw.record_batch(1, ops)
            assert dispatch_count() == 1
            # A stray reject can only be a genuine 5-keys-in-one-set
            # capacity collision (covered by the parity tests above).
            assert st.count(RecordStatus.ACCEPTED) >= n - 4
        grown = _gang_record_impl._cache_size() - cache_before
        # Buckets are pow2 with a floor of 16: sizes up to 128 can hit at
        # most {16, 32, 64, 128} -> O(log B), not O(B).
        assert grown <= 4, f"jit cache grew by {grown} entries"

    @pytest.mark.parametrize("n_lanes", [4, 256])
    def test_gang_kernels_match_oracles_across_row_tiles(self, n_lanes):
        """Single-key records, multi-key groups and gc, with colliding keys,
        rpc retries and mixed merge classes, stay bit-exact with the
        ``ref_gang_record``/``ref_gang_gc`` oracles — on one row tile
        (4 lanes x 64 sets) and on a grid of two (256 lanes = 16384 rows)."""
        from repro.kernels import (
            GangTable,
            gang_gc,
            gang_record,
            gang_record_groups,
            ref_gang_gc,
            ref_gang_record,
        )

        r = rng(n_lanes)
        S, W, L, G, K = 64, 4, n_lanes, 40, 3
        table = oracle = GangTable.empty(S, W, L)
        reasons_seen = set()
        for rnd in range(6):
            base = r.integers(0, 2 ** 32, size=(12, 2), dtype=np.uint32)
            pick = r.integers(0, 12, size=(G, K))
            khi, klo = base[pick, 0], base[pick, 1]
            kval = (r.random((G, K)) < 0.8).astype(np.int32)
            kval[:, 0] = 1
            kcls = r.choice(np.array([0, 2, 2], np.int32), size=(G, K))
            # Few lanes, at both ends of the row space (both tiles): keys
            # and rpcs collide often enough to hit every reason code.
            lanes = r.choice(np.array([0, 1, L - 2, L - 1], np.int32), G)
            rh = r.integers(0, 3, size=G).astype(np.uint32)
            rl = r.integers(0, 3, size=G).astype(np.uint32)
            if rnd % 2:
                res = gang_record_groups(table, S, khi, klo, kval, lanes, rh,
                                         rl, kcls)
                reasons, table = list(res.reasons), res.table
                n_keys = kval.sum(axis=1)
            else:
                reasons, _qh, _ql, table = gang_record(
                    table, S, khi[:, 0], klo[:, 0], lanes, rh, rl,
                    kcls[:, 0])
                reasons = list(reasons)
                n_keys = np.ones(G, np.int64)
            groups = [
                (int(lanes[g]), (int(rh[g]), int(rl[g])),
                 [(int(khi[g, k]), int(klo[g, k]), int(kcls[g, k]))
                  for k in range(K) if kval[g, k]][:n_keys[g]])
                for g in range(G)
            ]
            want, oracle = ref_gang_record(oracle, S, groups)
            assert reasons == want
            reasons_seen.update(want)
            for a, b in zip(table, oracle):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            ents = list(dict.fromkeys(
                (int(lanes[g]), (int(khi[g, 0]), int(klo[g, 0])),
                 (int(rh[g]), int(rl[g])))
                for g in r.integers(0, G, size=10)))
            aged = (r.random(L) < 0.5).astype(np.int32)
            cleared, table = gang_gc(
                table, S, *(np.array([e[1][i] for e in ents]) for i in (0, 1)),
                *(np.array([e[2][i] for e in ents]) for i in (0, 1)),
                np.array([e[0] for e in ents]), aged)
            want_clr, oracle = ref_gang_gc(
                oracle, S, ents, [i for i in range(L) if aged[i]])
            assert [bool(c) for c in cleared] == want_clr
            for a, b in zip(table, oracle):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert reasons_seen >= {1, 2, 3}


class TestFusedClusterBatch:
    """The fused multi-shard driver (core/fastbatch.py): one dispatch per
    routed batch, outcome parity with the Python backend, safe fallback."""

    def _mk(self, backend, **kw):
        kw.setdefault("geometry", WitnessGeometry(256, 4))
        c = ShardedCluster(n_shards=4, f=3, witness_backend=backend,
                           seed=7, **kw)
        return c, c.new_client()

    def test_cross_shard_batch_single_dispatch(self):
        c, s = self._mk("device")
        c.update_batch(s, [s.op_set(f"w{i}", "v") for i in range(8)])
        ops = [s.op_set(f"k{i}", "v") for i in range(16)]
        assert len({c.shard_of(op.keys[0]) for op in ops}) > 1
        reset_dispatch_count()
        outs = c.update_batch(s, ops)
        assert dispatch_count() == 1      # ONE dispatch, all shards
        reset_dispatch_count()
        assert all(o.fast_path and o.witness_accepts == 3 for o in outs)
        assert c._fused.stats["fused_batches"] == 2

    def test_single_shard_batch_single_dispatch(self):
        c, s = self._mk("device")
        keys = [f"s{i}" for i in range(200) if c.shard_of(f"s{i}") == 0][:8]
        c.update_batch(s, [s.op_set(k + "_warm", "v") for k in keys])
        reset_dispatch_count()
        c.update_batch(s, [s.op_set(k, "v") for k in keys])
        assert dispatch_count() == 1
        reset_dispatch_count()

    def test_outcomes_match_python_backend(self):
        """Same mixed workload (conflicts, deletes, increments, RIFL retry,
        drains) on both backends: per-op outcomes and master stats must be
        identical."""
        import random

        def drive(backend):
            c, s = self._mk(backend, sync_batch=10)
            rng_ = random.Random(5)
            seen = []
            out = []
            for r in range(6):
                ops = []
                for _ in range(12):
                    k = f"k{rng_.randrange(8)}"
                    ops.append(s.op_set(k, f"v{r}") if rng_.random() < .7
                               else s.op_incr(k))
                if seen and r == 4:
                    ops[0] = seen[0]          # RIFL retry of an old op
                seen.extend(ops)
                for o in c.update_batch(s, ops):
                    out.append((o.value, o.rtts, o.fast_path, o.synced_path,
                                o.witness_accepts))
            return c, out

        cd, od = drive("device")
        cp, op_ = drive("python")
        assert od == op_
        for sid in range(4):
            assert cd.shards[sid].master.stats == cp.shards[sid].master.stats
        assert cd._fused.stats["fused_ops"] > 0

    def test_ring_window_conflicts_match_host(self):
        """auto_sync=False keeps the unsynced window alive across batches:
        the device ring must flag the same conflicts the host dict would."""
        def drive(backend):
            c, s = self._mk(backend, auto_sync=False, sync_batch=1000)
            o1 = c.update_batch(s, [s.op_set("a", "1"), s.op_set("b", "2")])
            o2 = c.update_batch(s, [s.op_set("a", "3"), s.op_set("c", "4")])
            return [(o.fast_path, o.synced_path, o.rtts) for o in o1 + o2]

        assert drive("device") == drive("python")

    def test_multikey_op_declines_to_fallback(self):
        c = ShardedCluster(n_shards=1, f=3, witness_backend="device",
                           geometry=WitnessGeometry(256, 4))
        s = c.new_client()
        op = s.session_for(0).op_mset([("m1", "1"), ("m2", "2")])
        outs = c.update_batch(s, [op, s.op_set("plain", "3")])
        assert all(o.witness_accepts == 3 for o in outs)
        assert c._fused.stats["declined"] == 1
        assert c._fused.stats["fused_batches"] == 0
        # The NEXT all-plain batch fuses again (ring rebuilds from the log).
        outs2 = c.update_batch(s, [s.op_set("p2", "4")])
        assert outs2[0].fast_path
        assert c._fused.stats["fused_batches"] == 1

    def test_crash_recovery_invalidates_ring(self):
        """A master crash between fused batches must not leak stale ring
        state: replayed ops live in the new window, batches stay correct."""
        c, s = self._mk("device", auto_sync=False, sync_batch=1000)
        c.update_batch(s, [s.op_set(f"k{i}", f"v{i}") for i in range(12)])
        for sid in range(4):
            c.shards[sid].crash_master()
        outs = c.update_batch(s, [s.op_set(f"k{i}", "post") for i in range(12)])
        assert len(outs) == 12
        for i in range(12):
            assert c.read(s, s.op_get(f"k{i}")).value == "post"

    def test_per_shard_hmset_batch_one_record_dispatch(self):
        """A one-field HMSET batch over 4 shards declines the fused path and
        costs exactly ONE record dispatch plus its gc dispatches."""
        import repro.kernels as K

        c, s = self._mk("device", sync_batch=4)
        ops = [s.op_hmset(f"h{i}", (("f0", "v"),)) for i in range(32)]
        assert len({c.shard_of(op.keys[0]) for op in ops}) == 4
        calls = {"gc": 0, "record": 0}
        real_gc, real_groups = K.gang_gc, K.gang_record_groups

        def gc(*a, **kw):
            calls["gc"] += 1
            return real_gc(*a, **kw)

        def groups(*a, **kw):
            calls["record"] += 1
            return real_groups(*a, **kw)

        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(K, "gang_gc", gc)
            mp.setattr(K, "gang_record_groups", groups)
            reset_dispatch_count()
            outs = c.update_batch(s, ops)
            n = dispatch_count()
            reset_dispatch_count()
        assert c._fused.stats["declined"] == 1
        assert calls["record"] == 1 and calls["gc"] > 0
        assert n == 1 + calls["gc"]
        assert all(o.fast_path and o.witness_accepts == 3 for o in outs)

    @staticmethod
    def _drive_per_shard(backend, phased, sync_batch=6):
        """Mixed batches that decline the fused path (each holds an HMSET),
        with conflicts, a RIFL retry and a dropped witness, through the
        phased ``update_batch`` or a shard-by-shard loop of
        ``ShardGroup.update_batch`` (the per-shard path before stacking)."""
        import random

        c = ShardedCluster(n_shards=4, f=3, witness_backend=backend, seed=7,
                           sync_batch=sync_batch,
                           geometry=WitnessGeometry(256, 4))
        s = c.new_client()
        c.shards[2].witness_drop(1)
        rng_ = random.Random(11)
        seen, out = [], []
        for r in range(5):
            ops = [s.op_hmset(f"h{rng_.randrange(6)}", ((f"f{r % 2}", r),))]
            for _ in range(15):
                k = f"k{rng_.randrange(10)}"
                ops.append(s.op_set(k, f"v{r}") if rng_.random() < .6
                           else s.op_hmset(k, (("a", r), ("b", r))))
            if r == 3:
                ops[1] = seen[2]                # RIFL retry of an old op
            seen.extend(ops)
            if phased:
                res = c.update_batch(s, ops)
            else:
                groups = {}
                for i, op in enumerate(ops):
                    groups.setdefault(c.shard_of(op.keys[0]), []).append(i)
                res = [None] * len(ops)
                for sid, idxs in groups.items():
                    for i, o in zip(idxs, c.shards[sid].update_batch(
                            s.session_for(sid), [ops[i] for i in idxs])):
                        res[i] = o
            out += [(o.value, o.rtts, o.fast_path, o.synced_path,
                     o.witness_accepts) for o in res]
        hist = [(h["op"].rpc_id, h["value"], h["invoke"]) for h in c.history]
        return c, out, hist

    def test_per_shard_path_matches_shard_loop_and_python_backend(self):
        """The phased per-shard path gives the outcomes and history of the
        shard-by-shard loop, on both backends, and the backends agree."""
        runs = {(b, p): self._drive_per_shard(b, p)
                for b in ("device", "python") for p in (True, False)}
        (_c, out, hist) = runs[("python", False)]
        assert any(not o[2] for o in out) and any(o[2] for o in out)
        for (b, p), (c, o, h) in runs.items():
            assert o == out, (b, p)
            assert h == hist, (b, p)
        cd = runs[("device", True)][0]
        assert cd._fused.stats["fused_batches"] == 0
        for sid in range(4):
            assert cd.shards[sid].master.store.snapshot() == \
                runs[("python", False)][0].shards[sid].master.store.snapshot()

    @pytest.mark.parametrize("backend", ["python", "device"])
    def test_per_shard_batch_resolves_orphaned_txn_lock(self, backend):
        """A shard whose master round hits an orphaned txn lock: the shards
        begun before it finish, the txn is resolved, and the batch runs on
        from that shard, with the outcomes and history of resolving and
        re-running shard by shard."""
        from repro.core.txn import participant_state, prepare_op

        def drive(phased):
            c = ShardedCluster(n_shards=4, f=3, witness_backend=backend,
                               seed=7, geometry=WitnessGeometry(256, 4))
            s = c.new_client()
            keys = {}
            for i in range(400):
                keys.setdefault(c.shard_of(f"t{i}"), []).append(f"t{i}")
            spec = s.txn_spec([(keys[2][0], "x"), (keys[3][0], "y")])
            p0 = [p for p in spec.parts if p.shard_id == 2][0]
            assert c.shards[2].txn_prepare(s.session_for(2),
                                           prepare_op(spec, p0)).granted
            ops = [s.op_hmset(keys[sid][j], (("f", j),))
                   for sid in (1, 2, 0, 3) for j in range(1, 4)]
            ops.insert(5, s.op_set(keys[2][0], "after"))   # locked key
            if phased:
                res = c.update_batch(s, ops)
            else:
                groups = {}
                for i, op in enumerate(ops):
                    groups.setdefault(c.shard_of(op.keys[0]), []).append(i)
                res = [None] * len(ops)
                for sid, idxs in groups.items():
                    sub = s.session_for(sid)
                    done = c._with_txn_resolution(
                        lambda sid=sid, sub=sub, idxs=idxs:
                        c.shards[sid].update_batch(
                            sub, [ops[i] for i in idxs]))
                    for i, o in zip(idxs, done):
                        res[i] = o
            assert participant_state(c.shards[2].master, spec, p0) \
                == "aborted"
            assert c.read(s, s.op_get(keys[2][0])).value == "after"
            return ([(o.value, o.rtts, o.fast_path, o.synced_path,
                      o.witness_accepts) for o in res],
                    [(h["op"].rpc_id, h["value"], h["invoke"])
                     for h in c.history])

        assert drive(True) == drive(False)

    def test_fused_respects_dropped_witness(self):
        c, s = self._mk("device")
        c.shards[0].witness_drop(0)
        keys = [f"d{i}" for i in range(400) if c.shard_of(f"d{i}") == 0][:4]
        outs = c.update_batch(s, [s.op_set(k, "v") for k in keys])
        assert all(not o.fast_path and o.witness_accepts == 2 for o in outs)
        assert c._fused.stats["declined"] >= 1
