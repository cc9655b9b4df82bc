"""Witness-gang kernel tests: the served record, gc and fused-batch kernels
(``gang_record``, ``gang_record_groups``, ``gang_gc``,
``gang_fastpath_batch``) in interpret mode against the oracles of
``repro.kernels.ref``, over shape sweeps, collision-heavy batches and
hypothesis property tests; plus the two key hashes the served path relies
on (``ref_keyhash2x32`` in the jit, ``np_keyhash2x32`` on the host).

hypothesis is optional: the _hyp shim turns the property tests into skips
when it isn't installed, so this file always collects (the oracle sweeps and
smoke tests below run regardless).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from _gang import (
    CONFLICT,
    DUP,
    FULL,
    INSERT,
    accepted,
    assert_tables_equal,
    live_rows,
    oracle,
    oracle_groups,
    raw_lanes,
    record,
    record_groups,
    route,
    rpcs,
)
from _hyp import given, settings, st

from repro.kernels import (
    GangTable,
    dispatch_count,
    gang_fastpath_batch,
    gang_gc,
    gang_record,
    np_keyhash2x32,
    record_fits,
    ref_conflict_scan,
    ref_gang_gc,
    ref_keyhash2x32,
    reset_dispatch_count,
)
from repro.kernels.witness_record import gang_tile_rows


def rng(seed=0):
    return np.random.default_rng(seed)


def gc_all(table, n_sets, k_hi, k_lo, rpc, lane=0, aged=(0,)):
    """``gang_gc`` of the given (key, rpc) entries at one lane; ``aged``
    lists the lanes that age.  Returns (cleared, table)."""
    n_lanes = table.occ.shape[1] // n_sets
    aged_mask = np.zeros(max(n_lanes, 1), np.int32)
    aged_mask[list(aged)] = 1
    cleared, table = gang_gc(table, n_sets, k_hi, k_lo, rpc[0], rpc[1],
                             np.full(len(k_hi), lane, np.int32), aged_mask)
    return np.asarray(cleared), table


def ring_conflicts(w_hi, w_lo, tail, count, q_hi, q_lo, n_sets=16):
    """Conflict bits of RAW queries against one shard's ring, which holds
    the MIXED lanes ``w`` with live span (``tail``, ``count``).  No query is
    predicted to execute, so the ring is the whole window."""
    (n,) = np.asarray(q_hi).shape
    res = gang_fastpath_batch(
        GangTable.empty(n_sets, 4, 1), n_sets, q_hi, q_lo, *rpcs(n),
        np.zeros(n, np.int32), np.zeros(256, np.int32),
        np.zeros((1, 1), np.int32),
        jnp.asarray(np.asarray(w_hi, np.uint32)[None]),
        jnp.asarray(np.asarray(w_lo, np.uint32)[None]),
        np.array([tail]), np.array([count]))
    return res.conflicts


class TestKeyhash:
    @pytest.mark.parametrize("n", [1, 7, 128, 1000, 4096])
    @pytest.mark.parametrize("dtype", [np.uint32, np.int32])
    def test_matches_oracle(self, n, dtype):
        """The host hash (``record_many``/``gc_many``) equals the in-jit
        hash of every gang op, bit for bit."""
        r = rng(n)
        hi = r.integers(0, 2**31, n).astype(dtype)
        lo = r.integers(0, 2**31, n).astype(dtype)
        kh, kl = np_keyhash2x32(hi, lo)
        rh, rl = ref_keyhash2x32(jnp.asarray(hi), jnp.asarray(lo))
        np.testing.assert_array_equal(kh, np.asarray(rh))
        np.testing.assert_array_equal(kl, np.asarray(rl))

    def test_smoke_deterministic(self):
        """No-hypothesis smoke: fixed input, fixed expected behaviour — this
        file must never collect to zero tests."""
        hi = np.arange(8, dtype=np.uint32)
        lo = np.arange(8, dtype=np.uint32)[::-1].copy()
        oh1, ol1 = np_keyhash2x32(hi, lo)
        oh2, ol2 = np_keyhash2x32(hi, lo)
        np.testing.assert_array_equal(oh1, oh2)
        np.testing.assert_array_equal(ol1, ol2)
        # distinct inputs should not collide on this tiny sample
        assert len(set(ol1.tolist())) == 8

    def test_shard_route_matches_python_router(self):
        """Device placement must agree bit-for-bit with the protocol-side
        KeyRouter (shared fmix32 chain) for every shard count we deploy."""
        from repro.core.shard import KeyRouter
        from repro.core.types import keyhash

        keys = [f"user{i}" for i in range(300)] + list(range(100))
        hi, lo = raw_lanes([keyhash(k) for k in keys])
        for n_shards in (1, 2, 3, 4, 8):
            router = KeyRouter(n_shards)
            dev = route(hi, lo, router.slot_map)
            py = np.array([router.shard_of(k) for k in keys])
            np.testing.assert_array_equal(dev, py)
        # 4-way split is roughly balanced (hash quality, not exactness)
        counts = np.bincount(route(hi, lo, KeyRouter(4).slot_map),
                             minlength=4)
        assert counts.min() > len(keys) // 8

    @settings(deadline=None, max_examples=20)
    @given(a=st.integers(0, 2**32 - 1), b=st.integers(0, 2**32 - 1),
           bit=st.integers(0, 63))
    def test_avalanche(self, a, b, bit):
        """Flipping one input bit flips a healthy share of output bits."""
        hi1, lo1 = np.uint32(a), np.uint32(b)
        x = (int(a) << 32) | int(b)
        y = x ^ (1 << bit)
        hi2, lo2 = np.uint32(y >> 32), np.uint32(y & 0xFFFFFFFF)
        o1 = ref_keyhash2x32(jnp.uint32(hi1), jnp.uint32(lo1))
        o2 = ref_keyhash2x32(jnp.uint32(hi2), jnp.uint32(lo2))
        diff = (int(o1[0]) ^ int(o2[0])).bit_count() + \
               (int(o1[1]) ^ int(o2[1])).bit_count()
        assert diff >= 10   # 64 output bits; ideal ~32


class TestWitnessRecord:
    """``gang_record`` on a one-lane gang against ``ref_gang_record``."""

    @pytest.mark.parametrize("sets,ways,batch", [
        (16, 2, 64), (64, 4, 300), (256, 4, 512), (1024, 4, 1000),
        (1024, 8, 257),
    ])
    def test_matches_oracle(self, sets, ways, batch):
        r = rng(sets * ways + batch)
        t = GangTable.empty(sets, ways)
        qh = r.integers(0, 2**32, batch, dtype=np.uint32)
        ql = r.integers(0, sets * 6, batch, dtype=np.uint32)  # force pressure
        rsn_k, tk = record(t, sets, qh, ql)
        rsn_r, tr = oracle(t, sets, qh, ql)
        np.testing.assert_array_equal(rsn_k, rsn_r)
        assert_tables_equal(tk, tr)

    def test_conflict_semantics(self):
        """Same key twice under two rpcs => second rejected (the x<-1 / x<-5
        rule)."""
        t = GangTable.empty(16, 4)
        rsn, _t2 = record(t, 16, [7, 7], [3, 3])
        assert rsn.tolist() == [INSERT, CONFLICT]

    def test_gc_then_accept(self):
        t = GangTable.empty(16, 4)
        qh = np.array([7], np.uint32)
        ql = np.array([3], np.uint32)
        _rsn, t = record(t, 16, qh, ql, rpc=rpcs(1, start=1))
        cleared, t = gc_all(t, 16, qh, ql, rpcs(1, start=1))
        assert cleared.tolist() == [1]
        rsn2, t = record(t, 16, qh, ql, rpc=rpcs(1, start=2))
        assert rsn2.tolist() == [INSERT]

    @settings(deadline=None, max_examples=15)
    @given(seed=st.integers(0, 1000), sets=st.sampled_from([16, 64]),
           ways=st.sampled_from([2, 4]))
    def test_property_no_duplicate_keys_live(self, seed, sets, ways):
        """Invariant: an occupied witness never holds two slots with the same
        (hi, lo) key — the commutativity guarantee in table form."""
        r = rng(seed)
        n = 200
        t = GangTable.empty(sets, ways)
        qh = r.integers(0, 8, n, dtype=np.uint32)     # tiny keyspace
        ql = r.integers(0, 8, n, dtype=np.uint32)
        _rsn, t = record(t, sets, qh, ql)
        hi, lo, occ = live_rows(t, sets)
        live = [(int(h), int(l)) for h, l, o in
                zip(hi.ravel(), lo.ravel(), occ.ravel()) if o]
        assert live and len(live) == len(set(live))

    def test_gc_matches_oracle_sweep(self):
        r = rng(5)
        t = GangTable.empty(64, 4)
        qh = r.integers(0, 2**32, 200, dtype=np.uint32)
        ql = r.integers(0, 512, 200, dtype=np.uint32)
        r_hi, r_lo = rpcs(200)
        _rsn, t = record(t, 64, qh, ql)
        ck, tk = gc_all(t, 64, qh[:77], ql[:77], (r_hi[:77], r_lo[:77]))
        cr, tr = ref_gang_gc(
            t, 64, [(0, (int(qh[i]), int(ql[i])), (0, int(r_lo[i])))
                    for i in range(77)], [0])
        assert [bool(c) for c in ck] == cr
        assert_tables_equal(tk, tr)


class TestConflictScan:
    """``gang_fastpath_batch``'s ring scan against ``ref_conflict_scan``
    over the ring's live span."""

    @pytest.mark.parametrize("u,b", [(64, 16), (512, 256), (700, 123),
                                     (2048, 1024)])
    def test_matches_oracle(self, u, b):
        r = rng(u + b)
        raw_hi = r.integers(0, 2**32, u, dtype=np.uint32)
        raw_lo = r.integers(0, 2**32, u, dtype=np.uint32)
        wh, wl = np_keyhash2x32(raw_hi, raw_lo)      # the ring holds mixed
        tail = int(r.integers(0, u))
        count = int(r.integers(u // 4, u))
        live = (((np.arange(u) - tail) % u) < count).astype(np.int32)
        # A quarter of the queries re-touch window keys: the span's first
        # and last live slots and the dead slots beside them, then half
        # live picks and half anywhere.
        edges = np.array([tail, tail + count - 1, tail + count, tail - 1]) % u
        n_hit = max(b // 4, 6)
        n_live = (n_hit - 4) // 2
        pos = np.concatenate([edges,
                              (tail + r.integers(0, count, n_live)) % u,
                              r.integers(0, u, n_hit - 4 - n_live)])
        qh = np.concatenate([raw_hi[pos], r.integers(0, 2**32, b - n_hit,
                                                     dtype=np.uint32)])
        ql = np.concatenate([raw_lo[pos], r.integers(0, 2**32, b - n_hit,
                                                     dtype=np.uint32)])
        ck = ring_conflicts(wh, wl, tail, count, qh, ql)
        mh, ml = np_keyhash2x32(qh, ql)
        cr = ref_conflict_scan(jnp.asarray(wh), jnp.asarray(wl),
                               jnp.asarray(live), jnp.asarray(mh),
                               jnp.asarray(ml))
        np.testing.assert_array_equal(ck, np.asarray(cr))
        assert ck.sum() > 0

    def test_invalid_window_entries_never_hit(self):
        """Ring slots outside the live span never conflict, whatever they
        hold."""
        wh, wl = np_keyhash2x32(np.array([5, 5], np.uint32),
                                np.array([9, 9], np.uint32))
        q = (np.array([5], np.uint32), np.array([9], np.uint32))
        assert ring_conflicts(wh, wl, 0, 0, *q).tolist() == [0]
        # Slot 0 holds the key but only slot 1 is live.
        wl1 = np.array([wl[0], 0], np.uint32)
        assert ring_conflicts(wh, wl1, 1, 1, *q).tolist() == [0]
        assert ring_conflicts(wh, wl1, 0, 1, *q).tolist() == [1]


class TestGangRecordParity:
    """The record kernel is bit-exact with ``ref_gang_record`` on
    adversarial batches."""

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
        t = GangTable.empty(sets, ways)
        qh = r.integers(0, kspan, batch).astype(np.uint32)
        ql = r.integers(0, span, batch).astype(np.uint32)
        rsn_k, t_k = record(t, sets, qh, ql)
        rsn_r, t_r = oracle(t, sets, qh, ql)
        np.testing.assert_array_equal(rsn_k, rsn_r)
        assert_tables_equal(t_k, t_r)

    def test_duplicate_keys_single_batch(self):
        """Same key B times in one batch: exactly one accept (the first)."""
        t = GangTable.empty(16, 4)
        rsn, t2 = record(t, 16, np.full(9, 7), np.full(9, 3))
        assert rsn.tolist() == [INSERT] + [CONFLICT] * 8
        assert int((np.asarray(t2.occ) > 0).sum()) == 1

    def test_full_set_capacity_rejects(self):
        """W+k distinct keys probing one set: exactly W accepts, in order."""
        t = GangTable.empty(16, 4)
        qh = np.arange(7, dtype=np.uint32)           # distinct keys
        ql = np.full(7, 5, np.uint32)                # same set (5 & 15)
        rsn, t2 = record(t, 16, qh, ql)
        assert rsn.tolist() == [INSERT] * 4 + [FULL] * 3
        assert int((np.asarray(t2.occ)[:, 5] > 0).sum()) == 4

    def test_cross_set_permutation_invariance(self):
        """Permuting ops of OTHER sets never changes an op's verdict — sets
        are independent; only the order within a set matters."""
        r = rng(3)
        S, B = 16, 240
        t = GangTable.empty(S, 4)
        qh = r.integers(0, 6, B).astype(np.uint32)
        ql = r.integers(0, 64, B).astype(np.uint32)
        r_hi, r_lo = rpcs(B)
        rsn0, t0 = record(t, S, qh, ql, rpc=(r_hi, r_lo))
        # Stable-sort by set id: reorders across sets, preserves order within.
        perm = np.argsort(ql & (S - 1), kind="stable")
        rsn1, t1 = record(t, S, qh[perm], ql[perm],
                          rpc=(r_hi[perm], r_lo[perm]))
        np.testing.assert_array_equal(rsn0[perm], rsn1)
        assert_tables_equal(t0, t1)

    @pytest.mark.parametrize("n_lanes,sets", [(64, 256), (128, 128)])
    def test_multi_cell_grid_matches_oracle(self, n_lanes, sets):
        """Gangs of several row tiles: each grid cell walks only its own
        items, and the result stays bit-exact."""
        r = rng(n_lanes + sets)
        t = GangTable.empty(sets, 4, n_lanes)
        assert gang_tile_rows(t.occ.shape[1], sets) < t.occ.shape[1]
        qh = r.integers(0, 16, 600).astype(np.uint32)
        ql = r.integers(0, sets * 5, 600).astype(np.uint32)
        lanes = r.choice(np.array([0, 1, n_lanes - 2, n_lanes - 1],
                                  np.int32), 600)
        rsn_k, t_k = record(t, sets, qh, ql, lane=lanes)
        rsn_r, t_r = oracle(t, sets, qh, ql, lane=lanes)
        np.testing.assert_array_equal(rsn_k, rsn_r)
        assert_tables_equal(t_k, t_r)

    def test_batch_past_smem_rejected(self):
        """A batch whose work items would overflow v5e's SMEM raises before
        the compile (``check_smem``) instead of failing on the chip."""
        assert record_fits(16384, 1) and not record_fits(16385, 1)
        t = GangTable.empty(16, 4)
        n = 16385
        with pytest.raises(ValueError, match="SMEM"):
            record(t, 16, np.zeros(n, np.uint32), np.zeros(n, np.uint32))

    @settings(deadline=None, max_examples=25)
    @given(seed=st.integers(0, 10_000), sets=st.sampled_from([16, 64, 256]),
           ways=st.sampled_from([2, 4, 8]), batch=st.integers(1, 300),
           kspan=st.sampled_from([2, 5, 2**32 - 1]))
    def test_property_matches_oracle(self, seed, sets, ways, batch, kspan):
        r = rng(seed)
        t = GangTable.empty(sets, ways)
        qh = r.integers(0, kspan, batch).astype(np.uint32)
        ql = r.integers(0, max(2, sets * 3), batch).astype(np.uint32)
        rsn_k, t_k = record(t, sets, qh, ql)
        rsn_r, t_r = oracle(t, sets, qh, ql)
        np.testing.assert_array_equal(rsn_k, rsn_r)
        assert_tables_equal(t_k, t_r)

    @settings(deadline=None, max_examples=15)
    @given(seed=st.integers(0, 10_000))
    def test_property_permutation_invariance(self, seed):
        r = rng(seed)
        S, B = 32, 100
        t = GangTable.empty(S, 2)
        qh = r.integers(0, 4, B).astype(np.uint32)
        ql = r.integers(0, 128, B).astype(np.uint32)
        r_hi, r_lo = rpcs(B)
        rsn0, _ = record(t, S, qh, ql, rpc=(r_hi, r_lo))
        perm = np.argsort(ql & (S - 1), kind="stable")
        rsn1, _ = record(t, S, qh[perm], ql[perm],
                         rpc=(r_hi[perm], r_lo[perm]))
        np.testing.assert_array_equal(rsn0[perm], rsn1)


class TestGcRoundTrip:
    """``gang_record`` -> ``gang_gc`` -> ``gang_record`` chains on one gang,
    rebinding the table each call."""

    def test_record_gc_record_no_stale_occupancy(self):
        """gc leaves no stale occupancy and a full re-record of the same
        keys is accepted again."""
        r = rng(9)
        t = GangTable.empty(64, 4)
        qh = r.integers(0, 2**32, 120).astype(np.uint32)
        ql = np.arange(120, dtype=np.uint32)       # 2 keys a set at most
        rpc = rpcs(120)
        rsn1, t = record(t, 64, qh, ql, rpc=rpc)
        occupied = int((np.asarray(t.occ) > 0).sum())
        assert occupied == int(accepted(rsn1).sum()) > 0
        cleared, t = gc_all(t, 64, qh, ql, rpc)
        np.testing.assert_array_equal(cleared, accepted(rsn1))
        assert int(np.asarray(t.occ).sum()) == 0   # no stale occupancy
        assert int(np.asarray(t.age).sum()) == 0
        rsn2, t = record(t, 64, qh, ql, rpc=rpc)
        np.testing.assert_array_equal(rsn2, rsn1)

    def test_gc_then_accept_chain_reuses_table(self):
        """Functional chain that rebinds the table each call: many
        record/gc cycles stay self-consistent."""
        t = GangTable.empty(16, 2)
        qh = np.array([5, 6, 7], np.uint32)
        ql = np.array([1, 2, 3], np.uint32)
        for cycle in range(5):
            rpc = rpcs(3, start=1 + 3 * cycle)
            rsn, t = record(t, 16, qh, ql, rpc=rpc)
            assert rsn.tolist() == [INSERT] * 3
            cleared, t = gc_all(t, 16, qh, ql, rpc)
            assert cleared.tolist() == [1, 1, 1]
        assert int(np.asarray(t.occ).sum()) == 0


class TestFusedFastPath:
    """``gang_fastpath_batch``: the whole cluster batch in one dispatch."""

    NS, F, S, CAP = 4, 2, 128, 64

    def _batch(self, table, k_hi, k_lo, rpc, exec_pred, ring_hi, ring_lo,
               count):
        return gang_fastpath_batch(
            table, self.S, k_hi, k_lo, *rpc, exec_pred,
            np.arange(256, dtype=np.int32) % self.NS,
            np.arange(self.NS * self.F, dtype=np.int32).reshape(self.NS,
                                                                self.F),
            ring_hi, ring_lo, np.zeros(self.NS, np.int32), count)

    def _empty(self):
        ring = jnp.zeros((self.NS, self.CAP), jnp.uint32)
        return (GangTable.empty(self.S, 4, self.NS * self.F), ring, ring,
                np.zeros(self.NS, np.int32))

    def test_single_dispatch_per_batch(self):
        t, rh, rl, cnt = self._empty()
        r = rng(1)
        khi = r.integers(0, 2**32, 33).astype(np.uint32)
        klo = r.integers(0, 2**32, 33).astype(np.uint32)
        ex = np.ones(33, np.int32)
        self._batch(t, khi, klo, rpcs(33), ex, rh, rl, cnt)     # warm
        reset_dispatch_count()
        self._batch(t, khi, klo, rpcs(33), ex, rh, rl, cnt)
        assert dispatch_count() == 1
        reset_dispatch_count()

    def test_matches_unfused_pipeline(self):
        """gang_fastpath_batch == hash -> slot route -> ``gang_record`` at
        every copy's lane, plus ``ref_conflict_scan`` against the rings."""
        r = rng(5)
        t, rh, rl, cnt = self._empty()
        khi = r.integers(0, 2**32, 70).astype(np.uint32)
        klo = r.integers(0, 2**32, 70).astype(np.uint32)
        rpc = rpcs(70)
        ex = (np.arange(70) < 10).astype(np.int32)   # only 10 enter rings
        res = self._batch(t, khi, klo, rpc, ex, rh, rl, cnt)
        qh, ql = np_keyhash2x32(khi, klo)
        np.testing.assert_array_equal(res.q_hi, qh)
        np.testing.assert_array_equal(res.q_lo, ql)
        shard = (ql % 256).astype(np.int32) % self.NS
        np.testing.assert_array_equal(res.shard_ids, shard)
        lanes = (shard[:, None] * self.F + np.arange(self.F)).reshape(-1)
        rep = lambda x: np.repeat(x, self.F)
        rsn, _qh, _ql, t_rec = gang_record(
            t, self.S, rep(khi), rep(klo), lanes, rep(rpc[0]), rep(rpc[1]))
        np.testing.assert_array_equal(res.reasons, rsn.reshape(70, self.F))
        assert_tables_equal(res.table, t_rec)
        assert not res.conflicts.any()
        np.testing.assert_array_equal(
            res.counts, np.bincount(shard[:10], minlength=self.NS))
        # The next batch conflicts exactly with the ring's 10 entries.
        res2 = self._batch(res.table, khi[:20], klo[:20], rpcs(20, 100),
                           np.zeros(20, np.int32), res.ring_hi, res.ring_lo,
                           res.counts)
        con_r = ref_conflict_scan(
            jnp.asarray(qh[:10]), jnp.asarray(ql[:10]),
            jnp.ones(10, jnp.int32), jnp.asarray(qh[:20]),
            jnp.asarray(ql[:20]))
        np.testing.assert_array_equal(res2.conflicts, np.asarray(con_r))
        assert int(res2.conflicts.sum()) == 10

    def test_empty_ring_conflicts_with_nothing(self):
        """Ring contents outside the live span (count 0) never conflict;
        with ``ring_cls`` omitted every live entry is class SET and
        conflicts with a same-key query."""
        r = rng(8)
        t, _rh, _rl, cnt = self._empty()
        khi = r.integers(0, 2**32, 12).astype(np.uint32)
        klo = r.integers(0, 2**32, 12).astype(np.uint32)
        qh, ql = np_keyhash2x32(khi, klo)
        shard = (ql % 256).astype(np.int32) % self.NS
        ring_hi = np.zeros((self.NS, self.CAP), np.uint32)
        ring_lo = np.zeros((self.NS, self.CAP), np.uint32)
        ring_hi[shard, np.arange(12)] = qh        # stale: not live
        ring_lo[shard, np.arange(12)] = ql
        ex = np.zeros(12, np.int32)
        res = self._batch(t, khi, klo, rpcs(12), ex, jnp.asarray(ring_hi),
                          jnp.asarray(ring_lo), cnt)
        assert res.conflicts.tolist() == [0] * 12
        np.testing.assert_array_equal(res.counts, cnt)
        live = np.full(self.NS, self.CAP, np.int32)
        res2 = self._batch(t, khi, klo, rpcs(12, 50), ex,
                           jnp.asarray(ring_hi), jnp.asarray(ring_lo), live)
        assert res2.conflicts.tolist() == [1] * 12

    def test_shard_route_matches_key_router(self):
        from repro.core.shard import KeyRouter
        from repro.core.types import keyhash

        keys = [f"s{i}" for i in range(64)]
        hi, lo = raw_lanes([keyhash(k) for k in keys])
        router = KeyRouter(3)
        np.testing.assert_array_equal(
            route(hi, lo, router.slot_map),
            np.array([router.shard_of(k) for k in keys]))


class TestGroupRecord:
    """``gang_record_groups``: all-or-nothing multi-key records, one
    dispatch on accept AND reject."""

    KEYS = [(1, 1), (2, 2), (3, 3)]

    def test_accept_and_reject_single_dispatch(self):
        t = GangTable.empty(16, 2)
        record_groups(t, 16, [(9, self.KEYS)])     # warm the jit cache
        reset_dispatch_count()
        rsn, t = record_groups(t, 16, [(1, self.KEYS)])
        assert rsn.tolist() == [INSERT] and dispatch_count() == 1
        reset_dispatch_count()
        # Conflict: same keys again (different op) — rejects, still 1 call.
        rsn2, _t = record_groups(t, 16, [(2, self.KEYS)])
        assert rsn2.tolist() == [CONFLICT] and dispatch_count() == 1
        reset_dispatch_count()
        # Both verdicts in one batch, one call.
        rsn3, _t = record_groups(t, 16, [(3, [(4, 4), (5, 5)]),
                                         (4, self.KEYS)])
        assert rsn3.tolist() == [INSERT, CONFLICT] and dispatch_count() == 1
        reset_dispatch_count()

    def test_reject_leaves_table_bit_identical(self):
        t = GangTable.empty(16, 2)
        _rsn, t = record_groups(t, 16, [(1, [(7, 7)])])
        # Op with one fresh key and one conflicting key: must reject and
        # leave the table untouched (no partial insert, no rollback).
        rsn2, t2 = record_groups(t, 16, [(2, [(5, 5), (7, 7)])])
        assert rsn2.tolist() == [CONFLICT]
        assert_tables_equal(t2, t)

    @pytest.mark.parametrize("sets,ways,kspan", [
        (8, 2, 4), (16, 4, 6), (64, 4, 3),
    ])
    def test_matches_oracle_collision_heavy(self, sets, ways, kspan):
        r = rng(sets + ways)
        table = oracle_t = GangTable.empty(sets, ways)
        seen = set()
        for chunk in range(4):                     # 80 ops, 4 dispatches
            groups = []
            for i in range(20):
                K = int(r.integers(1, 7))
                hi = r.integers(0, kspan, K)
                lo = r.integers(0, kspan, K)
                groups.append((1 + 20 * chunk + i,
                               list(zip(hi.tolist(), lo.tolist()))))
            rsn, table = record_groups(table, sets, groups)
            want, oracle_t = oracle_groups(oracle_t, sets, groups)
            np.testing.assert_array_equal(rsn, want)
            assert_tables_equal(table, oracle_t)
            seen.update(want.tolist())
        assert INSERT in seen and seen & {CONFLICT, FULL}

    def test_same_rpc_retry_is_idempotent_dup(self):
        """A retry of the same op (same rpc, same keys) is accepted as a
        RIFL duplicate in-kernel and leaves the table as it was; another
        op on those keys conflicts."""
        t = GangTable.empty(16, 4)
        keys = [(3, 3), (4, 4)]
        rsn, t = record_groups(t, 16, [(1, keys)])
        assert rsn.tolist() == [INSERT]
        rsn2, t2 = record_groups(t, 16, [(2, keys)])
        assert rsn2.tolist() == [CONFLICT]
        rsn3, t3 = record_groups(t, 16, [(1, keys)])
        assert rsn3.tolist() == [DUP]
        assert_tables_equal(t3, t)

    def test_capacity_reject_all_or_nothing(self):
        t = GangTable.empty(1, 2)    # one set, two ways
        for k in (1, 2):
            rsn, t = record_groups(t, 1, [(k, [(k, k)])])
            assert rsn.tolist() == [INSERT]
        assert int((np.asarray(t.occ) > 0).sum()) == 2
        rsn2, t2 = record_groups(t, 1, [(3, [(9, 9), (10, 10)])])
        assert rsn2.tolist() == [FULL]   # capacity: whole op rejected
        assert_tables_equal(t2, t)

    def test_device_witness_multikey_one_dispatch_no_rollback(self):
        """DeviceWitness multi-key records go through the grouped kernel:
        1 dispatch when the op rejects, and no key of it is left behind."""
        from repro.core import DeviceWitness
        from repro.core.types import Op, OpType, RecordStatus

        w = DeviceWitness(64, 4)
        w.start(1)
        w.record(1, (7,), (1, 1), Op(OpType.SET, ("x",), (0,), (1, 1)))
        reject_op = Op(OpType.MSET, ("a", "b"), (1, 2), (2, 1))
        reset_dispatch_count()
        st = w._record_keys((5, 7), reject_op.rpc_id, reject_op)
        assert dispatch_count() == 1
        reset_dispatch_count()
        assert st is RecordStatus.REJECTED
        # Mirror, stats and device table agree: key 5 was never placed.
        assert w.stats["rejects_conflict"] == 1
        assert w.occupancy == 1
        assert int((np.asarray(w.gang.table.occ) > 0).sum()) == 1
