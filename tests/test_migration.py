"""Live-reconfiguration tests (repro.core.migration): SlotRouter <->
device slot-gather parity on random slot maps, online slot handover under
traffic, donor/receiver crashes mid-handover, the §3.6 fences, duplicate RIFL
retries across a slot move, hot-shard auto-split, and the serving store's
live session migration."""
import numpy as np
import pytest

from _gang import raw_lanes, route

from repro.core import (
    ShardedCluster,
    SlotMoving,
    SlotRouter,
    plan_rebalance,
)
from repro.core.types import keyhash
from repro.sim import check_linearizable_strict, run_migration_scenario


def key_on_shard(router, shard: int, tag: str = "k") -> str:
    for i in range(10_000):
        k = f"{tag}{i}"
        if router.shard_of(k) == shard:
            return k
    raise AssertionError(f"no key found for shard {shard}")


# ------------------------------------------------------------------ router
class TestSlotRouter:
    def test_uniform_map_matches_legacy_mod_n(self):
        """For pow2 shard counts dividing n_slots, slot routing reproduces
        the pre-slot-map mod-N placement exactly."""
        from repro.core.shard import _M32, mix2x32

        for n_shards in (1, 2, 4):
            r = SlotRouter.uniform(n_shards, n_slots=256)
            for i in range(200):
                kh = keyhash(f"u{i}")
                _, h3 = mix2x32((kh >> 32) & _M32, kh & _M32)
                assert r.shard_of(f"u{i}") == h3 % n_shards

    def test_assign_moves_slots_and_bumps_version(self):
        r = SlotRouter.uniform(2, n_slots=16)
        v0 = r.version
        moved = [s for s in range(16) if r.slot_map[s] == 0][:3]
        r.assign(moved, 1)
        assert r.version == v0 + 1
        assert all(r.slot_map[s] == 1 for s in moved)
        assert r.slots_of_shard(1) == sorted(
            set(r.slots_of_shard(1))
        )

    def test_parity_with_pallas_on_random_slot_maps(self):
        """SlotRouter <-> the device's slot gather (gang_fastpath_batch)
        bit-exact on random slot maps (the table-gather contract)."""
        rng = np.random.default_rng(11)
        keys = [f"user{i}" for i in range(300)] + list(range(64))
        hi, lo = raw_lanes([keyhash(k) for k in keys])
        for n_slots in (64, 256):
            for n_shards in (2, 3, 5):
                slot_map = rng.integers(0, n_shards, n_slots)
                router = SlotRouter(list(slot_map), n_shards=n_shards)
                dev = route(hi, lo, slot_map)
                py = np.array([router.shard_of(k) for k in keys])
                np.testing.assert_array_equal(dev, py)

    def test_fastpath_batch_routes_by_slot_map(self):
        """The fused batch places each op by the slot map it is given."""
        rng = np.random.default_rng(3)
        keys = [f"fk{i}" for i in range(100)]
        hi, lo = raw_lanes([keyhash(k) for k in keys])
        slot_map = rng.integers(0, 3, 64)
        router = SlotRouter(list(slot_map), n_shards=3)
        np.testing.assert_array_equal(
            route(hi, lo, slot_map),
            np.array([router.shard_of(k) for k in keys]),
        )


# ------------------------------------------------------- basic handover
class TestSlotHandover:
    def _seeded(self, n_shards=2, n_slots=64):
        c = ShardedCluster(n_shards=n_shards, f=3, n_slots=n_slots)
        cl = c.new_client()
        keys = [f"k{i}" for i in range(48)]
        for i, k in enumerate(keys):
            c.update(cl, cl.op_set(k, i))
        return c, cl, keys

    def test_migrate_moves_data_and_routing(self):
        c, cl, keys = self._seeded()
        dst = c.add_shard()
        slots = c.router.slots_of_shard(0)[:16]
        reports = c.migrate_slots(slots, dst)
        assert sum(r.keys_moved for r in reports) > 0
        moved = [k for k in keys if c.router.slot_of(k) in set(slots)]
        assert moved, "no seeded key lived in the moved slots"
        for k in moved:
            assert c.shard_of(k) == dst
            # data lives at the receiver, not the donor
            assert c.shards[dst].master.store.get(k) is not None
            assert c.shards[0].master.store.get(k) is None
        for i, k in enumerate(keys):     # nothing lost anywhere
            assert c.read(cl, cl.op_get(k)).value == i

    def test_moving_slot_redirects_then_serves(self):
        c, cl, keys = self._seeded()
        k = keys[0]
        slot = c.slot_of(k)
        dst = 1 - c.shard_of(k)
        migs = c.start_migration([slot], dst)
        with pytest.raises(SlotMoving):
            c.update(cl, cl.op_set(k, "during"))
        with pytest.raises(SlotMoving):
            c.read(cl, cl.op_get(k))
        for m in migs:
            m.run()
        # redirected op re-issues fresh and lands at the new owner
        out = c.update(cl, cl.op_set(k, "after"))
        assert out.value == "OK" and c.shard_of(k) == dst
        assert c.read(cl, cl.op_get(k)).value == "after"

    def test_untouched_slots_stay_fast_during_migration(self):
        c, cl, _keys = self._seeded()
        c.sync_all()                      # clean windows: no false conflicts
        dst = c.add_shard()
        slots = set(c.router.slots_of_shard(0)[:8])
        # Fresh, distinct keys on NON-moving slots (repeat writes to one key
        # would trip the ordinary §3.2.3 conflict path, not migration).
        fresh = iter(k for i in range(100_000)
                     if c.router.slot_of(k := f"u{i}") not in slots)
        migs = c.start_migration(sorted(slots), dst)
        for m in migs:
            while m.stage != "done":
                m.step()
                for _ in range(4):
                    k = next(fresh)
                    out = c.update(cl, cl.op_set(k, m.stage))
                    assert out.fast_path and out.rtts == 1, (k, m.stage)

    def test_rifl_duplicate_retry_across_slot_move(self):
        """Satellite: an op completed on the donor, retried after its slot
        moved, must RIFL-dedup at the receiver (same result, no
        double-apply) — the completion record migrated with the data."""
        c, cl, _ = self._seeded()
        op = cl.op_incr("counter")
        assert c.update(cl, op).value == 1
        # Model the lost-response retry RIFL actually permits: the client
        # never saw the first result, so its piggybacked ack frontier must
        # still sit AT the op's seq (an acked op is by contract never
        # retried, and ack-driven gc is free to forget its moved completion
        # record).  Rewind the completion state the harness advanced when it
        # delivered the response the "client" supposedly lost.
        cl._ids.first_incomplete = min(cl._ids.first_incomplete, op.rpc_id[1])
        cl._ids._completed.discard(op.rpc_id[1])
        slot = c.slot_of("counter")
        src = c.shard_of("counter")
        dst = 1 - src
        c.migrate_slots([slot], dst)
        dups_before = c.shards[dst].master.stats["dups"]
        log_before = len(c.shards[dst].master.log)
        out = c.update(cl, op)           # exact retry of the moved op
        assert out.value == 1            # original result re-externalized
        assert c.shards[dst].master.stats["dups"] == dups_before + 1
        assert len(c.shards[dst].master.log) == log_before
        assert c.read(cl, cl.op_get("counter")).value == 1
        # The retry completed and acked; the next op to reach THIS master
        # piggybacks the advanced frontier, which gc's the moved record
        # (the ack-driven overlay truncation).
        k_dst = next(f"after{i}" for i in range(10_000)
                     if c.shard_of(f"after{i}") == dst)
        assert c.update(cl, cl.op_set(k_dst, "1")).value is not None
        assert c.shards[dst].master.migrated_rifl == {}
        assert c.shards[dst].master.stats["migrated_rifl_gcd"] >= 1
        ok, key = check_linearizable_strict(c.history)
        assert ok, f"violation on {key}"

    def test_fenced_stale_witness_record_rejected(self):
        """Satellite: an in-flight update carrying the pre-handover
        WitnessListVersion (its records landed at the OLD witnesses) is
        refused by the master after the fence; the §3.6 refetch-and-retry
        then lands it at the new owner."""
        c, cl, keys = self._seeded()
        k = keys[0]
        src = c.shard_of(k)
        dst = 1 - src
        stale_wlv = c.config.fetch(src).witness_list_version
        op = cl.op_set(k, "stale")
        c.migrate_slots([c.slot_of(k)], dst)
        assert c.config.fetch(src).witness_list_version == stale_wlv + 1
        verdict, res = c.shards[src].master.handle_update(
            op, stale_wlv, (), 0.0
        )
        assert verdict == "error" and res.error == "WRONG_WITNESS_VERSION"
        # ... and even with a fresh wlv the donor no longer owns the key.
        verdict, res = c.shards[src].master.handle_update(
            op, c.config.fetch(src).witness_list_version, (), 0.0
        )
        assert verdict == "error" and res.error == "NOT_OWNER"
        out = c.update(cl, cl.op_set(k, "fresh"))   # client re-routes
        assert out.value == "OK"
        assert c.shards[dst].master.store.get(k) == "fresh"

    def test_donor_recovery_ignores_migrated_witness_remnants(self):
        """§3.6: after the handover, a donor crash must NOT replay witness
        records for the moved slots back into its store."""
        c = ShardedCluster(n_shards=2, f=3, n_slots=64, sync_batch=1000,
                           auto_sync=False)
        cl = c.new_client()
        keys = [f"w{i}" for i in range(24)]
        for i, k in enumerate(keys):
            c.update(cl, cl.op_set(k, i))     # unsynced + witness-recorded
        slots = c.router.slots_of_shard(0)[:32]
        c.migrate_slots(slots, 1)
        moved = [k for k in keys if c.router.slot_of(k) in set(slots)]
        assert all(c.shard_of(k) == 1 for k in moved)
        c.crash_master(0)
        for k in moved:
            assert c.shards[0].master.store.get(k) is None
            assert c.read(cl, cl.op_get(k)).value == keys.index(k)

    def test_add_and_remove_shard_round_trip(self):
        c, cl, keys = self._seeded()
        dst = c.add_shard()
        assert c.n_shards == 3
        c.migrate_slots(c.router.slots_of_shard(0)[:20], dst)
        reports = c.remove_shard(dst)
        assert c.shards[dst].retired and c.n_shards == 2
        assert sum(r.keys_moved for r in reports) >= 0
        assert not c.router.slots_of_shard(dst)
        for i, k in enumerate(keys):
            assert c.read(cl, cl.op_get(k)).value == i
        ok, key = check_linearizable_strict(c.history)
        assert ok, f"violation on {key}"


    def test_acked_op_duplicate_across_move_still_ignored(self):
        """Review regression: an op whose completion record was already
        ACKED away migrates as the ignore-as-duplicate marker (result
        None); a delayed network duplicate at the receiver must be ignored,
        not re-executed (re-execution would clobber later writes)."""
        c, cl, _ = self._seeded()
        op = cl.op_set("dupkey", "v1")
        assert c.update(cl, op).value == "OK"
        # Later traffic piggybacks the ack; the donor deletes the record.
        for i in range(3):
            c.update(cl, cl.op_set(f"after{i}", i))
        src = c.shard_of("dupkey")
        assert c.shards[src].master.rifl.check_duplicate(op.rpc_id).result \
            is None                      # synthetic ignore-marker now
        dst = 1 - src
        c.migrate_slots([c.slot_of("dupkey")], dst)
        c.update(cl, cl.op_set("dupkey", "v2"))      # newer write at recv
        verdict, res = c.shards[dst].master.handle_update(
            op, c.config.fetch(dst).witness_list_version, (), 0.0
        )
        assert verdict == "dup"                      # ignored, NOT re-run
        assert c.read(cl, cl.op_get("dupkey")).value == "v2"

    def test_mset_retry_follows_migrated_leg(self):
        """Review regression: retrying an mset with its original ``parts``
        after one leg's slots migrated must route that leg to the NEW owner
        and RIFL-dedup there (the completion records moved with the data).
        """
        c = ShardedCluster(n_shards=2, f=3, n_slots=64)
        cl = c.new_client()
        k0 = key_on_shard(c.router, 0, "ma")
        k1 = key_on_shard(c.router, 1, "mb")
        parts = cl.mset_parts([(k0, "x"), (k1, "y")])
        # Both legs actually applied, but the client never saw the reply.
        for sid, sub in parts.items():
            c.shards[sid].update(cl.session_for(sid), sub)
        src = c.shard_of(k0)
        dst = 1 - src
        c.migrate_slots([c.slot_of(k0)], dst)        # k0's leg moves
        logs = {s: len(c.shards[s].master.log) for s in range(2)}
        out = c.mset(cl, [(k0, "x"), (k1, "y")], parts=parts)
        assert out.value == "OK"
        # both legs deduped: no new MSET log entries anywhere
        assert {s: len(c.shards[s].master.log) for s in range(2)} == logs
        assert c.read(cl, cl.op_get(k0)).value == "x"
        assert c.read(cl, cl.op_get(k1)).value == "y"

    def test_mset_retry_split_leg_fails_loudly(self):
        """A migration that SPLITS one leg's keys across shards makes the
        original identity unreplayable: the retry raises a descriptive
        error instead of double-applying."""
        c = ShardedCluster(n_shards=2, f=3, n_slots=64)
        cl = c.new_client()
        ks = [f"sp{i}" for i in range(200) if c.shard_of(f"sp{i}") == 0]
        a = next(k for k in ks)
        b = next(k for k in ks if c.slot_of(k) != c.slot_of(a))
        parts = cl.mset_parts([(a, 1), (b, 2)])
        assert len(parts) == 1                       # one 2-key leg
        c.migrate_slots([c.slot_of(a)], 1)           # split the leg
        with pytest.raises(ValueError, match="invalidated"):
            cl.mset_parts([(a, 1), (b, 2)], prev=parts)

    def test_redirected_fresh_identities_released(self):
        """Review regression: a SlotMoving redirect must not freeze the
        client's ack frontier — identities the cluster allocated for the
        redirected mset/txn are abandoned, so later acks keep advancing."""
        c = ShardedCluster(n_shards=2, f=3, n_slots=64)
        cl = c.new_client()
        k = key_on_shard(c.router, 0, "fr")
        migs = c.start_migration([c.slot_of(k)], 1)
        with pytest.raises(SlotMoving):
            c.mset(cl, [(k, "v")])
        with pytest.raises(SlotMoving):
            c.txn(cl, writes=[(k, "v")])
        for m in migs:
            m.run()
        out = c.update(cl, cl.op_set(k, "v"))
        assert out.value == "OK"
        sess = cl.session_for(0)
        # the frontier advanced past every allocated id: no hole means the
        # redirected mset/txn identities were released, not leaked
        assert sess.first_incomplete > 1
        assert not sess._completed, \
            "abandoned ids left a hole in the ack frontier"


# --------------------------------------------------- crash mid-handover
class TestCrashMidHandover:
    @pytest.mark.parametrize("crash", ["donor", "receiver"])
    def test_crash_between_transfer_and_commit(self, crash):
        """Satellite: donor/receiver failover after the transfer but before
        the commit point; resume() redoes sync->transfer->handover and the
        strict checker stays green with zero lost writes."""
        r = run_migration_scenario(
            n_shards_before=2, n_shards_after=4, n_slots=64,
            ops_per_window=12, n_keys=64, crash=crash, seed=13,
        )
        assert r.resumed >= 1, "crash was never injected mid-handover"
        assert r.mismatches == 0
        assert r.history_ok, f"violation on {r.offending_key}"

    def test_clean_live_reshard_scenario(self):
        r = run_migration_scenario(
            n_shards_before=2, n_shards_after=4, n_slots=64,
            ops_per_window=30, n_keys=160, crash=None, seed=3,
        )
        assert r.mismatches == 0 and r.history_ok
        assert r.redirects == 0 or r.redirected_retried_ok >= 0
        # untouched slots stayed within 5% of steady-state fast ratio
        assert r.steady_fast - r.migration_fast_untouched <= 0.05

    def test_donor_crash_before_sync_replays_then_moves(self):
        """Crash the donor while slots are frozen but BEFORE the sync
        stage: witness replay restores the unsynced ops (slots still owned),
        and the resumed handover moves the recovered data."""
        c = ShardedCluster(n_shards=2, f=3, n_slots=64, sync_batch=1000,
                           auto_sync=False)
        cl = c.new_client()
        keys = [f"c{i}" for i in range(16)]
        for i, k in enumerate(keys):
            c.update(cl, cl.op_set(k, i))     # all unsynced
        slots = c.router.slots_of_shard(0)[:32]
        migs = c.start_migration(slots, 1)
        for m in migs:
            m.step()                           # freeze done, sync pending
            rep = c.crash_master(m.src)
            assert rep.replayed >= 0
            m.resume()
            m.run()
        for i, k in enumerate(keys):
            assert c.read(cl, cl.op_get(k)).value == i


# ------------------------------------------------------- hot-shard split
class TestHotShardRebalance:
    def test_plan_rebalance_moves_hottest_slots(self):
        loads = [0] * 16
        slot_map = [0] * 8 + [1] * 8
        for s in range(8):
            loads[s] = 100                    # shard 0 very hot
        moves = plan_rebalance(loads, slot_map, [0, 1], max_moves=16)
        assert moves, "no moves planned for an 8x imbalance"
        moved = [s for slots in moves.values() for s in slots]
        assert all(slot_map[s] == 0 for s in moved)
        assert 1 in moves

    def test_plan_rebalance_noops_when_balanced(self):
        loads = [10] * 16
        slot_map = [i % 4 for i in range(16)]
        assert plan_rebalance(loads, slot_map, [0, 1, 2, 3]) == {}

    def test_cluster_rebalance_spreads_hot_shard(self):
        import random

        c = ShardedCluster(n_shards=4, f=3)
        cl = c.new_client()
        rng = random.Random(5)
        hot = [k for k in (f"h{i}" for i in range(600))
               if c.shard_of(k) == 0][:40]
        for _ in range(300):
            c.update(cl, cl.op_set(rng.choice(hot), "v"))
        out = c.rebalance()
        assert sum(len(v) for v in out["moves"].values()) > 0
        # counters reset for the next measurement window (checked before the
        # verification reads below re-feed them)
        assert all(not g.slot_ops for g in c.shards)
        spread = {s: sum(1 for k in hot if c.shard_of(k) == s)
                  for s in range(4)}
        assert spread[0] < len(hot)           # hot shard shed load
        assert sum(spread.values()) == len(hot)
        for k in hot:                          # nothing lost
            assert c.read(cl, cl.op_get(k)).value == "v"


# -------------------------------------------------------- serving layer
class TestServingLiveMigration:
    def test_sessions_survive_live_migration_and_crash(self):
        from repro.serving.kvstore import CurpSessionStore, SessionState

        store = CurpSessionStore(f=3, sync_batch=8, n_shards=2, n_slots=64)
        for i in range(12):
            store.commit(SessionState(f"s{i}", [1, 2, i]))
        placed = {f"s{i}": store.shard_of(f"s{i}") for i in range(12)}
        dst = store.add_shard()
        slots = store.cluster.router.slots_of_shard(0)[:16]
        store.migrate_sessions(slots, dst)
        moved = [sid for sid in placed
                 if store.cluster.router.slot_of(
                     f"session:{sid}") in set(slots)]
        # the version-keyed cache refetched the new placement
        for sid in moved:
            assert store.shard_of(sid) == dst
        for i in range(12):                   # commits keep flowing
            store.commit(SessionState(f"s{i}", [1, 2, i, 99]))
        store.crash_and_recover()
        for i in range(12):
            st = store.load(f"s{i}")
            assert st is not None and st.tokens == [1, 2, i, 99]

    def test_store_rebalance_passthrough(self):
        from repro.serving.kvstore import CurpSessionStore, SessionState

        store = CurpSessionStore(f=3, n_shards=2, n_slots=64)
        for i in range(30):
            store.commit(SessionState(f"r{i}", [i]))
        out = store.rebalance()
        assert "moves" in out and "reports" in out
        for i in range(30):
            st = store.load(f"r{i}")
            assert st is not None and st.tokens == [i]
