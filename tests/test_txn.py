"""Mini-transaction subsystem tests (repro.core.txn): single-shard
short-circuit, cross-shard 2PC, coordinator/participant crashes at every
2PC stage, recovery resolution, the prepare/resolve race, and the serving
store's atomic group commit."""
import pytest

from repro.core import (
    CoordinatorCrash,
    ShardedCluster,
    TxnStatus,
    Witness,
)
from repro.core.txn import (
    TxnPending,
    abort_op,
    participant_state,
    prepare_op,
    resolve_txn,
)
from repro.sim import (
    TXN_CRASH_STAGES,
    check_linearizable_strict,
    run_txn_crash_scenario,
)

N_SHARDS = 4


def key_on_shard(router, shard: int, tag: str = "k") -> str:
    for i in range(10_000):
        k = f"{tag}{i}"
        if router.shard_of(k) == shard:
            return k
    raise AssertionError(f"no key found for shard {shard}")


@pytest.fixture(params=["python", "device"])
def cluster(request):
    sets = 1024 if request.param == "python" else 256
    return ShardedCluster(n_shards=N_SHARDS, f=3,
                          witness_backend=request.param, witness_sets=sets)


class TestTxnBasics:
    def test_single_shard_short_circuit_1rtt(self, cluster):
        cl = cluster.new_client()
        k1 = key_on_shard(cluster.router, 0, "a")
        k2 = key_on_shard(cluster.router, 0, "b")
        out = cluster.txn(cl, writes=[(k1, 1), (k2, 2)])
        assert out.status is TxnStatus.COMMITTED
        assert out.rtts == 1 and out.fast_path and out.n_shards == 1
        assert cluster.read(cl, cl.op_get(k1)).value == 1
        assert cluster.read(cl, cl.op_get(k2)).value == 2

    def test_cross_shard_commit_two_rounds(self, cluster):
        cl = cluster.new_client()
        kvs = [(key_on_shard(cluster.router, s), s * 10)
               for s in range(N_SHARDS)]
        out = cluster.txn(cl, writes=kvs)
        assert out.status is TxnStatus.COMMITTED
        assert out.rtts == 2 and out.fast_path
        assert out.n_shards == N_SHARDS
        for k, v in kvs:
            assert cluster.read(cl, cl.op_get(k)).value == v

    def test_read_set_values_returned_on_commit(self):
        c = ShardedCluster(n_shards=N_SHARDS, f=3)
        cl = c.new_client()
        k0 = key_on_shard(c.router, 0)
        k1 = key_on_shard(c.router, 1)
        c.update(cl, cl.op_set(k0, "seed"))
        c.sync_all()
        out = c.txn(cl, writes=[(k1, "w")], reads=[k0])
        assert out.status is TxnStatus.COMMITTED
        assert out.reads == {k0: "seed"}

    def test_single_shard_read_write_history_recorded_once(self):
        """Regression: a committed single-shard txn that reads AND writes
        the same key must appear in the history exactly once — a duplicate
        entry would force two linearization points for one atomic op and
        make the strict checker reject a correct execution."""
        c = ShardedCluster(n_shards=2, f=3)
        cl = c.new_client()
        c.update(cl, cl.op_set("k", "old"))
        c.sync_all()
        out = c.txn(cl, writes=[("k", "new")], reads=["k"])
        assert out.status is TxnStatus.COMMITTED
        assert out.reads == {"k": "old"}
        from repro.core.types import OpType

        txn_entries = [h for h in c.history
                       if h["op"].op_type is OpType.TXN]
        assert len(txn_entries) == 1
        ok, key = check_linearizable_strict(c.history)
        assert ok, f"phantom violation on {key}"

    def test_mset_atomic_matches_mset_values(self):
        c = ShardedCluster(n_shards=N_SHARDS, f=3)
        cl = c.new_client()
        kvs = [(key_on_shard(c.router, s, "ma"), f"v{s}")
               for s in range(N_SHARDS)]
        out = c.mset_atomic(cl, kvs)
        assert out.status is TxnStatus.COMMITTED
        for k, v in kvs:
            assert c.read(cl, cl.op_get(k)).value == v

    def test_same_spec_rerun_is_idempotent(self):
        c = ShardedCluster(n_shards=2, f=3)
        cl = c.new_client()
        kvs = [(key_on_shard(c.router, 0), 1), (key_on_shard(c.router, 1), 2)]
        spec = cl.txn_spec(kvs)
        out1 = c.txn(cl, None, spec=spec)
        lens = [len(g.master.log) for g in c.shards]
        out2 = c.txn(cl, None, spec=spec)   # full client retry
        assert out1.status is out2.status is TxnStatus.COMMITTED
        assert [len(g.master.log) for g in c.shards] == lens  # no re-apply

    def test_conflicting_concurrent_txn_aborts(self):
        """B's prepare hits A's undecided intent lock -> B votes NO and
        aborts; A then commits untouched."""
        c = ShardedCluster(n_shards=2, f=3)
        ca, cb = c.new_client(), c.new_client()
        k0 = key_on_shard(c.router, 0)
        k1 = key_on_shard(c.router, 1)
        spec_a = ca.txn_spec([(k0, "a0"), (k1, "a1")])
        for p in spec_a.parts:   # A prepares everywhere, doesn't decide yet
            vote = c.shards[p.shard_id].txn_prepare(
                ca.session_for(p.shard_id), prepare_op(spec_a, p))
            assert vote.granted
        out_b = c.txn(cb, writes=[(k0, "b0"), (k1, "b1")])
        assert out_b.status is TxnStatus.ABORTED
        assert out_b.abort_reason == "TXN_LOCKED"
        # finish A
        from repro.core.txn import commit_op

        for p in spec_a.parts:
            c.shards[p.shard_id].txn_decide(
                commit_op(spec_a, p), ca.session_for(p.shard_id))
        assert c.read(ca, ca.op_get(k0)).value == "a0"
        assert c.read(ca, ca.op_get(k1)).value == "a1"

    def test_regular_op_blocked_then_resolved(self):
        """A plain SET on an intent-locked key trips TXN_PENDING; the
        cluster resolves the orphan (abort: not all prepared) and retries."""
        c = ShardedCluster(n_shards=2, f=3)
        ca, cb = c.new_client(), c.new_client()
        k0 = key_on_shard(c.router, 0)
        k1 = key_on_shard(c.router, 1)
        spec = ca.txn_spec([(k0, "x"), (k1, "y")])
        p0 = spec.parts[0]
        assert c.shards[p0.shard_id].txn_prepare(
            ca.session_for(p0.shard_id), prepare_op(spec, p0)).granted
        locked = p0.write_kvs[0][0]
        out = c.update(cb, cb.op_set(locked, "after"))
        assert out.value == "OK"
        assert c.read(cb, cb.op_get(locked)).value == "after"
        assert participant_state(
            c.shards[p0.shard_id].master, spec, p0) == "aborted"

    def test_txn_pending_raised_without_resolution(self):
        """ShardGroup-level: the raw master path raises TxnPending with the
        blocking spec attached (the cluster layer is what resolves)."""
        c = ShardedCluster(n_shards=2, f=3)
        ca = c.new_client()
        k0 = key_on_shard(c.router, 0)
        k1 = key_on_shard(c.router, 1)
        spec = ca.txn_spec([(k0, "x"), (k1, "y")])
        p0 = spec.parts[0]
        c.shards[p0.shard_id].txn_prepare(
            ca.session_for(p0.shard_id), prepare_op(spec, p0))
        locked = p0.write_kvs[0][0]
        sub = ca.session_for(p0.shard_id)
        with pytest.raises(TxnPending) as ei:
            c.shards[p0.shard_id].update(sub, sub.op_set(locked, "z"))
        assert ei.value.spec.txn_id == spec.txn_id


class TestTxnCrashStages:
    """Coordinator/participant crashes at every 2PC message stage: the
    strict checker passes and no intent leaks past recovery."""

    @pytest.mark.parametrize("stage", TXN_CRASH_STAGES)
    @pytest.mark.parametrize("participant_crash", [False, True])
    def test_stage_crash_atomic(self, stage, participant_crash):
        r = run_txn_crash_scenario(
            stage=stage, n_shards=3, n_txns=10,
            participant_crash=participant_crash, seed=5,
        )
        assert r.intents_after == 0, "intent leaked past recovery"
        assert r.history_ok, f"strict violation on {r.offending_key}"
        if stage == "prepare-sent":
            # Not every leg prepared: resolution must abort.
            assert r.crashed_decision == "ABORTED"
        else:
            # Every leg prepared (decision possibly already partially
            # applied): resolution must commit.
            assert r.crashed_decision == "COMMITTED"

    def test_commit_sent_final_state_complete(self):
        """Crash after the first COMMIT leg: resolution re-commits the rest,
        so every write of the crashed txn is visible."""
        r = run_txn_crash_scenario(stage="commit-sent", n_shards=3,
                                   n_txns=8, seed=2)
        assert r.crashed_decision == "COMMITTED"
        assert r.history_ok and r.intents_after == 0

    def test_prepare_sent_no_partial_write(self):
        """Crash after the first PREPARE: resolution aborts; none of the
        crashed txn's writes may be visible (no torn write)."""
        r = run_txn_crash_scenario(stage="prepare-sent", n_shards=3,
                                   n_txns=8, seed=4)
        assert r.crashed_decision == "ABORTED"
        assert r.history_ok and r.intents_after == 0


class TestTxnRecoveryRaces:
    def test_straggler_prepare_refused_after_abort_resolution(self):
        """The classic 2PC race: resolution aborts a half-prepared txn;
        a delayed PREPARE for the missing leg must be refused (tombstone),
        not re-open the transaction."""
        c = ShardedCluster(n_shards=2, f=3)
        cl = c.new_client()
        k0 = key_on_shard(c.router, 0)
        k1 = key_on_shard(c.router, 1)
        spec = cl.txn_spec([(k0, "v0"), (k1, "v1")])
        p0, p1 = spec.parts
        assert c.shards[p0.shard_id].txn_prepare(
            cl.session_for(p0.shard_id), prepare_op(spec, p0)).granted
        assert resolve_txn(c, spec) is TxnStatus.ABORTED
        vote = c.shards[p1.shard_id].txn_prepare(
            cl.session_for(p1.shard_id), prepare_op(spec, p1))
        assert not vote.granted and vote.error == "TXN_DECIDED"
        assert c.read(cl, cl.op_get(k0)).value is None
        assert c.read(cl, cl.op_get(k1)).value is None

    def test_participant_crash_resurfaces_intent_and_resolves(self):
        """A participant master dies holding a prepared intent: backup
        restore + witness replay re-surface it; recovery resolves it
        cluster-wide (commit: all legs were prepared)."""
        c = ShardedCluster(n_shards=2, f=3, sync_batch=1000, auto_sync=False)
        cl = c.new_client()
        k0 = key_on_shard(c.router, 0)
        k1 = key_on_shard(c.router, 1)

        def crash_before_decide(stage, shard_id, idx):
            if stage == "decide" and idx == 0:
                raise CoordinatorCrash()

        with pytest.raises(CoordinatorCrash):
            c.txn(cl, writes=[(k0, "x"), (k1, "y")],
                  on_message=crash_before_decide)
        victim = c.router.shard_of(k0)
        assert c.shards[victim].master.store.txn_intents()
        rep = c.crash_master(victim)
        assert rep.txn_intents == 1          # intent survived into recovery
        assert rep.txn_resolved == 1 and rep.txn_committed == 1
        assert c.read(cl, cl.op_get(k0)).value == "x"
        assert c.read(cl, cl.op_get(k1)).value == "y"
        assert not any(g.master.store.txn_intents() for g in c.shards)

    def test_abort_tombstone_survives_master_crash(self):
        """The decision tombstone (RIFL record under decide_rpc) must be
        durable across a participant failover once synced."""
        c = ShardedCluster(n_shards=2, f=3)
        cl = c.new_client()
        k0 = key_on_shard(c.router, 0)
        k1 = key_on_shard(c.router, 1)
        spec = cl.txn_spec([(k0, "v0"), (k1, "v1")])
        p0, p1 = spec.parts
        c.shards[p0.shard_id].txn_prepare(
            cl.session_for(p0.shard_id), prepare_op(spec, p0))
        resolve_txn(c, spec)                 # aborts + tombstones both legs
        c.sync_all()
        c.crash_master(p1.shard_id)
        vote = c.shards[p1.shard_id].txn_prepare(
            cl.session_for(p1.shard_id), prepare_op(spec, p1))
        assert not vote.granted and vote.error == "TXN_DECIDED"

    def test_history_strict_linearizable_through_crash_and_recovery(self):
        c = ShardedCluster(n_shards=3, f=3)
        cl = c.new_client()
        keys = {s: key_on_shard(c.router, s, "h") for s in range(3)}

        def crash_mid_decide(stage, shard_id, idx):
            if stage == "decide" and idx == 1:
                raise CoordinatorCrash()

        c.txn(cl, writes=[(keys[0], "a"), (keys[1], "b")])
        with pytest.raises(CoordinatorCrash):
            c.txn(cl, writes=[(keys[1], "c"), (keys[2], "d")],
                  on_message=crash_mid_decide)
        c.crash_master(1)
        for k in keys.values():
            c.read(cl, cl.op_get(k))
        ok, key = check_linearizable_strict(c.history)
        assert ok, f"violation on {key}"


class TestWitnessIntentTombstones:
    def test_prepare_records_conflict_with_overlapping_keys(self):
        """A recorded PREPARE occupies its keys at the witness: an
        overlapping single-key record must be rejected until gc (the
        'tombstoned intent' that keeps commutativity checks sound)."""
        from repro.core.types import Op, OpType, RecordStatus, keyhash

        c = ShardedCluster(n_shards=2, f=3, sync_batch=1000, auto_sync=False)
        cl = c.new_client()
        k0 = key_on_shard(c.router, 0)
        k1 = key_on_shard(c.router, 1)
        spec = cl.txn_spec([(k0, "x"), (k1, "y")])
        p0 = spec.parts[0]
        c.shards[p0.shard_id].txn_prepare(
            cl.session_for(p0.shard_id), prepare_op(spec, p0))
        w: Witness = c.shards[p0.shard_id].witnesses[0]
        probe = Op(OpType.SET, (k0,), ("z",), (4242, 1))
        st = w.record(c.config.fetch(p0.shard_id).master_id,
                      probe.key_hashes(), probe.rpc_id, probe)
        assert st is RecordStatus.REJECTED
        assert not w.commutes_with_all((keyhash(k0),))

    def test_prepare_witness_records_gcd_after_sync(self):
        """Once the prepare is synced to backups its witness records are
        collected — capacity is returned even before the decision."""
        c = ShardedCluster(n_shards=2, f=3)
        cl = c.new_client()
        k0 = key_on_shard(c.router, 0)
        k1 = key_on_shard(c.router, 1)
        spec = cl.txn_spec([(k0, "x"), (k1, "y")])
        p0 = spec.parts[0]
        c.shards[p0.shard_id].txn_prepare(
            cl.session_for(p0.shard_id), prepare_op(spec, p0))
        occ_before = c.shards[p0.shard_id].witnesses[0].occupancy
        assert occ_before >= 1
        c.shards[p0.shard_id].sync_now()
        assert c.shards[p0.shard_id].witnesses[0].occupancy == 0
        # the intent itself is still there (undecided), now backup-durable
        assert c.shards[p0.shard_id].master.store.txn_intent(spec.txn_id)
        c.shards[p0.shard_id].txn_decide(
            abort_op(spec, p0), cl.session_for(p0.shard_id))


class TestServingAtomicCommit:
    def test_store_txn_atomic_group_commit(self):
        from repro.serving.kvstore import CurpSessionStore, SessionState

        store = CurpSessionStore(f=3, sync_batch=8, n_shards=4)
        group = [SessionState(f"g{i}", [1, i]) for i in range(6)]
        out = store.txn(group)
        assert out.status is TxnStatus.COMMITTED
        shards = {store.shard_of(s.session_id) for s in group}
        assert out.n_shards == len(shards) >= 2
        for s in group:
            st = store.load(s.session_id)
            assert st is not None and st.tokens == s.tokens

    def test_store_txn_survives_full_crash(self):
        from repro.serving.kvstore import CurpSessionStore, SessionState

        store = CurpSessionStore(f=3, sync_batch=1000, n_shards=2)
        store.txn([SessionState(f"c{i}", [i]) for i in range(4)])
        store.crash_and_recover()
        for i in range(4):
            st = store.load(f"c{i}")
            assert st is not None and st.tokens == [i]

    def test_store_txn_empty_group_noop(self):
        from repro.serving.kvstore import CurpSessionStore

        store = CurpSessionStore(f=3, n_shards=2)
        out = store.txn([])
        assert out.status is TxnStatus.COMMITTED and out.n_shards == 0


class TestTxnInUpdateBatch:
    def test_write_set_transactions_share_a_batch_with_updates(self):
        """Write-set transactions ride ``update_batch`` with plain updates:
        a multi-shard one prepares and commits in the first round, and a
        plain SET and a single-shard transaction that meet its intents run
        in the next round, after its commit, on the slow path."""
        from repro.core import telemetry

        cl = ShardedCluster(n_shards=N_SHARDS, f=3)
        s = cl.new_client()
        a = key_on_shard(cl.router, 0, "a")
        b = key_on_shard(cl.router, 1, "b")
        c = key_on_shard(cl.router, 0, "c")
        multi = s.op_txn(s.txn_spec([(a, "1"), (b, "2")], reads=[c]))
        single = s.op_txn(s.txn_spec([(c, "4")]))
        names = ("txn.batch.rounds", "txn.batch.deferred",
                 "txn.batch.multi_shard")
        reg = telemetry.registry()
        before = [reg.counter(n).value for n in names]
        out = cl.update_batch(s, [multi, s.op_set(a, "3"), single])
        assert [reg.counter(n).value - v for n, v in zip(names, before)] \
            == [2, 2, 1]
        assert out[0].value == ("COMMITTED", (None,))
        assert out[0].rtts == 2 and out[0].fast_path
        # Both meet the first transaction's legs in their master's
        # unsynced window: its decision left their sync to this round.
        assert [(o.fast_path, o.synced_path) for o in out[1:]] \
            == [(False, True)] * 2
        assert [cl.shards[cl.shard_of(k)].master.store.get(k)
                for k in (a, b, c)] == ["3", "2", "4"]
