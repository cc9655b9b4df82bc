"""Flight-recorder tests: registry instruments, causal tracing, AIMD
admission, the host's reason-code stats on every record path (set-parallel,
grouped multi-key, fused cluster fast path), and the profiler spans of the
served update path (``telemetry.span``)."""
import numpy as np
import pytest

from repro.core import DeviceWitness, ShardedCluster, Witness, telemetry
from repro.core.client import ClientSession
from repro.core.device_witness import WitnessGang
from repro.core.overload import AdmissionQueue, AimdBound
from repro.core.telemetry import (
    Histogram,
    MetricsRegistry,
    Tracer,
    _mix_id,
    stage_attribution,
)
from repro.core.types import Op, OpType, RecordStatus

# Kernel reason-code columns (index 0 unused).
_R_INSERT, _R_DUP, _R_CONFLICT, _R_FULL = 1, 2, 3, 4
_STAT_OF = {_R_INSERT: "reason_insert", _R_DUP: "reason_dup",
            _R_CONFLICT: "reason_conflict", _R_FULL: "reason_full"}


# ---------------------------------------------------------------------------
# instruments
# ---------------------------------------------------------------------------
class TestInstruments:
    def test_histogram_percentiles_match_numpy(self):
        r = np.random.default_rng(7)
        xs = np.concatenate([
            r.lognormal(mean=2.0, sigma=1.5, size=4000),
            r.uniform(0.0, 5.0, size=1000),
        ])
        h = Histogram("t")
        for v in xs:
            h.record(float(v))
        assert h.count == len(xs)
        assert h.max == pytest.approx(float(xs.max()))
        assert h.mean == pytest.approx(float(xs.mean()), rel=1e-9)
        for q in (0.5, 0.9, 0.99):
            exact = float(np.quantile(xs, q))
            # log-bucket resolution at _SUB=5 bounds relative error ~2.2%;
            # nearest-rank vs interpolation adds a little on small tails.
            assert h.percentile(q) == pytest.approx(exact, rel=0.10), q

    def test_histogram_small_and_zero(self):
        h = Histogram("t")
        assert h.percentile(0.99) == 0.0
        h.record(0.0)
        assert h.percentile(0.5) == 0.0   # capped at observed max
        h.record(1000.0)
        assert h.percentile(1.0) == pytest.approx(1000.0, rel=0.05)

    def test_registry_reset_in_place_keeps_handles(self):
        reg = MetricsRegistry()
        c = reg.counter("c")
        g = reg.gauge("g")
        h = reg.histogram("h")
        c.inc(3)
        g.set(9.0)
        h.record(5.0)
        reg.reset()
        # The SAME objects are live and zeroed — hot-path holders never
        # re-fetch between scenario runs.
        assert c is reg.counter("c") and c.value == 0
        assert g is reg.gauge("g") and g.max == 0.0
        assert h is reg.histogram("h") and h.count == 0
        c.inc()
        assert reg.counter("c").value == 1

    def test_registry_type_mismatch_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_null_registry_while_disabled(self):
        telemetry.disable()
        try:
            inst = telemetry.get_registry().histogram("nope")
            inst.record(5.0)
            assert inst.percentile(0.5) == 0.0
            assert inst.count == 0
        finally:
            telemetry.enable()
        assert telemetry.get_registry() is telemetry.registry()


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------
class TestTracer:
    def test_sampling_is_deterministic_and_roughly_proportional(self):
        tr = Tracer(sample=0.25)
        ids = [(c, s) for c in range(40) for s in range(25)]
        kept = [i for i in ids if tr.sampled(i)]
        assert kept == [i for i in ids if tr.sampled(i)]  # stable
        assert 0.15 < len(kept) / len(ids) < 0.35
        assert _mix_id((1, 2)) != _mix_id((2, 1))

    def test_children_parent_to_root_and_close_open(self):
        tr = Tracer()
        root = tr.begin((1, 1), "op", 0.0, actor="client")
        tr.span((1, 1), "witness_record", 1.0, 2.0, actor="w0")
        tr.span((1, 1), "master_update", 3.0, 1.5, actor="m0")
        tr.end(root, 10.0, status="1rtt")
        # forced spans get their own trace
        tr.span(("sync", "m0"), "master_sync", 5.0, 2.0, force=True)
        leaked = tr.begin((9, 9), "op", 8.0)
        assert leaked is not None
        assert tr.close_open(20.0) == 1
        ids = {s.span_id for s in tr.spans}
        for s in tr.spans:
            assert s.end is not None
            assert s.parent is None or s.parent in ids
        kids = [s for s in tr.spans if s.trace_id == (1, 1) and s.parent]
        assert {s.parent for s in kids} == {root}
        assert [s.status for s in tr.spans if s.trace_id == (9, 9)] \
            == ["unfinished"]

    def test_export_chrome_roundtrip(self, tmp_path):
        import json

        tr = Tracer()
        r = tr.begin((1, 2), "op", 0.0, actor="client")
        tr.span((1, 2), "witness_record", 1.0, 2.0, actor="w0",
                status="accepted")
        tr.instant((1, 2), "timeout", 5.0, actor="client")
        tr.end(r, 6.0)
        path = tmp_path / "trace.json"
        doc = tr.export_chrome(str(path))
        loaded = json.loads(path.read_text())
        assert loaded == doc
        evs = loaded["traceEvents"]
        assert {e["ph"] for e in evs} == {"X", "i", "M"}
        xs = [e for e in evs if e["ph"] == "X"]
        assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in xs)
        names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
        assert {"client", "w0"} <= names

    def test_stage_attribution_tail_cohort(self):
        tr = Tracer()
        for i in range(100):
            r = tr.begin((1, i), "op", 0.0)
            dur = 1.0 + float(i)   # distinct durations: clean p99 cut
            tr.span((1, i), "master_update", 0.1, dur)
            tr.end(r, dur + 0.2)
        att = stage_attribution(tr, tail_q=0.99)
        assert att["n_ops"] == 100
        assert att["tail_n"] == 2          # ops 98 and 99 at/above the cut
        assert att["stages_tail"]["master_update"] == pytest.approx(99.5)
        assert att["stages_all"]["master_update"] == pytest.approx(50.5)


# ---------------------------------------------------------------------------
# trace survives a mid-scenario master crash
# ---------------------------------------------------------------------------
class TestTraceCrashSurvival:
    def test_spans_closed_and_parents_resolve_across_crash(self):
        from repro.sim import OpenLoopWorkload, run_openloop_scenario
        from repro.core.overload import ArmorConfig

        tr = Tracer(sample=1.0)
        r = run_openloop_scenario(
            workload=OpenLoopWorkload(rate_ops_per_us=0.05, n_clients=8,
                                      n_items=8, seed=5),
            duration_us=6_000.0, f=1, armor=ArmorConfig(queue_capacity=16),
            seed=5, heartbeat=True, fail_master_at={0: 2_500.0}, tracer=tr,
        )
        assert r.failovers, "crash was never detected"
        assert tr.spans, "tracer saw nothing"
        assert not tr.open_spans(), "spans leaked past scenario teardown"
        ids = {s.span_id for s in tr.spans}
        for s in tr.spans:
            assert s.end is not None and s.end >= s.start
            assert s.parent is None or s.parent in ids
        # The kill is visible in the trace: ops in flight at the crash
        # either closed as failed/unfinished or paid timeout retries before
        # completing against the recovered master.
        roots = [s for s in tr.spans if s.name == "op"]
        assert roots
        detours = {ev["name"] for ev in tr.instants}
        assert "timeout" in detours or any(
            s.status in ("failed", "unfinished") for s in roots)


# ---------------------------------------------------------------------------
# AIMD adaptive admission
# ---------------------------------------------------------------------------
class TestAimdBound:
    def test_converges_to_delay_target_and_backs_off(self):
        q = AdmissionQueue(4, scope="t1")
        h = Histogram("svc")
        for _ in range(100):
            h.record(2.0)          # p50 ~= 2 µs
        ctl = AimdBound(q, h, target_delay_us=40.0)
        for _ in range(50):
            ctl.tick()
        assert abs(q.capacity - 20) <= 1   # 40 / 2 = 20, additive approach
        # Service time inflates 10x -> multiplicative decrease toward 4.
        h.reset()
        for _ in range(100):
            h.record(20.0)
        caps = [ctl.tick() for _ in range(6)]
        assert caps[0] < 20 and q.capacity <= max(4, caps[0])
        assert q.capacity >= ctl.min_cap

    def test_holds_bound_without_signal(self):
        q = AdmissionQueue(16, scope="t2")
        h = Histogram("svc")
        ctl = AimdBound(q, h, target_delay_us=40.0)
        for _ in range(5):
            assert ctl.tick() == 16    # < 16 samples: no move
        h.record(0.0)                  # degenerate p50 == 0 guard
        for _ in range(20):
            h.record(0.0)
        assert ctl.tick() == 16


# ---------------------------------------------------------------------------
# reason-code stats: one count per settled outcome
# ---------------------------------------------------------------------------
def _host_reasons(*witnesses) -> np.ndarray:
    out = np.zeros(5, np.int64)
    for w in witnesses:
        for code, stat in _STAT_OF.items():
            out[code] += w.stats[stat]
    return out


def _accepted(statuses) -> int:
    return sum(1 for st in statuses if st is RecordStatus.ACCEPTED)


class TestReasonCounterParity:
    """``DeviceWitness.stats["reason_*"]`` counts every settled kernel
    outcome once, on every record path, and agrees with the statuses the
    witness returned."""

    def test_collision_heavy_setparallel_batch(self):
        s = ClientSession(client_id=1)
        dw = DeviceWitness(16, 2)
        dw.start(master_id=1)
        # Tiny keyspace: inserts, then conflicts on the same keys, then a
        # full set; retries of recorded rpcs are dups.
        ops = [s.op_set(f"k{i % 6}", "v") for i in range(40)]
        st = dw.record_batch(1, ops)
        st += dw.record_batch(1, ops[:10])   # exact dup retries
        host = _host_reasons(dw)
        assert host[_R_INSERT] > 0 and host[_R_CONFLICT] > 0
        assert host[_R_DUP] > 0
        assert host.sum() == len(st)
        assert host[_R_INSERT] + host[_R_DUP] == _accepted(st) \
            == dw.stats["accepts"]

    def test_dup_retry_single_op_grouped_path(self):
        s = ClientSession(client_id=2)
        dw = DeviceWitness(16, 2)
        dw.start(master_id=1)
        op = s.op_set("x", "v")
        for _ in range(3):   # first insert, then 2 idempotent dup accepts
            assert dw.record(1, op.key_hashes(), op.rpc_id, op) \
                is RecordStatus.ACCEPTED
        op2 = s.op_set("x", "w")
        assert dw.record(1, op2.key_hashes(), op2.rpc_id, op2) \
            is RecordStatus.REJECTED
        host = _host_reasons(dw)
        assert host[_R_INSERT] == 1
        assert host[_R_DUP] == 2
        assert host[_R_CONFLICT] == 1
        assert host[_R_FULL] == 0

    def test_multikey_groups_batch(self):
        s = ClientSession(client_id=3)
        dw = DeviceWitness(16, 2)
        dw.start(master_id=1)
        ops = [s.op_mset([(f"a{i}", "1"), (f"b{i % 3}", "2")])
               for i in range(12)]
        st = dw.record_batch(1, ops)
        st += dw.record_batch(1, ops[:4])          # multi-key dup retries
        host = _host_reasons(dw)
        # Grouped accounting is per-GROUP (one count per op), like _settle.
        assert host.sum() == 16
        assert host[_R_INSERT] + host[_R_DUP] == _accepted(st)
        assert host[_R_DUP] == _accepted(st[12:])

    def test_full_sets_reason_full(self):
        s = ClientSession(client_id=4)
        dw = DeviceWitness(2, 1)   # 2 sets x 1 way: fills instantly
        dw.start(master_id=1)
        ops = [s.op_set(f"u{i}", "v") for i in range(16)]
        st = dw.record_batch(1, ops)
        host = _host_reasons(dw)
        assert host[_R_FULL] + host[_R_CONFLICT] > 0
        assert host[_R_FULL] + host[_R_CONFLICT] == len(st) - _accepted(st)
        assert host[_R_FULL] == dw.stats["rejects_full"]

    def test_parity_matches_python_witness_outcomes(self):
        """Same batch on both witness backends: the device witness's reason
        counts agree with the python Witness's own outcome bookkeeping."""
        s = ClientSession(client_id=5)
        ops = [s.op_set(f"k{i % 5}", "v") for i in range(30)]
        pw, dw = Witness(64, 4), DeviceWitness(64, 4)
        pw.start(master_id=9)
        dw.start(master_id=9)
        assert pw.record_batch(9, ops) == dw.record_batch(9, ops)
        host = _host_reasons(dw)
        assert host[_R_INSERT] == \
            pw.stats["accepts"] - pw.stats["accepts_dup"]
        assert host[_R_DUP] == pw.stats["accepts_dup"]
        assert host[_R_CONFLICT] == pw.stats["rejects_conflict"]
        assert host[_R_FULL] == pw.stats["rejects_full"]

    def test_fused_cluster_fastpath_parity(self):
        """The one-dispatch multi-shard fast path counts one outcome per
        (op, witness copy): the granularity the driver settles at."""
        from repro.sim.workload import BatchedWorkload

        cluster = ShardedCluster(n_shards=2, f=2, seed=3,
                                 witness_backend="device")
        session = cluster.new_client()
        wl = BatchedWorkload(batch_size=32, conflict_frac=0.3, seed=3)
        outs = []
        for _ in range(3):
            outs += cluster.update_batch(session, wl.batch(session))
        assert cluster._fused.stats["fused_batches"] == 3
        witnesses = [w for sh in cluster.shards for w in sh.witnesses]
        host = _host_reasons(*witnesses)
        assert host.sum() == 3 * 32 * 2
        assert host[_R_INSERT] > 0
        assert host[_R_INSERT] + host[_R_DUP] == \
            sum(o.witness_accepts for o in outs)

    def test_drain_zeroes_and_lane_recycle_resets(self):
        """A recycled lane starts from an empty table and its new tenant's
        counts from zero: the key the old tenant held inserts afresh."""
        s = ClientSession(client_id=6)
        gang = WitnessGang(16, 2, n_lanes=2)
        w = DeviceWitness(16, 2, gang=gang)
        w.start(master_id=1)
        op = s.op_set("x", "v")
        w.record(1, op.key_hashes(), op.rpc_id, op)
        assert _host_reasons(w)[_R_INSERT] == 1
        lane = w.lane
        w.end()
        w2 = DeviceWitness(16, 2, gang=gang)
        w2.start(master_id=2)
        w3 = DeviceWitness(16, 2, gang=gang)
        w3.start(master_id=3)
        assert lane in (w2.lane, w3.lane)        # lane actually recycled
        tenant = w2 if w2.lane == lane else w3
        assert _host_reasons(tenant).sum() == 0
        op2 = s.op_set("x", "w")
        assert tenant.record(tenant.master_id, op2.key_hashes(), op2.rpc_id,
                             op2) is RecordStatus.ACCEPTED
        host = _host_reasons(tenant)
        assert host[_R_INSERT] == 1 and host.sum() == 1


# ---------------------------------------------------------------------------
# profiler spans of the served update path
# ---------------------------------------------------------------------------
def _spans(tmp_path, fn):
    """Run ``fn`` under a CPU profiler session; return its ``curp.*`` host
    spans as (name, start_ns, end_ns), in start order."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(e.name[len(telemetry.SPAN_PREFIX):], e.start_ns,
                     e.end_ns) for e in line.events
                    if e.name.startswith(telemetry.SPAN_PREFIX)]
    return sorted(out, key=lambda x: (x[1], -x[2]))


def _parents(spans):
    """Each span's innermost enclosing span (None for a root); raises on
    spans that overlap without nesting."""
    parents, stack = [], []
    for i, (_n, a, b) in enumerate(spans):
        while stack and spans[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            assert b <= spans[stack[-1]][2], "spans overlap without nesting"
        parents.append(stack[-1] if stack else None)
        stack.append(i)
    return parents


def _small_cluster():
    from repro.core import WitnessGeometry

    cl = ShardedCluster(n_shards=2, f=2, sync_batch=4,
                        witness_backend="device",
                        geometry=WitnessGeometry(64, 4))
    return cl, cl.new_client()


def _phases_after_root(spans):
    parents = _parents(spans)
    assert [n for n, _a, _b in spans].count("update_batch") == 1
    assert spans[0][0] == "update_batch" and parents[0] is None
    assert all(p == 0 for p in parents[1:]), "phases nest under the root"
    names = [n for n, _a, _b in spans[1:]]
    assert names.count("sync") >= names.count("gc") >= 1
    return names


def _waves(names):
    """The syncs of each drain wave: every wave is one ``sync`` span per
    master that synced, then ONE ``gc`` span for all of them."""
    waves = []
    while names:
        k = 0
        while names[k] == "sync":
            k += 1
        assert k >= 1 and names[k] == "gc", names
        waves.append(k)
        names = names[k + 1:]
    return waves


class TestProfilerSpans:
    def test_fused_path_spans(self, tmp_path):
        cl, s = _small_cluster()
        cl.update_batch(s, [s.op_set(f"w{i}", "v") for i in range(16)])
        ops = [s.op_set(f"k{i}", "v") for i in range(16)]
        spans = _spans(tmp_path, lambda: cl.update_batch(s, ops))
        assert cl._fused.stats["fused_batches"] == 2
        names = _phases_after_root(spans)
        assert names[:4] == ["preflight", "record", "settle", "master"]
        assert set(names[4:]) == {"sync", "gc"}
        assert _waves(names[4:])[0] == 2      # both masters, one gc

    def test_per_shard_path_spans(self, tmp_path):
        """A one-field HMSET batch declines the fused path: its preflight,
        then the per-shard grouping, the master rounds of every shard, ONE
        record and settle for every witness of every shard, then per shard
        the classification, then the drain: each wave one sync per master
        that syncs and one gc for all of them."""
        cl, s = _small_cluster()
        cl.update_batch(s, [s.op_hmset(f"w{i}", (("f0", "v"),))
                            for i in range(16)])
        ops = [s.op_hmset(f"h{i}", (("f0", "v"),)) for i in range(16)]
        spans = _spans(tmp_path, lambda: cl.update_batch(s, ops))
        assert cl._fused.stats["fused_batches"] == 0
        names = _phases_after_root(spans)
        n_shards = len({cl.shard_of(op.keys[0]) for op in ops})
        assert names[:2] == ["preflight", "preflight"]
        assert names[2:4 + n_shards] == \
            ["master"] * n_shards + ["record", "settle"]
        rest = names[4 + n_shards:]
        assert rest[:n_shards] == ["master"] * n_shards
        assert _waves(rest[n_shards:])[0] == n_shards

    def test_disabled_emits_no_spans(self, tmp_path):
        cl, s = _small_cluster()
        cl.update_batch(s, [s.op_set(f"w{i}", "v") for i in range(16)])
        ops = [s.op_set(f"k{i}", "v") for i in range(16)]
        telemetry.disable()
        try:
            assert telemetry.span("x") is telemetry.span("y")
            spans = _spans(tmp_path, lambda: cl.update_batch(s, ops))
        finally:
            telemetry.enable()
        assert spans == []


# ---------------------------------------------------------------------------
# stacked-record counters of the per-shard path
# ---------------------------------------------------------------------------
class TestStackedRecordCounters:
    def test_redis_shaped_call_counts_one_stacked_record(self):
        """One call of one-field HMSETs over 4 shards at f=2 declines the
        fused path and records in ONE stacked dispatch over all 4*f lanes."""
        from repro.core import WitnessGeometry

        f = 2
        cl = ShardedCluster(n_shards=4, f=f, witness_backend="device",
                            geometry=WitnessGeometry(64, 4))
        s = cl.new_client()
        ops = [s.op_hmset(f"user{i}", (("field0", "v"),)) for i in range(32)]
        assert len({cl.shard_of(op.keys[0]) for op in ops}) == 4
        names = ("witness.stacked_records", "witness.stacked_lanes")
        reg = telemetry.registry()
        before = [reg.counter(n).value for n in names]
        cl.update_batch(s, ops)
        assert cl._fused.stats["declined"] == 1
        assert [reg.counter(n).value - b for n, b in zip(names, before)] \
            == [1, 4 * f]

    def test_redis_shaped_call_counts_one_stacked_gc(self):
        """One call of one-field HMSETs over 16 masters at f=2 gc's every
        syncing master's witnesses in ONE stacked dispatch: one record and
        one gc dispatch for the whole call."""
        from repro.core import WitnessGeometry
        from repro.kernels import dispatch_count

        f = 2
        cl = ShardedCluster(n_shards=16, f=f, witness_backend="device",
                            sync_batch=4, geometry=WitnessGeometry(64, 4))
        s = cl.new_client()
        ops = [s.op_hmset(f"user{i}", (("field0", "v"),)) for i in range(160)]
        names = ("witness.gc_dispatches", "witness.stacked_gcs",
                 "witness.stacked_gc_lanes")
        reg = telemetry.registry()
        before = [reg.counter(n).value for n in names]
        syncs = [g.master.stats["batch_syncs"] for g in cl.shards]
        d0 = dispatch_count()
        cl.update_batch(s, ops)
        synced = sum(g.master.stats["batch_syncs"] > n
                     for g, n in zip(cl.shards, syncs))
        assert cl._fused.stats["declined"] == 1
        assert synced > 1
        assert [reg.counter(n).value - b for n, b in zip(names, before)] \
            == [1, 1, synced * f]
        assert dispatch_count() - d0 == 2


# ---------------------------------------------------------------------------
# dispatch-count shim rides the registry
# ---------------------------------------------------------------------------
class TestDispatchShim:
    def test_dispatch_count_is_a_registry_counter(self):
        from repro.kernels import dispatch_count, reset_dispatch_count

        reset_dispatch_count()
        before = telemetry.registry().counter("kernels.dispatches").value
        assert dispatch_count() == before == 0
        s = ClientSession(client_id=7)
        dw = DeviceWitness(16, 2)
        dw.start(master_id=1)
        dw.record_batch(1, [s.op_set("a", "v")])
        assert dispatch_count() == \
            telemetry.registry().counter("kernels.dispatches").value > 0
