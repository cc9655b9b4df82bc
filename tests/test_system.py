"""End-to-end behaviour tests: the paper's claims exercised through the whole
stack (protocol -> simulator -> framework integration)."""
import statistics

import pytest

from repro.core import LocalCluster
from repro.sim import UniformWriteWorkload, run_scenario


def test_end_to_end_paper_story():
    """The abstract's three claims, end to end:
    1. CURP completes updates in 1 RTT (vs 2 for primary-backup);
    2. latency ~halves vs synchronous replication;
    3. consistency survives a master crash."""
    curp = run_scenario(mode="curp", f=3, n_clients=1, n_ops=800,
                        op_factory=UniformWriteWorkload(seed=1), seed=1)
    sync = run_scenario(mode="sync", f=3, n_clients=1, n_ops=800,
                        op_factory=UniformWriteWorkload(seed=1), seed=1)
    assert curp.fast_fraction > 0.98                       # 1-RTT fast path
    m_curp = statistics.median(curp.update_latencies)
    m_sync = statistics.median(sync.update_latencies)
    assert m_sync / m_curp > 1.7                           # ~2x

    crash = run_scenario(mode="curp", f=3, n_clients=4, n_ops=200,
                         op_factory=UniformWriteWorkload(seed=2), seed=3,
                         crash_at_us=1200.0)
    from repro.sim import check_linearizable

    ok, key = check_linearizable(crash.history)
    assert ok and crash.recovery is not None


def test_witness_capacity_figure11_shape():
    """Appendix B.1: 4-way associativity massively outlasts direct-mapped."""
    import numpy as np

    from repro.kernels import GangTable, gang_record

    def inserts_to_first_reject(ways: int, slots: int = 256, seed: int = 0):
        rng = np.random.default_rng(seed)
        n_sets = slots // ways
        t = GangTable.empty(n_sets, ways)
        n = slots * 4
        qh = rng.integers(0, 2**32, n, dtype=np.uint32)
        ql = rng.integers(0, 2**32, n, dtype=np.uint32)
        rsn, _qh, _ql, _t = gang_record(
            t, n_sets, qh, ql, np.zeros(n, np.int32), np.zeros(n, np.uint32),
            np.arange(1, n + 1, dtype=np.uint32))
        rejects = np.where(np.asarray(rsn) > 2)[0]
        return int(rejects[0]) if len(rejects) else n

    direct = statistics.mean(
        inserts_to_first_reject(1, seed=s) for s in range(5)
    )
    assoc4 = statistics.mean(
        inserts_to_first_reject(4, seed=s) for s in range(5)
    )
    assert assoc4 > 2.5 * direct


def test_cluster_migration_filtering():
    """§3.6 case 3: ops on a migrated partition are rejected/ignored."""
    c = LocalCluster(f=3)
    cl = c.new_client()
    c.update(cl, cl.op_set("mine", 1))
    # master gives up ownership of keys starting with "theirs"
    c.sync_now()
    c.master.owned_partition = lambda k: not str(k).startswith("theirs")
    op = cl.op_set("theirs:x", 5)
    verdict, res = c.master.handle_update(
        op, c.config.fetch(0).witness_list_version, (), 0.0
    )
    assert verdict == "error" and res.error == "NOT_OWNER"
    # replay of a stray witness record for a migrated key is ignored too
    n = c.master.replay_from_witness([op])
    assert n == 0
