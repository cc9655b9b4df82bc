"""Figure 11 / Appendix B.1: expected recordings before a witness-slot
conflict, by associativity — Monte Carlo over random keys driven through the
served record kernel (``gang_record`` on a one-lane gang).  Paper:
direct-mapped 4096 slots conflicts after ~80 inserts; 4-way associativity
fixes it."""
from __future__ import annotations

import numpy as np

from repro.kernels import GangTable, gang_record

from .common import emit


def inserts_to_first_reject(ways: int, slots: int, seed: int) -> int:
    rng = np.random.default_rng(seed)
    n_sets = slots // ways
    t = GangTable.empty(n_sets, ways)
    n = slots * 2
    qh = rng.integers(0, 2**32, n, dtype=np.uint32)
    ql = rng.integers(0, 2**32, n, dtype=np.uint32)
    # One rpc per record; reasons 1-2 accept, 3-4 reject.
    rsn, _qh, _ql, _t = gang_record(
        t, n_sets, qh, ql, np.zeros(n, np.int32), np.zeros(n, np.uint32),
        np.arange(1, n + 1, dtype=np.uint32))
    rejects = np.where(np.asarray(rsn) > 2)[0]
    return int(rejects[0]) if len(rejects) else n


def main(slots: int = 4096, trials: int = 12) -> dict:
    rows = []
    derived = {}
    for ways in (1, 2, 4, 8):
        xs = [inserts_to_first_reject(ways, slots, s) for s in range(trials)]
        mean = float(np.mean(xs))
        rows.append({"ways": ways, "slots": slots,
                     "mean_inserts_to_conflict": mean})
        derived[f"ways{ways}"] = mean
    emit(rows, "fig11: witness capacity vs associativity")
    derived["paper_direct_mapped"] = 80.0
    derived["assoc4_vs_direct"] = derived["ways4"] / derived["ways1"]
    print("derived:", derived)
    return derived


if __name__ == "__main__":
    main()
