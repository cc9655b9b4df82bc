"""The comparison that decides ``correct``.

The record kind's reference (a subclass of ``reference.Reference``) replays
the window's requests in the order the benchmark sent them.  Four numbers are
compared, each an exact count with the limit 0:

* ``outcome_mismatches``: acknowledged updates whose outcome (value, fast
  path, synced path, round trips, witness accepts) differs from the
  reference's;
* ``read_mismatches``: reads served in the window whose value differs from
  the reference's value at the point the read was served;
* ``replica_mismatches``: after a sync of every master, values of
  acknowledged keys, read back from each key's master and from each of its
  backups, that differ from the reference's;
* ``unacknowledged``: requests of the window that never got a reply.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Set, Tuple

from chipbench.reference import Reference

LIMITS = {"outcome_mismatches": 0, "read_mismatches": 0,
          "replica_mismatches": 0, "unacknowledged": 0}


def written(kind, actions: List[Tuple]) -> Set[str]:
    """Every key the window's update batches wrote."""
    return set().union(*(kind.written(act[1]) for act in actions
                         if act[0] == "batch"))


def replay(kind, cfg: dict, snapshot: Optional[Dict[str, Any]],
           actions: List[Tuple]) -> Reference:
    """Run the kind's reference over a window's actions; returns it with, for
    every action, its expected rows (batches) or value (reads) in
    ``ref.expected``."""
    ref = kind.Reference(cfg, snapshot)
    ref.expected = []
    for act in actions:
        if act[0] == "read":
            ref.expected.append(ref.read(act[1]))
        else:
            ref.expected.append(ref.update_batch(act[1]))
    return ref


def compare(ref: Reference, actions: List[Tuple], replicas: Dict[str, List[Any]],
            keys: Set[str], attempted: int, acknowledged: int
            ) -> Dict[str, Tuple[int, int]]:
    """Each number compared with its limit: ``{name: (value, limit)}``;
    ``keys`` are the keys the window wrote, each of which ``replicas`` must
    hold."""
    outcome = reads = 0
    for act, want in zip(actions, ref.expected):
        if act[0] == "read":
            reads += act[2] != want
        else:
            got = act[2]
            outcome += sum(a != b for a, b in zip(got, want))
            outcome += abs(len(got) - len(want))
    replica = sum(v != ref.values.get(k)
                  for k, vals in replicas.items() for v in vals)
    replica += len(keys - set(replicas))
    nums = {"outcome_mismatches": outcome, "read_mismatches": reads,
            "replica_mismatches": replica,
            "unacknowledged": attempted - acknowledged}
    return {k: (v, LIMITS[k]) for k, v in nums.items()}


def passed(nums: Dict[str, Tuple[int, int]]) -> bool:
    return all(v <= lim for v, lim in nums.values())
