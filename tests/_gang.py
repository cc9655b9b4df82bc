"""Shared helpers for the witness-gang kernel tests.

Each helper runs one served kernel op (``gang_record``,
``gang_record_groups``, ``gang_fastpath_batch``) on a small gang, or the
matching oracle of ``repro.kernels.ref``, with the bookkeeping the tests
would otherwise repeat: one distinct rpc identity per op, a lane per op,
and padding of ragged groups.
"""
import jax.numpy as jnp
import numpy as np

from repro.kernels import (
    GangTable,
    gang_fastpath_batch,
    gang_record,
    gang_record_groups,
    ref_gang_record,
)

INSERT, DUP, CONFLICT, FULL = 1, 2, 3, 4


def rpcs(n, start=1):
    """One distinct rpc identity per op: client 0, sequence ``start + i``."""
    return (np.zeros(n, np.uint32),
            np.arange(start, start + n, dtype=np.uint32))


def accepted(reasons) -> np.ndarray:
    """Accept bit per op: a fresh insert or an idempotent duplicate."""
    return np.isin(np.asarray(reasons), (INSERT, DUP)).astype(np.int32)


def _ops(k_hi, k_lo, cls, lane, rpc):
    k_hi = np.asarray(k_hi, np.uint32)
    k_lo = np.asarray(k_lo, np.uint32)
    (n,) = k_hi.shape
    cls = np.zeros(n, np.int32) if cls is None else np.asarray(cls, np.int32)
    lanes = np.broadcast_to(np.asarray(lane, np.int32), (n,))
    r_hi, r_lo = rpcs(n) if rpc is None else rpc
    return k_hi, k_lo, cls, lanes, r_hi, r_lo


def record(table, n_sets, k_hi, k_lo, cls=None, lane=0, rpc=None):
    """Single-key ops through ``gang_record`` (ONE dispatch); op i under
    rpc ``(0, i + 1)`` unless ``rpc`` gives (rpc_hi, rpc_lo) arrays.
    Returns (reasons, table)."""
    k_hi, k_lo, cls, lanes, r_hi, r_lo = _ops(k_hi, k_lo, cls, lane, rpc)
    reasons, _qh, _ql, table = gang_record(
        table, n_sets, k_hi, k_lo, lanes, r_hi, r_lo, cls)
    return np.asarray(reasons), table


def oracle(table, n_sets, k_hi, k_lo, cls=None, lane=0, rpc=None):
    """``ref_gang_record`` of the ops ``record`` takes, each a group of
    one key.  Returns (reasons, table)."""
    k_hi, k_lo, cls, lanes, r_hi, r_lo = _ops(k_hi, k_lo, cls, lane, rpc)
    groups = [(int(lanes[i]), (int(r_hi[i]), int(r_lo[i])),
               [(int(k_hi[i]), int(k_lo[i]), int(cls[i]))])
              for i in range(len(k_hi))]
    reasons, table = ref_gang_record(table, n_sets, groups)
    return np.asarray(reasons), table


def record_groups(table, n_sets, groups, lane=0):
    """``groups`` is a list of ``(rpc_lo, [(k_hi, k_lo), ...])``: one
    all-or-nothing group each, through ONE ``gang_record_groups`` call.
    Returns (reasons, table)."""
    G = len(groups)
    K = max(len(keys) for _rpc, keys in groups)
    k_hi = np.zeros((G, K), np.uint32)
    k_lo = np.zeros((G, K), np.uint32)
    k_val = np.zeros((G, K), np.int32)
    for g, (_rpc, keys) in enumerate(groups):
        for k, (h, l) in enumerate(keys):
            k_hi[g, k], k_lo[g, k], k_val[g, k] = h, l, 1
    res = gang_record_groups(
        table, n_sets, k_hi, k_lo, k_val, np.full(G, lane, np.int32),
        np.zeros(G, np.uint32),
        np.array([rpc for rpc, _keys in groups], np.uint32))
    return np.asarray(res.reasons), res.table


def oracle_groups(table, n_sets, groups, lane=0):
    """``ref_gang_record`` of the groups ``record_groups`` takes."""
    reasons, table = ref_gang_record(
        table, n_sets,
        [(lane, (0, rpc), [(int(h), int(l)) for h, l in keys])
         for rpc, keys in groups])
    return np.asarray(reasons), table


def route(k_hi, k_lo, slot_map, n_shards=8, n_sets=16):
    """Shard of each RAW key by ``gang_fastpath_batch``'s slot gather, on
    a gang of one witness lane per shard.  No op is predicted to execute,
    so nothing enters the (empty) rings."""
    k_hi = np.asarray(k_hi, np.uint32)
    (n,) = k_hi.shape
    ring = jnp.zeros((n_shards, 16), jnp.uint32)
    empty = np.zeros(n_shards, np.int32)
    res = gang_fastpath_batch(
        GangTable.empty(n_sets, 4, n_shards), n_sets, k_hi, k_lo, *rpcs(n),
        np.zeros(n, np.int32), slot_map,
        np.arange(n_shards, dtype=np.int32)[:, None], ring, ring, empty,
        empty)
    return res.shard_ids


def raw_lanes(key_hashes):
    """Split 64-bit key hashes into RAW (hi, lo) uint32 lanes."""
    hi = np.array([(h >> 32) & 0xFFFFFFFF for h in key_hashes], np.uint32)
    lo = np.array([h & 0xFFFFFFFF for h in key_hashes], np.uint32)
    return hi, lo


def assert_tables_equal(a, b):
    for name, x, y in zip(GangTable._fields, a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=name)


def live_rows(table, n_sets, lane=0):
    """The lane's (keys_hi, keys_lo, occ) planes as [n_sets, W] rows."""
    rows = slice(lane * n_sets, (lane + 1) * n_sets)
    return tuple(np.asarray(p)[:, rows].T
                 for p in (table.keys_hi, table.keys_lo, table.occ))
