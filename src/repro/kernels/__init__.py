"""Pallas TPU kernels for the CURP protocol hot spots (DESIGN.md §4).

witness_record — SET-PARALLEL batched witness record (paper §4.2): the batch
                 is bucketed by probed set and whole "rounds" (one query per
                 set) resolve vectorized, so wall-clock scales with the
                 longest per-set run, not the batch size
conflict_scan  — master commutativity check vs the unsynced window (§4.3)
keyhash        — 2x32-lane key hashing (TPU adaptation of the 64-bit hash)
fastpath_batch — the fused pipeline: keyhash2x32 -> shard_route ->
                 witness_record -> conflict_scan as ONE device dispatch per
                 update batch (vs 3-4 dispatches per op on the per-op path)
txn_probe      — all-or-nothing transactional probe: ONE op's multi-key
                 record resolved in ONE dispatch on accept AND reject (the
                 record-then-rollback scheme paid a second gc dispatch)

Fast-path pipeline docs (set-parallel layout, VMEM budget, and the buffer
donation/aliasing contract) live in witness_record.py's module docstring and
in README.md next to this file.  Validated in interpret mode against the
pure-jnp oracles in ref.py; the model-zoo code deliberately contains no
Pallas so the dry-run roofline reflects real XLA numbers (DESIGN.md §4).
"""
from .ops import (
    DEFAULT_N_SLOTS,
    FastPathResult,
    conflict_matrix_np,
    matrix_rows,
    GangFastPathResult,
    GangRecordResult,
    GangTable,
    TxnProbeResult,
    WitnessTable,
    conflict_scan,
    default_slot_map,
    dispatch_count,
    fastpath_batch,
    gang_fastpath_batch,
    gang_gc,
    gang_rows,
    gang_record,
    gang_record_groups,
    keyhash2x32,
    np_keyhash2x32,
    ref_conflict_scan,
    ref_gang_gc,
    ref_gang_record,
    ref_keyhash2x32,
    ref_witness_gc,
    ref_witness_record,
    ref_witness_record_txn,
    reset_dispatch_count,
    shard_route,
    txn_probe,
    witness_gc,
    witness_record,
    witness_record_seq,
)

__all__ = [
    "DEFAULT_N_SLOTS", "default_slot_map",
    "FastPathResult", "TxnProbeResult", "WitnessTable", "conflict_scan",
    "keyhash2x32", "shard_route", "witness_gc", "witness_record",
    "witness_record_seq", "fastpath_batch", "txn_probe", "dispatch_count",
    "reset_dispatch_count", "ref_conflict_scan", "ref_keyhash2x32",
    "ref_witness_gc", "ref_witness_record", "ref_witness_record_txn",
    "GangTable", "GangRecordResult", "GangFastPathResult",
    "gang_record", "gang_record_groups", "gang_gc", "gang_fastpath_batch",
    "gang_rows",
    "np_keyhash2x32", "ref_gang_record", "ref_gang_gc",
    "matrix_rows", "conflict_matrix_np",
]
