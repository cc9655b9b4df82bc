"""Pallas TPU kernels for the CURP witness side (DESIGN.md §4).

One kernel family serves every path: a gang of witness tables stacked into
one device-resident ``GangTable``, a record kernel and a gc kernel over it
(witness_record.py), and four jitted wrappers, each ONE dispatch (ops.py):

gang_record         — a batch of single-key records, each at its own lane
gang_record_groups  — a batch of per-group all-or-nothing multi-key records
gang_gc             — rpc-matched clears plus §4.5 aging of whole lanes
gang_fastpath_batch — the fused cluster batch: hash -> slot route -> ring
                      conflict scan -> ring append -> record at every target
                      shard's f witness lanes

The kernels are validated in interpret mode against the oracles in ref.py;
README.md next to this file describes the layout and how the protocol layer
drives them.  The model-zoo code deliberately contains no Pallas so the
dry-run roofline reflects real XLA numbers (DESIGN.md §4).
"""
from .ops import (
    DEFAULT_N_SLOTS,
    GangFastPathResult,
    GangRecordResult,
    GangTable,
    conflict_matrix_np,
    dispatch_count,
    gang_fastpath_batch,
    gang_gc,
    gang_record,
    gang_record_groups,
    gang_rows,
    matrix_rows,
    np_keyhash2x32,
    record_fits,
    ref_conflict_scan,
    ref_gang_gc,
    ref_gang_record,
    ref_keyhash2x32,
    reset_dispatch_count,
)

__all__ = [
    "DEFAULT_N_SLOTS", "dispatch_count", "reset_dispatch_count",
    "GangTable", "GangRecordResult", "GangFastPathResult",
    "gang_record", "gang_record_groups", "gang_gc", "gang_fastpath_batch",
    "gang_rows", "record_fits",
    "ref_keyhash2x32", "np_keyhash2x32", "ref_conflict_scan",
    "ref_gang_record", "ref_gang_gc", "matrix_rows", "conflict_matrix_np",
]
