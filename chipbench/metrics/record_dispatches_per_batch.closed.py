"""Witness record kernel dispatches per update_batch call (record_many
splits a record that overflows SMEM): the program's
witness.record_dispatches counter over the window's calls, as the record
kind accumulated it (None where the kind keeps no such count)."""


def read(run):
    counts = getattr(run.kind, "counts", None)
    n = len(run.window.batch_spans)
    if not counts or "witness.record_dispatches" not in counts or not n:
        return None
    return counts["witness.record_dispatches"] / n
