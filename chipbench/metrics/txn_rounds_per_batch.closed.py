"""Transaction rounds per update_batch call: the program's
txn.batch.rounds counter over the window's calls, as the record kind
accumulated it (None where the kind keeps no such count)."""


def read(run):
    counts = getattr(run.kind, "counts", None)
    n = len(run.window.batch_spans)
    if not counts or "txn.batch.rounds" not in counts or not n:
        return None
    return counts["txn.batch.rounds"] / n
