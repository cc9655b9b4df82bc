"""Updates per update_batch call in the window (the open loop's batches grow
and shrink with the load)."""


def read(run):
    spans = run.window.batch_spans
    return sum(n for _a, _b, n in spans) / len(spans) if spans else None
