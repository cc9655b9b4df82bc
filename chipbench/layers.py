"""Reductions that several per-layer metric readers share: per-batch device
time, host self time and idle share from a reduced trace, and dispatches per
batch from the program's counter."""
from __future__ import annotations


def batches(run) -> int:
    """update_batch calls in the traced window (the run's own count when
    the run was not traced)."""
    if run.trace is not None:
        return len(run.trace.span_list("update_batch"))
    return len(run.window.batch_spans)


def per_batch_us(run, ns: float):
    n = batches(run)
    return ns / 1e3 / n if n else None


def host_ms_per_batch(run):
    if run.trace is None:
        return None
    own = run.trace.host_self_ns("update_batch")
    return sum(own) / len(own) / 1e6 if own else None


def dispatches_per_batch(run):
    n = len(run.window.batch_spans)
    return run.dispatches / n if n else None


def device_idle_share(run):
    t = run.trace
    if t is None or not t.window_s:
        return None
    return 1.0 - t.busy_s / t.window_s
