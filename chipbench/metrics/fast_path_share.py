"""Acknowledged updates that completed on CURP's 1-RTT fast path, over all
acknowledged updates of the window."""


def read(run):
    fast = run.window.fast
    return sum(fast) / len(fast) if fast else None
