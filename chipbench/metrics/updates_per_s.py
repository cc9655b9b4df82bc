"""Acknowledged updates in the window over the window's seconds (host clock;
the window ends when the last batch returns)."""


def read(run):
    n = len(run.window.fast)
    return n / run.window.seconds if n else None
