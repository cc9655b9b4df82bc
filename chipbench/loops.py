"""The measured window: a closed or an open loop of requests through the
served path, recording everything the checks and the metrics need.

Server semantics, shared by both loops: each turn takes every request that
is due, serves its reads one by one through ``ShardedCluster.read`` (the only
read path), then hands up to ``batch`` of its updates to the record kind's
``send``, which serves them through the program's entry point (for key-value
records one ``ShardedCluster.update_batch`` call).  ``actions`` keeps the
requests as the generator made them, in order, with what came back, so the
kind's reference can replay them exactly.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, List, Tuple

import numpy as np


@dataclass
class Window:
    actions: List[Tuple] = field(default_factory=list)
    batch_spans: List[Tuple[float, float, int]] = field(default_factory=list)
    latency_s: List[float] = field(default_factory=list)
    update_index: List[int] = field(default_factory=list)   # open loop
    fast: List[bool] = field(default_factory=list)
    attempted: int = 0
    acknowledged: int = 0
    reads: int = 0
    seconds: float = 0.0
    lateness_s: float = 0.0


class Server:
    """One serving turn: reads, then one update batch."""

    def __init__(self, cluster, kind, span: Callable) -> None:
        self.cl = cluster
        self.kind = kind
        self.s = cluster.new_client()
        self.span = span

    def reads(self, w: Window, reqs) -> None:
        with self.span("bench.read"):
            for _op, key, _f, _v in reqs:
                value = self.cl.read(self.s, self.s.op_get(key)).value
                w.actions.append(("read", key, value))
                w.acknowledged += 1
                w.reads += 1

    def updates(self, w: Window, reqs, t0: float) -> float:
        ts = time.perf_counter()
        rows = self.kind.send(self.cl, self.s, reqs, self.span)
        te = time.perf_counter()
        w.batch_spans.append((ts - t0, te - t0, len(reqs)))
        w.actions.append(("batch", reqs, rows))
        w.acknowledged += len(rows)
        w.fast.extend(r[0] for r in rows)
        return te - t0


def closed_loop(server: Server, gen, seconds: float) -> Window:
    """Batches of ``batch`` requests back to back until ``seconds`` pass."""
    w = Window()
    t0 = time.perf_counter()
    i = 0
    while True:
        with server.span("bench.generate"):
            reqs = gen.batch(i)
        w.attempted += len(reqs)
        server.reads(w, [r for r in reqs if r[0] == "read"])
        ups = [r for r in reqs if r[0] == "update"]
        start = time.perf_counter() - t0
        end = server.updates(w, ups, t0) if ups else time.perf_counter() - t0
        w.latency_s.extend([end - start] * len(ups))
        i += 1
        if end >= seconds:
            w.seconds = end
            return w


def open_loop(server: Server, due: np.ndarray, reqs, cap: int) -> Window:
    """Requests arrive at ``due`` (seconds from the window's start) whatever
    the server does; an update's latency runs from its due instant to the
    return of the batch that acknowledged it."""
    w = Window(attempted=len(reqs))
    pending: deque = deque()
    late = 0.0
    n, i = len(reqs), 0
    t0 = time.perf_counter()
    while i < n or pending:
        now = time.perf_counter() - t0
        j = int(np.searchsorted(due, now, side="right"))
        reads = []
        for k in range(i, j):
            late += now - due[k]
            (reads if reqs[k][0] == "read" else pending).append(k)
        i = max(i, j)
        if reads:
            server.reads(w, [reqs[k] for k in reads])
        if pending:
            take = [pending.popleft() for _ in range(min(cap, len(pending)))]
            end = server.updates(w, [reqs[k] for k in take], t0)
            w.latency_s.extend(end - due[k] for k in take)
            w.update_index.extend(take)
        elif i < n:
            with server.span("bench.wait"):
                pause = due[i] - (time.perf_counter() - t0)
                if pause > 0:
                    time.sleep(pause)
    w.seconds = time.perf_counter() - t0
    w.lateness_s = late / max(1, n)
    return w


def no_span(_name: str):
    return contextlib.nullcontext()
