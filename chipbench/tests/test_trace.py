"""``trace.py`` on a small trace recorded on the chip: one TPU v5 lite, three
1024-update batches of uniform 100 B SETs through ``update_batch`` of the
``ramcloud-16m-f3`` deployment (16 masters, f=3, 1024 x 4 witnesses), each
inside a ``bench.update_batch`` span.  The file keeps the device's
``XLA Modules`` and ``XLA Ops`` lines and the benchmark's spans, and nothing
else of the host.  Checked: busy union, per-module kernel time, host self
time and the record kernel's roofline arithmetic, each against a recount made
here by other means."""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import catalog, roofline, trace

DATA = Path(__file__).parent / "data" / "closed_3batch.xplane.pb"
BATCHES = 3


@pytest.fixture(scope="module")
def raw():
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(DATA))


@pytest.fixture(scope="module")
def t(raw):
    return trace.reduce(raw)


def _ops(raw, t):
    tpu = raw.find_plane_with_name("/device:TPU:0")
    (ops,) = [ln for ln in tpu.lines if ln.name == "XLA Ops"]
    return [e for e in ops.events if e.end_ns > t.start and e.start_ns < t.end]


def test_window_and_spans(t):
    assert len(t.span_list("update_batch")) == BATCHES
    assert t.chips == 1
    assert 0 < t.busy_s < t.window_s


def test_busy_union_matches_a_sweep(raw, t):
    ops = _ops(raw, t)
    ends = np.array([(max(e.start_ns, t.start), +1) for e in ops]
                    + [(min(e.end_ns, t.end), -1) for e in ops],
                    dtype=[("x", float), ("d", int)])
    ends.sort(order=["x", "d"])
    depth = np.cumsum(ends["d"])
    covered = np.sum(np.diff(ends["x"])[depth[:-1] > 0])
    assert abs(covered / 1e9 - t.busy_s) < 1e-9 + 1e-6 * t.busy_s
    assert covered <= sum(e.end_ns - e.start_ns for e in ops)


def test_kernel_time_per_module(raw, t):
    ops = _ops(raw, t)
    by_name = {k: sum(e.end_ns - e.start_ns for e in ops
                      if e.name.startswith(f"%{k}"))
               for k in ("gang_record_pallas", "gang_gc_pallas")}
    assert t.kernel(trace.RECORD_MODULES) == pytest.approx(
        by_name["gang_record_pallas"])
    assert t.kernel(trace.GC_MODULES) == pytest.approx(by_name["gang_gc_pallas"])
    assert t.runs(trace.FUSED_MODULES) == BATCHES
    assert t.runs(trace.GC_MODULES) >= 2
    assert 0 < t.prep(trace.FUSED_MODULES)
    assert t.kernel(trace.RECORD_MODULES) + t.prep(trace.FUSED_MODULES) \
        <= sum(e.end_ns - e.start_ns for e in ops)


def test_host_self_time(t):
    spans = t.span_list("update_batch")
    own = t.host_self_ns("update_batch")
    for (a, b), o in zip(spans, own):
        assert 0 < o < b - a
    assert sum(b - a - o for (a, b), o in zip(spans, own)) <= t.busy_s * 1e9


def test_roofline_arithmetic(t):
    cell = catalog.Catalog().cell("ramcloud16.write_uniform.closed")
    batch = [("update", f"user{i}", None, "v") for i in range(1024)]
    window = SimpleNamespace(actions=[("batch", batch, [])] * BATCHES)
    items = roofline.record_items(cell.kind, cell.cfg, window)
    assert items == BATCHES * 1024 * 3
    assert roofline.record_bytes(cell.cfg, items) == items * (6 * 4 * 4 + 6 * 4
                                                              + 8 * 4)
    run = SimpleNamespace(trace=t, cfg=cell.cfg, kind=cell.kind, window=window,
                          peaks={"hbm_bytes_per_s": 819e9})
    share = catalog.reader("record_kernel_roofline.closed")(run)
    want = 100 * items * 152 / 819e9 / (t.kernel(trace.RECORD_MODULES) / 1e9)
    assert share == pytest.approx(want)
    assert 0 < share < 100


def test_breakdown(t):
    bd = t.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    secs = [s for _n, s in bd["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) <= t.window_s - t.busy_s + 1e-9
