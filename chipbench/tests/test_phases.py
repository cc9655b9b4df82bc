"""``phases.py`` on traces recorded on the chip (one TPU v5 lite), through
``python3 chipbench/phases.py --keep``: two ``update_batch`` calls of each
closed cell (``ramcloud16``: the fused path; ``redis16``: the per-shard
path), cut to the device's ``XLA Modules`` and ``XLA Ops`` lines and the
host's ``bench.*`` and ``curp.*`` spans.  Checked: the nesting of the
program's spans, each span's self time against a recount from its children,
that the split adds up to each call's length less the device busy time in
it and leaves out spans outside any call, and the phase named for each idle
gap.  ``closed_3batch.xplane.pb``
predates the program's spans: there the module finds nothing and names the
gaps as ``trace.py`` does."""
from __future__ import annotations

from pathlib import Path

import pytest

from chipbench import phases, trace

DATA = Path(__file__).parent / "data"
CELLS = {"ramcloud16": ["preflight", "record", "settle", "master"],
         "redis16": ["preflight", "preflight", "master", "record", "settle",
                     "record", "settle", "master"]}


def _load(name: str):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(DATA / name))
    t = trace.reduce(pd)
    return t, phases.read(pd, t)


@pytest.fixture(scope="module", params=sorted(CELLS))
def cell(request):
    t, spans = _load(f"{request.param}_2batch.xplane.pb")
    return request.param, t, spans


def _roots(spans):
    return [i for i, s in enumerate(spans) if s.parent is None]


def test_parent_trace_has_no_program_spans():
    t, spans = _load("closed_3batch.xplane.pb")
    assert spans == []
    assert phases.split(spans, t) is None
    assert phases.named_gaps(spans, t) == \
        [list(g) for g in t.idle_gaps()[:10]]


def test_nesting(cell):
    name, t, spans = cell
    roots = _roots(spans)
    bench = t.span_list("update_batch")
    assert len(roots) == len(bench) >= 2
    assert all(spans[i].name == phases.ROOT_SPAN for i in roots)
    for i in roots:
        assert any(a <= spans[i].start and spans[i].end <= b
                   for a, b in bench)
    for s in spans:
        if s.parent is not None:
            assert s.name in phases.PHASES
            assert spans[s.parent].name == phases.ROOT_SPAN
            p = spans[s.parent]
            assert p.start <= s.start and s.end <= p.end
    for i in roots:
        kids = [s.name for s in spans if s.parent == i]
        assert kids[:len(CELLS[name])] == CELLS[name]
        assert kids.count("sync") == kids.count("gc")


def test_self_time_recount(cell):
    """Self time recounted from the span's direct children, which are
    disjoint and inside it: length less theirs, device time likewise."""
    _name, t, spans = cell
    for i, s in enumerate(spans):
        kids = [k for k in spans if k.parent == i]
        want = ((s.end - s.start) - sum(k.end - k.start for k in kids)
                - (t.busy_in(s.start, s.end)
                   - sum(t.busy_in(k.start, k.end) for k in kids)))
        assert phases.self_ns(s, t) == pytest.approx(want, rel=1e-9, abs=1)
        assert phases.self_ns(s, t) >= 0


def test_split_adds_up(cell):
    _name, t, spans = cell
    out = phases.split(spans, t)
    calls = len(t.span_list("update_batch"))
    own = sum(v for k, v in out.items() if k.endswith("_ms")) * calls * 1e6
    roots = [spans[i] for i in _roots(spans)]
    want = sum((s.end - s.start) - t.busy_in(s.start, s.end) for s in roots)
    assert own == pytest.approx(want, rel=1e-9)
    host_ms = sum(t.host_self_ns("update_batch")) / calls / 1e6
    assert own / calls / 1e6 == pytest.approx(host_ms, rel=0.03)
    assert out["unspanned_ms"] < 0.05 * host_ms
    assert out["syncs_per_batch"] * calls == \
        sum(s.name == "sync" for s in spans) >= calls


def test_spans_outside_calls_are_left_out(cell):
    """A sync round outside any call (one a read sets off) changes
    nothing of the per-call split."""
    _name, t, spans = cell
    a = spans[-1].end
    extra = [phases.Span("sync", a + 1, a + 2, spans[-1].thread),
             phases.Span("gc", a + 3, a + 4, spans[-1].thread)]
    assert phases.in_calls(spans) == spans
    assert phases.split(spans + extra, t) == phases.split(spans, t)


def test_named_gaps(cell):
    """Each gap keeps ``trace.py``'s name and adds the phase whose own time
    (its span less its children) covers most of the gap."""
    _name, t, spans = cell
    named = phases.named_gaps(spans, t)
    assert [g[1] for g in named] == [g[1] for g in t.idle_gaps()[:10]]
    gaps = phases.gaps(t)[:10]

    def cover(a, b, s, e):
        return max(0.0, min(b, e) - max(a, s))

    for (name, _secs), (bench, _s), (a, b) in zip(named, t.idle_gaps(),
                                                  gaps):
        own = {}
        for i, s in enumerate(spans):
            c = cover(a, b, s.start, s.end) - sum(
                cover(a, b, k.start, k.end) for k in spans if k.parent == i)
            own[s.name] = own.get(s.name, 0.0) + c
        own = {k: v for k, v in own.items() if v > 0}
        want = bench + ("/" + max(own, key=own.get) if own else "")
        assert name == want
    assert any("/" in g[0] for g in named)
