"""CPU rehearsal of ``chip_smoke.py``: its phases at a tiny size with the
Pallas kernels in interpret mode, its checks, and its refusal to run without
a TPU.  The chip run itself is ``python chip_smoke.py`` on the chip."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

from repro.core.shard import SlotRouter

ROOT = Path(__file__).resolve().parents[1]
TINY = dict(n_shards=4, f=3, n_sets=64, n_ways=4)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def runs(smoke):
    wl = smoke.Workload(3, n_keys=600, batch=64, zipf_batches=6,
                        mset_batches=2,
                        shard_of=SlotRouter.uniform(4).shard_of, n_shards=4)
    quiet = dict(TINY, log=lambda *_: None)
    return (wl, smoke.run_backend("device", wl, **quiet),
            smoke.run_backend("python", wl, **quiet))


def test_smoke_phases_match_python_reference_on_cpu(smoke, runs):
    wl, dev, ref = runs
    assert smoke.check(dev, ref, wl) == []
    assert dev["fused"]["fused_batches"] > 0
    assert dev["fused"]["declined"] == len(wl.mixed)
    assert set(dev["reads"]) == set(wl.model())
    # The zipfian mix exercises both outcomes: fast ops and slow ones.
    assert 0 < dev["fast"] < len(dev["outcomes"])


@pytest.mark.parametrize("tamper", ["value", "outcome", "declined"])
def test_smoke_check_flags_disagreement(smoke, runs, tamper):
    wl, dev, ref = runs
    bad = dict(dev, reads=dict(dev["reads"]), outcomes=list(dev["outcomes"]),
               fused=dict(dev["fused"]))
    if tamper == "value":
        key = next(iter(bad["reads"]))
        bad["reads"][key] = "stale"
    elif tamper == "outcome":
        fast, *rest = bad["outcomes"][0]
        bad["outcomes"][0] = (not fast, *rest)
    else:
        bad["fused"]["declined"] += 1
    assert smoke.check(bad, ref, wl)


def test_smoke_refuses_to_run_without_a_tpu(smoke, capsys, monkeypatch):
    import jax

    real = smoke.device_line()
    assert real["platform"] == jax.devices()[0].platform
    # Check the refusal branch only, even on a host whose JAX finds a TPU:
    # the full-size smoke must never start from here.
    monkeypatch.setattr(smoke, "device_line",
                        lambda: dict(real, platform="cpu"))
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert "platform=cpu" in out.splitlines()[0]
