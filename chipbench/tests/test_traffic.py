"""The YCSB generator is deterministic per seed and keeps the mix's shares."""
from __future__ import annotations

import numpy as np
import pytest

from chipbench import catalog

SEEDS = (0, 2**31 + 5, 2**33 + 1)


@pytest.fixture(scope="module")
def cells():
    cat = catalog.Catalog()
    return {n: cat.cell(n) for n in cat.cells()}


def _open():
    return catalog.Catalog().pair("ramcloud-16m-f3", "ycsb_a.open80")


def _small(cell):
    cfg = dict(cell.cfg, records=50_000)
    return cell.generator, cell.traffic, cfg


@pytest.mark.parametrize("seed", SEEDS)
def test_closed_batches_deterministic(cells, seed):
    for cell in cells.values():
        if cell.traffic["loop"] != "closed":
            continue
        G, t, cfg = _small(cell)
        a, b = G(t, cfg, seed), G(t, cfg, seed)
        assert a.batch(3) == b.batch(3)
        assert a.batch(3) != a.batch(4)
        assert a.batch(0) != G(t, cfg, seed + 1).batch(0)
        reqs = a.batch(0)
        assert len(reqs) == t["batch"]
        assert all(r[0] == "update" for r in reqs)
        assert all(len(r[3]) == cfg["record"]["value_bytes"] for r in reqs)
        if cfg["record"]["kind"] == "hash":
            assert {r[2] for r in reqs} <= {f"field{i}" for i in range(10)}


@pytest.mark.parametrize("seed", SEEDS)
def test_open_schedule_deterministic_with_published_shares(cells, seed):
    G, t, cfg = _small(_open())
    due, reqs = G(t, cfg, seed).schedule(2.0)
    due2, reqs2 = G(t, cfg, seed).schedule(2.0)
    assert np.array_equal(due, due2) and reqs == reqs2
    assert len(reqs) == round(t["rate"] * 2.0)
    assert np.all(np.diff(due) >= 0) and 0 <= due[0] and due[-1] < 2.0
    reads = sum(r[0] == "read" for r in reqs)
    assert reads == round(len(reqs) * t["read_share"])


def test_ycsb_key_names():
    """CoreWorkload.buildKeyName with insertorder=hashed, zeropadding=1:
    "user" + Utils.fnvhash64(keynum), values from YCSB's Java."""
    from chipbench.traffic.ycsb import fnvhash64, key_names

    spec = {"prefix": "user", "insertorder": "hashed", "zeropadding": 1}
    assert fnvhash64([0]).tolist() == [abs(_java_fnv(0))]
    assert fnvhash64([1, 999_999]).tolist() == [abs(_java_fnv(1)),
                                               abs(_java_fnv(999_999))]
    assert key_names([0, 1], spec) == [f"user{abs(_java_fnv(0))}",
                                       f"user{abs(_java_fnv(1))}"]
    assert key_names([7], dict(spec, insertorder="ordered",
                               zeropadding=7)) == ["user0000007"]


def _java_fnv(val: int) -> int:
    """Utils.fnvhash64 before Math.abs, with Java's signed 64-bit long."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h = ((h ^ (val & 0xFF)) * 1099511628211) & ((1 << 64) - 1)
        val >>= 8
    return h - (1 << 64) if h >= 1 << 63 else h


def test_zipfian_skew_and_uniform_spread(cells):
    """ScrambledZipfianGenerator: zipf 0.99 over 10^10 items folded onto the
    records; the hottest record gets about 1/ZETAN of the draws, the same
    record for every seed."""
    from chipbench.traffic.ycsb import ZETAN

    z = _open()
    _G, t, cfg = _small(z)
    cfg = dict(cfg, records=1_000_000)
    G = z.generator
    g = G(dict(t, rate=100_000), cfg, 7)
    _due, reqs = g.schedule(2.0)
    counts = {}
    for r in reqs:
        counts[r[1]] = counts.get(r[1], 0) + 1
    hot = max(counts, key=counts.get)
    assert abs(counts[hot] / len(reqs) - 1 / ZETAN) < 0.1 / ZETAN
    loaded = set(g.snapshot()[0])
    assert set(counts) <= loaded
    _d, other = G(dict(t, rate=100_000), cfg, 8).schedule(2.0)
    c2 = {}
    for r in other:
        c2[r[1]] = c2.get(r[1], 0) + 1
    assert max(c2, key=c2.get) == hot
    u = cells["ramcloud16.write_uniform.closed"]
    G, t, cfg = _small(u)
    names = {k: i for i, k in enumerate(G(t, cfg, 1).snapshot()[0])}
    nums = [names[r[1]] for i in range(20) for r in G(t, cfg, 1).batch(i)]
    assert min(nums) >= 0 and max(nums) < cfg["records"]
    assert np.bincount(nums).max() < 6
