"""YCSB request generator: the one generator every ``kind: "ycsb"`` traffic
file is read by.

A request is ``(op, key, field, value)``: ``op`` is ``"read"`` or
``"update"``, ``key`` a record key of the configuration's key space,
``field`` the field an update writes (hash records only, else None) and
``value`` the update's ``value_bytes``-long string.  Everything comes from
the seed, so the same seed gives the same requests.

Keys and key choice follow YCSB's ``CoreWorkload`` at its defaults:

* record ``i`` is named by ``buildKeyName``: with ``insertorder=hashed`` the
  number is ``fnvhash64(i)`` (YCSB's ``Utils.fnvhash64``, which ends in
  ``Math.abs``), written in decimal after the prefix ``"user"`` and zero
  padded to ``zeropadding`` digits (1: no padding);
* ``uniform`` draws a record uniformly (``UniformLongGenerator``);
* ``zipfian`` is YCSB's ``ScrambledZipfianGenerator`` as ``CoreWorkload``
  builds it for a mix without inserts: a ``ZipfianGenerator`` over
  ``ITEM_COUNT = 10^10`` items at theta 0.99 with the precomputed
  ``ZETAN``, its draw folded onto ``records + 1`` items by ``fnvhash64``,
  and a draw past the last loaded record drawn again (``nextKeynum``).  The
  hottest record gets about 1/ZETAN = 3.8% of the requests, and the same
  records are hot for every seed.

Every seed gets the same number of requests and the same read/update split;
only which records, fields, values and arrival instants differ.

* ``closed``: ``batch(i)`` is the i-th batch of ``batch`` requests.
* ``open``: ``schedule(seconds)`` gives ``round(rate * seconds)`` requests
  with arrival instants spread uniformly over the window, the arrival process
  of a Poisson stream of that many requests.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

Request = Tuple[str, str, Optional[str], Optional[str]]

FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)

# ScrambledZipfianGenerator's constants.
ITEM_COUNT = 10_000_000_000
ZETAN = 26.46902820178302
USED_ZIPFIAN_CONSTANT = 0.99


def fnvhash64(vals) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` of each value (int64 in, int64 out)."""
    v = np.asarray(vals, np.int64).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (v & np.uint64(0xFF))) * FNV_PRIME_64
            v = v >> np.uint64(8)
    return np.abs(h.view(np.int64))


def key_names(nums: Sequence[int], keys: dict) -> List[str]:
    """``CoreWorkload.buildKeyName`` of each record number."""
    nums = np.asarray(nums, np.int64)
    if keys["insertorder"] == "hashed":
        nums = fnvhash64(nums)
    fmt = f"{keys['prefix']}%0{max(1, keys['zeropadding'])}d"
    return [fmt % n for n in nums.tolist()]


class ScrambledZipfian:
    """``ScrambledZipfianGenerator(0, records)`` with YCSB's ITEM_COUNT and
    ZETAN, which ``CoreWorkload`` uses at theta 0.99."""

    def __init__(self, records: int, theta: float) -> None:
        if theta != USED_ZIPFIAN_CONSTANT:
            raise ValueError("YCSB precomputes ZETAN for theta 0.99 only")
        self.records = records
        self.itemcount = records + 1
        self.items = ITEM_COUNT + 1           # ZipfianGenerator(0, ITEM_COUNT)
        self.theta = theta
        self.alpha = 1.0 / (1.0 - theta)
        zeta2 = 1.0 + 0.5 ** theta
        self.eta = ((1.0 - (2.0 / self.items) ** (1.0 - theta))
                    / (1.0 - zeta2 / ZETAN))

    def _ranks(self, u: np.ndarray) -> np.ndarray:
        uz = u * ZETAN
        ranks = (self.items * (self.eta * u - self.eta + 1.0) ** self.alpha
                 ).astype(np.int64)
        ranks[uz < 1.0 + 0.5 ** self.theta] = 1
        ranks[uz < 1.0] = 0
        return ranks

    def draw(self, rng: np.random.Generator, n: int) -> np.ndarray:
        out = fnvhash64(self._ranks(rng.random(n))) % self.itemcount
        bad = np.flatnonzero(out >= self.records)
        while bad.size:
            out[bad] = fnvhash64(self._ranks(rng.random(bad.size))) % self.itemcount
            bad = bad[out[bad] >= self.records]
        return out


class Generator:
    def __init__(self, traffic: dict, cfg: dict, seed: int) -> None:
        self.t = traffic
        self.n = cfg["records"]
        self.keyspec = cfg["keys"]
        rec = cfg["record"]
        self.fields = rec.get("fields")
        self.field_format = rec.get("field_format")
        self.value_bytes = rec["value_bytes"]
        self.seed = seed % (1 << 64)
        if traffic["keys"] == "zipfian":
            self._zipf = ScrambledZipfian(self.n, traffic["theta"])

    def _rng(self, *stream: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, *stream])

    def keys(self, nums: Sequence[int]) -> List[str]:
        return key_names(nums, self.keyspec)

    def _nums(self, rng, n: int) -> np.ndarray:
        if self.t["keys"] == "uniform":
            return rng.integers(0, self.n, n)
        return self._zipf.draw(rng, n)

    def values(self, rng, n: int) -> List[str]:
        half = (self.value_bytes + 1) // 2
        raw = rng.integers(0, 256, (n, half), dtype=np.uint8).tobytes().hex()
        step = 2 * half
        return [raw[i * step:i * step + self.value_bytes] for i in range(n)]

    def _requests(self, rng, kinds: np.ndarray) -> List[Request]:
        n = len(kinds)
        keys = self.keys(self._nums(rng, n))
        vals = self.values(rng, n)
        fields = (rng.integers(0, self.fields, n) if self.fields
                  else np.zeros(n, np.int64))
        out: List[Request] = []
        for kind, key, f, v in zip(kinds, keys, fields, vals):
            if kind:
                out.append(("read", key, None, None))
            else:
                field = (self.field_format % f) if self.fields else None
                out.append(("update", key, field, v))
        return out

    def _kinds(self, rng, n: int) -> np.ndarray:
        """Exactly round(n * read_share) reads, in an order from the seed."""
        kinds = np.zeros(n, bool)
        kinds[:int(round(n * self.t["read_share"]))] = True
        rng.shuffle(kinds)
        return kinds

    def batch(self, i: int) -> List[Request]:
        rng = self._rng(1, i)
        return self._requests(rng, self._kinds(rng, self.t["batch"]))

    def schedule(self, seconds: float) -> Tuple[np.ndarray, List[Request]]:
        rng = self._rng(2)
        n = int(round(self.t["rate"] * seconds))
        due = np.sort(rng.uniform(0.0, seconds, n))
        return due, self._requests(rng, self._kinds(rng, n))

    def snapshot(self) -> Tuple[List[str], list]:
        """The records a loaded deployment holds before the window."""
        rng = self._rng(3)
        keys = self.keys(np.arange(self.n))
        if not self.fields:
            return keys, self.values(rng, self.n)
        vals = self.values(rng, self.n * self.fields)
        names = [self.field_format % f for f in range(self.fields)]
        return keys, [dict(zip(names, vals[i * self.fields:(i + 1) * self.fields]))
                      for i in range(self.n)]
