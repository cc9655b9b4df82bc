"""Plain reference of a CURP key-value deployment, for deciding ``correct``.

Written from the protocol (CURP, arXiv 1710.09921, §3-§4) and the
configuration's stated rules, and independent of the code under test: it
imports nothing of ``repro`` and takes nothing the program made.  It replays
the exact requests the benchmark sent, in the order it sent them, and gives
what every acknowledgement must say.  What a request records and does is its
record kind's: each ``chipbench/kinds/<kind>.py`` subclasses ``Reference``
with the request's (hash, class) pairs and its effect on the values.  The
rest is the deployment's, shared by every kind:

* key placement: a 64-bit key hash (FNV-1a over the key's UTF-8 bytes, then
  the SplitMix64 finaliser), mixed into two 32-bit lanes by the murmur3
  finaliser; the mixed low lane mod ``slots`` picks a slot, and slot ``i``
  belongs to master ``i % masters``;
* each master executes every update at once and replies in one round trip
  only when the update commutes with every unsynced update it holds
  (a same-key pair commutes only when both are of one mergeable class);
  otherwise it syncs before replying (2 RTTs, "synced");
* each of the master's ``f`` witnesses is a ``sets`` x ``ways``
  set-associative table keyed by ``hash % sets``; it accepts an update,
  all-or-nothing over the update's (hash, class) pairs, when no held pair
  of the same hash conflicts and the set has a free way for every pair;
* a fast reply that some witness rejected needs a sync (2 RTTs, not fast);
* at the end of a batch, a master that had a conflict, a rejected record or
  at least ``sync_batch`` unsynced updates syncs everything unsynced, and
  its witnesses drop the synced records;
* a read of a key with an unsynced update syncs that master first.

``fault="no_master_sync"`` breaks one stated guarantee for the control run,
that a master syncs when the protocol requires it: the master never starts
a sync itself (not before replying to a conflicting update, nor at
``sync_batch`` unsynced updates) and syncs only when a client asks because
a witness rejected its record, or a read needs it.
"""
from __future__ import annotations

from collections import Counter
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

M64 = (1 << 64) - 1
M32 = 0xFFFFFFFF

# Merge classes of the (hash, class) pairs an update expands to.
CLS_SET, CLS_HMSET, CLS_FIELD, CLS_OTHER = 0, 3, 4, 8
MERGEABLE = frozenset({2, 3, 5, 6, 7})   # INCR, HMSET, SADD, APPEND, MAX

Row = Tuple[bool, bool, int, int, Any]   # fast, synced, rtts, accepts, value


def conflicts(a: int, b: int) -> bool:
    return not (a == b and a in MERGEABLE)


def splitmix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) & M64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & M64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & M64
    return (x ^ (x >> 31)) & M64


def keyhash(key: str) -> int:
    h = 0xCBF29CE484222325
    for b in key.encode():
        h = ((h ^ b) * 0x100000001B3) & M64
    return splitmix64(h)


def fmix32(x: int) -> int:
    x &= M32
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & M32
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & M32
    return x ^ (x >> 16)


def mixed_lo(kh: int) -> int:
    """Low lane of the two-lane mix of a 64-bit hash (the slot lane)."""
    h1 = fmix32(((kh & M32) + 0x9E3779B9) & M32)
    h2 = fmix32(((kh >> 32) & M32) ^ h1)
    return fmix32((h1 + h2 * 5 + 0xE6546B64) & M32)


def keyhash_np(keys: Sequence[str]) -> np.ndarray:
    """``keyhash`` of many ASCII keys at once (uint64), keys of one length
    together."""
    lens = np.fromiter(map(len, keys), np.int64, len(keys))
    out = np.empty(len(keys), np.uint64)
    for n in np.unique(lens):
        idx = np.flatnonzero(lens == n)
        raw = np.frombuffer("".join(keys[i] for i in idx).encode(),
                            np.uint8).reshape(len(idx), -1)
        h = np.full(len(idx), 0xCBF29CE484222325, np.uint64)
        with np.errstate(over="ignore"):
            for col in raw.T:
                h = (h ^ col.astype(np.uint64)) * np.uint64(0x100000001B3)
            x = h + np.uint64(0x9E3779B97F4A7C15)
            x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
            x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
            out[idx] = x ^ (x >> np.uint64(31))
    return out


def _fmix32_np(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        x = x ^ (x >> np.uint32(16))
        x = x * np.uint32(0x85EBCA6B)
        x = x ^ (x >> np.uint32(13))
        x = x * np.uint32(0xC2B2AE35)
        return x ^ (x >> np.uint32(16))


def shard_of_np(keys: Sequence[str], masters: int, slots: int) -> np.ndarray:
    """Owning master of many ASCII keys at once."""
    kh = keyhash_np(keys)
    lo = (kh & np.uint64(M32)).astype(np.uint32)
    hi = (kh >> np.uint64(32)).astype(np.uint32)
    with np.errstate(over="ignore"):
        h1 = _fmix32_np(lo + np.uint32(0x9E3779B9))
        h2 = _fmix32_np(hi ^ h1)
        h3 = _fmix32_np(h1 + h2 * np.uint32(5) + np.uint32(0xE6546B64))
    return ((h3 % np.uint32(slots)) % np.uint32(masters)).astype(np.int64)


class _Witness:
    def __init__(self, n_sets: int, n_ways: int) -> None:
        self.n_sets, self.n_ways = n_sets, n_ways
        self.sets: Dict[int, List[Tuple[int, int, int]]] = {}

    def record(self, pairs, tag: int) -> bool:
        claimed: Counter = Counter()
        plan = []
        for kh, cls in dict.fromkeys(pairs):
            s = kh % self.n_sets
            held = self.sets.get(s, ())
            for hkh, hcls, _tag in held:
                if hkh == kh and conflicts(hcls, cls):
                    return False
            if len(held) + claimed[s] >= self.n_ways:
                return False
            claimed[s] += 1
            plan.append((s, kh, cls))
        for s, kh, cls in plan:
            self.sets.setdefault(s, []).append((kh, cls, tag))
        return True

    def gc(self, kh: int, tag: int) -> None:
        s = kh % self.n_sets
        held = self.sets.get(s)
        if held:
            held[:] = [h for h in held if not (h[0] == kh and h[2] == tag)]


class _Master:
    def __init__(self, f: int, n_sets: int, n_ways: int) -> None:
        self.window: Dict[int, Counter] = {}
        self.unsynced: List[Tuple[Tuple[Tuple[int, int], ...], int]] = []
        self.want_sync = False
        self.witnesses = [_Witness(n_sets, n_ways) for _ in range(f)]

    def commutes(self, pairs) -> bool:
        for kh, cls in pairs:
            for held in self.window.get(kh, ()):
                if conflicts(held, cls):
                    return False
        return True

    def sync(self) -> None:
        for pairs, tag in self.unsynced:
            for kh, cls in pairs:
                per = self.window[kh]
                per[cls] -= 1
                if per[cls] == 0:
                    del per[cls]
                    if not per:
                        del self.window[kh]
                for w in self.witnesses:
                    w.gc(kh, tag)
        self.unsynced.clear()
        self.want_sync = False


class Reference:
    """The deployment's plain model: one dict of values and, per master, its
    unsynced window and its witnesses' tables.  A record kind's subclass
    gives ``pairs`` and ``apply`` of its requests."""

    def __init__(self, cfg: dict, snapshot: Optional[Dict[str, Any]] = None,
                 fault: Optional[str] = None) -> None:
        assert fault in (None, "no_master_sync"), fault
        w = cfg["witness"]
        self.f = cfg["f"]
        self.masters = cfg["masters"]
        self.slots = cfg["slots"]
        self.sync_batch = cfg["sync_batch"]
        self.fault = fault
        self.values: Dict[str, Any] = dict(snapshot or {})
        self.m = [_Master(self.f, w["sets"], w["ways"])
                  for _ in range(self.masters)]
        self._kh: Dict[str, int] = {}
        self._tag = 0

    def _hash(self, key: str) -> int:
        kh = self._kh.get(key)
        if kh is None:
            kh = self._kh[key] = keyhash(key)
        return kh

    def shard_of(self, key: str) -> int:
        return (mixed_lo(self._hash(key)) % self.slots) % self.masters

    def pairs(self, req) -> Tuple[Tuple[int, int], ...]:
        """The (hash, class) pairs ``req`` records at each witness."""
        raise NotImplementedError

    def apply(self, req) -> Any:
        """Apply ``req`` to ``values``; returns what its reply says."""
        raise NotImplementedError

    def update_batch(self, updates: Sequence) -> List[Row]:
        """Update requests ``(op, key, field, value)``, as the generator made
        them, sent as one batch; one row each."""
        rows: List[Row] = []
        touched: Dict[int, bool] = {}
        for req in updates:
            sid = self.shard_of(req[1])
            m = self.m[sid]
            pairs = self.pairs(req)
            self._tag += 1
            accepts = sum(w.record(pairs, self._tag) for w in m.witnesses)
            commutes = self.fault is not None or m.commutes(pairs)
            result = self.apply(req)
            m.unsynced.append((pairs, self._tag))
            for kh, cls in pairs:
                m.window.setdefault(kh, Counter())[cls] += 1
            need = touched.get(sid, False)
            if not commutes:
                m.want_sync = need = True
                rows.append((False, True, 2, accepts, result))
            else:
                if len(m.unsynced) >= self.sync_batch and self.fault is None:
                    m.want_sync = True
                if accepts == self.f:
                    rows.append((True, False, 1, accepts, result))
                else:
                    need = True
                    rows.append((False, False, 2, accepts, result))
            touched[sid] = need
        for sid, need in touched.items():
            if need or self.m[sid].want_sync:
                self.m[sid].sync()
        return rows

    def read(self, key: str) -> Any:
        m = self.m[self.shard_of(key)]
        value = self.values.get(key)
        if self._hash(key) in m.window:
            m.sync()
        return value
