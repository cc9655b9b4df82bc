"""Work of the witness record algorithm, whatever kernel implements it.

An item is one (key, witness copy) pair: every pair of an update's
(hash, class) pairs, at each of its master's f witnesses.  Recording an item
must read the W ways of its set in each of the six table planes (key hi/lo,
occupancy and class, rpc hi/lo, age), write one slot of each plane, read the
item's seven input words (key hi/lo, row, valid, class, rpc hi/lo) and write
its reason word.  Tiles a kernel sweeps without touching them are not work:
a kernel that skips them comes nearer its roofline, not further.
"""
from __future__ import annotations

PLANES = 6
WORD = 4
ITEM_WORDS_IN, ITEM_WORDS_OUT = 7, 1


def record_items(kind, cfg: dict, window) -> int:
    """Items recorded by the window's update batches: the (hash, class) pairs
    of every request, as its record kind counts them, at each of f
    witnesses."""
    pairs = sum(kind.pairs(r) for act in window.actions if act[0] == "batch"
                for r in act[1])
    return pairs * cfg["f"]


def record_bytes(cfg: dict, items: int) -> int:
    ways = cfg["witness"]["ways"]
    per_item = (PLANES * ways * WORD + PLANES * WORD
                + (ITEM_WORDS_IN + ITEM_WORDS_OUT) * WORD)
    return items * per_item
