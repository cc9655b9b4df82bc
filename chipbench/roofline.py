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


def pairs_per_update(cfg: dict) -> int:
    return 1 if cfg["record"]["kind"] == "object" else 2


def record_items(cfg: dict, window) -> int:
    """Items recorded by the window's update batches."""
    updates = sum(n for _a, _b, n in window.batch_spans)
    return updates * pairs_per_update(cfg) * cfg["f"]


def record_bytes(cfg: dict, items: int) -> int:
    ways = cfg["witness"]["ways"]
    per_item = (PLANES * ways * WORD + PLANES * WORD
                + (ITEM_WORDS_IN + ITEM_WORDS_OUT) * WORD)
    return items * per_item
