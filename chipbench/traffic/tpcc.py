"""TPC-C request generator: the one generator every ``kind: "tpcc"`` traffic
file is read by (TPC-C Standard Specification rev. 5.11).

The population is §4.3.3.1's, generated from the seed, laid out as the
``tpcc`` record kind's keys (``chipbench/kinds/tpcc.py``): per warehouse one
WAREHOUSE row, ``districts`` DISTRICT rows, ``customers`` CUSTOMER rows per
district with their last-name index, ``items`` STOCK rows and a replica of
the ``items`` ITEM rows.  Strings are random alphanumeric at the widths
§4.3.3.1 gives, money is integer cents and rates integer 1/10000ths; the
address fields of a row are one string of their summed widths.

A request is a tuple whose first element is ``"update"``:

* ``("update", "new_order", rid, w, d, c, lines, entry_d)`` with ``lines``
  the ``(i_id, supply_w, qty)`` of §2.4.1: 5–15 lines, each supplied by a
  remote warehouse with probability ``remote_line``, and for a share
  ``rollback`` of New-Orders an unused item id on the last line;
* ``("update", "payment", rid, w, d, c_w, c_d, c_id, c_last, h_amount,
  h_date)`` of §2.5.1: a remote customer with probability
  ``remote_payment``, selected by last name with probability
  ``by_last_name`` (``c_last`` is then the name and ``c_id`` the customer
  the last-name index selects, §2.5.2.2: the terminal's view of a static
  index), else by id (``c_last`` None).

Customer ids, item ids and last names are NURand(1023, 1, customers),
NURand(8191, 1, items) and NURand(255, 0, 999) (§2.1.6), with the run's
constants C drawn from the seed within §2.1.6.1's bounds.  ``rid`` names
the request (it keys its HISTORY row); ``entry_d``/``h_date`` are the
batch's logical date.  ``batch(i)`` is the i-th batch of ``batch``
requests, New-Order or Payment drawn per request at ``mix``'s odds.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

ALNUM = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz"
                      b"ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789", np.uint8)
SYLLABLES = ("BAR", "OUGHT", "ABLE", "PRI", "PRES", "ESE", "ANTI", "CALLY",
             "ATION", "EING")
LOAD_DATE = 1_000_000          # C_SINCE: the population's logical date


def last_name(num: int) -> str:
    """§4.3.2.3: the syllables of the three digits of ``num``."""
    return (SYLLABLES[num // 100] + SYLLABLES[num // 10 % 10]
            + SYLLABLES[num % 10])


def nurand(rng, a: int, x: int, y: int, c: int, n: int) -> np.ndarray:
    """§2.1.6: (((random(0, A) | random(x, y)) + C) % (y - x + 1)) + x."""
    r = rng.integers(0, a + 1, n) | rng.integers(x, y + 1, n)
    return (r + c) % (y - x + 1) + x


def a_strings(rng, lo: int, hi: int, n: int) -> List[str]:
    """``n`` random alphanumeric strings of lengths uniform in [lo, hi]."""
    lens = rng.integers(lo, hi + 1, n)
    raw = ALNUM[rng.integers(0, len(ALNUM), int(lens.sum()))].tobytes()
    raw = raw.decode()
    ends = np.cumsum(lens).tolist()
    starts = [0] + ends[:-1]
    return [raw[a:b] for a, b in zip(starts, ends)]


def n_strings(rng, width: int, n: int) -> List[str]:
    raw = (rng.integers(0, 10, n * width) + 48).astype(np.uint8)
    s = raw.tobytes().decode()
    return [s[i * width:(i + 1) * width] for i in range(n)]


def with_original(rng, strings: List[str]) -> List[str]:
    """§4.3.3.1: 10% of I_DATA / S_DATA hold "ORIGINAL" at a random
    position."""
    out = list(strings)
    for i in np.flatnonzero(rng.random(len(out)) < 0.1).tolist():
        s = out[i]
        at = int(rng.integers(0, len(s) - 8 + 1))
        out[i] = s[:at] + "ORIGINAL" + s[at + 8:]
    return out


def addresses(rng, n: int) -> List[str]:
    """STREET_1, STREET_2, CITY (10–20 each), STATE (2) and ZIP (4 random
    digits and "11111") as one string."""
    parts = [a_strings(rng, 10, 20, n) for _ in range(3)]
    state = a_strings(rng, 2, 2, n)
    zips = [z + "11111" for z in n_strings(rng, 4, n)]
    return ["".join(p) for p in zip(*parts, state, zips)]


class Generator:
    def __init__(self, traffic: dict, cfg: dict, seed: int) -> None:
        self.t = traffic
        self.W = cfg["warehouses"]
        sc = cfg["scale"]
        self.n_d, self.n_c, self.n_i = (sc["districts"], sc["customers"],
                                        sc["items"])
        self.next_o = sc["d_next_o_id"]
        self.seed = seed % (1 << 64)
        rng = self._rng(0)
        # §2.1.6.1: the run's C_LAST constant differs from the load's by
        # 65..119, not 96 or 112.
        self.c_load = int(rng.integers(0, 256))
        delta = int(rng.choice([d for d in range(65, 120)
                                if d not in (96, 112)]))
        self.c_run = (self.c_load + delta) % 256
        self.c_id = int(rng.integers(0, 1024))
        self.c_item = int(rng.integers(0, 8192))
        self._names: Dict[int, Tuple[List[str], np.ndarray]] = {}
        self._index: Dict[Tuple[int, int], Dict[str, Tuple[int, ...]]] = {}

    def _rng(self, *stream: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, *stream])

    # ------------------------------------------------------------ population
    def _customer_names(self, w: int) -> Tuple[List[str], np.ndarray]:
        """C_FIRST and the last-name number of every customer of ``w``,
        district-major."""
        if w not in self._names:
            rng = self._rng(4, w)
            n = self.n_d * self.n_c
            first = a_strings(rng, 8, 16, n)
            c = np.tile(np.arange(1, self.n_c + 1), self.n_d)
            nums = np.where(
                c <= 1000, c - 1,
                nurand(rng, 255, 0, min(999, self.n_c - 1), self.c_load, n))
            self._names[w] = (first, nums)
        return self._names[w]

    def last_name_index(self, w: int, d: int) -> Dict[str, Tuple[int, ...]]:
        """District ``d`` of ``w``'s customer ids by last name, sorted by
        C_FIRST (§2.5.2.2)."""
        if (w, d) not in self._index:
            first, nums = self._customer_names(w)
            lo = (d - 1) * self.n_c
            by: Dict[str, List[Tuple[str, int]]] = {}
            for c in range(1, self.n_c + 1):
                by.setdefault(last_name(int(nums[lo + c - 1])), []).append(
                    (first[lo + c - 1], c))
            self._index[(w, d)] = {name: tuple(c for _f, c in sorted(v))
                                   for name, v in by.items()}
        return self._index[(w, d)]

    def _items(self) -> List[Tuple]:
        rng = self._rng(5)
        n = self.n_i
        return list(zip(rng.integers(1, 10001, n).tolist(),
                        a_strings(rng, 14, 24, n),
                        rng.integers(100, 10001, n).tolist(),
                        with_original(rng, a_strings(rng, 26, 50, n))))

    def _warehouse(self, w: int, items: Sequence[Tuple]
                   ) -> Tuple[List[str], List]:
        rng = self._rng(6, w)
        keys: List[str] = [f"{{{w}}}:W", f"{{{w}}}:W_YTD"]
        vals: List = [(a_strings(rng, 6, 10, 1)[0], addresses(rng, 1)[0],
                       int(rng.integers(0, 2001))), 30_000_000]
        d_names = a_strings(rng, 6, 10, self.n_d)
        d_addr = addresses(rng, self.n_d)
        d_tax = rng.integers(0, 2001, self.n_d).tolist()
        for d in range(1, self.n_d + 1):
            keys += [f"{{{w}}}:D:{d}", f"{{{w}}}:D_NEXT:{d}",
                     f"{{{w}}}:D_YTD:{d}"]
            vals += [(d_names[d - 1], d_addr[d - 1], d_tax[d - 1]),
                     self.next_o, 3_000_000]
        first, nums = self._customer_names(w)
        n = self.n_d * self.n_c
        addr = addresses(rng, n)
        phone = n_strings(rng, 16, n)
        credit = np.where(rng.random(n) < 0.1, "BC", "GC").tolist()
        disc = rng.integers(0, 5001, n).tolist()
        data = a_strings(rng, 300, 500, n)
        lim = 5_000_000
        for d in range(1, self.n_d + 1):
            for c in range(1, self.n_c + 1):
                j = (d - 1) * self.n_c + c - 1
                keys += [f"{{{w}}}:C:{d}:{c}", f"{{{w}}}:CB:{d}:{c}"]
                vals += [(first[j], "OE", last_name(int(nums[j])), addr[j],
                          phone[j], LOAD_DATE, credit[j], lim, disc[j], 0),
                         (-1000, 1000, 1, data[j])]
            for name, ids in self.last_name_index(w, d).items():
                keys.append(f"{{{w}}}:CL:{d}:{name}")
                vals.append(ids)
        keys += [f"{{{w}}}:I:{i}" for i in range(1, self.n_i + 1)]
        vals += items
        qty = rng.integers(10, 101, self.n_i).tolist()
        dist = a_strings(rng, 24 * self.n_d, 24 * self.n_d, self.n_i)
        s_data = with_original(rng, a_strings(rng, 26, 50, self.n_i))
        keys += [f"{{{w}}}:S:{i}" for i in range(1, self.n_i + 1)]
        vals += [(q, 0, 0, 0, ds, sd) for q, ds, sd in zip(qty, dist, s_data)]
        return keys, vals

    def snapshot(self) -> Tuple[List[str], list]:
        """The population of every warehouse (§4.3.3.1), ITEM replicated."""
        items = self._items()
        keys: List[str] = []
        vals: list = []
        for w in range(1, self.W + 1):
            k, v = self._warehouse(w, items)
            keys += k
            vals += v
        return keys, vals

    # -------------------------------------------------------------- requests
    def _other(self, rng, w: int) -> int:
        """A warehouse other than ``w``, uniform (``w`` when it is the
        only one)."""
        if self.W == 1:
            return w
        o = int(rng.integers(1, self.W))
        return o + (o >= w)

    def batch(self, i: int) -> List[Tuple]:
        t = self.t
        rng = self._rng(1, i)
        n = t["batch"]
        share = t["mix"]["new_order"] / (t["mix"]["new_order"]
                                         + t["mix"]["payment"])
        is_no = rng.random(n) < share
        date = LOAD_DATE + 1 + i
        lo, hi = t["ol_cnt"]
        out: List[Tuple] = []
        for j in range(n):
            rid = f"{i}.{j}"
            w = int(rng.integers(1, self.W + 1))
            d = int(rng.integers(1, self.n_d + 1))
            if is_no[j]:
                c = int(nurand(rng, 1023, 1, self.n_c, self.c_id, 1)[0])
                cnt = int(rng.integers(lo, hi + 1))
                items = nurand(rng, 8191, 1, self.n_i, self.c_item,
                               cnt).tolist()
                if rng.random() < t["rollback"]:
                    items[-1] = self.n_i + 1          # an unused item id
                remote = rng.random(cnt) < t["remote_line"]
                qty = rng.integers(t["qty"][0], t["qty"][1] + 1, cnt)
                lines = tuple(
                    (it, self._other(rng, w) if r else w, int(q))
                    for it, r, q in zip(items, remote.tolist(),
                                        qty.tolist()))
                out.append(("update", "new_order", rid, w, d, c, lines,
                            date))
                continue
            if rng.random() < t["remote_payment"]:
                c_w = self._other(rng, w)
                c_d = int(rng.integers(1, self.n_d + 1))
            else:
                c_w, c_d = w, d
            amount = int(rng.integers(t["h_amount_cents"][0],
                                      t["h_amount_cents"][1] + 1))
            if rng.random() < t["by_last_name"]:
                num = int(nurand(rng, 255, 0, min(999, self.n_c - 1),
                                 self.c_run, 1)[0])
                c_last = last_name(num)
                ids = self.last_name_index(c_w, c_d)[c_last]
                c_id = ids[(len(ids) - 1) // 2]
            else:
                c_last = None
                c_id = int(nurand(rng, 1023, 1, self.n_c, self.c_id, 1)[0])
            out.append(("update", "payment", rid, w, d, c_w, c_d, c_id,
                        c_last, amount, date))
        return out
