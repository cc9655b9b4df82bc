"""Record kind ``tpcc``: TPC-C New-Order and Payment (rev. 5.11, §2.4 and
§2.5) as stored-procedure transactions on a warehouse-partitioned CURP
cluster.  A request is the ``tpcc`` generator's tuple
(``chipbench/traffic/tpcc.py``); rows and keys are laid out as the
configuration states (its ``schema``).

``Reference`` is the plain reference: written from §2.4.2 and §2.5.2's
profiles over one dict, with the deployment's placement, per-master
unsynced windows and witness tables, the batch's round rule and the sync
rule.  It imports nothing of ``repro``.

* placement: a key's hash tag (the text between its first ``{`` and the
  next ``}``, when not empty) is hashed in its place, as Redis Cluster
  does; the tag's slot is picked as for any key (``reference.py``).  Slot
  ``i`` belongs to master ``i % masters``, except that the slot of
  warehouse ``w``'s tag belongs to master ``(w - 1) % masters``, assigned
  in warehouse order;
* merge classes: READ (9) commutes with READ, INCR (2) with INCR, and every
  other pair of classes on one key conflicts; SET is 0;
* a transaction has one leg per master it touches (``legs``), each with
  its keys and their classes; a leg's pairs are recorded at its master's
  witnesses and held in its master's unsynced window like an update's;
* rounds: each round takes every pending request in order and runs its
  legs in master order.  A leg meets the intent of an undecided
  transaction of the round when one of its keys is held by that
  transaction with a conflicting class; the request is then deferred: the
  legs it ran are aborted at once (an abort is logged, held in the window
  and asks for a sync, but records nothing) and it runs again in the next
  round as a new transaction.  A single-leg transaction executes at once;
  a leg of a multi-leg one takes its locks and reads what it exports.
  After every request, each master that ran a leg classifies them as
  ``reference.py`` classifies updates and syncs when one needed it or it
  asked for a sync; then each multi-leg transaction, in order, commits
  (or aborts when a leg asked for a rollback) at every leg: the decision
  is logged, held in the window and asks for a sync.  After the last
  round every master that asked for a sync syncs;
* a multi-leg transaction's row is fast when every leg was, synced when
  any leg was, takes its slowest leg's round trips plus one, and sums its
  legs' witness accepts; the value is §2.4.3.3's or §2.5.3.4's output:
  ``(O_ID, total, ((S_QUANTITY, brand-generic, OL_AMOUNT), ...))``, or
  ``("ROLLBACK",)``; ``(C_ID, C_BALANCE, C_CREDIT)``.

``fault="no_master_sync"`` breaks the guarantee ``reference.py`` names: no
master starts a sync itself (before replying to a conflicting leg, at
``sync_batch`` unsynced entries, or after a decision).
"""
from __future__ import annotations

from collections import Counter
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from chipbench import deploy, reference
from chipbench.loops import no_span
from chipbench.reference import Row, keyhash, mixed_lo

SET, INCR, OTHER, READ = 0, 2, 8, 9
MERGEABLE = frozenset({2, 3, 5, 6, 7, 9})
DIST = 24


def conflicts(a: int, b: int) -> bool:
    return not (a == b and a in MERGEABLE)


def tag_of(key: str) -> Optional[str]:
    i = key.find("{")
    j = key.find("}", i + 1) if i >= 0 else -1
    return key[i + 1:j] if j > i + 1 else None


def warehouse_of(key: str) -> int:
    return int(key[1:key.index("}")])


def _total(amounts, discount: int, w_tax: int, d_tax: int) -> int:
    """sum * (1 - discount) * (1 + w_tax + d_tax), rates in 1/10000ths,
    cents rounded half up."""
    x = sum(amounts) * (10000 - discount) * (10000 + w_tax + d_tax)
    return (x + 50_000_000) // 100_000_000


class _Witness(reference._Witness):
    def record(self, pairs, tag: int) -> bool:
        claimed: Counter = Counter()
        plan = []
        for kh, cls in dict.fromkeys(pairs):
            s = kh % self.n_sets
            held = self.sets.get(s, ())
            if any(h == kh and conflicts(c, cls) for h, c, _t in held):
                return False
            if len(held) + claimed[s] >= self.n_ways:
                return False
            claimed[s] += 1
            plan.append((s, kh, cls))
        for s, kh, cls in plan:
            self.sets.setdefault(s, []).append((kh, cls, tag))
        return True


class _Master(reference._Master):
    """A master of the base reference with the READ class, and the intent
    locks of its undecided transactions: key -> {txn: class}."""

    def __init__(self, f: int, n_sets: int, n_ways: int) -> None:
        super().__init__(f, n_sets, n_ways)
        self.witnesses = [_Witness(n_sets, n_ways) for _ in range(f)]
        self.locks: Dict[str, Dict[int, int]] = {}

    def commutes(self, pairs) -> bool:
        return not any(conflicts(held, cls) for kh, cls in pairs
                       for held in self.window.get(kh, ()))

    def log(self, pairs, tag: int) -> None:
        self.unsynced.append((pairs, tag))
        for kh, cls in pairs:
            self.window.setdefault(kh, Counter())[cls] += 1

    def blocked(self, decl, txn: int) -> bool:
        return any(owner != txn and conflicts(c, cls)
                   for key, cls in decl
                   for owner, c in self.locks.get(key, {}).items())

    def unlock(self, decl, txn: int) -> None:
        for key, _cls in decl:
            held = self.locks.get(key)
            if held is not None:
                held.pop(txn, None)
                if not held:
                    del self.locks[key]


class Reference(reference.Reference):
    def __init__(self, cfg: dict, snapshot: Optional[Dict[str, Any]] = None,
                 fault: Optional[str] = None) -> None:
        super().__init__(cfg, snapshot, fault)
        w = cfg["witness"]
        self.m = [_Master(self.f, w["sets"], w["ways"])
                  for _ in range(self.masters)]
        self.slot_map = [s % self.masters for s in range(self.slots)]
        for wh in range(1, cfg["warehouses"] + 1):
            self.slot_map[self._slot(str(wh))] = (wh - 1) % self.masters
        self._txn = 0
        # What the program's txn.batch.* counters must read.
        self.counts: Counter = Counter()

    def _slot(self, text: str) -> int:
        return mixed_lo(self._hash(text)) % self.slots

    def shard_of(self, key: str) -> int:
        t = tag_of(key)
        return self.slot_map[self._slot(key if t is None else t)]

    # ------------------------------------------------------------- requests
    def legs(self, req) -> List[Tuple[int, str, list]]:
        """``(master, role, [(key, class), ...])`` per leg, in master
        order."""
        if req[1] == "new_order":
            _u, _k, _rid, w, d, c, lines, _date = req
            home = self.shard_of(f"{{{w}}}:W")
            decl = {home: [(f"{{{w}}}:W", READ), (f"{{{w}}}:D:{d}", READ),
                           (f"{{{w}}}:D_NEXT:{d}", SET),
                           (f"{{{w}}}:C:{d}:{c}", READ)]
                    + [(f"{{{w}}}:I:{i}", READ) for i, _s, _q in lines]}
            for i, sw, _q in lines:
                decl.setdefault(self.shard_of(f"{{{sw}}}:W"), []).append(
                    (f"{{{sw}}}:S:{i}", SET))
            return [(sid, "home" if sid == home else "supply",
                     list(dict.fromkeys(decl[sid]))) for sid in sorted(decl)]
        _u, _k, rid, w, d, c_w, c_d, c_id, c_last, _amt, _date = req
        home = [(f"{{{w}}}:W", READ), (f"{{{w}}}:W_YTD", INCR),
                (f"{{{w}}}:D:{d}", READ), (f"{{{w}}}:D_YTD:{d}", INCR),
                (f"{{{w}}}:H:{rid}", SET)]
        cust = [(f"{{{c_w}}}:C:{c_d}:{c_id}", READ),
                (f"{{{c_w}}}:CB:{c_d}:{c_id}", SET)]
        if c_last is not None:
            cust.append((f"{{{c_w}}}:CL:{c_d}:{c_last}", READ))
        hs, cs = self.shard_of(f"{{{w}}}:W"), self.shard_of(f"{{{c_w}}}:W")
        if hs == cs:
            return [(hs, "both", home + cust)]
        return sorted([(hs, "home", home), (cs, "customer", cust)])

    def _prepare(self, req, role: str, sid: int) -> Optional[dict]:
        """What a leg reads at PREPARE for the others: an unused item asks
        for a rollback (None); a supplier exports S_DIST_xx and S_DATA; a
        customer selected by last name exports its C_ID."""
        v = self.values
        if req[1] == "new_order":
            _u, _k, _rid, w, d, _c, lines, _date = req
            if role == "home":
                return None if any(f"{{{w}}}:I:{i}" not in v
                                   for i, _s, _q in lines) else {}
            out = {}
            for i, sw, _q in lines:
                if self.shard_of(f"{{{sw}}}:W") == sid:
                    row = v.get(f"{{{sw}}}:S:{i}")
                    if row is None:
                        return None
                    out[(sw, i)] = (row[4][DIST * (d - 1):DIST * d], row[5])
            return out
        c_w, c_d, c_id, c_last = req[5:9]
        if role == "home" or c_last is None:
            return {}
        ids = v[f"{{{c_w}}}:CL:{c_d}:{c_last}"]
        return {"C_ID": ids[(len(ids) - 1) // 2]}

    def _stock(self, sw: int, i: int, d: int, qty: int, w: int):
        key = f"{{{sw}}}:S:{i}"
        q, ytd, cnt, rem, dist, data = self.values[key]
        q = q - qty if q >= qty + 10 else q - qty + 91
        self.values[key] = (q, ytd + qty, cnt + 1, rem + (sw != w), dist,
                            data)
        return q, dist[DIST * (d - 1):DIST * d], data

    def _commit(self, req, role: str, sid: int, fwd: dict) -> Any:
        """A leg's effect (§2.4.2.2, §2.5.2.2) and its part of the output."""
        v = self.values
        if req[1] == "new_order":
            _u, _k, _rid, w, d, c, lines, date = req
            mine = [self.shard_of(f"{{{sw}}}:W") == sid
                    for _i, sw, _q in lines]
            if role == "supply":
                return {n: self._stock(sw, i, d, q, w)[0]
                        for n, ((i, sw, q), here) in enumerate(
                            zip(lines, mine), 1) if here}
            o_id = v[f"{{{w}}}:D_NEXT:{d}"]
            v[f"{{{w}}}:D_NEXT:{d}"] = o_id + 1
            v[f"{{{w}}}:O:{d}:{o_id}"] = (
                c, date, None, len(lines),
                int(all(sw == w for _i, sw, _q in lines)))
            v[f"{{{w}}}:NO:{d}:{o_id}"] = (o_id, d, w)
            out, amounts = [], []
            for n, ((i, sw, q), here) in enumerate(zip(lines, mine), 1):
                _im, _name, price, i_data = v[f"{{{w}}}:I:{i}"]
                if here:
                    s_q, dist, s_data = self._stock(sw, i, d, q, w)
                else:
                    s_q = None
                    dist, s_data = fwd[(sw, i)]
                amounts.append(q * price)
                brand = ("B" if "ORIGINAL" in i_data and "ORIGINAL" in s_data
                         else "G")
                v[f"{{{w}}}:OL:{d}:{o_id}:{n}"] = (i, sw, None, q, q * price,
                                                  dist)
                out.append((s_q, brand, q * price))
            total = _total(amounts, v[f"{{{w}}}:C:{d}:{c}"][8],
                           v[f"{{{w}}}:W"][2], v[f"{{{w}}}:D:{d}"][2])
            return (o_id, total, out)
        _u, _k, rid, w, d, c_w, c_d, c_id, _last, amt, date = req
        out = None
        if role != "home":
            credit = v[f"{{{c_w}}}:C:{c_d}:{c_id}"][6]
            bal, ytd, cnt, data = v[f"{{{c_w}}}:CB:{c_d}:{c_id}"]
            if credit == "BC":
                data = f"{c_id} {c_d} {c_w} {d} {w} {amt}|{data}"[:500]
            v[f"{{{c_w}}}:CB:{c_d}:{c_id}"] = (bal - amt, ytd + amt, cnt + 1,
                                               data)
            out = (c_id, bal - amt, credit)
        if role != "customer":
            v[f"{{{w}}}:W_YTD"] += amt
            v[f"{{{w}}}:D_YTD:{d}"] += amt
            v[f"{{{w}}}:H:{rid}"] = (
                fwd.get("C_ID", c_id), c_d, c_w, d, w, date, amt,
                v[f"{{{w}}}:W"][0] + "    " + v[f"{{{w}}}:D:{d}"][0])
        return out

    @staticmethod
    def _value(req, parts: Optional[List[Any]]):
        """The transaction's output from its legs' parts (None: rolled
        back)."""
        if parts is None:
            return ("ROLLBACK",)
        if req[1] == "payment":
            return next(p for p in parts if p is not None)
        home = next(p for p in parts if isinstance(p, tuple))
        supplied = {}
        for p in parts:
            if isinstance(p, dict):
                supplied.update(p)
        o_id, total, lines = home
        return (o_id, total, tuple(
            (supplied[n] if s_q is None else s_q, brand, amount)
            for n, (s_q, brand, amount) in enumerate(lines, 1)))

    def commit(self, req) -> Any:
        """Commit ``req`` at every leg, as the round's decision or recovery's
        resolution does for a transaction whose every leg prepared: each leg
        reads what it exports (values no transaction writes), then each
        commits with them all.  Returns the transaction's value, a rollback
        when a leg asks for one."""
        legs = self.legs(req)
        exports = [self._prepare(req, role, sid) for sid, role, _d in legs]
        if any(ex is None for ex in exports):
            return self._value(req, None)
        fwd: dict = {}
        for ex in exports:
            fwd.update(ex)
        return self._value(req, [self._commit(req, role, sid, fwd)
                                 for sid, role, _d in legs])

    def _decide(self, m: _Master, decl) -> None:
        """A COMMIT or ABORT leg: logged, held in the window, and the
        master asks for a sync."""
        self._tag += 1
        m.log(tuple((self._hash(k), c) for k, c in decl), self._tag)
        m.want_sync = m.want_sync or self.fault is None

    def update_batch(self, updates: Sequence) -> List[Row]:
        rows: List[Optional[Row]] = [None] * len(updates)
        pending = list(range(len(updates)))
        while pending:
            self.counts["txn.batch.rounds"] += 1
            live: Set[int] = set()
            ran: Dict[int, List[Tuple[int, bool, int]]] = {}  # sid -> steps
            done: Dict[int, list] = {}     # multi-leg request -> its legs
            singles: Dict[int, Tuple[int, Any]] = {}
            deferred = []
            for i in pending:
                req = updates[i]
                self._txn += 1
                txn = self._txn
                legs = self.legs(req)
                steps = []
                for sid, role, decl in legs:
                    m = self.m[sid]
                    if m.blocked(decl, txn):
                        break
                    pairs = tuple((self._hash(k), c) for k, c in decl)
                    self._tag += 1
                    accepts = sum(w.record(pairs, self._tag)
                                  for w in m.witnesses)
                    commutes = self.fault is not None or m.commutes(pairs)
                    m.log(pairs, self._tag)
                    if len(legs) == 1:
                        ex = self._prepare(req, role, sid)
                        result = (None if ex is None
                                  else self._commit(req, role, sid, ex))
                    else:
                        for key, cls in decl:
                            m.locks.setdefault(key, {})[txn] = cls
                        result = self._prepare(req, role, sid)
                    if not commutes:
                        m.want_sync = True
                    elif (len(m.unsynced) >= self.sync_batch
                          and self.fault is None):
                        m.want_sync = True
                    ran.setdefault(sid, []).append((i, commutes, accepts))
                    steps.append((sid, role, decl, len(ran[sid]) - 1, result))
                else:
                    if len(legs) == 1:
                        singles[i] = (steps[0][3], steps[0][4])
                    else:
                        live.add(txn)
                        done[i] = (txn, steps)
                    continue
                for sid, _role, decl, _pos, _res in steps:
                    self.m[sid].unlock(decl, txn)
                    self._decide(self.m[sid], decl)
                deferred.append(i)
                self.counts["txn.batch.deferred"] += 1
            outcome: Dict[Tuple[int, int], Row] = {}
            for sid, steps in ran.items():
                m = self.m[sid]
                need = False
                for pos, (_i, commutes, accepts) in enumerate(steps):
                    if not commutes:
                        outcome[(sid, pos)] = (False, True, 2, accepts, None)
                    elif accepts == self.f:
                        outcome[(sid, pos)] = (True, False, 1, accepts, None)
                    else:
                        need = True
                        outcome[(sid, pos)] = (False, False, 2, accepts, None)
                if need or m.want_sync:
                    m.sync()
            for i, (pos, result) in singles.items():
                sid = self.legs(updates[i])[0][0]
                rows[i] = outcome[(sid, pos)][:4] + (
                    self._value(updates[i],
                                None if result is None else [result]),)
            for i, (txn, steps) in done.items():
                req = updates[i]
                commit = all(res is not None for *_x, res in steps)
                value = self.commit(req) if commit else self._value(req, None)
                for sid, _role, decl, _pos, _res in steps:
                    self.m[sid].unlock(decl, txn)
                    self._decide(self.m[sid], decl)
                legs = [outcome[(sid, pos)] for sid, _r, _d, pos, _x in steps]
                rows[i] = (all(r[0] for r in legs), any(r[1] for r in legs),
                           max(r[2] for r in legs) + 1,
                           sum(r[3] for r in legs), value)
                self.counts["txn.batch.multi_shard"] += 1
            pending = deferred
        for m in self.m:
            if m.want_sync:
                m.sync()
        return rows  # type: ignore[return-value]


class _Overlay(dict):
    """A store's data that reads a row of ``base`` on first touch, so a
    backup's log replays over the loaded population without copying it."""

    def __init__(self, base: Dict[str, Any], wrap) -> None:
        super().__init__()
        self.base, self.wrap = base, wrap

    def get(self, key, default=None):
        if key in self:
            return self[key]
        if key not in self.base:
            return default
        vv = self[key] = self.wrap(self.base[key])
        return vv


FIRST_O_ID = 3001    # §4.3.3.1: every district's D_NEXT_O_ID at load
COUNTERS = ("txn.batch.rounds", "txn.batch.deferred",
            "witness.record_dispatches")


class Kind:
    Reference = Reference

    def __init__(self) -> None:
        self.counts = dict.fromkeys(COUNTERS, 0)
        self._next_o: Dict[Tuple[int, int], int] = {}
        self._o_of: Dict[str, int] = {}

    def spec(self, session, req):
        """The program's transaction for one request."""
        from repro.apps import tpcc

        if req[1] == "new_order":
            _u, _k, _rid, w, d, c, lines, date = req
            return tpcc.new_order(session, w, d, c, lines, date)
        _u, _k, rid, w, d, c_w, c_d, c_id, c_last, amount, date = req
        return tpcc.payment(session, w, d, c_w, c_d, c_id, c_last, amount,
                            rid, date)

    def send(self, cluster, session, reqs, span) -> List[Row]:
        """Serve one turn's transactions through one
        ``ShardedCluster.update_batch`` call; one outcome row each."""
        from repro.core import telemetry

        reg = telemetry.registry()
        before = {n: reg.counter(n).value for n in COUNTERS}
        with span("bench.make_ops"):
            ops = [session.op_txn(self.spec(session, r)) for r in reqs]
        with span("bench.update_batch"):
            out = cluster.update_batch(session, ops)
        for n in COUNTERS:
            self.counts[n] += reg.counter(n).value - before[n]
        return [(o.fast_path, o.synced_path, o.rtts, o.witness_accepts,
                 o.value) for o in out]

    def pairs(self, req) -> int:
        """(hash, class) pairs one attempt of ``req`` records, summed over
        its legs."""
        if req[1] == "new_order":
            lines = req[6]
            return (4 + len({i for i, _s, _q in lines})
                    + len({(s, i) for i, s, _q in lines}))
        return 7 + (req[8] is not None)

    def written(self, reqs) -> Set[str]:
        """The keys a batch wrote: a New-Order's D_NEXT, stock rows and the
        ORDER, NEW-ORDER and ORDER-LINE rows of the next o_id of its
        district (every New-Order counted, so rollbacks add keys nothing
        wrote); a Payment's YTDs, customer balance and HISTORY row.  A
        window's batches are asked in order; a request asked again keeps
        the o_id it was given."""
        out: Set[str] = set()
        for req in reqs:
            if req[1] == "new_order":
                _u, _k, rid, w, d, _c, lines, _date = req
                o = self._o_of.get(rid)
                if o is None:
                    o = self._o_of[rid] = self._next_o.get((w, d), FIRST_O_ID)
                    self._next_o[(w, d)] = o + 1
                out.add(f"{{{w}}}:D_NEXT:{d}")
                out.update((f"{{{w}}}:O:{d}:{o}", f"{{{w}}}:NO:{d}:{o}"))
                out.update(f"{{{w}}}:OL:{d}:{o}:{n}" for n in range(1, 16))
                out.update(f"{{{sw}}}:S:{i}" for i, sw, _q in lines)
            else:
                _u, _k, rid, w, d, c_w, c_d, c_id = req[:8]
                out.update((f"{{{w}}}:W_YTD", f"{{{w}}}:D_YTD:{d}",
                            f"{{{c_w}}}:CB:{c_d}:{c_id}", f"{{{w}}}:H:{rid}"))
        return out

    def assign(self, cluster, cfg: dict) -> None:
        """Give warehouse ``w``'s slot to master ``(w - 1) % masters`` on the
        empty cluster, as an operator's CLUSTER ADDSLOTS would."""
        for w in range(1, cfg["warehouses"] + 1):
            cluster.router.assign([cluster.router.slot_of(f"{{{w}}}")],
                                  (w - 1) % cfg["masters"])

    def snapshot(self, cluster, cfg: dict, keys: Sequence[str],
                 values: Sequence[Any]) -> None:
        """Assign the warehouses' slots, then give every master and each of
        its backups its warehouses' rows as one synced bulk MSET entry (as
        ``object`` records are loaded).  Resets the counters the per-layer
        metrics read: this runs on the measured cluster only."""
        from repro.core.backup import LogEntry

        self.assign(cluster, cfg)
        self.counts = dict.fromkeys(COUNTERS, 0)
        self._next_o, self._o_of = {}, {}
        ref = Reference(cfg)
        owner_of = {w: ref.shard_of(f"{{{w}}}:W")
                    for w in range(1, cfg["warehouses"] + 1)}
        owner = np.fromiter((owner_of[warehouse_of(k)] for k in keys),
                            np.int64, len(keys))
        for i in range(0, len(keys), max(1, len(keys) // 997)):
            if cluster.shard_of(keys[i]) != owner[i]:
                raise RuntimeError(f"key placement of {keys[i]!r} disagrees "
                                   "with the deployment's stated hash")
        order = np.argsort(owner, kind="stable")
        bounds = np.searchsorted(owner[order], np.arange(cfg["masters"] + 1))
        loader = cluster.new_client()
        for sid, g in enumerate(cluster.shards):
            idx = order[bounds[sid]:bounds[sid + 1]].tolist()
            op = loader.session_for(sid).op_mset([(keys[i], values[i])
                                                  for i in idx])
            entry = LogEntry(op, "OK")
            g.master.restore_from_log([entry])
            for b in g.backups:
                b.log = [entry]

    def warm_up(self, cfg: dict, traffic: dict, gen) -> int:
        """Compile, on a scratch gang of the deployment's shape, every record
        dispatch shape a batch of ``batch`` transactions can reach (groups
        and keys bucketed as the program pads them, split to fit SMEM) and
        every gc shape its syncs can reach, so the window compiles nothing.
        A dispatch's keys are its widest leg's pairs: every request has a
        leg of at least 5 (a Payment's home leg; a New-Order's home leg
        takes 4 rows and its items), and a dispatch of a split round takes a
        run of every witness's ops, so record shapes start at 8 keys.
        Returns the number of dispatches made.  A program without the
        deployment's procedures fails here, before any compile."""
        from repro.apps import tpcc  # noqa: F401
        from repro.kernels import gang_gc, gang_record_groups, record_fits

        gang = deploy.build(cfg).gang
        cap = 4 * traffic["batch"]
        sent = 0
        k = 8
        while k <= 64:
            g = 4
            while g <= cap and record_fits(g, k):
                z = np.zeros((g, k), np.uint32)
                gang_record_groups(gang.table, gang.n_sets, z, z,
                                   np.zeros((g, k), np.int32),
                                   np.zeros(g, np.int32), z[:, 0], z[:, 0],
                                   np.zeros((g, k), np.int32))
                sent += 1
                g *= 2
            k *= 2
        g = 16
        while g <= 8 * cap:    # gc: up to 32768 entries, the most SMEM holds
            z = np.zeros(g, np.uint32)
            gang_gc(gang.table, gang.n_sets, z, z, z, z, np.zeros(g, np.int32),
                    np.zeros(gang.n_lanes, np.int32))
            sent += 1
            g *= 2
        return sent

    def read_back(self, cluster, cfg: dict, keys: Sequence[str],
                  base: Dict[str, Any]) -> Dict[str, List[Any]]:
        """After a sync, each key's value at its master and at each backup:
        a backup's value is its log after the loaded entry replayed through
        the store over the loaded rows."""
        from repro.core.store import KVStore, VersionedValue

        out: Dict[str, List[Any]] = {}
        by_shard: Dict[int, List[str]] = {}
        for k in keys:
            by_shard.setdefault(cluster.shard_of(k), []).append(k)
        for sid, ks in by_shard.items():
            g = cluster.shards[sid]
            views = []
            for b in g.backups:
                store = KVStore()
                store._data = _Overlay(base, VersionedValue)
                for e in b.log[1:]:
                    store.execute(e.op)
                views.append(store)
            for k in ks:
                out[k] = [g.master.store.get(k)] + [v.get(k) for v in views]
        return out
