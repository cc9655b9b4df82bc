"""TPC-C New-Order and Payment as stored-procedure transactions.

The schema is TPC-C rev. 5.11's (§1.3), partitioned by warehouse: every key
carries its warehouse's hash tag ``{w}``, so a warehouse's rows share one
master (``repro.core.shard.hash_tag``).  Rows are split vertically where the
mix writes one part of a row and only reads the rest; each key is declared
to the witnesses with the class of what the procedure does to it:

==========================  =====  ============================================
key                         class  value
==========================  =====  ============================================
``{w}:W``                   READ   (W_NAME, W_ADDRESS, W_TAX)
``{w}:W_YTD``               INCR   W_YTD
``{w}:D:{d}``               READ   (D_NAME, D_ADDRESS, D_TAX)
``{w}:D_NEXT:{d}``          SET    D_NEXT_O_ID
``{w}:D_YTD:{d}``           INCR   D_YTD
``{w}:C:{d}:{c}``           READ   (C_FIRST, C_MIDDLE, C_LAST, C_ADDRESS,
                                   C_PHONE, C_SINCE, C_CREDIT, C_CREDIT_LIM,
                                   C_DISCOUNT, C_DELIVERY_CNT)
``{w}:CB:{d}:{c}``          SET    (C_BALANCE, C_YTD_PAYMENT, C_PAYMENT_CNT,
                                   C_DATA)
``{w}:CL:{d}:{last}``       READ   customer ids of that last name, by C_FIRST
``{w}:I:{i}``               READ   (I_IM_ID, I_NAME, I_PRICE, I_DATA)
``{w}:S:{i}``               SET    (S_QUANTITY, S_YTD, S_ORDER_CNT,
                                   S_REMOTE_CNT, S_DIST_01..10, S_DATA)
``{w}:O:{d}:{o}``           -      (O_C_ID, O_ENTRY_D, O_CARRIER_ID, O_OL_CNT,
                                   O_ALL_LOCAL)
``{w}:NO:{d}:{o}``          -      (NO_O_ID, NO_D_ID, NO_W_ID)
``{w}:OL:{d}:{o}:{n}``      -      (OL_I_ID, OL_SUPPLY_W_ID, OL_DELIVERY_D,
                                   OL_QUANTITY, OL_AMOUNT, OL_DIST_INFO)
``{w}:H:{h}``               SET    (H_C_ID, H_C_D_ID, H_C_W_ID, H_D_ID, H_W_ID,
                                   H_DATE, H_AMOUNT, H_DATA)
==========================  =====  ============================================

ITEM is replicated in every warehouse's partition.  S_DIST_01..10 are one
240-character string, S_DIST_xx its 24 characters ``xx``.  Money is integer
cents and rates integer 1/10000ths.  The ORDER, NEW-ORDER and ORDER-LINE rows
a New-Order inserts need no pair of their own: they are keyed by the
``o_id`` its ``D_NEXT`` pair orders.  A HISTORY row is keyed by the
request's id ``h``.

A transaction has one leg per master it touches.  New-Order's home leg
(§2.4.2.2) takes the warehouse, district, customer and item rows and the
stock of the lines its master supplies; each other supplying master's leg
takes its stock rows and exports, at PREPARE, their S_DIST_xx and S_DATA,
which no transaction writes.  An unused item (no ITEM row) makes the home
leg ask for a rollback.  Payment's home leg (§2.5.2.2) takes the warehouse
and district rows and inserts the HISTORY row; the customer's leg (the same
leg when the customer is local) takes the customer rows and, for a
selection by last name, the last-name index, exporting the selected C_ID.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

from repro.core.merge import CLS_INCR, CLS_READ, CLS_SET
from repro.core.txn import (
    Procedure, TxnPart, TxnSpec, register_procedure,
)

NEW_ORDER = "tpcc.new_order"
PAYMENT = "tpcc.payment"
ROLLBACK = ("ROLLBACK",)
C_DATA_MAX = 500     # §1.3: C_DATA is at most 500 characters
DIST_WIDTH = 24      # §1.3: S_DIST_xx is 24 characters


# ------------------------------------------------------------------- keys
def k_warehouse(w: int) -> str:
    return f"{{{w}}}:W"


def k_w_ytd(w: int) -> str:
    return f"{{{w}}}:W_YTD"


def k_district(w: int, d: int) -> str:
    return f"{{{w}}}:D:{d}"


def k_d_next(w: int, d: int) -> str:
    return f"{{{w}}}:D_NEXT:{d}"


def k_d_ytd(w: int, d: int) -> str:
    return f"{{{w}}}:D_YTD:{d}"


def k_customer(w: int, d: int, c: int) -> str:
    return f"{{{w}}}:C:{d}:{c}"


def k_balance(w: int, d: int, c: int) -> str:
    return f"{{{w}}}:CB:{d}:{c}"


def k_last_name(w: int, d: int, last: str) -> str:
    return f"{{{w}}}:CL:{d}:{last}"


def k_item(w: int, i: int) -> str:
    return f"{{{w}}}:I:{i}"


def k_stock(w: int, i: int) -> str:
    return f"{{{w}}}:S:{i}"


def k_order(w: int, d: int, o: int) -> str:
    return f"{{{w}}}:O:{d}:{o}"


def k_new_order(w: int, d: int, o: int) -> str:
    return f"{{{w}}}:NO:{d}:{o}"


def k_order_line(w: int, d: int, o: int, n: int) -> str:
    return f"{{{w}}}:OL:{d}:{o}:{n}"


def k_history(w: int, h: str) -> str:
    return f"{{{w}}}:H:{h}"


# ------------------------------------------------------------- procedures
def _take_stock(get, put, w: int, d: int, i: int, qty: int,
                remote: bool) -> Tuple[int, str, str]:
    """§2.4.2.2's stock update; returns (S_QUANTITY after it, S_DIST_xx,
    S_DATA)."""
    key = k_stock(w, i)
    s_qty, s_ytd, s_cnt, s_remote, s_dist, s_data = get(key)
    s_qty = s_qty - qty if s_qty >= qty + 10 else s_qty - qty + 91
    put(key, (s_qty, s_ytd + qty, s_cnt + 1, s_remote + int(remote),
              s_dist, s_data))
    return s_qty, s_dist[DIST_WIDTH * (d - 1):DIST_WIDTH * d], s_data


def order_total(amounts: Sequence[int], discount: int, w_tax: int,
                d_tax: int) -> int:
    """sum(OL_AMOUNT) * (1 - C_DISCOUNT) * (1 + W_TAX + D_TAX) in cents,
    rounded half up (rates in 1/10000ths)."""
    num = sum(amounts) * (10000 - discount) * (10000 + w_tax + d_tax)
    return (num + 50_000_000) // 100_000_000


class NewOrder(Procedure):
    """Leg args: ``("home", w, d, c, lines, entry_d, here)`` with ``lines``
    the order's ``(i_id, supply_w, qty)`` and ``here`` a flag per line that
    this leg's master supplies it, or ``("supply", w, d, lines)`` with
    ``lines`` the ``(line_no, supply_w, i_id, qty)`` this leg's master
    supplies.  The value is ``(O_ID, total, ((S_QUANTITY, brand_generic,
    OL_AMOUNT), ...))`` per line, or ``ROLLBACK``."""

    def prepare(self, get, args) -> Optional[dict]:
        if args[0] == "home":
            w, lines = args[1], args[4]
            if any(get(k_item(w, i)) is None for i, _sw, _q in lines):
                return None
            return {}
        _role, _w, d, lines = args
        out = {}
        for _n, sw, i, _q in lines:
            row = get(k_stock(sw, i))
            if row is None:
                return None
            out[("S", sw, i)] = (
                row[4][DIST_WIDTH * (d - 1):DIST_WIDTH * d], row[5])
        return out

    def commit(self, get, put, args, forwarded) -> Any:
        if args[0] == "supply":
            _role, w, d, lines = args
            return tuple(
                (n, _take_stock(get, put, sw, d, i, q, sw != w)[0])
                for n, sw, i, q in lines)
        _role, w, d, c, lines, entry_d, here = args
        w_tax = get(k_warehouse(w))[2]
        d_tax = get(k_district(w, d))[2]
        o_id = get(k_d_next(w, d))
        put(k_d_next(w, d), o_id + 1)
        discount = get(k_customer(w, d, c))[8]
        all_local = int(all(sw == w for _i, sw, _q in lines))
        put(k_order(w, d, o_id), (c, entry_d, None, len(lines), all_local))
        put(k_new_order(w, d, o_id), (o_id, d, w))
        out, amounts = [], []
        for n, ((i, sw, qty), mine) in enumerate(zip(lines, here), 1):
            _im, _name, price, i_data = get(k_item(w, i))
            if mine:
                s_qty, dist, s_data = _take_stock(get, put, sw, d, i, qty,
                                                  sw != w)
            else:
                s_qty = None
                dist, s_data = forwarded[("S", sw, i)]
            amount = qty * price
            amounts.append(amount)
            bg = "B" if "ORIGINAL" in i_data and "ORIGINAL" in s_data \
                else "G"
            put(k_order_line(w, d, o_id, n), (i, sw, None, qty, amount, dist))
            out.append((s_qty, bg, amount))
        return (o_id, order_total(amounts, discount, w_tax, d_tax),
                tuple(out))

    def combine(self, spec: TxnSpec, results: Optional[Dict[int, Any]]):
        if results is None:
            return ROLLBACK
        home, supplied = None, {}
        for part in spec.parts:
            if part.args[0] == "home":
                home = results[part.shard_id]
            else:
                supplied.update(results[part.shard_id])
        o_id, total, lines = home
        return (o_id, total, tuple(
            (supplied[n] if s_qty is None else s_qty, bg, amount)
            for n, (s_qty, bg, amount) in enumerate(lines, 1)))


class Payment(Procedure):
    """Leg args: ``(role, w, d, c_w, c_d, c_id, c_last, h_amount, h_id,
    h_date)`` with ``role`` ``"home"`` (warehouse, district, HISTORY),
    ``"customer"`` (the customer's rows) or ``"both"``; ``c_last`` is None
    for a selection by id.  The value is ``(C_ID, C_BALANCE, C_CREDIT)``."""

    def prepare(self, get, args) -> Optional[dict]:
        role, _w, _d, c_w, c_d, c_id, c_last = args[:7]
        if role == "home" or c_last is None:
            return {}
        ids = get(k_last_name(c_w, c_d, c_last))
        picked = ids[(len(ids) - 1) // 2]     # §2.5.2.2: n/2 rounded up
        if picked != c_id:
            raise ValueError(f"Payment declares customer {c_id}, but the "
                             f"last-name index selects {picked}")
        return {"C_ID": picked}

    def commit(self, get, put, args, forwarded) -> Any:
        role, w, d, c_w, c_d, c_id, _last, amount, h_id, h_date = args
        out = "OK"
        if role != "home":
            credit = get(k_customer(c_w, c_d, c_id))[6]
            bal, ytd, cnt, data = get(k_balance(c_w, c_d, c_id))
            if credit == "BC":
                data = (f"{c_id} {c_d} {c_w} {d} {w} {amount}|"
                        + data)[:C_DATA_MAX]
            put(k_balance(c_w, c_d, c_id),
                (bal - amount, ytd + amount, cnt + 1, data))
            out = (c_id, bal - amount, credit)
        if role != "customer":
            put(k_w_ytd(w), get(k_w_ytd(w)) + amount)
            put(k_d_ytd(w, d), get(k_d_ytd(w, d)) + amount)
            h_data = get(k_warehouse(w))[0] + "    " + get(k_district(w, d))[0]
            put(k_history(w, h_id),
                (forwarded.get("C_ID", c_id), c_d, c_w, d, w, h_date, amount,
                 h_data))
        return out

    def combine(self, spec: TxnSpec, results: Optional[Dict[int, Any]]):
        (part,) = [p for p in spec.parts if p.args[0] != "home"]
        return results[part.shard_id]


register_procedure(NEW_ORDER, NewOrder())
register_procedure(PAYMENT, Payment())


# ------------------------------------------------------------------- specs
def _part(sid: int, proc: str, args: Tuple, decl) -> TxnPart:
    return TxnPart(shard_id=sid, prepare_rpc=None, decide_rpc=None,
                   write_kvs=(), proc=proc, args=args,
                   decl=tuple(dict.fromkeys(decl)))


def new_order(session, w: int, d: int, c: int,
              lines: Sequence[Tuple[int, int, int]], entry_d: int) -> TxnSpec:
    """A New-Order of customer ``c`` of district ``d`` of warehouse ``w``;
    ``lines`` are ``(i_id, supply_w, qty)``.  One leg per master: the home
    warehouse's, and one for each other master that supplies a line."""
    lines = tuple(tuple(line) for line in lines)
    shard = session.router.shard_of
    hs = shard(k_warehouse(w))
    owner = [shard(k_warehouse(sw)) for _i, sw, _q in lines]
    decl = [(k_warehouse(w), CLS_READ), (k_district(w, d), CLS_READ),
            (k_d_next(w, d), CLS_SET), (k_customer(w, d, c), CLS_READ)]
    decl += [(k_item(w, i), CLS_READ) for i, _sw, _q in lines]
    decl += [(k_stock(sw, i), CLS_SET)
             for (i, sw, _q), o in zip(lines, owner) if o == hs]
    parts = [_part(hs, NEW_ORDER,
                   ("home", w, d, c, lines, entry_d,
                    tuple(o == hs for o in owner)), decl)]
    for sid in sorted(set(owner) - {hs}):
        mine = tuple((n, sw, i, q)
                     for n, ((i, sw, q), o) in enumerate(zip(lines, owner), 1)
                     if o == sid)
        parts.append(_part(sid, NEW_ORDER, ("supply", w, d, mine),
                           [(k_stock(sw, i), CLS_SET)
                            for _n, sw, i, _q in mine]))
    return session.new_txn(sorted(parts, key=lambda p: p.shard_id))


def payment(session, w: int, d: int, c_w: int, c_d: int, c_id: int,
            c_last: Optional[str], amount: int, h_id: str,
            h_date: int) -> TxnSpec:
    """A Payment of ``amount`` cents to warehouse ``w``, district ``d``, by
    customer ``c_id`` of district ``c_d`` of warehouse ``c_w``, selected by
    last name when ``c_last`` is given (``c_id`` is then the index's pick,
    which the customer's leg checks)."""
    shard = session.router.shard_of
    home = [(k_warehouse(w), CLS_READ), (k_w_ytd(w), CLS_INCR),
            (k_district(w, d), CLS_READ), (k_d_ytd(w, d), CLS_INCR),
            (k_history(w, h_id), CLS_SET)]
    cust = [(k_customer(c_w, c_d, c_id), CLS_READ),
            (k_balance(c_w, c_d, c_id), CLS_SET)]
    if c_last is not None:
        cust.append((k_last_name(c_w, c_d, c_last), CLS_READ))
    args = (w, d, c_w, c_d, c_id, c_last, amount, h_id, h_date)
    hs, cs = shard(k_warehouse(w)), shard(k_warehouse(c_w))
    if hs == cs:
        parts = [_part(hs, PAYMENT, ("both",) + args, home + cust)]
    else:
        parts = sorted([_part(hs, PAYMENT, ("home",) + args, home),
                        _part(cs, PAYMENT, ("customer",) + args, cust)],
                       key=lambda p: p.shard_id)
    return session.new_txn(parts)
