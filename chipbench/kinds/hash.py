"""Record kind ``hash``: a record is a hash of fields, an update an HMSET of
one field (CURP on Redis, driven as YCSB's Redis binding does).  A request
is the traffic generator's ``(op, key, field, value)``.  Everything else is
as for ``object`` records."""
from __future__ import annotations

from typing import Any

from chipbench import reference
from chipbench.kinds.object import Kind as Whole
from chipbench.reference import CLS_FIELD, CLS_HMSET


def field_subkey(key: str, field: str) -> str:
    """The derived per-field key an HMSET records besides its base key."""
    return f"{key!r}\x1fhf\x1f{field!r}"


class Reference(reference.Reference):
    def pairs(self, req):
        _op, key, field, _value = req
        return ((self._hash(key), CLS_HMSET),
                (self._hash(field_subkey(key, field)), CLS_FIELD))

    def apply(self, req):
        _op, key, field, value = req
        cur = self.values.get(key)
        h = dict(cur) if isinstance(cur, dict) else {}
        h[field] = value
        self.values[key] = h
        return "OK"


class Kind(Whole):
    Reference = Reference

    def op(self, session, req):
        _op, key, field, value = req
        return session.op_hmset(key, ((field, value),))

    def warm_request(self, cfg: dict, key: str, value: str):
        return ("update", key, cfg["record"]["field_format"] % 0, value)

    def logged(self, cur: Any, op) -> Any:
        if op.op_type.name == "HMSET":
            h = dict(cur) if isinstance(cur, dict) else {}
            h.update(op.args[0])
            return h
        return super().logged(cur, op)

    def pairs(self, req) -> int:
        return 2    # the key's HMSET pair and its one field's pair
