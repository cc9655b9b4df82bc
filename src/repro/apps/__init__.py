"""Applications served as stored-procedure transactions over the sharded
CURP store (``repro.core.txn``)."""
