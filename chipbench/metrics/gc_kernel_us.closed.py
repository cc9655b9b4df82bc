"""Witness gc kernel (gang_gc_pallas) device time per update_batch call, us."""
from chipbench import layers
from chipbench import trace


def read(run):
    t = run.trace
    if t is None or not t.kernel(trace.GC_MODULES):
        return None
    return layers.per_batch_us(run, t.kernel(trace.GC_MODULES))
