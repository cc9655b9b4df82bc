"""XLA prep (hash, route, ring scan, intra-batch conflicts, append, sort)
of the fused batch program per update_batch call, us: device time of the
non-kernel operations of jit__gang_fastpath_impl."""
from chipbench import layers
from chipbench import trace


def read(run):
    t = run.trace
    if t is None or not t.runs(trace.FUSED_MODULES):
        return None
    return layers.per_batch_us(run, t.prep(trace.FUSED_MODULES))
