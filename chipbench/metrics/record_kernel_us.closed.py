"""Record kernel (gang_record_pallas) device time per update_batch call, us,
summed over the fused, per-master and grouped record programs."""
from chipbench import layers
from chipbench import trace


def read(run):
    t = run.trace
    if t is None or not t.kernel(trace.RECORD_MODULES):
        return None
    return layers.per_batch_us(run, t.kernel(trace.RECORD_MODULES))
