"""Record kernel's share of its roofline, %: the least time the chip needs
to move the bytes the record algorithm must touch (roofline.record_bytes of
every (key, witness copy) item recorded in the traced window, over the HBM
peak) divided by the kernel's device time.  The bound is bandwidth: the
kernel does integer compares, no matrix work."""
from chipbench import roofline
from chipbench import trace


def read(run):
    t = run.trace
    kernel_s = t.kernel(trace.RECORD_MODULES) / 1e9 if t is not None else 0.0
    if not kernel_s:
        return None
    items = roofline.record_items(run.kind, run.cfg, run.window)
    need_s = roofline.record_bytes(run.cfg, items) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * need_s / kernel_s
