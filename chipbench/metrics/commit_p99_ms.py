"""99th percentile of commit latency over all updates of the window, ms,
measured as for commit_p50_ms (one sample per update, not per batch)."""
import numpy as np


def read(run):
    lat = run.window.latency_s
    return float(np.percentile(lat, 99)) * 1e3 if lat else None
