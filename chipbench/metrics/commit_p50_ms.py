"""Median commit latency over all updates of the window, ms: from each
update's due instant to the return of the batch that acknowledged it."""
import numpy as np


def read(run):
    lat = run.window.latency_s
    return float(np.percentile(lat, 50)) * 1e3 if lat else None
