"""p50_ms: median due-to-resolution latency over every answered request sent in the window."""
import numpy as np


def read(run):
    lat = run.latencies_ms()
    return float(np.percentile(lat, 50)) if lat.size else None
