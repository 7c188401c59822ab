"""frontend.p99_ms: 99th percentile of the due-to-resolution latency over
every answered request sent in the window (read in the traced run).  It is
per-layer, not end to end: the chip's host stands still now and then (about
110 ms every few seconds, now and then a second or more), for the whole
machine, and a 30 s window's top 1% is 0.3 s of requests."""
import numpy as np


def read(run):
    lat = run.latencies_ms()
    return float(np.percentile(lat, 99)) if lat.size else None
