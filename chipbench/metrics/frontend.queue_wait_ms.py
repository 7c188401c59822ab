"""frontend.queue_wait_ms: mean wait from submit to dispatch start, from the
frontend telemetry's queue-wait samples.  Those sit in a sliding window of
the last 4,096 requests (``serve/telemetry.py`` WINDOW), so at high rates
this covers the window's end, not all of it."""
import numpy as np


def read(run):
    with run.telemetry._obs_lock:
        wait = list(run.telemetry.queue_wait_s)
    return float(np.mean(wait)) * 1e3 if wait else None
