"""engine.dispatch_ms (read as ``engine.dispatch_ms.bulk`` and
``engine.dispatch_ms.interactive``): mean host-clock time of one engine
dispatch (``search_padded``, which ends in the device-to-host copy), from
the telemetry's per-bucket samples (the last 4,096 per bucket)."""
import numpy as np


def read(run):
    with run.telemetry._obs_lock:
        lat = [s for b in run.telemetry.buckets.values() for s in b.lat_s]
    return float(np.mean(lat)) * 1e3 if lat else None
