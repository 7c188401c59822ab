"""frontend.queue_wait_mean_ms: mean wait from submit to dispatch start over
every request the run served, from the frontend telemetry's lifetime
counters (``queue_wait_s_total`` / ``served``).  None where the telemetry
keeps no such total."""


def read(run):
    t = run.telemetry
    with t._obs_lock:
        total, served = getattr(t, "queue_wait_s_total", None), t.served
    return 1e3 * total / served if total is not None and served else None
