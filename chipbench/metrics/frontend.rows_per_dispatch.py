"""frontend.rows_per_dispatch: real query rows per engine dispatch, from the
telemetry's cumulative per-bucket counters (warm-up probes excluded)."""


def read(run):
    b = run.telemetry.buckets.values()
    dispatches = sum(x.dispatches for x in b)
    return sum(x.rows_valid for x in b) / dispatches if dispatches else None
