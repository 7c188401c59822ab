"""device.idle_share: the share of the traced interval in which no operation
ran on the device (1 - union of device-op intervals / interval)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace_window_s)
