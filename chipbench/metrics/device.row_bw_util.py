"""device.row_bw_util: the algorithm's bytes (``chipbench/work.py``) of the
requests answered inside the traced interval, over the chip's HBM bandwidth
times the interval: the whole system's share of the bound, whatever kernel
or engine does the work."""


def read(run):
    if run.trace is None:
        return None
    done = run.traced()
    if not done:
        return None
    return 100.0 * run.work_bytes(done) / (run.peak("hbm_bytes_per_s") * run.trace_window_s)
