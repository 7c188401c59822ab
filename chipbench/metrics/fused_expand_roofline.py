"""fused_expand_roofline: the algorithm's bytes (``chipbench/work.py``) of the
requests answered inside the traced interval, over the chip's HBM bandwidth,
as a share of the summed device time of ``fused_expand``'s events in the
trace.  Bandwidth bounds this kernel: it does no matrix work."""


def read(run):
    if run.trace is None or not run.trace["kernel_s"].get("fused_expand"):
        return None
    done = run.traced()
    if not done:
        return None
    floor_s = run.work_bytes(done) / run.peak("hbm_bytes_per_s")
    return 100.0 * floor_s / run.trace["kernel_s"]["fused_expand"]
