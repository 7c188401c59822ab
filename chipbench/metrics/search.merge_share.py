"""search.merge_share: device time of the ops under the search program's
``hop.merge`` scope (the pool merge: ``pool_merge`` and the encode and decode
around it), as a share of device busy time.  None where the trace's ops carry
no program scope."""
from chipbench import program_trace


def read(run):
    pt = program_trace.of_run(run)
    if pt is None or set(pt["scope_s"]) <= {program_trace.NO_SCOPE}:
        return None
    return 100.0 * pt["scope_s"].get("hop.merge", 0.0) / run.trace["busy_s"]
