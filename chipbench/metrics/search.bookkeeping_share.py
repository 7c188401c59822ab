"""search.bookkeeping_share: device time of the ops under the search
program's ``hop.select`` (beam selection, the neighbor tile, the dedup sort)
and ``hop.scatter`` (the visited-status scatter) scopes, as a share of device
busy time: the per-hop cost that grows with the index, not with the work.
None where the trace's ops carry no program scope."""
from chipbench import program_trace


def read(run):
    pt = program_trace.of_run(run)
    if pt is None or set(pt["scope_s"]) <= {program_trace.NO_SCOPE}:
        return None
    s = pt["scope_s"]
    return 100.0 * (s.get("hop.select", 0.0) + s.get("hop.scatter", 0.0)) / run.trace["busy_s"]
