"""engine.fetch_idle_share (read as ``engine.fetch_idle_share.bulk`` and
``engine.fetch_idle_share.interactive``): the share of the traced interval
in which the device sat idle, in gaps of 10 us or more, while the program's
innermost open span was ``index.fetch``: the copy of one dispatch's results
to the host (``AnnIndex.search``).  None where the trace holds no such span."""
from chipbench import program_trace


def read(run):
    pt = program_trace.of_run(run)
    if pt is None or "index.fetch" not in pt["spans"]:
        return None
    return 100.0 * pt["program_idle_s"].get("index.fetch", 0.0) / run.trace_window_s
