"""What tracing costs the serve path.

    python3 chipbench/trace_cost.py --spans 200000
    python3 chipbench/trace_cost.py --workload sift128.bulk --seed 7 --seconds 30

The first form times the full set of host spans that one dispatch opens
(``core/spans.py``: ``frontend.wait``, then ``frontend.dispatch`` with its
five children) with no profiler session active, and prints microseconds per
dispatch.  The second runs the cell twice in one process, with the profiler
off and then on (``TRACE_S`` seconds of the window traced, as ``--trace 1``
does), and prints each run's end-to-end metrics over the whole window, so
the traced run's cost is diluted by the share of the window it traced.
"""
import argparse
import io
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CHILDREN = ("frontend.batch", "index.prepare", "index.launch", "index.fetch",
            "frontend.resolve")


def span_cost_us(n: int) -> float:
    """Microseconds per dispatch's span set, no profiler session active."""
    from repro.core import spans

    t = time.perf_counter()
    for i in range(n):
        with spans.span("frontend.wait"):
            pass
        with spans.dispatch(i, rows=84, bucket=128):
            for name in CHILDREN:
                with spans.span(name):
                    pass
    return (time.perf_counter() - t) / n * 1e6


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--spans", type=int, default=0)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    if args.spans:
        print(json.dumps({"spans_us_per_dispatch": span_cost_us(args.spans)}), flush=True)
    if not args.workload:
        return 0
    import jax

    from chipbench import harness

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    loaded = harness.load_cell(bench, args.workload)
    # the traced run reads the end-to-end metrics too
    loaded["per_layer"] = loaded["end_to_end"] + loaded["per_layer"]
    harness.enable_compile_cache(jax)
    for trace in (False, True):
        res = harness.measure(loaded, args.seed, args.seconds, trace, time.perf_counter(),
                              out=io.StringIO(), err=io.StringIO())
        print(json.dumps({"workload": args.workload, "seed": args.seed, "trace": int(trace),
                          "correct": res["correct"],
                          "metrics": {k: v["value"] for k, v in res["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
