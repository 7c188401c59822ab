"""Find the knee of an open-loop cell: the highest offered rate it sustains.

    python3 chipbench/sweep.py --workload sift128.interactive --seed 5 \\
        --seconds 10 --rates 200,400,800,1200

One process sets the cell up once (data, build, frontend warmed at the
mix's buckets), then offers each rate for ``--seconds`` with the mix's own
arrival generator and waits for every request.  Per rate it prints the
offered and completed rows per second, p50/p99 latency from the scheduled
send, and the requests in flight (sent and not yet answered: the most at
any time in the window, and at its close).  The knee is the highest rate at
which completed keeps up with offered (98% or more) and no more than two top
buckets' worth of rows are in flight at the close; the sweep stops after the
first rate that falls below 90%.  Runs on a TPU only (``--rehearse SCALE`` for a CPU
rehearsal).
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def in_flight(requests, t_close):
    """(the most requests sent and not yet answered at any time in the window,
    and how many at its close)."""
    events = sorted([(r.t_sent, 1) for r in requests]
                    + [(r.t_done, -1) for r in requests if r.t_done is not None],
                    key=lambda e: (e[0], e[1]))
    depth, most, at_close = 0, 0, None
    for t, step in events:
        if t > t_close and at_close is None:
            at_close = depth
        depth += step
        if t <= t_close:
            most = max(most, depth)
    return most, depth if at_close is None else at_close


def step(fe, queries, mix, rate, seconds, seed):
    import numpy as np

    from chipbench import drive

    window = drive.Window(fe.submit, queries, int(mix["rows_per_request"]), seed,
                          lambda name: contextlib.nullcontext())
    t0 = time.perf_counter()
    drive.run_open(window, rate, seconds, seed, t0)
    unanswered = window.wait_all(t0 + seconds + drive.LATE_S)
    done = [r for r in window.requests if r.error is None and r.t_done is not None]
    rows_in = sum(len(r.rows) for r in done if r.t_done <= t0 + seconds)
    lat = np.asarray([(r.t_done - r.t_due) * 1e3 for r in done])
    most, at_close = in_flight(window.requests, t0 + seconds)
    return {"rate_per_s": rate,
            "offered_rows_per_s": sum(len(r.rows) for r in window.requests) / seconds,
            "completed_rows_per_s": rows_in / seconds,
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "in_flight_max": most, "in_flight_at_close": at_close,
            "generator_late_p99_ms": float(np.percentile(
                [(r.t_sent - r.t_due) * 1e3 for r in window.requests], 99)),
            "failed": sum(1 for r in window.requests if r.error is not None),
            "unanswered": unanswered}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--rehearse", type=float, default=None, metavar="SCALE")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from chipbench import harness

    loaded = harness.load_cell(json.loads((ROOT / "BENCHMARK.json").read_text()),
                               args.workload)
    config, mix = loaded["config"], loaded["mix"]
    if mix["loop"] != "open":
        print("sweep: the workload's mix is not an open loop", file=sys.stderr)
        return 2
    try:
        if args.rehearse is None:
            harness.require_chips(jax.devices(), int(loaded["cell"]["chips"]))
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache(jax)
    base, queries = harness.cell_data(config, 1.0 if args.rehearse is None else args.rehearse)
    _, fe, _ = harness.build_frontend(config, mix, base)
    fe.start()
    print(json.dumps({"workload": args.workload, "setup_s": time.perf_counter() - T_PROCESS}),
          flush=True)
    rows, knee = [], None
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            r = step(fe, queries, mix, rate, args.seconds, args.seed)
            rows.append(r)
            print(json.dumps(r), flush=True)
            keeps_up = r["completed_rows_per_s"] >= 0.98 * r["offered_rows_per_s"]
            rows_in_flight = r["in_flight_at_close"] * mix["rows_per_request"]
            if keeps_up and rows_in_flight <= 2 * mix["buckets"][-1]:
                knee = rate
            if r["completed_rows_per_s"] < 0.9 * r["offered_rows_per_s"]:
                break
    finally:
        fe.stop()
    print("| offered rows/s | completed rows/s | p50 ms | p99 ms | in flight, most | "
          "in flight at close |")
    print("|---|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['offered_rows_per_s']} | {r['completed_rows_per_s']} | {r['p50_ms']} | "
              f"{r['p99_ms']} | {r['in_flight_max']} | {r['in_flight_at_close']} |")
    print(json.dumps({"knee_rate_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
