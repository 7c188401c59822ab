"""Run one benchmark cell on the chip it is started on.

    python3 chipbench/run.py --workload sift128.bulk --seed 7 --seconds 20 --trace 0

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics read from a profiler trace of part of the window.  The
last line of standard output is the result as one JSON object; the numbers
the correctness check compared, each beside its limit, are the last lines
of standard error.  Without a TPU, or with fewer chips than the cell asks
for, it exits 2 and prints no result.  ``--rehearse SCALE`` runs on any
backend at SCALE times the configuration's vector counts (a CPU rehearsal;
the result names the device it ran on).
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=float, default=None, metavar="SCALE")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from chipbench import harness

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    loaded = harness.load_cell(bench, args.workload)
    try:
        if args.rehearse is None:
            harness.require_chips(jax.devices(), int(loaded["cell"]["chips"]))
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(f"# compile cache: {harness.enable_compile_cache(jax)}", flush=True)
    result = harness.measure(
        loaded, args.seed, args.seconds, bool(args.trace), T_PROCESS,
        require_tpu=args.rehearse is None,
        scale=1.0 if args.rehearse is None else args.rehearse)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
