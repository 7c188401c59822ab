# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: ``PYTHONPATH=src python -m benchmarks.run [--only X]``.

Covers every table/figure of the paper (DESIGN.md §8) plus kernel micros and
the dry-run roofline table.  Scale via BENCH_N / BENCH_Q env vars.
"""
import argparse
import sys
import time
import traceback

from benchmarks import bench_autotune as A
from benchmarks import bench_chaos as C_
from benchmarks import bench_engine as E
from benchmarks import bench_paper as P
from benchmarks import bench_kernels as K
from benchmarks import bench_mutate as M
from benchmarks import bench_recovery as D
from benchmarks import bench_roofline as R
from benchmarks import bench_serve as S
from repro.launch.compile_cache import enable_compile_cache

BENCHES = [
    ("engine_beam_sweep", E.engine_beam_sweep),
    ("engine_estimate_sweep", E.engine_estimate_sweep),
    ("engine_router_sweep", E.engine_router_sweep),
    ("engine_pallas_parity", E.engine_pallas_parity),
    ("serve_single", S.serve_single),
    ("serve_sharded", S.serve_sharded),
    ("autotune_two_phase", A.bench_autotune),
    ("mutate_streaming", M.mutate_streaming),
    ("chaos_serving", C_.chaos_serving),
    ("recovery_ingest", D.recovery_ingest),
    ("recovery_replay", D.recovery_replay),
    ("recovery_chaos", D.recovery_chaos),
    ("fig2_time_breakdown", P.fig2_time_breakdown),
    ("fig6_8_angles", P.fig6_8_angles),
    ("fig10_recall_qps", P.fig10_recall_qps),
    ("fig11_recall_speedup", P.fig11_recall_speedup),
    ("table3_efs_ablation", P.table3_efs_ablation),
    ("table4_5_error_analysis", P.table4_5_error_analysis),
    ("fig13_threshold", P.fig13_threshold),
    ("fig14_15_neighbors_k", P.fig14_15_neighbors_k),
    ("fig16_metrics", P.fig16_metrics),
    ("fig17_scalability", P.fig17_scalability),
    ("table6_7_construction", P.table6_7_construction),
    ("fig18_strategies", P.fig18_strategies),
    ("kernels_micro", K.kernels_micro),
    ("roofline_table", R.roofline_table),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="substring filter")
    args = ap.parse_args()
    print(f"# compile cache: {enable_compile_cache()}")
    failed, ran = [], []
    for name, fn in BENCHES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        print(f"# === {name} ===", flush=True)
        try:
            fn()
            ran.append(name)
        except Exception as e:   # noqa: BLE001 — harness: one bench must not kill the run
            failed.append(name)
            print(f"{name},nan,{{\"error\": \"{e!r}\"}}")
            traceback.print_exc()
        print(f"#     ({time.time()-t0:.1f}s)", flush=True)
    # stamp the persisted perf trajectories (benchmarks/common.py)
    from benchmarks import common as C
    for prefix, file in (("engine", "BENCH_engine.json"),
                         ("serve", "BENCH_serve.json"),
                         ("autotune", "BENCH_autotune.json"),
                         ("mutate", "BENCH_mutate.json"),
                         ("chaos", "BENCH_chaos.json"),
                         ("recovery", "BENCH_recovery.json")):
        if any(n.startswith(prefix) for n in ran):
            path = C.persist_bench("_meta", {
                "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                # dataset sizes are per-bench (each section records n_base)
                "bench_q": C.N_QUERY, "smoke": C.SMOKE,
                "benches": [n for n in ran if n.startswith(prefix)],
            }, file=file)
            print(f"# {prefix} results persisted to {path}")
    if failed:
        print(f"# FAILED: {failed}")
        sys.exit(1)
    print("# all benchmarks ok")


if __name__ == '__main__':
    main()
