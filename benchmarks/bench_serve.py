"""Serving-frontend benchmarks (persisted to committed BENCH_serve.json).

One ragged request trace (log-uniform sizes up to the top bucket) replayed
through ``repro.serve.ServeFrontend`` against both backends:

* ``serve_single``  — one ``AnnIndex`` in-process;
* ``serve_sharded`` — a ``ShardedAnnIndex`` over every device of this
  process, in-process (on the CPU, set
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for N shards).

Acceptance (ISSUE 5): per-bucket p50/p95/p99 latency + QPS for both
backends, and ``recompiles_after_warmup == 0`` across the ragged trace —
every batch shape a request can produce was pre-jitted by the bucket
warmup.  ``BENCH_SMOKE=1`` shrinks sizes and diverts the JSON to .cache/.
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import (SMOKE, dataset, cached_index, emit,
                               persist_bench, smoke_scale)
from repro.core.sharded_index import ShardedAnnIndex, shard_dataset
from repro.core.spec import SearchSpec
from repro.data.vectors import exact_ground_truth, recall_at_k
from repro.launch.mesh import make_local_mesh
from repro.serve import ServeFrontend

BUCKETS = (1, 4, 8) if SMOKE else (1, 8, 32, 64)
N_REQUESTS = 6 if SMOKE else 48


def ragged_trace(n_requests: int, top: int, seed: int = 7) -> np.ndarray:
    """Log-uniform request sizes in [1, top]: mostly small, a few full
    (same distribution as ``repro.launch.serve.ragged_sizes`` — size 1 MUST
    occur so the committed numbers cover the single-query rung)."""
    rng = np.random.default_rng(seed)
    sizes = np.exp(rng.uniform(0, np.log(top + 1), n_requests)).astype(int)
    return np.clip(sizes, 1, top)


def replay(fe: ServeFrontend, queries: np.ndarray, sizes: np.ndarray,
           coalesce: int = 3) -> np.ndarray:
    """Submit the trace; returns the concatenated result ids.

    The first quarter dispatches one request at a time (an idle server:
    every rung — including bucket 1 — gets solo-dispatch latency samples);
    the rest flushes every ``coalesce`` submissions (a loaded server: the
    micro-batcher coalesces)."""
    solo = max(1, len(sizes) // 4)
    offs = np.concatenate([[0], np.cumsum(sizes)])
    futs = []
    for i in range(len(sizes)):
        futs.append(fe.submit(queries[offs[i]:offs[i + 1]]))
        if i < solo or i % coalesce == coalesce - 1:
            fe.flush()
    fe.flush()
    return np.concatenate([f.result()[0] for f in futs])


def _run_trace(index, spec: SearchSpec, ds, gt) -> dict:
    sizes = ragged_trace(N_REQUESTS, BUCKETS[-1])
    need = int(sizes.sum())
    q = np.take(ds.queries, np.arange(need) % len(ds.queries), axis=0)
    gtr = np.take(gt, np.arange(need) % len(ds.queries), axis=0)
    fe = ServeFrontend(index, spec, buckets=BUCKETS,
                       max_pending_rows=4 * BUCKETS[-1])
    ids = replay(fe, q, sizes)
    summ = fe.telemetry.summary()
    summ["recall_at_k"] = round(recall_at_k(ids, gtr, spec.k), 3)
    summ["trace"] = {"requests": len(sizes), "rows": need,
                     "sizes_min_max": [int(sizes.min()), int(sizes.max())]}
    assert summ["recompiles_after_warmup"] == 0, \
        f"a batch shape escaped the bucket ladder: {summ}"
    return summ


def serve_single():
    """Single-index backend behind the bucketed frontend."""
    ds = dataset("sift-synth", n_base=smoke_scale(4000, 600))
    idx = cached_index(ds)
    gt = exact_ground_truth(ds, k=10)
    spec = SearchSpec(efs=64, k=10, router="crouting")
    summ = _run_trace(idx, spec, ds, gt)
    emit("serve_single", 0.0,
         {"qps": summ["qps"], "p50_ms": summ["latency"]["p50_ms"],
          "p99_ms": summ["latency"]["p99_ms"],
          "recall": summ["recall_at_k"],
          "recompiles": summ["recompiles_after_warmup"]})
    summ["n_base"] = int(ds.base.shape[0])
    persist_bench("serve_single", summ, file="BENCH_serve.json")
    return summ


def serve_sharded():
    """Sharded backend over every device this process has, in-process (one
    process per chip; on the CPU the caller sets
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before start)."""
    n_dev = len(jax.devices())
    if n_dev < 2:
        raise RuntimeError(
            f"serve_sharded needs >= 2 devices, this process has {n_dev}; on "
            "the CPU set XLA_FLAGS=--xla_force_host_platform_device_count=8")
    ds = dataset("sift-synth", n_base=smoke_scale(4000, 600))
    gt = exact_ground_truth(ds, k=10)
    arrays = shard_dataset(ds.base, n_shards=n_dev, graph="hnsw",
                           m=smoke_scale(16, 8), efc=smoke_scale(96, 48))
    mesh = make_local_mesh(n_dev, "shards")
    spec = SearchSpec(efs=64, k=10, router="crouting", max_hops=2048)
    summ = _run_trace(ShardedAnnIndex(arrays, mesh, spec=spec), spec, ds, gt)
    summ["n_base"] = int(ds.base.shape[0])
    summ["n_shards"] = n_dev
    emit("serve_sharded", 0.0,
         {"qps": summ["qps"], "p50_ms": summ["latency"]["p50_ms"],
          "p99_ms": summ["latency"]["p99_ms"],
          "recall": summ["recall_at_k"], "shards": summ["n_shards"],
          "recompiles": summ["recompiles_after_warmup"]})
    persist_bench("serve_sharded", summ, file="BENCH_serve.json")
    return summ
