#!/usr/bin/env python3
"""Chip smoke test: the serving path, end to end, on a TPU.

    python chip_smoke.py                # one chip: phases A and B
    python chip_smoke.py --four-chips   # one 2x2 host: the sharded layout

Everything is generated from ``--seed``: a clustered base set, held-out
queries and their exact brute-force top-10, an HNSW graph (m=16, efc=128)
built on the host, and a ragged trace of 64 requests with sizes drawn
log-uniform over [1, 128] (the 1/8/32/128 bucket ladder).

* Phase A — the shape of ann-benchmarks' sift-128-euclidean: 128-d fp32,
  L2, k=10, 1,000 queries, efs 256.  n_base = 10^5, cut from the
  published 10^6 because the host-side NumPy HNSW build is the time bound
  of a run.
* Phase B — gist-960-euclidean's width: 960-d, n_base = 10^4, 200 queries,
  efs 100; it drives the kernels at a width that is not a multiple of 128.

Each phase serves the trace through ``ServeFrontend`` with its background
worker, once per configuration: engine jnp|pallas x router none|crouting
(estimate exact), and pallas with estimate sq8.  It fails (exit 1) unless:
every future resolves; no bucket recompiles after warmup; no dispatch
failure or worker error; the Pallas engine's program holds
``tpu_custom_call`` (the kernels are compiled, not interpreted); recall@10
>= the phase's floor for every configuration; pallas matches jnp on the
ids of >= 99% of queries with dist_calls totals within 0.5%; crouting makes
fewer distance calls than none at recall within 0.01; sq8 makes stage-1
calls.  ``--four-chips`` instead serves phase A's data as a 4-shard
``ShardedAnnIndex`` (router crouting, engines jnp and pallas) and checks
recall, global unique ids, pallas against jnp ids, counters summed across
shards, and shards placed on 4 distinct devices.

Each phase prints a JSON digest (set-up seconds, counts, recall, device
bytes); no rate is a result here.  The last line is the device record.
Without a TPU it exits 2 and prints nothing on stdout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
BUCKETS = (1, 8, 32, 128)
K = 10
N_REQUESTS = 64
PHASES = {
    # name: (n_base, dim, n_query, recall floor, efs)
    # efs 256 in A: at 10^5, efs 100 leaves crouting at recall 0.944 vs
    # none's 0.989, and 160 at 0.980 vs 0.998; at 256, 0.991 vs 0.999
    "A": (100_000, 128, 1_000, 0.95, 256),
    "B": (10_000, 960, 200, 0.95, 100),
}
REDUCED = {
    "A": "n_base 10^6 -> 10^5: the host NumPy HNSW build (m=16, efc=128) "
         "takes 6-7 minutes at 10^5 and over an hour at 10^6, past a "
         "20-minute run; 10^6 waits for a device-side build",
    "B": "n_base 10^6 -> 10^4 at 960-d: same host build bound",
}
CONFIGS = (
    ("jnp/none", dict(engine="jnp", router="none")),
    ("jnp/crouting", dict(engine="jnp", router="crouting")),
    ("pallas/none", dict(engine="pallas", router="none")),
    ("pallas/crouting", dict(engine="pallas", router="crouting")),
    ("pallas/crouting/sq8", dict(engine="pallas", router="crouting",
                                 estimate="sq8")),
)


class CheckFailed(RuntimeError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise CheckFailed(what)


class CompileClock:
    """Seconds spent in XLA backend compiles (jax.monitoring events)."""

    def __init__(self):
        import jax

        self.seconds = 0.0

        def on_event(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(on_event)


def make_phase_data(n_base, dim, n_query, seed):
    from repro.data.vectors import exact_ground_truth, make_dataset
    from repro.launch.serve import ragged_sizes

    ds = make_dataset(n_base=n_base, n_query=n_query, dim=dim, seed=seed)
    gt = exact_ground_truth(ds, k=K)
    sizes = ragged_sizes(N_REQUESTS, BUCKETS[-1], seed=seed + 1)
    rows = np.arange(int(sizes.sum())) % n_query     # queries cycle
    return ds, gt, sizes, rows


def serve_trace(index, spec, queries, sizes):
    """Replay the trace through the frontend's background worker; returns
    (ids [rows, K], per-request results, telemetry summary, warm seconds)."""
    from repro.serve import ServeFrontend

    t0 = time.perf_counter()
    fe = ServeFrontend(index, spec, buckets=BUCKETS,
                       max_pending_rows=int(sizes.sum()))
    warm_s = time.perf_counter() - t0
    offs = np.concatenate([[0], np.cumsum(sizes)])
    with fe:
        futs = [fe.submit(queries[offs[i]:offs[i + 1]])
                for i in range(len(sizes))]
        done = [f.result(timeout=900) for f in futs]
    summ = fe.telemetry.summary()
    check(summ["recompiles_after_warmup"] == 0,
          f"{spec}: a batch shape recompiled after warmup")
    check(summ["dispatch_failures"] == 0 and summ["worker_errors"] == 0
          and summ["requests"]["failed"] == 0,
          f"{spec}: dispatch failures or worker errors: {summ}")
    check(summ["requests"]["served"] == len(sizes), f"{spec}: unserved")
    ids = np.concatenate([r[0] for r in done])
    return ids, done, summ, warm_s


def engine_program_text(index, spec, d):
    """StableHLO of the session's engine at the top bucket."""
    import jax
    import jax.numpy as jnp
    from repro.core.search import build_search_fn

    g = index.graph
    spec = spec.replace(metric=g.metric,
                        use_hierarchy=g.upper_neighbors is not None)
    _, fn = build_search_fn(g, spec)
    q = jax.ShapeDtypeStruct((BUCKETS[-1], d), jnp.float32)
    ct = jax.ShapeDtypeStruct((), jnp.float32)
    return fn._jitted.lower(fn._tables, q, ct).as_text()


def device_bytes(tables) -> int:
    import jax

    return int(sum(a.nbytes for a in jax.tree.leaves(
        {k: v for k, v in tables.items() if k != "n"})))


def run_phase(name, n_base, dim, n_query, floor, efs, seed, clock,
              rehearse):
    from repro.core.index import AnnIndex
    from repro.core.search import build_search_fn
    from repro.core.spec import SearchSpec
    from repro.data.vectors import recall_at_k

    print(f"# phase {name}: {REDUCED[name]}", flush=True)
    t0 = time.perf_counter()
    ds, gt, sizes, rows = make_phase_data(n_base, dim, n_query, seed)
    data_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    index = AnnIndex.build(ds.base, graph="hnsw", m=16, efc=128, seed=seed)
    build_s = time.perf_counter() - t0
    queries, gt_rows = ds.queries[rows], gt[rows]
    c0 = clock.seconds

    res, warm = {}, {}
    for label, kw in CONFIGS:
        spec = SearchSpec(k=K, efs=efs, max_hops=2048, **kw)
        ids, done, summ, warm[label] = serve_trace(index, spec, queries,
                                                   sizes)
        stats = [r[2] for r in done]
        cat = {f: np.concatenate([getattr(s, f) for s in stats])
               for f in ("dist_calls", "hops", "sq8_calls")}
        res[label] = {
            "ids": ids, "dist_calls": cat["dist_calls"],
            "recall": recall_at_k(ids, gt_rows, K),
            "mean_dist_calls": float(cat["dist_calls"].mean()),
            "mean_hops": float(cat["hops"].mean()),
            "mean_sq8_calls": float(cat["sq8_calls"].mean()),
            "max_iters": max(int(s.iters) for s in stats),
            "dispatches": sum(b["dispatches"]
                              for b in summ["buckets"].values()),
        }
        if kw["engine"] == "pallas" and not rehearse:
            text = engine_program_text(index, spec, dim)
            check("tpu_custom_call" in text,
                  f"{label}: no tpu_custom_call, kernels were interpreted")

    for label, r in res.items():
        check(r["recall"] >= floor,
              f"phase {name} {label}: recall@10 {r['recall']} < {floor}")
    agree = {}
    for router in ("none", "crouting"):
        j, p = res[f"jnp/{router}"], res[f"pallas/{router}"]
        same = float(np.mean(np.all(j["ids"] == p["ids"], axis=1)))
        tj, tp = int(j["dist_calls"].sum()), int(p["dist_calls"].sum())
        agree[router] = {"ids_equal_frac": same, "dist_calls_jnp": tj,
                         "dist_calls_pallas": tp,
                         "exact": bool(same == 1.0 and tj == tp)}
        check(same >= 0.99, f"phase {name} {router}: pallas ids agree "
                            f"with jnp on {same:.4f} < 0.99 of queries")
        check(abs(tp - tj) <= 0.005 * tj,
              f"phase {name} {router}: dist_calls pallas {tp} vs jnp {tj}")
    for eng in ("jnp", "pallas"):
        none, cr = res[f"{eng}/none"], res[f"{eng}/crouting"]
        check(cr["mean_dist_calls"] < none["mean_dist_calls"],
              f"phase {name} {eng}: crouting saves no distance calls")
        check(cr["recall"] >= none["recall"] - 0.01,
              f"phase {name} {eng}: crouting recall {cr['recall']} vs "
              f"none {none['recall']}")
    check(res["pallas/crouting/sq8"]["mean_sq8_calls"] > 0,
          f"phase {name}: sq8 made no stage-1 calls")

    import jax

    tables, _ = build_search_fn(index.graph, SearchSpec(
        k=K, efs=efs, engine="pallas", estimate="sq8", max_hops=2048,
        use_hierarchy=True))
    mem = jax.devices()[0].memory_stats() or {}
    return {
        "phase": name, "n_base": n_base, "d": dim, "efs": efs,
        "reduced": REDUCED[name], "n_query": n_query, "requests": len(sizes),
        "rows_served": int(sizes.sum()),
        "device_table_bytes": device_bytes(tables),
        "setup_seconds": {"data_and_ground_truth": data_s, "build": build_s,
                          "frontend_warm": warm,
                          "xla_compile": clock.seconds - c0},
        "configs": {k: {f: v[f] for f in ("recall", "mean_dist_calls",
                                          "mean_hops", "mean_sq8_calls",
                                          "max_iters", "dispatches")}
                    for k, v in res.items()},
        "pallas_vs_jnp": agree,
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
    }


def run_four_chips(seed, clock, scale):
    import jax
    import jax.numpy as jnp
    from repro.core.search import _search_batch, ensure_kernel_tables
    from repro.core.sharded_index import ShardedAnnIndex, shard_dataset
    from repro.core.spec import SearchSpec
    from repro.data.vectors import recall_at_k
    from repro.launch.mesh import make_local_mesh

    n_base, dim, n_query, floor, efs = PHASES["A"]
    n_base, n_query = int(n_base * scale), max(int(n_query * scale), 8)
    check(len(jax.devices()) >= 4, f"--four-chips needs 4 devices, "
                                   f"JAX has {len(jax.devices())}")
    t0 = time.perf_counter()
    ds, gt, sizes, rows = make_phase_data(n_base, dim, n_query, seed)
    data_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    arrays = shard_dataset(ds.base, n_shards=4, graph="hnsw", m=16, efc=128,
                           seed=seed)
    build_s = time.perf_counter() - t0
    mesh = make_local_mesh(4, "shards")
    c0 = clock.seconds

    served, placed = {}, set()
    for engine in ("jnp", "pallas"):
        spec = SearchSpec(k=K, efs=efs, router="crouting", engine=engine,
                          max_hops=2048)
        index = ShardedAnnIndex(arrays, mesh, spec=spec)
        placed = {d for a in index._placed.values() for d in a.devices()}
        check(len(placed) == 4, f"shards span {len(placed)} devices, not 4")
        for a in index._placed.values():
            shard_devs = {s.device for s in a.addressable_shards}
            check(len(shard_devs) == 4, "a sharded table is not split 4 ways")

        ids, _, summ, warm_s = serve_trace(index, spec, ds.queries[rows],
                                           sizes)
        rec = recall_at_k(ids, gt[rows], K)
        check(rec >= floor, f"four-chip {engine} recall@10 {rec} < {floor}")
        valid = ids[ids >= 0]
        check(valid.size and valid.max() < n_base,
              f"{engine}: ids are not global")
        check(all(len(set(r[r >= 0])) == (r >= 0).sum() for r in ids),
              f"{engine}: an id repeats within a query's results")
        merged = summ["search"]
        served[engine] = {
            "ids": ids, "recall": rec, "frontend_warm": warm_s,
            "mean_per_query": {f: merged[f] / int(sizes.sum())
                               for f in ("dist_calls", "hops")},
            "max_iters": merged["iters"]}
    same = float(np.mean(np.all(served["jnp"]["ids"] == served["pallas"]["ids"],
                                axis=1)))
    check(same >= 0.99, f"four-chip pallas ids agree with jnp on {same:.4f} "
                        f"< 0.99 of queries")

    # the serve step's counters are psums over the shards: one batch, each
    # shard searched alone on one device, must add up to them
    q = ds.queries[:BUCKETS[-1]]
    _, _, tot = index.search(q, spec=spec)
    ct = jnp.asarray(arrays.cos_theta, jnp.float32)
    one = spec.replace(metric=arrays.metric, use_hierarchy=False)
    sums = {"dist_calls": 0, "hops": 0, "est_calls": 0}
    one_shard = jax.jit(lambda t, x, c: _search_batch(
        {**t, "n": arrays.ns}, x, c, one))
    for s in range(4):
        tables = ensure_kernel_tables({
            "vectors": arrays.vectors[s], "neighbors": arrays.neighbors[s],
            "edge_eu": arrays.edge_eu[s], "norms": arrays.norms[s],
            "entry": arrays.entries[s]})
        r = one_shard(tables, jnp.asarray(q), ct)
        for f in sums:
            sums[f] += int(np.sum(getattr(r, f)))
    for f, v in sums.items():
        check(int(getattr(tot, f)) == v,
              f"sharded {f} {getattr(tot, f)} != sum over shards {v}")

    return {
        "phase": "A-sharded", "n_base": n_base, "d": dim, "efs": efs,
        "shards": 4, "reduced": REDUCED["A"], "n_query": n_query,
        "requests": len(sizes), "rows_served": int(sizes.sum()),
        "devices": sorted(str(d) for d in placed),
        "device_table_bytes": int(sum(a.nbytes for a in {
            id(a): a for a in index._placed.values()}.values())),
        "setup_seconds": {"data_and_ground_truth": data_s, "build": build_s,
                          "frontend_warm": {e: s["frontend_warm"]
                                            for e, s in served.items()},
                          "xla_compile": clock.seconds - c0},
        "configs": {f"{e}/crouting": {f: s[f] for f in
                                      ("recall", "mean_per_query",
                                       "max_iters")}
                    for e, s in served.items()},
        "pallas_vs_jnp_ids_equal_frac": same,
        "one_batch_sum_over_shards": sums,
        "peak_bytes_in_use": (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use"),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve phase A as a 4-shard ShardedAnnIndex only")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="allow a non-TPU backend (CPU rehearsal; Pallas "
                         "runs interpreted)")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="multiply n_base and queries (rehearsals only)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"# compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    try:
        if args.four_chips:
            digest = run_four_chips(args.seed, clock, args.scale)
            print(json.dumps(digest), flush=True)
        else:
            for name, (n_base, dim, n_query, floor, efs) in PHASES.items():
                digest = run_phase(name, int(n_base * args.scale), dim,
                                   max(int(n_query * args.scale), 8), floor,
                                   efs, args.seed, clock,
                                   dev.platform != "tpu")
                print(json.dumps(digest), flush=True)
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
