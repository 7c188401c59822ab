"""Distributed serving: sharded search must merge to (near-)single-device
results; straggler hop-budget degrades gracefully.  Runs in a subprocess so
the 8 host devices don't leak into other tests."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import json
import numpy as np, jax
from repro.core.sharded_index import shard_dataset, ShardedAnnIndex
from repro.core.index import AnnIndex
from repro.core.spec import SearchSpec
from repro.data.vectors import make_dataset, exact_ground_truth, recall_at_k
from repro.launch.mesh import make_local_mesh

ds = make_dataset(n_base=3000, n_query=40, dim=48, n_clusters=24, seed=0)
gt = exact_ground_truth(ds, k=10)
arrays = shard_dataset(ds.base, n_shards=8, graph="hnsw", m=12, efc=64)
mesh = make_local_mesh(8, "shards")
out = {}

spec = SearchSpec(k=10, efs=48, router="crouting", max_hops=2048)
idx = ShardedAnnIndex(arrays, mesh, spec=spec)
ids, d, stats = idx.search(ds.queries)
out["recall_sharded"] = recall_at_k(ids, gt, 10)
out["calls"] = int(stats.dist_calls)
# the typed stats carry the registry router name + aggregate counters
out["stats_ok"] = bool(stats.router == "crouting"
                       and int(stats.est_calls) > 0
                       and int(stats.iters) > 0)

# global ids must be valid and deduplicated per query
ok = True
for row in ids:
    real = [i for i in row if i >= 0]
    ok &= len(set(real)) == len(real) and all(0 <= i < 3000 for i in real)
out["ids_valid"] = bool(ok)

# single- index reference (same total data, one graph)
ref = AnnIndex.build(ds.base, graph="hnsw", m=12, efc=64)
rids, _, _ = ref.search(ds.queries, spec=SearchSpec(k=10, efs=48,
                                                    router="crouting"))
out["recall_single"] = recall_at_k(rids, gt, 10)

# straggler mitigation: tiny hop budget must still return (degraded) results
idx2 = ShardedAnnIndex(arrays, mesh, spec=spec.replace(max_hops=8))
ids2, _, stats2 = idx2.search(ds.queries)
out["recall_budget"] = recall_at_k(ids2, gt, 10)
out["calls_budget"] = int(stats2.dist_calls)

# a plugin router's extra counters must survive the shard psum (review
# finding: the serve step used to drop SearchResult.extra silently)
import dataclasses
import jax.numpy as jnp
from repro.core.routers import EdgeAngleRouter, register_router

@dataclasses.dataclass(frozen=True)
class CountingRouter(EdgeAngleRouter):
    def estimate_rank(self, ctx):
        est_rank, _ = super().estimate_rank(ctx)
        return est_rank, {"my_tests": jnp.sum(ctx.try_prune, axis=1,
                                              dtype=jnp.int32)}

register_router(CountingRouter(name="counting", prunes=True,
                               extra_counters=("my_tests",)))
idx3 = ShardedAnnIndex(arrays, mesh, spec=spec.replace(router="counting"))
_, _, stats3 = idx3.search(ds.queries[:8])
out["extra_counter"] = int(stats3.extra["my_tests"])

# --- ISSUE 5 spec parity: per-call spec routes through resolve_search_spec
# and request-only fields (k / cos_theta) reuse the jitted serve step
step0 = idx._step(idx.spec)
n_cache0 = step0._cache_size()
ids_k, d_k, _ = idx.search(ds.queries, spec=spec.replace(k=5, cos_theta=0.6))
out["k_override_shape_ok"] = bool(ids_k.shape == (40, 5))
out["k_override_no_rejit"] = bool(
    idx._step(idx.spec) is step0 and step0._cache_size() == n_cache0
    and len(idx._steps) == 1)
# legacy kwargs and the pre-parity positional scalar are retired: both
# spellings must raise TypeError now (ISSUE 6 shim removal)
def _raises_type_error(fn):
    try:
        fn()
    except TypeError:
        return True
    return False

out["legacy_kwarg_raises"] = _raises_type_error(
    lambda: idx.search(ds.queries, cos_theta=0.6, k=5))
out["positional_scalar_raises"] = _raises_type_error(
    lambda: idx.search(ds.queries, 0.6))
out["ctor_kwarg_raises"] = _raises_type_error(
    lambda: ShardedAnnIndex(arrays, mesh, k=5))

# --- ISSUE 5 valid mask: padded lanes contribute ZERO to the shard-reduced
# counter totals (the serving frontend's bucket-padding contract)
qpad = np.concatenate([ds.queries[:10], np.repeat(ds.queries[:1], 6, 0)])
vmask = np.arange(16) < 10
ids_p, d_p, st_pad = idx.search(qpad, valid=vmask)
_, _, st_ref = idx.search(ds.queries[:10])
out["padded_counters_zero"] = bool(
    int(st_pad.dist_calls) == int(st_ref.dist_calls)
    and int(st_pad.hops) == int(st_ref.hops)
    and int(st_pad.est_calls) == int(st_ref.est_calls))

# --- ISSUE 5 frontend over the sharded backend: ragged trace, results
# bit-identical to direct search, zero compiles on the request path
from repro.serve import ServeFrontend
fe = ServeFrontend(idx, spec, buckets=(1, 8, 16, 40))
ok = True
for n in (1, 3, 8, 16, 40):
    fut = fe.submit(ds.queries[:n]); fe.flush()
    f_ids, f_d, f_st = fut.result()
    r_ids, r_d, r_st = idx.search(ds.queries[:n])
    ok &= (f_ids == r_ids).all() and np.allclose(f_d, r_d)
    ok &= int(f_st.dist_calls) == int(r_st.dist_calls)
out["frontend_matches_direct"] = bool(ok)
out["frontend_recompiles"] = int(fe.telemetry.recompiles_after_warmup)
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_sharded_index_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out["ids_valid"]
    assert out["stats_ok"]
    # sharded top-k merge over 8 sub-indexes should beat one global graph at
    # equal efs (it runs efs per shard) — require >= single-graph - 2%
    assert out["recall_sharded"] >= out["recall_single"] - 0.02, out
    assert out["recall_sharded"] > 0.9, out
    # bounded-hop straggler mode: returns, degraded but nonzero
    assert out["calls_budget"] < out["calls"], out
    assert out["recall_budget"] > 0.2, out
    # plugin-router extra counters round-trip through the shard reduction
    assert out["extra_counter"] > 0, out
    # ISSUE 5 spec parity: request-only overrides reuse the serve step, the
    # legacy shims warn and agree, padded lanes stay out of the counters,
    # and the serving frontend is bit-identical to direct sharded search
    assert out["k_override_shape_ok"], out
    assert out["k_override_no_rejit"], out
    assert out["legacy_kwarg_raises"], out
    assert out["positional_scalar_raises"], out
    assert out["ctor_kwarg_raises"], out
    assert out["padded_counters_zero"], out
    assert out["frontend_matches_direct"], out
    assert out["frontend_recompiles"] == 0, out


PALLAS_SCRIPT = r"""
import json
import numpy as np
from repro.core.sharded_index import shard_dataset, ShardedAnnIndex
from repro.core.spec import SearchSpec
from repro.data.vectors import make_dataset
from repro.launch.mesh import make_local_mesh

mesh = make_local_mesh(4, "shards")
ds = make_dataset(n_base=800, n_query=16, dim=48, n_clusters=8, seed=0)
arrays = shard_dataset(ds.base, n_shards=4, graph="hnsw", m=8, efc=32)
out = {}
for est in ("exact", "sq8"):
    res = {}
    for eng in ("jnp", "pallas"):
        spec = SearchSpec(k=10, efs=24, router="crouting", engine=eng,
                          estimate=est, max_hops=512)
        idx = ShardedAnnIndex(arrays, mesh, spec=spec)
        ids, d, st = idx.search(ds.queries)
        res[eng] = (ids.tolist(), int(st.dist_calls), int(st.sq8_calls))
    out[est] = res
out["placed"] = {f: list(a.shape) for f, a in idx._placed.items()}
# d == 128: the row table is the vectors table, placed once
ds128 = make_dataset(n_base=400, n_query=4, dim=128, n_clusters=4, seed=1)
a128 = shard_dataset(ds128.base, n_shards=4, graph="hnsw", m=8, efc=32)
i128 = ShardedAnnIndex(a128, mesh, spec=SearchSpec(k=10, efs=16,
                                                   engine="pallas"))
out["aliased"] = i128._placed["vectors_rows"] is i128._placed["vectors"]
out["jnp_tables"] = sorted(ShardedAnnIndex(a128, mesh)._placed)
print("RESULT " + json.dumps(out))
"""


def test_sharded_pallas_engine_reads_placed_kernel_tables():
    """The sharded serve step feeds the Pallas engines the kernel layouts
    converted once on the host and placed with the shards (d = 48 is padded
    to a 128-lane row table, SQ8 codes packed into int32 words), and agrees
    with the jnp engine on ids and counters."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", PALLAS_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    for est in ("exact", "sq8"):
        assert out[est]["jnp"] == out[est]["pallas"], est
    assert out["sq8"]["pallas"][2] > 0
    ns1 = 200 + 1                       # 800 rows over 4 shards + pad row
    assert out["placed"]["vectors_rows"] == [4, ns1, 128]
    assert out["placed"]["sq8_words"] == [4, ns1, 128]
    assert out["aliased"]
    assert "vectors_rows" not in out["jnp_tables"]
