"""Dataset-sharded distributed ANNS serving (DESIGN.md §6).

The billion-vector layout: every device owns one shard of the base vectors
plus a search graph built *over that shard*.  A query batch is replicated,
each device runs the batched CRouting engine on its shard, and the global
top-k is a cheap merge of per-shard top-k lists (k x n_shards candidates —
one small all-gather, not a vector-data collective).

Straggler mitigation: the per-shard search runs a *fixed hop budget*
(SearchSpec.max_hops), so one slow shard cannot stall the merge barrier —
quality degrades gracefully instead of latency (tested in
tests/test_sharded_index.py).

`serve_step` is the sharded search step; it is pure pjit (shard_map inside) and scales to any mesh by
flattening all mesh axes into the shard axis.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import distances as D
from repro.core.angles import sample_angle_profile
from repro.core.graph import GraphIndex
from repro.core.routers import get_router
from repro.core.search import _search_batch, ensure_kernel_tables
from repro.core.spec import SearchSpec, SearchStats, resolve_search_spec
from repro.fault import failpoints as fault
from repro.quant import sq8 as SQ


@dataclasses.dataclass
class ShardedIndexArrays:
    """Stacked per-shard device arrays (leading axis = shard)."""

    vectors: np.ndarray      # [S, ns+1, d]
    neighbors: np.ndarray    # [S, ns+1, M]
    edge_eu: np.ndarray      # [S, ns+1, M]
    norms: np.ndarray        # [S, ns+1]
    entries: np.ndarray      # [S]
    offsets: np.ndarray      # [S] global id of local id 0
    ns: int                  # local shard capacity (excl. pad row)
    metric: str
    cos_theta: float
    # SQ8 companion tables (per-shard grids; SearchSpec.estimate="sq8")
    sq8_codes: np.ndarray = None   # [S, ns+1, d] uint8
    sq8_lo: np.ndarray = None      # [S, d]
    sq8_scale: np.ndarray = None   # [S, d]
    sq8_eps: np.ndarray = None     # [S, d]
    # Pallas engine layouts (search.ensure_kernel_tables), filled on first
    # use by ShardedAnnIndex
    vectors_rows: np.ndarray = None  # [S, (ns+1)*ceil(d/128), 128] f32
    sq8_words: np.ndarray = None     # [S, (ns+1)*ceil(d/512), 128] int32


# per-shard tables of the serve step, in argument order
DATA_TABLES = ("vectors", "neighbors", "edge_eu", "norms", "entries",
               "offsets", "sq8_codes", "sq8_lo", "sq8_scale", "sq8_eps")


def step_tables(cfg: SearchSpec) -> Tuple[str, ...]:
    """Names of the per-shard tables ``cfg``'s serve step takes, in order:
    the Pallas engines add the kernel layouts they read."""
    if cfg.engine == "jnp":
        return DATA_TABLES
    sq8 = ("sq8_words",) if cfg.estimate in ("sq8", "both") else ()
    return DATA_TABLES + ("vectors_rows",) + sq8


def shard_dataset(base: np.ndarray, n_shards: int, metric: str = "l2",
                  graph: str = "hnsw", seed: int = 0,
                  profile_percentile: float = 90.0, **graph_kw
                  ) -> ShardedIndexArrays:
    """Round-robin-partition the base set; build one sub-graph per shard."""
    from repro.core.hnsw import build_hnsw
    from repro.core.nsg import build_nsg

    base = D.preprocess_vectors(np.ascontiguousarray(base, np.float32), metric)
    n, d = base.shape
    ns = (n + n_shards - 1) // n_shards
    builder = {"hnsw": build_hnsw, "nsg": build_nsg}[graph]

    graphs: List[GraphIndex] = []
    offsets = []
    cos_thetas = []
    for s in range(n_shards):
        lo, hi = s * ns, min((s + 1) * ns, n)
        sub = base[lo:hi]
        g = builder(sub, metric=metric, seed=seed + s, **graph_kw)
        graphs.append(g)
        offsets.append(lo)
        prof = sample_angle_profile(g, percentile=profile_percentile, seed=seed)
        cos_thetas.append(prof.cos_theta_star)

    m = max(g.max_degree for g in graphs)
    vecs = np.zeros((n_shards, ns + 1, d), np.float32)
    nbrs = np.full((n_shards, ns + 1, m), ns, np.int32)
    ed = np.full((n_shards, ns + 1, m), np.inf, np.float32)
    norms = np.ones((n_shards, ns + 1), np.float32)
    entries = np.zeros((n_shards,), np.int32)
    codes = np.zeros((n_shards, ns + 1, d), np.uint8)
    sq_lo = np.zeros((n_shards, d), np.float32)
    sq_scale = np.full((n_shards, d), 1e-12, np.float32)
    sq_eps = np.zeros((n_shards, d), np.float32)
    for s, g in enumerate(graphs):
        k = g.n
        vecs[s, :k] = g.vectors
        # remap pad ids (== k) to the stacked pad slot (== ns)
        nb = g.neighbors.copy()
        nb[nb >= k] = ns
        nbrs[s, :k, : g.max_degree] = nb
        ed[s, :k, : g.max_degree] = g.edge_eu_dist
        norms[s, :k] = g.norms if g.norms is not None else np.linalg.norm(g.vectors, axis=1)
        entries[s] = g.entry_point
        # per-shard SQ8 grid (fit on the shard's real rows; pad rows encode
        # the zero vector and are always masked)
        qp = SQ.sq8_train(g.vectors)
        codes[s] = SQ.sq8_encode(vecs[s], qp)
        sq_lo[s], sq_scale[s], sq_eps[s] = qp.lo, qp.scale, qp.eps
    return ShardedIndexArrays(
        vectors=vecs, neighbors=nbrs, edge_eu=ed, norms=norms, entries=entries,
        offsets=np.asarray(offsets, np.int64), ns=ns, metric=metric,
        cos_theta=float(np.median(cos_thetas)),
        sq8_codes=codes, sq8_lo=sq_lo, sq8_scale=sq_scale, sq8_eps=sq_eps)


def _backfill_sq8(arrays: ShardedIndexArrays) -> ShardedIndexArrays:
    """Fill missing SQ8 tables on a pre-existing ShardedIndexArrays."""
    S, _, d = arrays.vectors.shape
    codes = np.zeros(arrays.vectors.shape, np.uint8)
    lo = np.zeros((S, d), np.float32)
    scale = np.full((S, d), 1e-12, np.float32)
    eps = np.zeros((S, d), np.float32)
    for s in range(S):
        qp = SQ.sq8_train(arrays.vectors[s])
        codes[s] = SQ.sq8_encode(arrays.vectors[s], qp)
        lo[s], scale[s], eps[s] = qp.lo, qp.scale, qp.eps
    return dataclasses.replace(arrays, sq8_codes=codes, sq8_lo=lo,
                               sq8_scale=scale, sq8_eps=eps)


def make_serve_step(mesh: Mesh, cfg: SearchSpec, ns: int,
                    shard_axes: Optional[Tuple[str, ...]] = None):
    """Build the pjit-able distributed serve step.

    shard_axes: mesh axes flattened into the shard dimension (default: all).
    Returns (serve_step, in_shardings, out_shardings) ready for jit/lower.
    The step takes ``(*tables, queries, cos_theta, valid)``, the tables
    named by ``step_tables(cfg)`` each with a leading shard axis, where
    ``valid`` [B] bool marks the real lanes of a bucket-padded batch
    (padded lanes are born done inside the engine and contribute zero to
    every counter — see ``_search_batch``).

    The merge is ``efs``-wide: each shard contributes its whole result pool
    and the host slices to the request's ``k``, so ``k`` is request-only
    (canonical-spec contract — sweeping ``k`` or ``cos_theta`` never
    re-jits).  The third output is the aggregate counter vector
    ``[dist_calls, est_calls, rerank_calls, sq8_calls, hops, iters,
    *Router.extra_counters]`` (sums across shards and queries; ``iters`` is
    the max over shards — the straggler's iteration count) that
    ``ShardedAnnIndex.search`` wraps into a typed ``SearchStats``.
    """
    axes = tuple(shard_axes or mesh.axis_names)
    extra_names = get_router(cfg.router).extra_counters
    kk = cfg.efs              # merge width; k slices host-side
    names = step_tables(cfg)
    nt = len(names)

    def local_search(*args):
        queries, cos_theta, valid = args[nt:]
        # shard_map gives the local shard with a leading axis of size 1
        arrays = {nm: a[0] for nm, a in zip(names, args[:nt])}
        arrays["entry"] = arrays.pop("entries")
        offset = arrays.pop("offsets")
        arrays["n"] = ns
        res = _search_batch(arrays, queries, cos_theta, cfg, valid=valid)
        loc_d, loc_i = res.dists[:, :kk], res.ids[:, :kk]
        # int32 global ids (enable_x64 is off; fine below 2^31 vectors/shard set)
        glob_i = jnp.where(loc_i < ns, loc_i + offset.astype(jnp.int32), -1)
        # merge: gather per-shard pools along the shard axis, then re-top-k
        all_d = jax.lax.all_gather(loc_d, axes, tiled=False)   # [S, B, efs]
        all_i = jax.lax.all_gather(glob_i, axes, tiled=False)
        S = all_d.shape[0]
        flat_d = jnp.moveaxis(all_d, 0, 1).reshape(queries.shape[0], S * kk)
        flat_i = jnp.moveaxis(all_i, 0, 1).reshape(queries.shape[0], S * kk)
        neg, pos = jax.lax.top_k(-flat_d, kk)
        ids = jnp.take_along_axis(flat_i, pos, axis=1)
        sums = jax.lax.psum(jnp.stack(
            [jnp.sum(res.dist_calls), jnp.sum(res.est_calls),
             jnp.sum(res.rerank_calls), jnp.sum(res.sq8_calls),
             jnp.sum(res.hops)]
            + [jnp.sum(res.extra[nm]) for nm in extra_names]), axes)
        iters = jax.lax.pmax(res.iters, axes)
        stats_vec = jnp.concatenate([sums[:5], iters[None], sums[5:]])
        return -neg, ids, stats_vec

    pspec_data = P(axes)      # shard leading axis over all shard axes
    pspec_rep = P()           # queries / cos_theta / valid replicated

    serve = jax.shard_map(
        local_search, mesh=mesh,
        in_specs=(pspec_data,) * nt + (pspec_rep,) * 3,
        out_specs=(pspec_rep, pspec_rep, pspec_rep),
        check_vma=False,
    )
    in_sh = tuple(NamedSharding(mesh, s) for s in
                  (pspec_data,) * nt + (pspec_rep,) * 3)
    out_sh = tuple(NamedSharding(mesh, s) for s in (pspec_rep,) * 3)
    return serve, in_sh, out_sh


class ShardedAnnIndex:
    """Runtime wrapper: place shards on a mesh and serve batched queries.

    ``spec`` is the same ``SearchSpec`` the single-index path takes
    (``metric``/``use_hierarchy`` are overridden from the shard arrays);
    anything else — including the retired legacy kwargs and the pre-parity
    positional ``cos_theta`` scalar — raises ``TypeError``, for API parity
    with ``AnnIndex.search``.  Per-call specs that differ only
    in the request-only fields (``k``/``cos_theta``) reuse the jitted serve
    step (canonical-spec contract: ``k`` slices the ``efs``-wide merge
    host-side, ``cos_theta`` is a traced scalar); engine-shaping changes
    compile one new step, cached per canonical spec.  Routers that need
    per-graph companion tables (``Router.companion_tables``, e.g.
    ``finger``) are not yet plumbed through the stacked per-shard arrays
    and are rejected here.
    """

    DEFAULT_SEARCH = SearchSpec(k=10, efs=100, router="crouting",
                                max_hops=2048)

    def __init__(self, arrays: ShardedIndexArrays, mesh: Mesh,
                 spec: Optional[SearchSpec] = None):
        spec = resolve_search_spec(spec, self.DEFAULT_SEARCH,
                                   "ShardedAnnIndex")
        spec = dataclasses.replace(spec, metric=arrays.metric,
                                   use_hierarchy=False)
        self.arrays = arrays
        self.mesh = mesh
        self.spec = spec
        self.k = spec.k        # back-compat alias
        self.cfg = spec        # back-compat alias
        if arrays.sq8_codes is None:
            # arrays predating the SQ8 tables (direct construction, old
            # persisted shards): backfill per-shard grids from the stacked
            # vectors — the zero pad rows only widen the grid, so the
            # lower-bound contract is unaffected
            arrays = _backfill_sq8(arrays)
            self.arrays = arrays
        self._steps = {}       # canonical spec -> jitted serve step
        self._placed = {}      # table name -> device-placed stacked array
        self._step(spec)       # validate + pre-jit the construction spec

    def _step(self, spec: SearchSpec):
        """The jitted serve step for ``spec``, cached per canonical form."""
        key = spec.canonical()
        fn = self._steps.get(key)
        if fn is not None:
            return fn
        rt = get_router(spec.router)
        if rt.companion_tables:
            raise NotImplementedError(
                f"router {spec.router!r} needs companion tables "
                f"{rt.companion_tables} which the sharded arrays do not "
                "carry yet; use the single-index path")
        serve, in_sh, _ = make_serve_step(self.mesh, key, self.arrays.ns)
        fn = jax.jit(serve, in_shardings=in_sh)
        self._place(step_tables(key), in_sh[0])
        self._steps[key] = fn
        return fn

    def _place(self, names, sharding):
        """Put the named per-shard tables on the mesh, each once.  The
        Pallas layouts are converted on the host first, by the same
        ``ensure_kernel_tables`` as the single-index engine."""
        a = self.arrays
        if a.vectors_rows is None and "vectors_rows" in names:
            t = ensure_kernel_tables({"vectors": a.vectors,
                                      "sq8_codes": a.sq8_codes}, put=np.asarray)
            a = self.arrays = dataclasses.replace(
                a, vectors_rows=t["vectors_rows"], sq8_words=t["sq8_words"])
        for f in names:
            if f in self._placed:
                continue
            t = getattr(a, f)
            # at d == 128 the row table is the vectors array: one device copy
            self._placed[f] = (
                self._placed["vectors"] if f != "vectors" and t is a.vectors
                else jax.device_put(t, sharding))

    def search(self, queries: np.ndarray, spec=None, *,
               valid: Optional[np.ndarray] = None
               ) -> Tuple[np.ndarray, np.ndarray, SearchStats]:
        """Returns (ids [B,k], dists [B,k], SearchStats).

        ``spec`` overrides the construction spec for this call (same
        contract as ``AnnIndex.search``; non-``SearchSpec`` values raise
        ``TypeError``).  ``valid`` [B] bool marks the real lanes of a
        bucket-padded batch — padded lanes contribute zero to the counters.
        The stats fields are batch TOTALS reduced across shards (``iters``
        is the straggler's count), not per-query arrays — the per-shard
        engines ran behind one collective merge.
        """
        spec = resolve_search_spec(spec, self.spec, "ShardedAnnIndex.search")
        spec = dataclasses.replace(spec, metric=self.arrays.metric,
                                   use_hierarchy=False)
        # the device data plane is one collective: no partial results here —
        # a fault fails the whole dispatch, and the serving frontend
        # contains it per-batch (DESIGN.md §10 documents the asymmetry
        # with MutableShardedAnnIndex's host-side composition)
        fault.hit("sharded.search")
        fn = self._step(spec)
        q = D.preprocess_vectors(np.ascontiguousarray(queries, np.float32),
                                 self.arrays.metric)
        # precedence: spec override > profiled shard median
        ct = spec.cos_theta
        if ct is None:
            ct = self.arrays.cos_theta
        v = (jnp.ones((q.shape[0],), bool) if valid is None
             else jnp.asarray(valid, bool))
        d, i, sv = fn(*(self._placed[f] for f in step_tables(spec)),
                      jnp.asarray(q),
                      jnp.asarray(ct, jnp.float32), v)
        sv = np.asarray(sv)
        extra_names = get_router(spec.router).extra_counters
        stats = SearchStats(
            dist_calls=int(sv[0]), est_calls=int(sv[1]),
            rerank_calls=int(sv[2]), sq8_calls=int(sv[3]), hops=int(sv[4]),
            iters=int(sv[5]), router=spec.router,
            extra={nm: int(sv[6 + j]) for j, nm in enumerate(extra_names)})
        k = spec.k
        return np.asarray(i[:, :k]), np.asarray(d[:, :k]), stats
