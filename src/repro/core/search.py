"""Batched beam-expansion graph search in JAX (the TPU-native serving hot path).

Re-derivation of the paper's Algorithm 1/2 for fixed-shape SPMD execution
(DESIGN.md §3), restructured as a BATCH-LEVEL loop with per-hop beams:

* the candidate queue C and result queue T collapse into ONE sorted pool of
  size ``efs`` with per-slot expanded flags — provably equivalent to the
  two-heap formulation for expansion/termination decisions;
* per-node state is a dense uint8 status array (0 unvisited / 1 visited /
  2 pruned) — the pruned state doubles as CRouting's error-correction flag;
* ONE ``lax.while_loop`` drives the whole query batch: each iteration picks
  the best W (= ``SearchSpec.beam_width``) unexpanded pool entries per
  query and expands them together, producing a dense ``[B, W*M]`` neighbor
  tile.  Estimate + prune runs on the VPU path, exact distances on the
  MXU/DMA path, pool maintenance as one merge — and the fixed per-hop cost
  (candidate select, status scatter, loop overhead) is amortized ~W×.
* routing (which lanes skip their exact distance) is pluggable: the
  ``SearchSpec.router`` name resolves through the registry in
  ``repro.core.routers``, and the engine consumes the router's declared
  flags + ``estimate_rank`` hook instead of branching on strings.
* ``SearchSpec.engine`` dispatches the tile work:
    - ``"jnp"``     — pure-jnp reference semantics (the oracle path);
    - ``"pallas"``  — ``ops.fused_expand`` (estimate + prune + conditional
      row DMA + exact distance in one kernel) and the bitonic
      ``ops.pool_merge`` network in place of concat+argsort;
    - ``"pallas_unfused"`` — ``ops.crouting_prune`` + masked
      ``ops.gather_distance_pruned`` + ``ops.pool_merge`` (the composable
      kernel pipeline; slower in interpret mode, kept for kernel-level
      attribution).

Pad-row sentinel convention (repo-wide): ``graph_device_arrays`` appends one
zero vector at row index N; every masked/pruned/out-of-range lane of the
jnp engine gathers that row (the Pallas kernels issue no DMA for such a
lane).  Pool slots holding no candidate carry id N and distance +inf.

Two-stage quantized distances (``SearchSpec.estimate``, PAPERS.md: VSAG /
Probabilistic Routing): with ``estimate="sq8"`` or ``"both"`` the surviving
lanes of a tile do NOT fetch fp32 rows.  Stage 1 reads the uint8 SQ8 code
row (4x fewer bytes, kernels/sq8_distance.py) and computes an approximate
distance plus a conservative lower bound (repro/quant/sq8.py); a lane whose
lower bound already exceeds the pool bound is discarded (status PRUNED)
without ever touching the fp32 table.  Survivors enter the pool with their
approximate distance and a per-slot ``approx`` flag; stage 2 (the fp32 row
DMA + exact distance) runs lazily — when an approx entry is selected for
beam expansion, and for every approx entry left in the pool at return — so
candidates displaced from the pool before either event never pay the fp32
fetch.  ``SearchResult.rerank_calls`` counts stage-2 evaluations,
``sq8_calls`` stage-1 evaluations.

Semantic notes (tested in tests/test_engine_equivalence.py):

* Frozen bound: within one iteration all W*M lanes are evaluated against the
  *iteration-start* upper bound, whereas the scalar Algorithm 1 updates the
  bound after every insertion.  At W=1 the final pool per expansion is
  identical either way (merge-then-truncate == insert-with-evolving-bound);
  only CRouting prune decisions can differ, strictly toward *fewer* prunes
  (frozen bound >= evolving bound), i.e. toward accuracy.  The NumPy oracle
  exposes ``stale_bound=True`` to check exact equivalence.
* Beam semantics (W>1): the W expansion nodes are the W best unexpanded pool
  entries whose distance beats the frozen bound; each distinct neighbor id
  is processed at most once per tile (first-occurrence dedup).  This trades
  a few extra expansions (the 2nd..Wth choices may be refuted by the 1st's
  results) for ~W× fewer loop iterations — recall at equal efs is no worse,
  dist_calls grow mildly; see benchmarks/bench_engine.py for the sweep.
"""
from __future__ import annotations

import weakref
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import GraphIndex
from repro.core.routers import RouterContext, get_router
from repro.core.spec import ENGINES, ESTIMATES, SearchSpec

STATUS_UNVISITED = 0
STATUS_VISITED = 1
STATUS_PRUNED = 2


class SearchResult(NamedTuple):
    ids: jax.Array        # [B, efs] int32, N = empty
    dists: jax.Array      # [B, efs] ranking distance
    dist_calls: jax.Array  # [B] int32 exact distance evaluations
    est_calls: jax.Array   # [B] int32 router estimates evaluated
    hops: jax.Array        # [B] int32 node expansions
    iters: jax.Array       # [] int32 batch-level hop-loop iterations
    rerank_calls: jax.Array  # [B] int32 stage-2 exact reranks (sq8 path)
    sq8_calls: jax.Array     # [B] int32 stage-1 quantized estimates
    # per-router counters ([B] int32 each), keys = Router.extra_counters.
    # None (not {}: NamedTuple defaults are class-level, a dict would be
    # shared mutable state) when constructed without one; the engine always
    # passes a real dict.
    extra: Optional[Dict[str, jax.Array]] = None


def graph_device_arrays(g: GraphIndex, with_sq8: bool = False) -> Dict[str, Any]:
    """Pack a GraphIndex into device arrays with a sentinel pad row at index N.

    Pad-row convention: row N of ``vectors`` (an all-zero vector, norm slot 1)
    is THE sentinel every masked lane resolves to — adjacency pad slots point
    at it, dead beam slots expand it (its neighbor list is all-pad), and the
    jnp engine gathers it for pruned lanes.  Pool slots holding no candidate
    carry id N.

    ``with_sq8`` adds the quantized companion tables (same pad-row
    convention: row N encodes the zero vector).  The default path skips them
    — exact-only configs shouldn't pay the encode pass or the +25% device
    memory; ``build_search_fn`` upgrades the cached dict in place the first
    time an sq8/both config asks (``ensure_sq8_arrays``).
    """
    n, d = g.n, g.dim
    vecs = np.concatenate([g.vectors, np.zeros((1, d), np.float32)], axis=0)
    nbrs = np.concatenate([g.neighbors, np.full((1, g.max_degree), n, np.int32)], axis=0)
    ed = np.concatenate([g.edge_eu_dist, np.full((1, g.max_degree), np.inf, np.float32)], axis=0)
    norms = g.norms if g.norms is not None else np.linalg.norm(g.vectors, axis=1)
    norms = np.concatenate([norms.astype(np.float32), np.ones(1, np.float32)])
    out = {
        "vectors": jnp.asarray(vecs),
        "neighbors": jnp.asarray(nbrs),
        "edge_eu": jnp.asarray(ed),
        "norms": jnp.asarray(norms),
        "entry": jnp.asarray(g.entry_point, jnp.int32),
        "n": n,
    }
    if with_sq8:
        ensure_sq8_arrays(g, out)
    # HNSW hierarchy: id->row maps + per-layer adjacency (top..1).
    if g.upper_neighbors:
        pos_maps, layer_nbrs = [], []
        for ids, mat in zip(g.upper_ids, g.upper_neighbors):
            pos = np.full(n + 1, -1, dtype=np.int32)
            pos[ids] = np.arange(len(ids), dtype=np.int32)
            pos_maps.append(jnp.asarray(pos))
            layer_nbrs.append(jnp.asarray(np.concatenate(
                [mat, np.full((1, mat.shape[1]), n, np.int32)], axis=0)))
        out["upper_pos"] = pos_maps
        out["upper_nbrs"] = layer_nbrs
    return out


def ensure_sq8_arrays(g: GraphIndex, arrays: Dict[str, Any]) -> Dict[str, Any]:
    """Add the SQ8 companion tables to a packed arrays dict (idempotent).

    Grid fit on the real rows; the pad row encodes the zero vector with the
    same params (its distances are always masked out)."""
    if "sq8_codes" not in arrays:
        from repro.quant import sq8 as SQ

        qp = SQ.sq8_train(g.vectors)
        vecs = np.concatenate(
            [g.vectors, np.zeros((1, g.dim), np.float32)], axis=0)
        arrays["sq8_codes"] = jnp.asarray(SQ.sq8_encode(vecs, qp))
        arrays["sq8_lo"] = jnp.asarray(qp.lo)
        arrays["sq8_scale"] = jnp.asarray(qp.scale)
        arrays["sq8_eps"] = jnp.asarray(qp.eps)
    return arrays


def ensure_kernel_tables(arrays: Dict[str, Any],
                         put=jnp.asarray) -> Dict[str, Any]:
    """Add the Pallas engines' table layouts to a packed arrays dict
    (idempotent): the fp32 rows as a ``row_table`` and, when the SQ8 tables
    exist, the codes packed by ``code_words``.  Converted once on the host
    and placed by ``put``; at d == 128 the row table is the ``vectors``
    array itself.  Tables may carry leading axes (per-shard stacks)."""
    from repro.kernels import ops

    if "vectors_rows" not in arrays:
        v = arrays["vectors"]
        arrays["vectors_rows"] = v if v.shape[-1] == ops.LANES else \
            put(ops.row_table(np.asarray(v)))
    if "sq8_codes" in arrays and "sq8_words" not in arrays:
        arrays["sq8_words"] = put(
            ops.code_words(np.asarray(arrays["sq8_codes"])))
    return arrays


def _rank_many(q, X, metric):
    """q [d], X [m, d] -> ranking distances [m]."""
    if metric == "l2":
        diff = X - q[None, :]
        return jnp.sum(diff * diff, axis=-1)
    return 1.0 - X @ q


def _rank_tile(queries, X, metric):
    """queries [B, d], X [B, L, d] -> ranking distances [B, L]."""
    if metric == "l2":
        diff = X - queries[:, None, :]
        return jnp.sum(diff * diff, axis=-1)
    return 1.0 - jnp.einsum("bld,bd->bl", X, queries)


def _rank_to_eu(rank, nq, nx, metric):
    if metric == "l2":
        return jnp.sqrt(jnp.maximum(rank, 0.0))
    return jnp.sqrt(jnp.maximum(nx * nx + nq * nq + 2.0 * rank - 2.0, 0.0))


def _eu2_to_rank(eu2, nq, nx, metric):
    if metric == "l2":
        return eu2
    return (eu2 - nx * nx - nq * nq + 2.0) / 2.0


def _descend(arrays, q, cfg: SearchSpec):
    """Greedy 1-NN descent through HNSW upper layers. Returns (entry, dist_calls)."""
    metric = cfg.metric
    cur = arrays["entry"]
    d_cur = _rank_many(q, arrays["vectors"][cur][None, :], metric)[0]
    calls = jnp.asarray(1, jnp.int32)
    if "upper_nbrs" not in arrays:
        return cur, d_cur, calls
    n = arrays["n"]
    for pos_map, lnbrs in zip(arrays["upper_pos"], arrays["upper_nbrs"]):
        def cond(s):
            cur, d_cur, calls, improved = s
            return improved

        def body(s):
            cur, d_cur, calls, _ = s
            row = pos_map[cur]
            nbrs = lnbrs[jnp.where(row >= 0, row, lnbrs.shape[0] - 1)]
            valid = nbrs < n
            dists = _rank_many(q, arrays["vectors"][nbrs], metric)
            dists = jnp.where(valid, dists, jnp.inf)
            calls = calls + jnp.sum(valid.astype(jnp.int32))
            j = jnp.argmin(dists)
            better = dists[j] < d_cur
            return (jnp.where(better, nbrs[j], cur).astype(jnp.int32),
                    jnp.where(better, dists[j], d_cur), calls, better)

        cur, d_cur, calls, _ = jax.lax.while_loop(
            cond, body, (cur, d_cur, calls, jnp.asarray(True)))
    return cur, d_cur, calls


def _first_occurrence(nbrs, valid, n):
    """Keep only the first valid lane per distinct neighbor id (per row).

    With a beam of W nodes the [B, W*M] tile can name the same neighbor from
    two expansion nodes; sequential Algorithm 1 would visit it once, so the
    tile must too (duplicates would double-count dist_calls and insert the
    id twice into the pool).

    Returns (first_mask, order, sorted_keys); the latter two let
    _rescue_pruned_duplicates reuse the same O(L log L) sort instead of
    re-sorting in the hot loop."""
    key = jnp.where(valid, nbrs, n + 1)
    order = jnp.argsort(key, axis=1, stable=True)
    sk = jnp.take_along_axis(key, order, axis=1)
    dup_sorted = jnp.concatenate(
        [jnp.zeros((nbrs.shape[0], 1), bool), sk[:, 1:] == sk[:, :-1]], axis=1)
    rows = jnp.arange(nbrs.shape[0])[:, None]
    dup = jnp.zeros_like(valid).at[rows, order].set(dup_sorted)
    return valid & ~dup, order, sk


def _rescue_pruned_duplicates(order, sk, prune):
    """Within-tile error correction, tile-local (O(L), reusing the dedup
    sort's (order, sorted_keys)).

    Returns (rescued, prune_final): ``rescued`` marks the SECOND valid lane
    of each id whose first lane was pruned (it must be computed exactly —
    the paper's PRUNED-revisit rule collapsed into one tile);
    ``prune_final`` clears the prune mark for such rescued ids.

    The stable sort by id groups each id's valid lanes in lane order, so the
    group head is the dedup winner (= the only lane ``prune`` can mark) and
    the slot right after it is the rescue candidate."""
    rows = jnp.arange(sk.shape[0])[:, None]
    pr_s = jnp.take_along_axis(prune, order, axis=1)
    pad = jnp.zeros((sk.shape[0], 1), bool)
    same = sk[:, 1:] == sk[:, :-1]
    same_prev = jnp.concatenate([pad, same], axis=1)
    prev_pruned = jnp.concatenate([pad, pr_s[:, :-1]], axis=1)
    rescued_s = same_prev & prev_pruned
    same_next = jnp.concatenate([same, pad], axis=1)
    keep_prune_s = pr_s & ~same_next      # pruned ids with no second lane
    zeros = jnp.zeros_like(prune)
    rescued = zeros.at[rows, order].set(rescued_s)
    prune_final = zeros.at[rows, order].set(keep_prune_s)
    return rescued, prune_final


def _search_batch(arrays, queries, cos_theta, cfg: SearchSpec, valid=None,
                  tombstone=None):
    """Whole-batch Algorithm 1/2 with W-wide beam expansion per iteration.

    Routing is delegated to the registry (``repro.core.routers``): the
    router's flags shape the trace (which lanes are eligible, whether a
    prune is final, whether the Pallas kernels may decide it) and its
    ``estimate_rank`` hook supplies the per-lane estimate when the decision
    is made on the jnp path.

    ``valid`` ([B] bool, optional) marks the real query lanes of a padded
    batch (the serving frontend rounds ragged batches up to a bucket size):
    padded lanes start ``done``, never expand a node, and contribute ZERO to
    every counter — so shard-reduced totals (``ShardedAnnIndex``) stay exact
    under bucket padding.  ``None`` means all lanes are real.

    ``tombstone`` ([n+1] bool device array, optional; pad row MUST be
    False) marks deleted nodes for the live-mutation path
    (``repro.mutate``).  Dead nodes keep routing — they enter the pool,
    get expanded, and their edges stay traversable, exactly as live nodes
    do — but they are masked out of the RESULT pool after the hop loop
    (id -> pad sentinel n, dist -> +inf, then re-sorted), so a deleted id
    can never be emitted.  Tombstones deliberately do not change the
    traversal trace: recall through a sparsely-tombstoned region matches
    the undeleted graph's routing behavior (FreshDiskANN-style filtered
    search).  ``None`` compiles the mask out entirely.
    """
    metric, efs, n = cfg.metric, cfg.efs, arrays["n"]
    W, engine = cfg.beam_width, cfg.engine
    rt = get_router(cfg.router)
    assert engine in ENGINES, f"unknown engine {engine!r}"
    assert cfg.estimate in ESTIMATES, f"unknown estimate {cfg.estimate!r}"
    assert 1 <= W <= efs, "beam_width must be in [1, efs]"
    assert cfg.beam_prune in ("best", "all"), \
        f"unknown beam_prune policy {cfg.beam_prune!r}"
    sq8_on = cfg.estimate in ("sq8", "both")
    if cfg.estimate in ("angle", "both"):
        assert rt.prunes, \
            f"estimate={cfg.estimate!r} needs a pruning router, " \
            f"got {cfg.router!r}"
    # pallas pool_merge rides the (approx, expanded) flags in the id low
    # bits (id*4 + approx*2 + exp)
    assert engine == "jnp" or n < 2 ** 29, \
        "pallas engines encode ids as id*4+flags in int32: shard below 2^29 " \
        "vectors or use engine='jnp'"
    B = queries.shape[0]
    M = arrays["neighbors"].shape[1]
    L = W * M
    rows = jnp.arange(B)
    use_pallas = engine in ("pallas", "pallas_unfused")
    if use_pallas:
        from repro.kernels import ops

        # kernel table layouts, converted once by ensure_kernel_tables
        assert "vectors_rows" in arrays, \
            "pallas engines read the ensure_kernel_tables layouts"
        vec_rows = arrays["vectors_rows"]
        if sq8_on:
            code_words = arrays["sq8_words"]

    nq = (jnp.linalg.norm(queries, axis=1) if metric != "l2"
          else jnp.ones((B,), jnp.float32))

    def _exact_rerank(ids, mask):
        """Stage-2: masked exact ranking distances for pool entries.  The
        fp32 row DMA happens HERE (and only here) on the sq8 path; masked
        lanes resolve to the pad row / +inf."""
        idx = jnp.where(mask, ids, n).astype(jnp.int32)
        if use_pallas:
            eu2 = ops.gather_distance_pruned(
                idx, (~mask).astype(jnp.int8), queries, vec_rows)
            r = _eu2_to_rank(eu2, nq[:, None], arrays["norms"][idx], metric)
        else:
            r = _rank_tile(queries, arrays["vectors"][idx], metric)
        return jnp.where(mask, r, jnp.inf)

    if cfg.use_hierarchy:
        entry, d_entry, calls0 = jax.vmap(
            lambda q: _descend(arrays, q, cfg))(queries)
    else:
        entry = jnp.broadcast_to(arrays["entry"], (B,)).astype(jnp.int32)
        ev = jnp.broadcast_to(arrays["vectors"][arrays["entry"]],
                              (B, queries.shape[1]))
        d_entry = _rank_tile(queries, ev[:, None, :], metric)[:, 0]
        calls0 = jnp.ones((B,), jnp.int32)

    if valid is None:
        done0 = jnp.zeros((B,), bool)
    else:
        # padded lanes are born done: zero hops, zero counters (the entry
        # distance above is masked out of calls0 too)
        done0 = ~valid
        calls0 = jnp.where(valid, calls0, 0)

    pool_d = jnp.full((B, efs), jnp.inf, jnp.float32).at[:, 0].set(d_entry)
    pool_id = jnp.full((B, efs), n, jnp.int32).at[:, 0].set(entry)
    pool_exp = jnp.zeros((B, efs), bool)
    pool_apx = jnp.zeros((B, efs), bool)   # slot holds a stage-1 estimate
    status = jnp.zeros((B, n + 1), jnp.uint8).at[rows, entry].set(STATUS_VISITED)

    State = (pool_d, pool_id, pool_exp, pool_apx, status, calls0,
             jnp.zeros((B,), jnp.int32),   # est_calls
             jnp.zeros((B,), jnp.int32),   # rerank_calls
             jnp.zeros((B,), jnp.int32),   # sq8_calls
             # per-router counters (registry-declared, see Router.extra_counters)
             {name: jnp.zeros((B,), jnp.int32) for name in rt.extra_counters},
             jnp.zeros((B,), jnp.int32),   # hops
             done0,                        # done (padded lanes born done)
             jnp.asarray(0, jnp.int32))    # iters

    def cond(s):
        *_, done, iters = s
        return jnp.any(~done) & (iters < cfg.max_hops)

    def body(s):
        (pool_d, pool_id, pool_exp, pool_apx, status, dcalls, ecalls,
         rrcalls, sqcalls, extras, hops, done, iters) = s

        # --- beam selection: best W unexpanded pool entries per query ------
        cand = (~pool_exp) & (pool_id < n)
        cand_d = jnp.where(cand, pool_d, jnp.inf)
        neg_top, beam_idx = jax.lax.top_k(-cand_d, W)          # [B, W]
        beam_d = -neg_top
        pool_full = pool_id[:, efs - 1] < n
        upper = jnp.where(pool_full, pool_d[:, efs - 1], jnp.inf)  # [B]
        active = (~done) & (hops < cfg.max_hops)
        slot_live = jnp.isfinite(beam_d) & (beam_d <= upper[:, None]) \
            & active[:, None]                                   # [B, W]
        # keep the per-query hop budget exact: only the first
        # (max_hops - hops) live slots may expand this iteration
        budget = cfg.max_hops - hops                            # [B]
        slot_live = slot_live & (jnp.cumsum(slot_live, axis=1)
                                 <= budget[:, None])
        done = done | ~jnp.any(slot_live, axis=1)

        c = jnp.where(slot_live,
                      jnp.take_along_axis(pool_id, beam_idx, axis=1),
                      n).astype(jnp.int32)                      # [B, W]
        dc = jnp.take_along_axis(pool_d, beam_idx, axis=1)      # [B, W]
        if sq8_on:
            # stage-2 rerank at expansion: an approx entry selected for the
            # beam gets its exact distance (and its flag cleared) before its
            # stored distance is used as d(c, q) for the tile's estimates
            sel_apx = jnp.take_along_axis(pool_apx, beam_idx, axis=1) \
                & slot_live
            dc = jnp.where(sel_apx, _exact_rerank(c, sel_apx), dc)
            pool_d = pool_d.at[rows[:, None], beam_idx].set(dc)
            pool_apx = pool_apx.at[rows[:, None], beam_idx].set(
                jnp.take_along_axis(pool_apx, beam_idx, axis=1) & ~sel_apx)
            nrr = jnp.sum(sel_apx, axis=1, dtype=jnp.int32)
            rrcalls = rrcalls + nrr
            dcalls = dcalls + nrr
        pool_exp = pool_exp.at[rows[:, None], beam_idx].set(
            jnp.take_along_axis(pool_exp, beam_idx, axis=1) | slot_live)

        # --- dense [B, W*M] neighbor tile ----------------------------------
        nbrs = arrays["neighbors"][c].reshape(B, L)             # [B, L]
        # stored edge distances may be bf16 (§Perf HC3); estimate math in f32
        ed = arrays["edge_eu"][c].astype(jnp.float32).reshape(B, L)
        st = jnp.take_along_axis(status, nbrs, axis=1)          # [B, L]
        in_range = nbrs < n
        lane_live = jnp.broadcast_to(slot_live[:, :, None],
                                     (B, W, M)).reshape(B, L)
        valid = in_range & (st != STATUS_VISITED) & lane_live
        if not rt.revisit_pruned:
            # no error correction (crouting_o): pruned lanes stay skipped
            valid = valid & (st != STATUS_PRUNED)
        if W > 1:
            first, dd_order, dd_keys = _first_occurrence(nbrs, valid, n)
        else:
            first = valid

        norms_c = arrays["norms"][c]                            # [B, W]
        dcq_eu = _rank_to_eu(dc, nq[:, None], norms_c, metric)  # [B, W]
        dcq_l = jnp.broadcast_to(dcq_eu[:, :, None], (B, W, M)).reshape(B, L)
        nx = arrays["norms"][nbrs]                              # [B, L]

        if metric == "l2":
            bound2 = jnp.broadcast_to(upper[:, None], (B, L))
        else:
            # est_rank >= upper  <=>  est2 >= inverse rank->eu^2 per lane
            bound2 = 2.0 * upper[:, None] + nx * nx \
                + (nq * nq)[:, None] - 2.0

        # --- router: estimate + prune (no neighbor-vector fetch here).
        # Edge-angle routers (Router.kernel_estimate) may have the decision
        # taken inside the Pallas kernels: the fused engine inside
        # fused_expand (est + prune + conditional DMA in one kernel), the
        # unfused engine in the crouting_prune kernel; otherwise the
        # router's estimate_rank hook runs on the jnp path.  All paths
        # evaluate the identical f32 expression for the edge-angle family,
        # so the decisions are bit-equal for l2.  The beam rescue path
        # (W>1, error-correcting router) must know prune BEFORE the fetch
        # set exists, so there the hook decides and the fused kernel's
        # eligible set is empty (its DMA skip still comes from eval_mask). -
        prunes = rt.prunes
        ct_eff = rt.cos_theta_eff(cos_theta)
        rescue = W > 1 and prunes and rt.revisit_pruned and not rt.permanent
        # with sq8 the fused fp32 kernel never runs, so the prune decision
        # is made outside it (jnp / crouting_prune — the same f32 math)
        kernel_prunes = engine == "pallas" and rt.kernel_estimate \
            and not rescue and not sq8_on
        if prunes:
            try_prune = first & (st == STATUS_UNVISITED) & pool_full[:, None]
            if W > 1 and cfg.beam_prune == "best":
                # top_k orders slots by distance, so slot 0 = the node
                # sequential search would be expanding right now; only its
                # lanes run the estimate test (see SearchSpec.beam_prune)
                try_prune = try_prune & (jnp.arange(L) < M)[None, :]
            if rt.counts_est:
                ecalls = ecalls + jnp.sum(try_prune, axis=1, dtype=jnp.int32)
        else:
            try_prune = jnp.zeros_like(first)

        if not prunes or kernel_prunes:
            prune = jnp.zeros_like(first)
        elif engine == "pallas_unfused" and rt.kernel_estimate:
            _, prune8 = ops.crouting_prune(ed, dcq_l, bound2, try_prune,
                                           ct_eff)
            prune = prune8 != 0
        else:
            ctx = RouterContext(
                arrays=arrays, queries=queries, nq=nq, c=c, dc=dc, nbrs=nbrs,
                ed=ed, dcq=dcq_l, nx=nx, try_prune=try_prune, upper=upper,
                cos_theta=cos_theta, metric=metric, n=n, beam_width=W,
                max_degree=M)
            est_rank, extra_inc = rt.estimate_rank(ctx)
            prune = try_prune & (est_rank >= upper[:, None])
            # repolint: ignore[trace-safety] extra_inc is a host dict of
            # counter names (Router.extra_counters), not a tracer — its
            # truthiness is concrete during tracing
            extras = {key: extras[key] + extra_inc.get(key, 0)
                      for key in extras} if extra_inc else extras

        if rescue:
            # Within-tile error correction (paper Alg. 2): sequentially, the
            # second encounter of a just-pruned node recomputes it exactly
            # (status PRUNED exempts it from re-estimation).  Collapsed into
            # the tile: a second valid lane of a pruned id computes, and the
            # id is then VISITED, not PRUNED.  Without this, beam dedup
            # silently disables error correction and recall drops.
            rescued, prune_kept = _rescue_pruned_duplicates(dd_order, dd_keys,
                                                            prune)
            compute = (first & ~prune) | rescued
            prune = prune_kept    # rescued ids end VISITED, not PRUNED
        else:
            compute = first & ~prune

        # --- distances: stage-1 quantized estimate (sq8) or exact fp32 ------
        if sq8_on:
            # stage 1: uint8 code-row gather + dequantized accumulate +
            # conservative lower bound for EVERY surviving lane — no fp32
            # row DMA on this path (that is stage 2's job, in _exact_rerank)
            if use_pallas:
                ad2, lb2 = ops.sq8_estimate(
                    nbrs, queries, compute, code_words,
                    arrays["sq8_lo"], arrays["sq8_scale"], arrays["sq8_eps"])
            else:
                from repro.quant.sq8 import sq8_dequantize_rows, sq8_estimate
                xhat = sq8_dequantize_rows(
                    arrays["sq8_codes"][jnp.where(compute, nbrs, n)],
                    arrays["sq8_lo"], arrays["sq8_scale"])
                ad2, lb2 = sq8_estimate(queries, xhat, arrays["sq8_eps"])
                ad2 = jnp.where(compute, ad2, jnp.inf)
                lb2 = jnp.where(compute, lb2, jnp.inf)
            ad_rank = _eu2_to_rank(ad2, nq[:, None], nx, metric)
            lb_rank = _eu2_to_rank(lb2, nq[:, None], nx, metric)
            # a lane whose true distance provably cannot beat the pool bound
            # is discarded without its fp32 row; PRUNED (not VISITED) so a
            # later encounter may re-estimate it against a tighter bound
            sq8_skip = compute & pool_full[:, None] \
                & (lb_rank >= upper[:, None])
            insert = compute & ~sq8_skip
            sqcalls = sqcalls + jnp.sum(compute, axis=1, dtype=jnp.int32)
            new_d = jnp.where(insert, ad_rank, jnp.inf)
        else:
            # exact fp32 distances (masked; non-compute lanes skip the HBM
            # row fetch on real TPU)
            if engine == "pallas":
                d2eu, prune8 = ops.fused_expand(
                    nbrs, queries, ed, dcq_l, bound2, ct_eff,
                    vec_rows, eval_mask=compute,
                    prune_eligible=try_prune if kernel_prunes
                    else jnp.zeros_like(try_prune))
                if kernel_prunes:
                    # the kernel both made the prune decision and skipped
                    # those lanes' DMAs (eval ∩ eligible lanes fetch only if
                    # unpruned)
                    prune = prune8 != 0
                    compute = compute & ~prune
                exact = _eu2_to_rank(d2eu, nq[:, None], nx, metric)
            elif engine == "pallas_unfused":
                d2eu = ops.gather_distance_pruned(
                    jnp.where(compute, nbrs, n), (~compute).astype(jnp.int8),
                    queries, vec_rows)
                exact = _eu2_to_rank(d2eu, nq[:, None], nx, metric)
            else:
                gathered = arrays["vectors"][jnp.where(compute, nbrs, n)]
                exact = _rank_tile(queries, gathered, metric)
            insert = compute
            new_d = jnp.where(compute, exact, jnp.inf)
            dcalls = dcalls + jnp.sum(compute, axis=1, dtype=jnp.int32)

        # --- status scatter: only lanes whose status changes write; all
        # other lanes are redirected to the pad column (same-value writes,
        # so the scatter stays deterministic) -------------------------------
        change = compute | prune
        if rt.permanent:
            # exact/trusted bound => discard is permanent (mark visited)
            new_st = jnp.full_like(st, STATUS_VISITED)
        else:
            new_st = jnp.where(insert, STATUS_VISITED, STATUS_PRUNED
                               ).astype(jnp.uint8)
        pad_val = status[:, n][:, None]
        status = status.at[rows[:, None], jnp.where(change, nbrs, n)].set(
            jnp.where(change, new_st, pad_val))

        # --- pool merge (merge-then-truncate == evolving-bound insertion) ---
        new_id = jnp.where(insert, nbrs, n).astype(jnp.int32)
        new_apx = insert if sq8_on else jnp.zeros_like(insert)
        if use_pallas:
            # approx + expanded flags ride the bitonic network in the id
            # low bits
            enc_pool = pool_id * 4 + pool_apx.astype(jnp.int32) * 2 \
                + pool_exp.astype(jnp.int32)
            enc_new = new_id * 4 + new_apx.astype(jnp.int32) * 2
            pool_d, enc = ops.pool_merge(pool_d, enc_pool, new_d, enc_new)
            pool_id = enc // 4
            pool_apx = (enc & 2) == 2
            pool_exp = (enc & 1) == 1
        else:
            md = jnp.concatenate([pool_d, new_d], axis=1)
            mi = jnp.concatenate([pool_id, new_id], axis=1)
            me = jnp.concatenate([pool_exp, jnp.zeros_like(insert)], axis=1)
            ma = jnp.concatenate([pool_apx, new_apx], axis=1)
            # lexicographic (dist, id) — the SAME tie-break as the pallas
            # pool_merge network, so the engines agree even on exact ties
            order = jnp.lexsort((mi, md), axis=1)[:, :efs]
            pool_d = jnp.take_along_axis(md, order, axis=1)
            pool_id = jnp.take_along_axis(mi, order, axis=1)
            pool_exp = jnp.take_along_axis(me, order, axis=1)
            pool_apx = jnp.take_along_axis(ma, order, axis=1)

        hops = hops + jnp.sum(slot_live, axis=1, dtype=jnp.int32)
        return (pool_d, pool_id, pool_exp, pool_apx, status, dcalls, ecalls,
                rrcalls, sqcalls, extras, hops, done, iters + 1)

    (pool_d, pool_id, pool_exp, pool_apx, status, dcalls, ecalls, rrcalls,
     sqcalls, extras, hops, done, iters) = jax.lax.while_loop(cond, body,
                                                              State)
    if tombstone is not None:
        # emission-time masking: dead entries routed normally through the
        # loop above; here they collapse to the pad sentinel so neither the
        # sq8 final rerank nor the caller ever sees them
        dead = tombstone[pool_id]          # pool_id in [0..n]; row n is False
        pool_d = jnp.where(dead, jnp.inf, pool_d)
        pool_id = jnp.where(dead, n, pool_id)
    if sq8_on:
        # stage-2 final rerank: every approx survivor still in the pool gets
        # its exact distance before results can be returned; entries
        # displaced earlier never paid their fp32 fetch
        mask = pool_apx & (pool_id < n)
        pool_d = jnp.where(mask, _exact_rerank(pool_id, mask), pool_d)
        nrr = jnp.sum(mask, axis=1, dtype=jnp.int32)
        rrcalls = rrcalls + nrr
        dcalls = dcalls + nrr
        order = jnp.lexsort((pool_id, pool_d), axis=1)
        pool_d = jnp.take_along_axis(pool_d, order, axis=1)
        pool_id = jnp.take_along_axis(pool_id, order, axis=1)
    elif tombstone is not None:
        # the sq8 branch above already re-sorted; the exact path must push
        # the newly-masked dead slots behind the survivors itself
        order = jnp.lexsort((pool_id, pool_d), axis=1)
        pool_d = jnp.take_along_axis(pool_d, order, axis=1)
        pool_id = jnp.take_along_axis(pool_id, order, axis=1)
    if valid is not None:
        # belt and braces: padded lanes never ran, but the counters must be
        # provably zero whatever path produced them (they feed shard psums)
        dcalls, ecalls, rrcalls, sqcalls, hops = (
            jnp.where(valid, a, 0)
            for a in (dcalls, ecalls, rrcalls, sqcalls, hops))
        extras = {k: jnp.where(valid, v, 0) for k, v in extras.items()}
    return SearchResult(ids=pool_id, dists=pool_d, dist_calls=dcalls,
                        est_calls=ecalls, hops=hops, iters=iters,
                        rerank_calls=rrcalls, sq8_calls=sqcalls, extra=extras)


# --- compiled-engine cache ---------------------------------------------------
# search_batch used to re-trace + re-jit on every call; repeated batches (the
# examples/serve_anns.py serving path, NSG construction) now hit a small
# keyed cache of compiled executables.  Device arrays are cached per GRAPH
# (one copy shared by every config sweeping that graph); jitted fns per
# (graph identity, cfg).  Weakrefs guard against id() reuse after gc, and
# dead-graph entries are purged on every call so their device buffers don't
# stay pinned.
_ARRAYS_CACHE: "dict[int, tuple]" = {}
_ENGINE_CACHE: "dict[tuple, tuple]" = {}
_ENGINE_CACHE_MAX = 16


class _BoundEngine:
    """A jitted engine with its graph's device tables bound as *arguments*.

    A closed-over array is baked into the executable as a constant — one
    copy of every table per compiled batch shape, in the compile and on the
    device; an argument is shared.  Called as ``fn(queries, cos_theta[,
    tombstone])``; ``_cache_size`` counts the compiled executables."""

    def __init__(self, jitted, tables):
        self._jitted, self._tables = jitted, tables

    def __call__(self, *args):
        return self._jitted(self._tables, *args)

    def _cache_size(self) -> int:
        return self._jitted._cache_size()


def _purge_dead_cache_entries():
    """Drop every cache entry tied to a collected graph.

    The compiled-fn cache needs BOTH checks: its own weakref, and that the
    graph id its key references still names a live arrays-cache entry — a
    stale (graph_id, cfg) entry would otherwise keep the fp32 + SQ8 device
    tables pinned (the jitted fn closes over them) long after the index is
    gone and its id has been reused (regression-tested in
    tests/test_engine_equivalence.py::test_engine_cache_does_not_grow...).
    """
    for k in [k for k, v in _ARRAYS_CACHE.items() if v[0]() is None]:
        del _ARRAYS_CACHE[k]
    for k in [k for k, v in _ENGINE_CACHE.items()
              if v[0]() is None or k[0] not in _ARRAYS_CACHE]:
        del _ENGINE_CACHE[k]


def _graph_arrays_cached(g: GraphIndex):
    hit = _ARRAYS_CACHE.get(id(g))
    if hit is not None and hit[0]() is g:
        return hit[1]
    arrays = graph_device_arrays(g)
    _ARRAYS_CACHE[id(g)] = (weakref.ref(g), arrays)
    return arrays


def build_search_fn(g: GraphIndex, cfg: SearchSpec, tombstones: bool = False):
    """Returns (arrays, jitted fn) for searching ``g`` under ``cfg``.

    The fn signature depends on ``tombstones``: the default is
    ``fn(queries [B,d], cos_theta) -> SearchResult``; with
    ``tombstones=True`` (the live-mutation path, ``repro.mutate``) it is
    ``fn(queries, cos_theta, tombstone [n+1] bool)`` — the mask is a traced
    argument, so flipping tombstones on/off per delete never re-jits.

    Cached per (graph identity, canonical spec, router instance,
    tombstones): calling twice with the same live graph and an equal spec
    returns the SAME jitted callable, so repeated search_batch calls reuse
    the compiled executable instead of re-tracing.  ``SearchSpec.k``/
    ``cos_theta`` are stripped from the key — they do not shape the trace.
    The resolved Router is part of the key because the jitted fn bakes its
    hooks in: re-registering a different router under the same name must
    miss.
    """
    _purge_dead_cache_entries()
    cfg = cfg.canonical()
    rt = get_router(cfg.router)
    key = (id(g), cfg, rt, tombstones)
    hit = _ENGINE_CACHE.get(key)
    if hit is not None:
        ref, arrays, fn = hit
        if ref() is g:
            return arrays, fn
        del _ENGINE_CACHE[key]

    arrays = _graph_arrays_cached(g)
    if cfg.estimate in ("sq8", "both"):
        # lazily upgrade the (shared) cached dict: exact-only graphs never
        # pay the encode pass or the extra device tables
        ensure_sq8_arrays(g, arrays)
    if cfg.engine != "jnp":
        ensure_kernel_tables(arrays)
    # router companion tables (e.g. finger signatures) upgrade it the same
    # lazy way the first time the router is configured for this graph
    rt.prepare(g, arrays)

    n = arrays["n"]
    tables = {k: v for k, v in arrays.items() if k != "n"}
    if tombstones:
        @jax.jit
        def run(tables, queries, cos_theta, tombstone):
            return _search_batch({**tables, "n": n},
                                 queries.astype(jnp.float32), cos_theta, cfg,
                                 tombstone=tombstone)
    else:
        @jax.jit
        def run(tables, queries, cos_theta):
            return _search_batch({**tables, "n": n},
                                 queries.astype(jnp.float32), cos_theta, cfg)
    run = _BoundEngine(run, tables)

    while len(_ENGINE_CACHE) >= _ENGINE_CACHE_MAX:
        _ENGINE_CACHE.pop(next(iter(_ENGINE_CACHE)))
    _ENGINE_CACHE[key] = (weakref.ref(g), arrays, run)
    return arrays, run


def search_batch(g: GraphIndex, queries: np.ndarray, cfg: SearchSpec,
                 cos_theta: float = 0.0, k: Optional[int] = None) -> SearchResult:
    """Convenience one-shot batched search (compiled fn cached per (graph, cfg))."""
    _, fn = build_search_fn(g, cfg)
    res = fn(jnp.asarray(queries), jnp.asarray(cos_theta, jnp.float32))
    if k is not None:
        res = res._replace(ids=res.ids[:, :k], dists=res.dists[:, :k])
    return res
