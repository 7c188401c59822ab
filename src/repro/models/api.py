"""Cell builder: (architecture x input-shape) -> lowerable step + specs.

Cells are lowerable shape specs (nothing in the serve path consumes
them); smoke tests consume build_smoke().  Everything here is ShapeDtypeStruct-based — no
parameter allocation for the full configs (DESIGN.md deliverable (f))."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ArchSpec, ShapeSpec
from repro.launch import sharding as SH
from repro.launch.mesh import data_axes
from repro.train import optimizer as opt


@dataclasses.dataclass
class Cell:
    arch_id: str
    shape_id: str
    step_name: str
    step_fn: Callable
    arg_specs: Tuple
    in_shardings: Any
    out_shardings: Any       # None -> let XLA choose
    model_flops: float       # "useful" flops (6·N·D convention; §Roofline)
    notes: str = ""
    static_argnums: Tuple[int, ...] = ()
    # --- loop-corrected accounting (EXPERIMENTS.md §Roofline methodology):
    # XLA cost_analysis counts each while/scan body ONCE.  For layer-scanned
    # models, `loop_fit` provides (L, build(L) -> Cell) so the dry-run can
    # 2-point-fit the per-layer body cost; `analytic_extra` adds the
    # statically-known inner-scan (attention tiles / loss chunks) shortfall;
    # `body_multiplier` scales all terms for data-dependent while loops
    # (the ANNS best-first search: one body execution == one hop).
    loop_fit: Optional[Tuple[int, Callable]] = None
    analytic_extra: Optional[Dict[str, float]] = None   # per-device adds
    body_multiplier: float = 1.0


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


OCFG = opt.AdamWConfig()
OCFG_BF16 = opt.AdamWConfig(state_dtype="bfloat16")


# ==========================================================================
# LM family
# ==========================================================================
def _lm_analytic_extra(cfg, B, S, mesh, train: bool) -> Dict[str, float]:
    """Per-device flops/bytes the compiled cost analysis misses because the
    attention tile scans and loss-chunk scan are while loops (bodies counted
    once).  Formulas documented in EXPERIMENTS.md §Roofline methodology."""
    H, dh, D = cfg.n_heads, cfg.dh, cfg.d_model
    L = cfg.n_layers
    n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    dp = n_dev // mesh.shape.get("model", 1)
    # attention work replicates over 'model' when H doesn't shard evenly
    attn_div = n_dev if H % mesh.shape.get("model", 1) == 0 else dp
    nq = max(S // min(cfg.block_q, S), 1)
    nk = max(S // min(cfg.block_k, S), 1)
    miss = 1.0 - 1.0 / (nq * nk)
    pass_f = 4.5 if train else 1.0     # fwd + remat-recompute + flash bwd
    attn_flops = pass_f * 4.0 * B * H * S * S * dh * L * miss / attn_div
    attn_bytes = (3.0 if train else 1.0) * nq * nk * B * H * dh \
        * (min(cfg.block_q, S) + 2 * min(cfg.block_k, S)) * 4.0 * L \
        * miss / attn_div
    out = {"flops": attn_flops, "bytes": attn_bytes}
    if train:
        nc = max(S // min(cfg.loss_chunk, S), 1)
        mc = 1.0 - 1.0 / nc
        V = cfg.padded_vocab
        out["flops"] += mc * 6.0 * B * S * D * V / n_dev
        out["bytes"] += mc * nc * (D * V * 2.0 + B * (S // nc) * V * 4.0) \
            * 2.0 / n_dev
    return out


def _lm_cell(spec: ArchSpec, shape: ShapeSpec, mesh, _cfg_override=None) -> Cell:
    from repro.models import transformer as T

    cfg = _cfg_override or spec.model_cfg
    B, S = shape.dims["global_batch"], shape.dims["seq_len"]
    ocfg = OCFG_BF16 if cfg.param_count() > 1e11 else OCFG
    params_spec = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    p_sh = SH.lm_param_sharding(mesh, params_spec)
    n_act = cfg.active_param_count()

    def fit_builder(L):
        sub = dataclasses.replace(cfg, n_layers=L, unroll_layers=True)
        return _lm_cell(spec, shape, mesh, _cfg_override=sub)

    loop_fit = None if _cfg_override else (cfg.n_layers, fit_builder)

    if shape.step == "train":
        opt_spec = jax.eval_shape(lambda: opt.adamw_init(params_spec, ocfg))
        o_sh = SH.lm_opt_sharding(mesh, opt_spec, p_sh)
        b_sh = SH.lm_batch_sharding(mesh)
        batch_spec = {"tokens": _sds((B, S), jnp.int32),
                      "labels": _sds((B, S), jnp.int32)}
        return Cell(spec.arch_id, shape.shape_id, "train_step",
                    T.make_train_step(cfg, ocfg),
                    (params_spec, opt_spec, batch_spec),
                    (p_sh, o_sh, b_sh), (p_sh, o_sh, None),
                    model_flops=6.0 * n_act * B * S,
                    notes=f"N_active={n_act:.3e}",
                    loop_fit=loop_fit,
                    analytic_extra=_lm_analytic_extra(cfg, B, S, mesh, True))

    if shape.step == "prefill":
        tok_sh = SH.lm_token_sharding(mesh, B)
        return Cell(spec.arch_id, shape.shape_id, "prefill_step",
                    T.make_prefill_step(cfg),
                    (params_spec, _sds((B, S), jnp.int32)),
                    (p_sh, tok_sh), None,
                    model_flops=2.0 * n_act * B * S,
                    loop_fit=loop_fit,
                    analytic_extra=_lm_analytic_extra(cfg, B, S, mesh, False))

    # serve: one-token decode against a KV cache of length S
    cache_spec = {
        "k": _sds((cfg.n_layers, B, S, cfg.n_kv_heads, cfg.dh), cfg.dtype),
        "v": _sds((cfg.n_layers, B, S, cfg.n_kv_heads, cfg.dh), cfg.dtype),
    }
    c_sh = SH.lm_cache_sharding(mesh, B, S)
    tok_sh = SH.lm_token_sharding(mesh, B)
    attn_flops = 4.0 * B * cfg.n_layers * cfg.n_heads * cfg.dh * S
    return Cell(spec.arch_id, shape.shape_id, "serve_step",
                T.make_serve_step(cfg),
                (params_spec, cache_spec, _sds((B, 1), jnp.int32),
                 _sds((), jnp.int32)),
                (p_sh, c_sh, tok_sh, SH._ns(mesh)), None,
                model_flops=2.0 * n_act * B + attn_flops,
                notes="decode; KV " + ("seq-sharded" if B == 1 else "batch-sharded"),
                loop_fit=loop_fit)


# ==========================================================================
# GNN family
# ==========================================================================
def _pad512(x: int) -> int:
    """Pad node/edge counts to a multiple of 512 (= the largest device count)
    so the arrays shard evenly; pad entries carry zero masks (DESIGN.md §7)."""
    return -(-x // 512) * 512


def _gnn_dims(shape: ShapeSpec):
    d = shape.dims
    if shape.shape_id == "minibatch_lg":
        n, e = d["sub_nodes"], d["sub_edges"]
        f, c, g = d["d_feat"], d.get("n_classes", 16), 1
    elif shape.shape_id == "molecule":
        n, e = d["n_nodes"] * d["batch"], d["n_edges"] * d["batch"]
        f, c, g = d["d_feat"], 16, d["batch"]
    else:
        n, e = d["n_nodes"], d["n_edges"]
        f, c, g = d["d_feat"], d.get("n_classes", 16), 1
    return _pad512(n), _pad512(e), f, c, g


def _gnn_batch_spec(n, e, f, g, task):
    sp = {
        "node_feat": _sds((n, f), jnp.float32),
        "pos": _sds((n, 3), jnp.float32),
        "atom_z": _sds((n,), jnp.int32),
        "edge_src": _sds((e,), jnp.int32),
        "edge_dst": _sds((e,), jnp.int32),
        "node_mask": _sds((n,), jnp.float32),
        "edge_mask": _sds((e,), jnp.float32),
        "labels": _sds((n,), jnp.int32),
        "label_mask": _sds((n,), jnp.float32),
        "graph_ids": _sds((n,), jnp.int32),
        "g_labels": _sds((g,), jnp.int32 if task == "graph_class" else jnp.float32),
    }
    return sp


def _gnn_flops(cfg, n, e, f):
    d = cfg.d_hidden
    if cfg.arch == "gin":
        return cfg.n_layers * (2 * e * d + 4 * n * d * d) + 2 * n * f * d
    if cfg.arch == "gat":
        h = cfg.n_heads
        return cfg.n_layers * (2 * n * f * h * d + 4 * e * h * d)
    if cfg.arch == "schnet":
        return cfg.n_layers * (2 * e * (cfg.n_rbf * d + d * d) + 4 * n * d * d)
    if cfg.arch == "egnn":
        return cfg.n_layers * (2 * e * (2 * d + 1) * d + 2 * e * d * d
                               + 4 * n * d * d)
    return 2.0 * e * d


def _gnn_cell(spec: ArchSpec, shape: ShapeSpec, mesh) -> Cell:
    from repro.models import gnn as G

    n, e, f, ncls, g = _gnn_dims(shape)
    task = spec.model_cfg.task
    if shape.shape_id == "molecule" and task == "node_class":
        task = "graph_class"
    cfg = dataclasses.replace(spec.model_cfg, n_classes=ncls, task=task)
    params_spec = jax.eval_shape(lambda: G.init_gnn(cfg, f, jax.random.PRNGKey(0)))
    p_sh = SH.gnn_param_sharding(mesh, params_spec)
    batch_spec = _gnn_batch_spec(n, e, f, g, task)
    b_sh = SH.gnn_batch_sharding(mesh, batch_spec)
    flops = _gnn_flops(cfg, n, e, f)

    opt_spec = jax.eval_shape(lambda: opt.adamw_init(params_spec, OCFG))
    o_sh = SH.gnn_param_sharding(mesh, opt_spec)
    return Cell(spec.arch_id, shape.shape_id, "train_step",
                G.make_gnn_train_step(cfg, OCFG),
                (params_spec, opt_spec, batch_spec),
                (p_sh, o_sh, b_sh), (p_sh, o_sh, None),
                model_flops=3.0 * flops,
                notes=f"task={task} n={n} e={e}")


# ==========================================================================
# RecSys family
# ==========================================================================
def _dlrm_cell(spec: ArchSpec, shape: ShapeSpec, mesh) -> Cell:
    from repro.models import dlrm as R

    cfg = spec.model_cfg
    params_spec = jax.eval_shape(lambda: R.init_dlrm(cfg, jax.random.PRNGKey(0)))
    p_sh = SH.dlrm_param_sharding(mesh, params_spec)

    if shape.step == "retrieval":
        Bq, Nc = shape.dims["batch"], shape.dims["n_candidates"]
        d = cfg.embed_dim
        # 1e6 rows shard evenly over the data axes (16 / 32), not over model
        cand_sh = SH._ns(mesh, data_axes(mesh), None)
        return Cell(spec.arch_id, shape.shape_id, "retrieval_step",
                    R.make_retrieval_step(cfg),
                    (_sds((Bq, d), jnp.float32), _sds((Nc, d), jnp.float32)),
                    (SH._ns(mesh, None, None), cand_sh), None,
                    model_flops=2.0 * Bq * Nc * d,
                    notes="brute-force scorer; CRouting-ANN variant in examples")

    B = shape.dims["batch"]
    mlp_flops = 0
    dims = (cfg.n_dense,) + cfg.bot_mlp
    mlp_flops += sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    n_int = cfg.n_sparse + 1
    dims = (n_int * (n_int - 1) // 2 + cfg.embed_dim,) + cfg.top_mlp
    mlp_flops += sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    mlp_flops += 2 * n_int * n_int * cfg.embed_dim
    batch_spec = {"dense": _sds((B, cfg.n_dense), jnp.float32),
                  "sparse_ids": _sds((B, cfg.n_sparse), jnp.int32),
                  "labels": _sds((B,), jnp.float32)}
    b_sh = SH.dlrm_batch_sharding(mesh, B)

    if shape.step == "train":
        opt_spec = jax.eval_shape(lambda: opt.adamw_init(params_spec, OCFG))
        o_sh = SH.dlrm_param_sharding(mesh, opt_spec)
        return Cell(spec.arch_id, shape.shape_id, "train_step",
                    R.make_dlrm_train_step(cfg, OCFG),
                    (params_spec, opt_spec, batch_spec),
                    (p_sh, o_sh, b_sh), (p_sh, o_sh, None),
                    model_flops=3.0 * B * mlp_flops)

    del batch_spec["labels"]
    del b_sh["labels"]
    return Cell(spec.arch_id, shape.shape_id, "serve_step",
                R.make_dlrm_serve_step(cfg),
                (params_spec, batch_spec), (p_sh, b_sh), None,
                model_flops=1.0 * B * mlp_flops)


# ==========================================================================
# ANNS family (the paper's own system)
# ==========================================================================
def _anns_cell(spec: ArchSpec, shape: ShapeSpec, mesh) -> Cell:
    from repro.core.sharded_index import make_serve_step
    from repro.core.spec import SearchSpec

    d = shape.dims
    n_shards = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    ns = -(-d["n_total"] // n_shards)
    m = d["max_degree"]
    dim, B, efs, k = d["dim"], d["batch"], d["efs"], d["k"]
    cfg = SearchSpec(efs=efs, k=k, router=spec.model_cfg.router, metric="l2",
                     max_hops=2 * efs, use_hierarchy=False)
    # k is request-only: the step merges efs-wide and the host slices to k
    serve, in_sh, out_sh = make_serve_step(mesh, cfg.canonical(), ns)
    vdt = jnp.dtype(getattr(spec.model_cfg, "vec_dtype", "float32"))
    arg_specs = (
        _sds((n_shards, ns + 1, dim), vdt),
        _sds((n_shards, ns + 1, m), jnp.int32),
        _sds((n_shards, ns + 1, m), vdt),      # stored edge dists follow
        _sds((n_shards, ns + 1), jnp.float32),
        _sds((n_shards,), jnp.int32),
        _sds((n_shards,), jnp.int32),
        _sds((n_shards, ns + 1, dim), jnp.uint8),   # SQ8 code table
        _sds((n_shards, dim), jnp.float32),         # SQ8 grid lo
        _sds((n_shards, dim), jnp.float32),         # SQ8 grid scale
        _sds((n_shards, dim), jnp.float32),         # SQ8 error radius
        _sds((B, dim), jnp.float32),
        _sds((), jnp.float32),
        _sds((B,), jnp.bool_),                      # bucket-pad valid mask
    )
    # useful work ~ exact distance evals: efs expansions x m neighbors x 2d
    flops = 2.0 * B * efs * m * dim
    # the best-first while body == ONE expansion (hop) across all query
    # lanes; empirical hops/query ~= 1.5*efs (benchmarks/bench_paper.py)
    hops = 1.5 * efs
    return Cell(spec.arch_id, shape.shape_id, "anns_serve_step", serve,
                arg_specs, in_sh, out_sh, model_flops=flops,
                notes=f"shards={n_shards} ns={ns} router={spec.model_cfg.router} "
                      f"hop_multiplier={hops:.0f}",
                body_multiplier=hops)


# ==========================================================================
# public API
# ==========================================================================
_BUILDERS = {"lm": _lm_cell, "gnn": _gnn_cell, "recsys": _dlrm_cell,
             "anns": _anns_cell}


def build_cell(spec: ArchSpec, shape_id: str, mesh) -> Cell:
    return _BUILDERS[spec.family](spec, spec.shape(shape_id), mesh)


# --------------------------------------------------------------------------
# smoke builders: reduced config + real (tiny) data, runs on one CPU device
# --------------------------------------------------------------------------
def build_smoke(spec: ArchSpec, seed: int = 0):
    """Returns (run_fn, metrics_keys): run_fn() executes one reduced-config
    step on CPU and returns a dict of outputs for assertions."""
    from repro.data import synthetic as syn

    if spec.family == "lm":
        from repro.models import transformer as T
        cfg = spec.smoke_cfg
        key = jax.random.PRNGKey(seed)
        params = T.init_params(cfg, key)
        ocfg = opt.AdamWConfig(lr=1e-3)
        state = opt.adamw_init(params, ocfg)
        batch = jax.tree_util.tree_map(
            jnp.asarray, syn.lm_batch(cfg.vocab, 2, 32, seed))
        ts = jax.jit(T.make_train_step(cfg, ocfg))

        def run():
            p2, s2, m = ts(params, state, batch)
            # one decode step too
            sv = jax.jit(T.make_serve_step(cfg))
            cache = {
                "k": jnp.zeros((cfg.n_layers, 2, 16, cfg.n_kv_heads, cfg.dh), cfg.dtype),
                "v": jnp.zeros((cfg.n_layers, 2, 16, cfg.n_kv_heads, cfg.dh), cfg.dtype),
            }
            logits, _ = sv(p2, cache, batch["tokens"][:, :1], jnp.asarray(0, jnp.int32))
            return {"loss": m["loss"], "logits": logits}
        return run

    if spec.family == "gnn":
        from repro.models import gnn as G
        cfg = spec.smoke_cfg
        task = cfg.task
        b = syn.random_graph_batch(64, 256, 8, cfg.n_classes, n_graphs=4,
                                   seed=seed, task=task)
        b = jax.tree_util.tree_map(jnp.asarray, b)
        params = G.init_gnn(cfg, 8, jax.random.PRNGKey(seed))
        state = opt.adamw_init(params, OCFG)
        ts = jax.jit(G.make_gnn_train_step(cfg, OCFG))

        def run():
            _, _, m = ts(params, state, b)
            out = G.gnn_forward(params, b, cfg)
            return {"loss": m["loss"], "out": out}
        return run

    if spec.family == "recsys":
        from repro.models import dlrm as R
        cfg = spec.smoke_cfg
        params = R.init_dlrm(cfg, jax.random.PRNGKey(seed))
        state = opt.adamw_init(params, OCFG)
        b = jax.tree_util.tree_map(
            jnp.asarray, syn.dlrm_batch(cfg.n_dense, cfg.table_rows(), 64, seed))
        ts = jax.jit(R.make_dlrm_train_step(cfg, OCFG))

        def run():
            _, _, m = ts(params, state, b)
            scores = R.make_dlrm_serve_step(cfg)(params,
                                                 {k: b[k] for k in ("dense", "sparse_ids")})
            return {"loss": m["loss"], "out": scores}
        return run

    # anns
    from repro.core.index import AnnIndex
    from repro.core.spec import SearchSpec
    from repro.data.vectors import make_dataset

    def run():
        ds = make_dataset(n_base=600, n_query=8, dim=32, n_clusters=8, seed=seed)
        idx = AnnIndex.build(ds.base, graph=spec.smoke_cfg.graph,
                             m=spec.smoke_cfg.m, efc=spec.smoke_cfg.efc)
        ids, dists, stats = idx.search(
            ds.queries, spec=SearchSpec(k=5, efs=32,
                                        router=spec.smoke_cfg.router))
        return {"loss": jnp.asarray(0.0), "out": jnp.asarray(dists),
                "ids": ids, "dist_calls": stats.dist_calls}
    return run
