"""DLRM (Naumov et al., arXiv:1906.00091) — the MLPerf recsys benchmark config.

JAX has no nn.EmbeddingBag: the lookup is implemented as ``jnp.take`` +
``jax.ops.segment_sum`` (multi-hot capable; Criteo features are single-hot).
The 26 sparse tables (~188M rows x 128) are the hot path; tables row-shard
over the 'model' mesh axis (classic table-parallel layout, DESIGN.md §6).

Steps: train_step (BCE), serve_step (scores), retrieval_step (1 query vs 1M
candidate embeddings — the shape where CRouting applies directly; see
examples/dlrm_retrieval.py for the ANN-served variant).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.train import optimizer as opt

# Criteo-1TB per-feature vocabulary sizes (MLPerf reference, max-ind-range=40M)
CRITEO_VOCAB_SIZES = [
    39884406, 39043, 17289, 7420, 20263, 3, 7120, 1543, 63, 38532951,
    2953546, 403346, 10, 2208, 11938, 155, 4, 976, 14, 39979771,
    25641295, 39664984, 585935, 12972, 108, 36,
]


@dataclasses.dataclass(frozen=True)
class DlrmConfig:
    name: str = "dlrm-mlperf"
    n_dense: int = 13
    n_sparse: int = 26
    embed_dim: int = 128
    bot_mlp: Tuple[int, ...] = (512, 256, 128)
    top_mlp: Tuple[int, ...] = (1024, 1024, 512, 256, 1)
    vocab_sizes: Tuple[int, ...] = tuple(CRITEO_VOCAB_SIZES)
    vocab_cap: int = 0          # >0: cap rows per table (smoke tests)
    dtype: str = "float32"

    def table_rows(self) -> List[int]:
        rows = [min(v, self.vocab_cap) if self.vocab_cap else v
                for v in self.vocab_sizes]
        # pad rows so sharding is even (pad rows are never looked up):
        # big tables to /512 (row-shard over EVERY device, §Perf HC1),
        # small tables to /16 ('model'-axis only)
        return [-(-r // 512) * 512 if r > 512 else -(-r // 16) * 16
                for r in rows]

    def param_count(self) -> int:
        rows = sum(self.table_rows())
        n = rows * self.embed_dim
        dims = (self.n_dense,) + self.bot_mlp
        n += sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
        n_int = self.n_sparse + 1
        d_int = n_int * (n_int - 1) // 2 + self.embed_dim
        dims = (d_int,) + self.top_mlp
        n += sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
        return n


def _mlp_init(key, dims, dt):
    ks = jax.random.split(key, len(dims) - 1)
    return [{"w": (jax.random.normal(k, (a, b)) / np.sqrt(a)).astype(dt),
             "b": jnp.zeros((b,), dt)} for k, a, b in zip(ks, dims[:-1], dims[1:])]


def _mlp(params, x, final_act=None):
    for i, l in enumerate(params):
        x = x @ l["w"] + l["b"]
        if i < len(params) - 1:
            x = jax.nn.relu(x)
        elif final_act is not None:
            x = final_act(x)
    return x


def init_dlrm(cfg: DlrmConfig, key) -> Dict[str, Any]:
    dt = jnp.dtype(cfg.dtype)
    rows = cfg.table_rows()
    ks = jax.random.split(key, len(rows) + 2)
    tables = [
        (jax.random.normal(ks[i], (r, cfg.embed_dim))
         / np.sqrt(cfg.embed_dim)).astype(dt)
        for i, r in enumerate(rows)
    ]
    n_int = cfg.n_sparse + 1
    d_int = n_int * (n_int - 1) // 2 + cfg.embed_dim
    return {
        "tables": tables,
        "bot": _mlp_init(ks[-2], (cfg.n_dense,) + cfg.bot_mlp, dt),
        "top": _mlp_init(ks[-1], (d_int,) + cfg.top_mlp, dt),
    }


# --------------------------------------------------------------------------
# EmbeddingBag: take + segment_sum (JAX-native; DESIGN.md §2 table)
# --------------------------------------------------------------------------
def embedding_bag(table, ids, bag_ids, n_bags, combiner: str = "sum"):
    """Multi-hot lookup: ids [L] rows of table, bag_ids [L] -> [n_bags, dim]."""
    rows = jnp.take(table, ids, axis=0)
    out = jax.ops.segment_sum(rows, bag_ids, num_segments=n_bags)
    if combiner == "mean":
        cnt = jax.ops.segment_sum(jnp.ones_like(ids, jnp.float32), bag_ids,
                                  num_segments=n_bags)
        out = out / jnp.maximum(cnt, 1.0)[:, None]
    return out


def table_parallel_lookup(tables, ids):
    """Explicit table-parallel embedding lookup (§Perf HC1).

    XLA's SPMD gather over row-sharded tables chooses to ALL-GATHER the whole
    table (~96 GB fp32) to every device; this shard_map does the classic
    layout instead: each device masked-gathers the rows it owns and a psum
    (batch-sized, not table-sized) combines.  Tables whose rows don't divide
    the device count stay replicated (they are tiny).  Falls back to plain
    takes without a mesh (smoke tests / single device)."""
    import numpy as np
    from jax.sharding import PartitionSpec as P
    try:
        from jax._src.mesh import thread_resources
        mesh = thread_resources.env.physical_mesh
    except Exception:   # noqa: BLE001 — jax-internal API probe; no-mesh fallback
        mesh = None
    if mesh is None or mesh.empty:
        return [jnp.take(t, ids[:, i], axis=0) for i, t in enumerate(tables)]

    axes = tuple(mesh.axis_names)
    ndev = int(np.prod([mesh.shape[a] for a in axes]))
    big = [t.shape[0] % ndev == 0 and t.shape[0] >= ndev for t in tables]

    def local(tables_loc, ids_rep):
        pos = jnp.int32(0)
        for a in axes:
            pos = pos * mesh.shape[a] + jax.lax.axis_index(a)
        parts, direct = [], {}
        for i, t in enumerate(tables_loc):
            if big[i]:
                rows_loc = t.shape[0]
                idx = ids_rep[:, i] - pos * rows_loc
                ok = (idx >= 0) & (idx < rows_loc)
                safe = jnp.clip(idx, 0, rows_loc - 1)
                parts.append(jnp.take(t, safe, axis=0)
                             * ok[:, None].astype(t.dtype))
            else:
                direct[i] = jnp.take(t, ids_rep[:, i], axis=0)
        if parts:
            summed = jax.lax.psum(jnp.stack(parts), axes)   # ONE batch-sized psum
        out, j = [], 0
        for i in range(len(tables_loc)):
            if big[i]:
                out.append(summed[j])
                j += 1
            else:
                out.append(direct[i])
        return tuple(out)

    in_specs = ([P(axes, None) if b else P(None, None) for b in big],
                P(None, None))
    out_specs = tuple(P(None, None) for _ in tables)
    return list(jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                              out_specs=out_specs, check_vma=False)(tables, ids))


def dot_interaction(vectors):
    """vectors [B, n, d] -> lower-triangle pairwise dots [B, n(n-1)/2]."""
    B, n, d = vectors.shape
    z = jnp.einsum("bnd,bmd->bnm", vectors, vectors)
    iu, ju = np.tril_indices(n, k=-1)
    return z[:, iu, ju]


def dlrm_forward(params, batch, cfg: DlrmConfig):
    """batch: dense [B, 13] float, sparse_ids [B, 26] int32 (single-hot)."""
    dense, sparse = batch["dense"], batch["sparse_ids"]
    B = dense.shape[0]
    x = _mlp(params["bot"], dense)                       # [B, 128]
    embs = table_parallel_lookup(params["tables"], sparse)  # single-hot bags
    z = jnp.stack([x] + embs, axis=1)                    # [B, 27, 128]
    inter = dot_interaction(z)                           # [B, 351]
    feat = jnp.concatenate([x, inter], axis=-1)
    return _mlp(params["top"], feat)[:, 0]               # logits [B]


def dlrm_loss(params, batch, cfg: DlrmConfig):
    logits = dlrm_forward(params, batch, cfg).astype(jnp.float32)
    y = batch["labels"].astype(jnp.float32)
    return jnp.mean(jnp.maximum(logits, 0) - logits * y
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def make_dlrm_train_step(cfg: DlrmConfig, ocfg: opt.AdamWConfig):
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(dlrm_loss)(params, batch, cfg)
        newp, news, metrics = opt.adamw_update(grads, opt_state, params, ocfg)
        metrics["loss"] = loss
        return newp, news, metrics
    return train_step


def make_dlrm_serve_step(cfg: DlrmConfig):
    def serve_step(params, batch):
        return jax.nn.sigmoid(dlrm_forward(params, batch, cfg).astype(jnp.float32))
    return serve_step


def make_retrieval_step(cfg: DlrmConfig, k: int = 100):
    """Score one user query against n_candidates item embeddings (batched dot
    — never a loop) and return top-k.  The CRouting-ANN alternative to this
    brute-force scorer lives in examples/dlrm_retrieval.py."""

    def retrieval_step(query, candidates):
        # query [Bq, d], candidates [Nc, d] -> (scores [Bq, k], ids [Bq, k])
        scores = query @ candidates.T                    # MXU batched dot
        top, idx = jax.lax.top_k(scores, k)
        return top, idx

    return retrieval_step
