"""Decoder-only LM family (dense + MoE) with scan-over-layers.

Covers the five assigned LM architectures (granite-8b, phi4-mini-3.8b,
qwen1.5-4b, granite-moe-1b-a400m, arctic-480b).  Parameters are stacked over
the layer axis and the forward pass is one `lax.scan` (+ per-step remat) so
the HLO stays small enough to compile 36-layer × 512-device programs on the
CPU dry-run host.

Steps exposed:
  train_step    causal-LM loss + AdamW update (train_* shapes)
  prefill_step  full-sequence forward that also emits the KV cache (prefill_*)
  serve_step    one-token decode against a KV cache (decode_* / long_*)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.train import optimizer as opt


@dataclasses.dataclass(frozen=True)
class MoeSpec:
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    dense_residual: bool = False   # arctic: dense FFN in parallel with MoE


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    moe: Optional[MoeSpec] = None
    rope_theta: float = 10000.0
    dtype: str = "bfloat16"
    remat: bool = True
    block_q: int = 256
    block_k: int = 1024
    loss_chunk: int = 512
    # dry-run accounting only: unroll the layer scan so XLA cost_analysis
    # sees every layer (while bodies are counted once; EXPERIMENTS.md
    # §Roofline methodology). Never set for real training (compile time).
    unroll_layers: bool = False

    @property
    def dh(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded to 128 so embed/lm_head shard over 'model' (=16);
        loss masks the pad columns (granite-moe's 49155 -> 49280)."""
        return -(-self.vocab // 128) * 128

    def param_count(self) -> int:
        D, F, V, H, Hkv, dh = (self.d_model, self.d_ff, self.vocab,
                               self.n_heads, self.n_kv_heads, self.dh)
        attn = D * (H + 2 * Hkv) * dh + H * dh * D
        if self.moe:
            ffn = self.moe.n_experts * 3 * D * F + D * self.moe.n_experts
            if self.moe.dense_residual:
                ffn += 3 * D * F
        else:
            ffn = 3 * D * F
        per_layer = attn + ffn + 2 * D
        return self.n_layers * per_layer + 2 * V * D + D

    def active_param_count(self) -> int:
        """Activated params per token (MoE: top_k of E experts)."""
        if not self.moe:
            return self.param_count()
        D, F = self.d_model, self.d_ff
        dense = self.param_count() - self.n_layers * self.moe.n_experts * 3 * D * F
        act = self.n_layers * self.moe.top_k * 3 * D * F
        return dense + act


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
def init_params(cfg: LMConfig, key) -> Dict[str, Any]:
    dt = jnp.dtype(cfg.dtype)
    D, F, V = cfg.d_model, cfg.d_ff, cfg.padded_vocab
    H, Hkv, dh, Ln = cfg.n_heads, cfg.n_kv_heads, cfg.dh, cfg.n_layers
    ks = jax.random.split(key, 16)

    def w(k, shape, scale=1.0):
        return L.dense_init(k, shape, dt, scale).astype(dt)

    layer = {
        "wq": w(ks[0], (Ln, D, H * dh)),
        "wk": w(ks[1], (Ln, D, Hkv * dh)),
        "wv": w(ks[2], (Ln, D, Hkv * dh)),
        "wo": w(ks[3], (Ln, H * dh, D)),
        "norm1": jnp.ones((Ln, D), dt),
        "norm2": jnp.ones((Ln, D), dt),
    }
    if cfg.qkv_bias:
        layer["bq"] = jnp.zeros((Ln, H * dh), dt)
        layer["bk"] = jnp.zeros((Ln, Hkv * dh), dt)
        layer["bv"] = jnp.zeros((Ln, Hkv * dh), dt)
    if cfg.moe:
        E = cfg.moe.n_experts
        layer["gate"] = w(ks[4], (Ln, D, E))
        layer["we_gate"] = w(ks[5], (Ln, E, D, F))
        layer["we_up"] = w(ks[6], (Ln, E, D, F))
        layer["we_down"] = w(ks[7], (Ln, E, F, D))
        if cfg.moe.dense_residual:
            layer["wr_gate"] = w(ks[8], (Ln, D, F))
            layer["wr_up"] = w(ks[9], (Ln, D, F))
            layer["wr_down"] = w(ks[10], (Ln, F, D))
    else:
        layer["w_gate"] = w(ks[4], (Ln, D, F))
        layer["w_up"] = w(ks[5], (Ln, D, F))
        layer["w_down"] = w(ks[6], (Ln, F, D))
    return {
        "embed": w(ks[11], (V, D), scale=np.sqrt(D)),  # ~N(0,1) rows
        "layers": layer,
        "final_norm": jnp.ones((D,), dt),
        "lm_head": w(ks[12], (D, V)),
    }


# --------------------------------------------------------------------------
# one transformer block (operating on a single layer's stacked slice)
# --------------------------------------------------------------------------
def _attn(x, lp, cfg: LMConfig, positions, kv_cache=None, kv_mask=None,
          cache_pos=None):
    """Returns (attn_out, (k, v)).  Training/prefill: k/v are the fresh
    per-layer cache slices.  Decode: kv_cache is updated in place at
    cache_pos *before* attending, so the token attends to itself."""
    B, S, D = x.shape
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
    q = x @ lp["wq"]
    k = x @ lp["wk"]
    v = x @ lp["wv"]
    if cfg.qkv_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(B, S, H, dh)
    k = k.reshape(B, S, Hkv, dh)
    v = v.reshape(B, S, Hkv, dh)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    if kv_cache is None:
        o = L.blockwise_causal_attention(q, k, v, block_q=cfg.block_q,
                                         block_k=cfg.block_k)
        out_kv = (L.shard_hint(k, L.BATCH_AXES, "model", None, None),
                  L.shard_hint(v, L.BATCH_AXES, "model", None, None))
    else:
        kc, vc = kv_cache   # [B, T, Hkv, dh]
        kc = jax.lax.dynamic_update_slice(kc, k, (0, cache_pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v, (0, cache_pos, 0, 0))
        o = L.decode_attention(q, kc, vc, kv_mask)
        out_kv = (kc, vc)
    return o.reshape(B, S, H * dh) @ lp["wo"], out_kv


def _ffn(x, lp, cfg: LMConfig):
    B, S, D = x.shape
    if cfg.moe:
        m = cfg.moe
        y = L.moe_layer(x.reshape(B * S, D), lp["gate"], lp["we_gate"],
                        lp["we_up"], lp["we_down"],
                        L.MoeConfig(m.n_experts, m.top_k, m.capacity_factor))
        y = y.reshape(B, S, D)
        if m.dense_residual:
            y = y + L.swiglu(x, lp["wr_gate"], lp["wr_up"], lp["wr_down"])
        return y
    return L.swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])


def _block(x, lp, cfg: LMConfig, positions, kv_cache=None, kv_mask=None,
           cache_pos=None):
    a, kv = _attn(L.rms_norm(x, lp["norm1"]), lp, cfg, positions, kv_cache,
                  kv_mask, cache_pos)
    x = x + a
    x = x + _ffn(L.rms_norm(x, lp["norm2"]), lp, cfg)
    return x, kv


# --------------------------------------------------------------------------
# forward passes
# --------------------------------------------------------------------------
def forward(params, tokens, cfg: LMConfig, collect_cache: bool = False):
    """tokens [B, S] -> hidden [B, S, D] (and stacked KV cache if asked)."""
    B, S = tokens.shape
    x = params["embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    def step(x, lp):
        # carry (= the remat-saved residual stack) lives batch- AND
        # sequence-sharded: Megatron-SP layout, [L,B,S,D]/(data*model) per dev
        x = L.shard_hint(x, L.BATCH_AXES, "model", None)
        f = functools.partial(_block, cfg=cfg, positions=positions)
        if cfg.remat:
            f = jax.checkpoint(f)
        x, kv = f(x, lp)
        return x, kv if collect_cache else 0.0

    x, caches = jax.lax.scan(step, x, params["layers"],
                             unroll=cfg.n_layers if cfg.unroll_layers else 1)
    x = L.rms_norm(x, params["final_norm"])
    return (x, caches) if collect_cache else x


def chunked_ce_loss(h, lm_head, labels, chunk: int, vocab: int):
    """Sequence-chunked causal-LM cross entropy (never materializes [B,S,V]);
    pad-vocab columns are masked out of the logsumexp."""
    B, S, D = h.shape
    chunk = min(chunk, S)
    nc = S // chunk
    hc = jnp.moveaxis(h.reshape(B, nc, chunk, D), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)
    v_pad = lm_head.shape[1]
    col_ok = (jnp.arange(v_pad) < vocab) if v_pad != vocab else None

    def per_chunk(acc, inp):
        hb, lb = inp
        logits = (hb @ lm_head).astype(jnp.float32)        # [B, chunk, Vpad]
        if col_ok is not None:
            logits = jnp.where(col_ok, logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(lse - gold), 0.0

    total, _ = jax.lax.scan(per_chunk, jnp.zeros((), jnp.float32), (hc, lc))
    return total / (B * S)


def loss_fn(params, batch, cfg: LMConfig):
    h = forward(params, batch["tokens"], cfg)
    return chunked_ce_loss(h, params["lm_head"], batch["labels"],
                           cfg.loss_chunk, cfg.vocab)


def make_train_step(cfg: LMConfig, ocfg: opt.AdamWConfig):
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg)
        new_params, new_state, metrics = opt.adamw_update(grads, opt_state, params, ocfg)
        metrics["loss"] = loss
        return new_params, new_state, metrics
    return train_step


def make_prefill_step(cfg: LMConfig):
    def prefill_step(params, tokens):
        h, caches = forward(params, tokens, cfg, collect_cache=True)
        logits = (h[:, -1, :] @ params["lm_head"]).astype(jnp.float32)[:, :cfg.vocab]
        kc, vc = caches     # each [L, B, S, Hkv, dh]
        return logits, {"k": kc, "v": vc}
    return prefill_step


def make_serve_step(cfg: LMConfig):
    """One-token decode. cache k/v: [L, B, T, Hkv, dh]; cur_len scalar."""

    def serve_step(params, cache, token, cur_len):
        B = token.shape[0]
        x = params["embed"][token]                         # [B, 1, D]
        positions = jnp.full((B, 1), cur_len, jnp.int32)
        T = cache["k"].shape[2]
        kv_mask = (jnp.arange(T) <= cur_len)[None, :].repeat(B, 0)

        def step(x, inp):
            lp, kc, vc = inp
            x, (kc, vc) = _block(x, lp, cfg, positions, kv_cache=(kc, vc),
                                 kv_mask=kv_mask, cache_pos=cur_len)
            return x, (kc, vc)

        x, (kc, vc) = jax.lax.scan(step, x, (params["layers"], cache["k"], cache["v"]),
                                   unroll=cfg.n_layers if cfg.unroll_layers else 1)
        x = L.rms_norm(x, params["final_norm"])
        logits = (x[:, 0, :] @ params["lm_head"]).astype(jnp.float32)[:, :cfg.vocab]
        return logits, {"k": kc, "v": vc}

    return serve_step
