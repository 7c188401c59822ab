"""Pallas TPU kernel: fused CRouting estimate + prune decision (paper Alg. 2).

One VPU pass over a batch of neighbor lists — this is the cosine-theorem inner
loop, and by design it never touches vector data (that is the whole point of
CRouting on TPU: the pruned lanes skip their HBM vector fetch):

    est2[b, m]  = ed[b, m]^2 + dcq[b]^2 - 2 * ed[b, m] * dcq[b] * cos_theta
    prune[b, m] = valid[b, m] & (est2 >= bound2[b])

Inputs stream from the adjacency-side arrays only: stored edge distances
(float32 [B, M]), the expansion node's query distance [B], and the per-lane
pool bound [B].  Output is the estimate and an int8 prune mask.

Tiling: grid over B; M lives in the lane dimension (callers pad M to a
multiple of 128; ops.crouting_prune handles it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _prune_kernel(ed_ref, dcq_ref, bound2_ref, valid_ref, ct_ref, est_ref, mask_ref):
    ed = ed_ref[...]                    # [bb, M]
    dcq = dcq_ref[...]                  # [bb, M] per-lane (beam tiles)
    b2 = bound2_ref[...]                # [bb, M]
    ct = ct_ref[0]
    est2 = ed * ed + dcq * dcq - 2.0 * ed * dcq * ct
    est2 = jnp.maximum(est2, 0.0)
    mask = (valid_ref[...] != 0) & (est2 >= b2)
    est_ref[...] = est2
    mask_ref[...] = mask.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def crouting_prune_pallas(ed, dcq, bound2, valid, cos_theta, *,
                          bb: int, interpret: bool):
    """ed [B, M], dcq [B, M], bound2 [B, M], valid [B, M] int8, cos_theta
    scalar -> (est2 [B, M] f32, prune [B, M] int8).

    dcq/bound2 are per-lane: the beam engine packs W expansion nodes per
    query into one [B, W*M] tile, so the expansion-node query distance (and
    for non-L2 metrics the rank-space bound) differs lane to lane.  The ops
    wrapper broadcasts 1-D [B] inputs for the classic single-node case.
    """
    B, M = ed.shape
    bb = min(bb, B)
    assert B % bb == 0, "pad batch to a block multiple (ops wrapper pads)"
    ct = jnp.asarray(cos_theta, jnp.float32).reshape(1)
    grid = (B // bb,)
    return pl.pallas_call(
        _prune_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, M), lambda i: (i, 0)),
            pl.BlockSpec((bb, M), lambda i: (i, 0)),
            pl.BlockSpec((bb, M), lambda i: (i, 0)),
            pl.BlockSpec((bb, M), lambda i: (i, 0)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((bb, M), lambda i: (i, 0)),
            pl.BlockSpec((bb, M), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, M), jnp.float32),
            jax.ShapeDtypeStruct((B, M), jnp.int8),
        ],
        interpret=interpret,
    )(ed, dcq, bound2, valid, ct)
