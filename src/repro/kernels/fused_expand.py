"""Pallas TPU kernel: FUSED CRouting expansion step.

One kernel performs the paper's whole inner loop (Alg. 2, lines 7-16 minus
the pool update) over a flat tile of L neighbor slots per query — for the
beam-expansion engine L = W*M (W frontier nodes per hop, each with M
neighbor slots; see core/search.py):

    est2 = ed^2 + dcq^2 - 2*ed*dcq*cos(theta*)        (no vector data)
    prune = prune_eligible & (est2 >= bound2)
    for m in range(L):
        if eval_mask[m] and not prune[m]:
            row = table[nbr[m]]       <-- the point: the HBM row DMA for the
            dist2[m] = |q - row|^2        neighbor vector is *conditionally
        else:                             skipped* for pruned lanes
            dist2[m] = +inf

`ed`, `dcq` and `bound2` are per-lane [B, L]: with a beam each lane belongs
to one of W expansion nodes, so the query distance (and, for non-L2 metrics,
the rank-space bound) varies across the tile.  `eval_mask` marks lanes whose
exact distance the caller wants if not pruned (valid + not-visited, computed
from the status array); `prune_eligible` marks lanes the estimate test
applies to (unvisited + pool-full).  Both default to "nbr id in range" in
the ops wrapper for standalone use.

This is the kernel-level realization of "CRouting skips the distance call":
on TPU the savings are the skipped random HBM reads (DESIGN.md §3).  The
estimate and prune decision run per lane on the scalar core, from SMEM, and
gate that lane's row DMA; the prune mask is written straight to an SMEM
output block.  One pass decides all bb x L lanes of a grid step and starts
every surviving lane's DMA, the step waits for them once, and only the
started lanes are consumed (``gather_distance.gather_lanes``).

Grid: (B/bb,).  cos(theta*) arrives as a (1, 1) SMEM scalar; the per-lane
scalars and the prune mask are (bb, L) SMEM blocks; the query rows and the
distance output are VMEM blocks; the table stays in HBM (``pl.ANY``) in the
row-table layout described in ``gather_distance``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather_distance import (gather_lanes, gather_scratch,
                                           lane_sum, put_lane)


def _expand_kernel(ct_ref, nbr_ref, ed_ref, dcq_ref, bound2_ref, eval_ref,
                   elig_ref, q_ref, table_ref, dist_ref, mask_ref, buf,
                   started, sem, *, bb: int, n_lanes: int, k: int, d: int):
    ct = ct_ref[0, 0]
    dist_ref[...] = jnp.full(dist_ref.shape, jnp.inf, dist_ref.dtype)

    def decide(r, m):
        ed, dcq = ed_ref[r, m], dcq_ref[r, m]
        est2 = jnp.maximum(ed * ed + dcq * dcq - 2.0 * ed * dcq * ct, 0.0)
        prune = (elig_ref[r, m] != 0) & (est2 >= bound2_ref[r, m])
        mask_ref[r, m] = prune.astype(jnp.int32)
        return (eval_ref[r, m] != 0) & ~prune

    def consume(r, m, row):
        diff = q_ref[pl.ds(r * k, k), :] - row
        put_lane(dist_ref, r, m, lane_sum(diff * diff, d))

    gather_lanes(bb, n_lanes, k, decide, nbr_ref, table_ref, buf, started,
                 sem, consume)


@functools.partial(jax.jit, static_argnames=("d", "bb", "interpret"))
def fused_expand_pallas(nbrs, queries, ed, dcq, bound2, cos_theta,
                        eval_mask, prune_eligible, table, *, d: int, bb: int,
                        interpret: bool):
    """nbrs [B,L] int32, queries [B*k,128] f32, ed/dcq/bound2 [B,L] f32,
    eval_mask/prune_eligible [B,L] int32, table [N*k,128] f32 (row-table
    layout), d the real width
    -> (dist2 [B,L] with +inf for pruned/masked lanes, prune [B,L] int32).
    B must be a multiple of bb (the ops wrapper pads)."""
    B, L = nbrs.shape
    k = queries.shape[0] // B
    assert B % bb == 0, "pad the batch to a row-block multiple"
    ct = jnp.asarray(cos_theta, jnp.float32).reshape(1, 1)

    def lanes(space=None):
        return pl.BlockSpec((bb, L), lambda i: (i, 0), memory_space=space)

    smem = pltpu.SMEM
    return pl.pallas_call(
        functools.partial(_expand_kernel, bb=bb, n_lanes=L, k=k, d=d),
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec(memory_space=smem),                 # cos theta*
            lanes(smem),                                     # neighbor ids
            lanes(smem),                                     # edge dists
            lanes(smem),                                     # d(c,q) per lane
            lanes(smem),                                     # bound^2 per lane
            lanes(smem),                                     # eval mask
            lanes(smem),                                     # prune-eligible
            pl.BlockSpec((bb * k, 128), lambda i: (i, 0)),   # query rows
            pl.BlockSpec(memory_space=pl.ANY),               # table in HBM
        ],
        out_specs=[lanes(), lanes(smem)],
        out_shape=[jax.ShapeDtypeStruct((B, L), jnp.float32),
                   jax.ShapeDtypeStruct((B, L), jnp.int32)],
        scratch_shapes=gather_scratch(bb, L, k, jnp.float32),
        interpret=interpret,
    )(ct, nbrs, ed, dcq, bound2, eval_mask, prune_eligible, queries, table)
