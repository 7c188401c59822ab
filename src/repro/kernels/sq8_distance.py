"""Pallas TPU kernel: SQ8 quantized distance estimate + lower bound.

Stage 1 of the two-stage distance engine (core/search.py,
``SearchSpec.estimate``): for each candidate lane the kernel DMAs the
neighbor's **uint8 code row**, dequantizes it against the per-dimension
affine grid and emits

    ad2[m] = |q - xhat|^2                     (the quantized estimate)
    lb2[m] = max(ad2 - 2 * sum|q - xhat|*eps, 0)   (conservative lower bound)

per lane — the same f32 expression as ``repro.quant.sq8.sq8_estimate``
(the jnp oracle), so stage-1 skip decisions agree between the jnp and
Pallas engines (bit-for-bit at d <= 128 in interpret mode).  Lanes with
``eval_mask == 0`` issue no code-row DMA (``gather_distance.gather_lanes``)
and report +inf.

Code layout: a row DMA moves whole 128-lane rows of a 32-bit table, so the
codes travel packed four to an int32 word (``ops.code_words``): word w of
word-row t holds dimension 512t + 128j + w in byte j, kq = ceil(d / 512)
word-rows per vector.  Unpacking byte j of every word yields "plane" j —
128 consecutive dimensions in lane order — so no lane shuffle is needed.
The query and lo/scale/eps rows arrive in the matching plane layout
(``ops.plane_rows``).  A code row costs ceil(d / 512) * 512 bytes: d bytes
at d = 512, 960 -> 1024, but 512 (the fp32 row's size) at d = 128.

Grid: (B/bb,).  Neighbor ids and the eval mask are (bb, L) SMEM blocks;
q rows, lo/scale/eps and the outputs are VMEM blocks; the code words stay
in HBM (``pl.ANY``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather_distance import (gather_lanes, gather_scratch,
                                           lane_sum, put_lane)


def _sq8_kernel(nbr_ref, eval_ref, q_ref, lo_ref, scale_ref, eps_ref,
                words_ref, ad2_ref, lb2_ref, buf, started, sem, *, bb: int,
                n_lanes: int, kq: int, n_planes: int, d: int):
    rows = kq * n_planes
    lo, scale, eps = lo_ref[...], scale_ref[...], eps_ref[...]
    ad2_ref[...] = jnp.full(ad2_ref.shape, jnp.inf, ad2_ref.dtype)
    lb2_ref[...] = jnp.full(lb2_ref.shape, jnp.inf, lb2_ref.dtype)

    def plane_sum(x):
        return lane_sum(x, d) if n_planes == 1 else jnp.sum(x, keepdims=True)

    def consume(r, m, words):
        q = q_ref[pl.ds(r * rows, rows), :]
        ad2 = slack = 0.0
        for j in range(n_planes):
            p = slice(j * kq, (j + 1) * kq)
            code = ((words >> (8 * j)) & 0xFF).astype(jnp.float32)
            delta = q[p] - (lo[p] + code * scale[p])
            ad2 = ad2 + plane_sum(delta * delta)
            slack = slack + plane_sum(jnp.abs(delta) * eps[p])
        put_lane(ad2_ref, r, m, ad2)
        put_lane(lb2_ref, r, m, jnp.maximum(ad2 - 2.0 * slack, 0.0))

    gather_lanes(bb, n_lanes, kq, lambda r, m: eval_ref[r, m] != 0, nbr_ref,
                 words_ref, buf, started, sem, consume)


@functools.partial(jax.jit, static_argnames=("d", "bb", "interpret"))
def sq8_distance_pallas(nbrs, queries, lo, scale, eps, eval_mask, words, *,
                        d: int, bb: int, interpret: bool):
    """nbrs [B,L] int32, queries [B*R,128] f32 and lo/scale/eps [R,128] f32
    in plane layout (R = kq * n_planes rows per vector), eval_mask [B,L]
    int32, words [N*kq,128] int32 packed codes, d the real width
    -> (ad2 [B,L] f32, lb2 [B,L] f32), +inf for masked lanes.
    B must be a multiple of bb (the ops wrapper pads)."""
    B, L = nbrs.shape
    rows = lo.shape[0]
    kq = pl.cdiv(d, 512)
    assert B % bb == 0, "pad the batch to a row-block multiple"

    def lanes(space=None):
        return pl.BlockSpec((bb, L), lambda i: (i, 0), memory_space=space)

    def grid_rows():
        return pl.BlockSpec((rows, 128), lambda i: (0, 0))

    return pl.pallas_call(
        functools.partial(_sq8_kernel, bb=bb, n_lanes=L, kq=kq,
                          n_planes=rows // kq, d=d),
        grid=(B // bb,),
        in_specs=[
            lanes(pltpu.SMEM),                               # neighbor ids
            lanes(pltpu.SMEM),                               # eval mask
            pl.BlockSpec((bb * rows, 128), lambda i: (i, 0)),  # query rows
            grid_rows(), grid_rows(), grid_rows(),           # lo/scale/eps
            pl.BlockSpec(memory_space=pl.ANY),               # codes in HBM
        ],
        out_specs=[lanes(), lanes()],
        out_shape=[jax.ShapeDtypeStruct((B, L), jnp.float32),
                   jax.ShapeDtypeStruct((B, L), jnp.float32)],
        scratch_shapes=gather_scratch(bb, L, kq, jnp.int32),
        interpret=interpret,
    )(nbrs, eval_mask, queries, lo, scale, eps, words)
