"""Pallas TPU kernel: sorted-pool merge via an in-VMEM bitonic network.

The second hot spot of best-first search: merging M freshly-computed
candidate distances into the sorted size-P result pool each hop.  XLA lowers
the naive concat+argsort to a full sort; here the merge is a fixed
compare-exchange network over a power-of-two padded buffer held in vregs —
data-independent control flow, exactly what the VPU wants.

Pallas TPU has no 64-bit sort lanes, so the network compares on the
distance tensor and applies identical selects to the id tensor; ties on
distance resolve to the smaller id.  Each exchange stage pairs lane l with
lane l ^ j: both partners come from lane rotations (``pltpu.roll``) by j and
by L - j, and the rotated lane iota picks which rotation holds the partner,
so the network needs no reshape and no gather.

Grid: one program per block of bb batch rows.  Pool and candidates are
concatenated and padded (outside the kernel, in the same jit) to a network
of L = pow2 >= 128 lanes, one full-width VMEM block per row block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _network_width(n: int) -> int:
    """Power-of-two network length >= n, at least one 128-lane vreg row."""
    p = 128
    while p < n:
        p *= 2
    return p


def _bitonic_stages(L: int):
    """Yield (stride, block) pairs of a full bitonic sort network of length L."""
    k = 2
    while k <= L:
        j = k // 2
        while j >= 1:
            yield j, k
            j //= 2
        k *= 2


def _sort_kernel(d_ref, i_ref, out_d_ref, out_i_ref, *, L: int):
    d, i = d_ref[...], i_ref[...]                              # [bb, L]
    lane = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    for j, k in _bitonic_stages(L):
        # partner of lane l is l ^ j: one of the two rotations holds it
        via_j = pltpu.roll(lane, j, 1) == (lane ^ j)
        p_d = jnp.where(via_j, pltpu.roll(d, j, 1), pltpu.roll(d, L - j, 1))
        p_i = jnp.where(via_j, pltpu.roll(i, j, 1), pltpu.roll(i, L - j, 1))
        lo = (lane & j) == 0                 # lane is the pair's first slot
        up = (lane & k) == 0                 # block sorts ascending
        # (a, b) = (first, second) slot of the pair, seen from either lane
        a_d, b_d = jnp.where(lo, d, p_d), jnp.where(lo, p_d, d)
        a_i, b_i = jnp.where(lo, i, p_i), jnp.where(lo, p_i, i)
        # lexicographic (dist, id): ties resolve to the smaller id
        a_min = (a_d < b_d) | ((a_d == b_d) & (a_i <= b_i))
        take_min = lo == up
        first = a_min == take_min            # keep the pair's first slot
        d = jnp.where(first, a_d, b_d)
        i = jnp.where(first, a_i, b_i)
    out_d_ref[...] = d
    out_i_ref[...] = i


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def pool_merge_pallas(pool_d, pool_i, new_d, new_i, *, bb: int,
                      interpret: bool):
    """pool_d/i [B, P] sorted asc, new_d/i [B, M] -> best-P of the union,
    sorted.  Ties on distance resolve to the smaller id (deterministic).
    B must be a multiple of bb (the ops wrapper pads)."""
    B, P = pool_d.shape
    assert B % bb == 0, "pad the batch to a row-block multiple"
    L = _network_width(P + new_d.shape[1])
    d = jnp.concatenate([pool_d, new_d], axis=1)
    i = jnp.concatenate([pool_i, new_i], axis=1)
    # network pad must sort AFTER every real input under the (dist, id)
    # tie-break, or +inf pool sentinels get displaced by fake entries — so
    # pad ids with int32 max, not -1
    pad = ((0, 0), (0, L - d.shape[1]))
    d = jnp.pad(d, pad, constant_values=jnp.inf)
    i = jnp.pad(i, pad, constant_values=jnp.iinfo(jnp.int32).max)
    spec = pl.BlockSpec((bb, L), lambda r: (r, 0))
    out_d, out_i = pl.pallas_call(
        functools.partial(_sort_kernel, L=L),
        grid=(B // bb,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((B, L), d.dtype),
                   jax.ShapeDtypeStruct((B, L), i.dtype)],
        interpret=interpret,
    )(d, i)
    return out_d[:, :P], out_i[:, :P]
