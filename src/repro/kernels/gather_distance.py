"""Pallas TPU kernel: masked row gather + squared-L2 distance.

The exact-distance path of one expansion: for each (query b, neighbor slot
m) whose fetch flag is set, the neighbor's vector row is DMA'd from the
HBM-resident table into a VMEM scratch row (``pltpu.make_async_copy``), and
``|q_b - row|^2`` is written to lane m of the output.  Lanes whose flag is
clear issue no DMA and report +inf — on TPU the skipped distance call is a
skipped random HBM read (DESIGN.md §3).

Row layout: a row DMA must move whole 128-lane rows of a 32-bit table (the
TPU compiler refuses a one-row slice of an (N, d) table unless d == 128),
so the kernels read a *row table*: the (N, d) table zero-padded to
k = ceil(d / 128) lane rows per vector and stored flat as (N*k, 128)
(``ops.row_table``).  At d = 128 that is the table itself.

The DMA machinery here (``gather_lanes``) is shared with the fused CRouting
expansion (``fused_expand``) and the SQ8 stage-1 kernel (``sq8_distance``):
per query row, one loop starts every flagged lane's DMA on a shared
semaphore, and a second loop waits for each and consumes the row, so a
query row's fetches are in flight together.

Grid: (B/bb,), bb query rows per step.  Neighbor ids and flags are (bb, L)
SMEM blocks (the scalar core drives the DMAs); the query rows and the
(bb, L) output are VMEM blocks; the table stays in HBM (``pl.ANY``).
Per-step VMEM: the (L*k, 128) row scratch plus the blocks — about 1 MB at
L = 256, d = 960.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _row_copy(table_ref, row, buf, m, k, sem):
    return pltpu.make_async_copy(table_ref.at[pl.ds(row * k, k)],
                                 buf.at[pl.ds(m * k, k)], sem)


def gather_lanes(r, n_lanes, k, fetch_fn, nbr_ref, table_ref, buf, flags,
                 sem, consume):
    """Fetch the rows of query row ``r``'s flagged lanes, then consume them.

    ``fetch_fn(m)`` -> scalar bool decides lane m (evaluated once, on the
    scalar core); ``consume(m, row)`` gets the lane's (k, 128) VMEM rows
    after its DMA completed.  Unflagged lanes start no DMA and are not
    consumed.
    """
    def start(m, carry):
        go = fetch_fn(m)
        flags[m] = go.astype(jnp.int32)

        @pl.when(go)
        def _():
            _row_copy(table_ref, nbr_ref[r, m], buf, m, k, sem).start()
        return carry

    jax.lax.fori_loop(0, n_lanes, start, 0)

    def finish(m, carry):
        @pl.when(flags[m] != 0)
        def _():
            _row_copy(table_ref, 0, buf, m, k, sem).wait()
            consume(m, buf[pl.ds(m * k, k), :])
        return carry

    jax.lax.fori_loop(0, n_lanes, finish, 0)


def put_lane(out_ref, r, m, value):
    """out_ref[r, m] = value ((1, 1) vector) via a lane-iota select on the
    row, so no dynamic lane index reaches Mosaic."""
    row = out_ref[pl.ds(r, 1), :]
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    out_ref[pl.ds(r, 1), :] = jnp.where(lane == m, value, row)


def lane_sum(x, d: int):
    """(k, 128) per-dimension terms of a d-wide vector -> (1, 1) sum.  At
    k = 1 the sum runs over exactly the d real lanes, the same reduction the
    jnp engine makes over a d-wide row (bit-equal in interpret mode)."""
    if x.shape[0] == 1:
        return jnp.sum(x[:, :d], axis=1, keepdims=True)
    return jnp.sum(x, keepdims=True)


def _gather_kernel(nbr_ref, fetch_ref, q_ref, table_ref, o_ref, buf, flags,
                   sem, *, bb: int, n_lanes: int, k: int, d: int):
    o_ref[...] = jnp.full(o_ref.shape, jnp.inf, o_ref.dtype)

    def per_row(r, carry):
        q = q_ref[pl.ds(r * k, k), :]

        def consume(m, row):
            diff = q - row
            put_lane(o_ref, r, m, lane_sum(diff * diff, d))

        gather_lanes(r, n_lanes, k, lambda m: fetch_ref[r, m] != 0, nbr_ref,
                     table_ref, buf, flags, sem, consume)
        return carry

    jax.lax.fori_loop(0, bb, per_row, 0)


@functools.partial(jax.jit, static_argnames=("d", "bb", "interpret"))
def gather_distance_pallas(indices, fetch, queries, table, *, d: int,
                           bb: int, interpret: bool):
    """indices [B, L] int32 (vector ids), fetch [B, L] int32, queries
    [B*k, 128] and table [N*k, 128] f32 in row-table layout, d the real
    width -> dist2 [B, L] f32, +inf where fetch == 0.  B must be a multiple
    of bb (the ops wrapper pads)."""
    B, L = indices.shape
    k = queries.shape[0] // B
    assert B % bb == 0, "pad the batch to a row-block multiple"
    return pl.pallas_call(
        functools.partial(_gather_kernel, bb=bb, n_lanes=L, k=k, d=d),
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec((bb, L), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),           # vector ids
            pl.BlockSpec((bb, L), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),           # fetch flags
            pl.BlockSpec((bb * k, 128), lambda i: (i, 0)),   # query rows
            pl.BlockSpec(memory_space=pl.ANY),               # table in HBM
        ],
        out_specs=pl.BlockSpec((bb, L), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, L), jnp.float32),
        scratch_shapes=[pltpu.VMEM((L * k, 128), jnp.float32),
                        pltpu.SMEM((L,), jnp.int32),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(indices, fetch, queries, table)
