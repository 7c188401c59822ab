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
expansion (``fused_expand``) and the SQ8 stage-1 kernel (``sq8_distance``)
and works on a whole grid step at once: one pass over the block's bb query
rows x L lanes decides each lane and starts its DMA, so every fetch of the
block is in flight before the first wait; the block then waits once for
all of them and consumes only the lanes that started one.

Grid: (B/bb,), bb query rows per step.  Neighbor ids and flags are (bb, L)
SMEM blocks (the scalar core drives the DMAs); the query rows and the
(bb, L) output are VMEM blocks; the table stays in HBM (``pl.ANY``).
Per-step scratch (``gather_scratch``): a (bb*L*k, 128) VMEM row buffer,
one slot per lane that can start — 1 MiB at bb = 8, L = 32, d = 960 and
8 MiB at L = 256 — and a bb*L-entry SMEM list of the started lanes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Lanes the start pass decides per loop trip, unrolled (where L allows).
# On a v5e, 32 took 135-137 us per B = 128, L = 32 call with no lane fetching,
# 16 took 151-158 us and a rolled loop 197-199 us: the pass is scalar-bound.
LANES_PER_TRIP = 32


def _row_copy(table_ref, row, buf, slot, k, sem):
    return pltpu.make_async_copy(table_ref.at[pl.ds(row * k, k)],
                                 buf.at[pl.ds(slot * k, k)], sem)


def gather_scratch(bb, n_lanes, k, dtype):
    """Scratch shapes of ``gather_lanes`` for a (bb, n_lanes) block of
    k-row vectors of ``dtype``: the row buffer, the started-lane list and
    the DMA semaphore, in the order ``gather_lanes`` takes them."""
    return [pltpu.VMEM((bb * n_lanes * k, 128), dtype),
            pltpu.SMEM((bb * n_lanes,), jnp.int32),
            pltpu.SemaphoreType.DMA(())]


def gather_lanes(bb, n_lanes, k, fetch_fn, nbr_ref, table_ref, buf, started,
                 sem, consume):
    """Fetch the rows of every flagged lane of a bb-row block, then consume
    them.

    ``fetch_fn(r, m)`` -> scalar bool decides lane m of query row r
    (evaluated once, on the scalar core); ``consume(r, m, rows)`` gets the
    lane's (k, 128) VMEM rows after its DMA completed.  Unflagged lanes
    start no DMA and are not consumed.

    The first pass starts the j-th flagged lane's DMA into buffer slot j
    and records its flat index r * n_lanes + m as ``started[j]``.  It runs
    unrolled over a chunk of lanes per loop trip: the chunk's decisions
    first, with no branch between them so that their SMEM loads and
    arithmetic schedule together, then the chunk's DMA starts.
    Every copy signals the one shared semaphore with the same byte count,
    and a wait is met by any copy's bytes, not its own: so the block waits
    once per started lane before it reads any slot, and only then consumes
    the started lanes, in the order they started.
    """
    chunk = math.gcd(n_lanes, LANES_PER_TRIP)

    def start_chunk(r, c, count):
        lanes = [c * chunk + u for u in range(chunk)]
        go = [fetch_fn(r, m) for m in lanes]
        for m, g in zip(lanes, go):
            @pl.when(g)
            def _(m=m, slot=count):
                _row_copy(table_ref, nbr_ref[r, m], buf, slot, k, sem).start()
                started[slot] = r * n_lanes + m
            count = count + g.astype(jnp.int32)
        return count

    def start_row(r, count):
        return jax.lax.fori_loop(
            0, n_lanes // chunk, lambda c, n: start_chunk(r, c, n), count)

    count = jax.lax.fori_loop(0, bb, start_row, jnp.int32(0))

    def wait(j, carry):
        _row_copy(table_ref, 0, buf, 0, k, sem).wait()
        return carry

    jax.lax.fori_loop(0, count, wait, 0)

    def finish(j, carry):
        i = started[j]
        consume(i // n_lanes, i % n_lanes, buf[pl.ds(j * k, k), :])
        return carry

    jax.lax.fori_loop(0, count, finish, 0)


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


def _gather_kernel(nbr_ref, fetch_ref, q_ref, table_ref, o_ref, buf,
                   started, sem, *, bb: int, n_lanes: int, k: int, d: int):
    o_ref[...] = jnp.full(o_ref.shape, jnp.inf, o_ref.dtype)

    def consume(r, m, row):
        diff = q_ref[pl.ds(r * k, k), :] - row
        put_lane(o_ref, r, m, lane_sum(diff * diff, d))

    gather_lanes(bb, n_lanes, k, lambda r, m: fetch_ref[r, m] != 0, nbr_ref,
                 table_ref, buf, started, sem, consume)


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
        scratch_shapes=gather_scratch(bb, L, k, jnp.float32),
        interpret=interpret,
    )(indices, fetch, queries, table)
