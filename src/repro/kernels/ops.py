"""Jit'd public wrappers around the Pallas kernels.

Handles padding to block multiples, dtype plumbing, and the backend
switch: kernels are compiled on a TPU backend and run in interpret mode on
the CPU backend (Python semantics, bit-accurate vs the TPU lowering's
math); any other backend raises, so no caller can fall back to the
interpreter on an accelerator.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.l2_distance import l2_distance_pallas
from repro.kernels.crouting_prune import crouting_prune_pallas
from repro.kernels.fused_expand import fused_expand_pallas
from repro.kernels.gather_distance import gather_distance_pallas
from repro.kernels.pool_merge import pool_merge_pallas
from repro.kernels.sq8_distance import sq8_distance_pallas

ROW_BLOCK = 8   # query rows per grid step (one f32 sublane tile)
LANES = 128


def interpret_mode() -> bool:
    """True on the CPU backend, False on TPU; any other backend raises."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels run compiled on TPU or interpreted "
                       f"on CPU; backend {backend!r} is neither")


def _pad_to(x, mult, axis, value):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    xp = np if isinstance(x, np.ndarray) else jnp    # host-side layouts too
    return xp.pad(x, widths, constant_values=value)


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def _rows(x, value=0):
    """Pad the batch axis to a ROW_BLOCK multiple."""
    return _pad_to(x, ROW_BLOCK, 0, value)


def row_table(x):
    """(..., N, d) -> (..., N*k, 128) f32, each vector zero-padded to
    k = ceil(d/128) lane rows — the layout the gather kernels DMA
    (gather_distance).  The identity at d == 128; already-converted tables
    (width 128) pass through.  NumPy in, NumPy out (the engine converts its
    tables once, on the host: core.search.ensure_kernel_tables)."""
    *lead, n, d = x.shape
    k = pl.cdiv(d, LANES)
    return _pad_to(x.astype(jnp.float32), LANES, x.ndim - 1,
                   0.0).reshape(*lead, n * k, LANES)


def _plane_counts(d: int):
    """(kq word-rows per code row, planes that hold real dimensions)."""
    return pl.cdiv(d, 4 * LANES), min(4, pl.cdiv(d, LANES))


def plane_rows(x):
    """(..., d) f32 -> (..., n_planes*kq, 128): row j*kq + t holds
    dimensions 512t + 128j + [0, 128), zero-padded (sq8_distance)."""
    d = x.shape[-1]
    kq, planes = _plane_counts(d)
    x = _pad_to(x.astype(jnp.float32), 4 * LANES, x.ndim - 1, 0.0)
    x = jnp.swapaxes(x.reshape(*x.shape[:-1], kq, 4, LANES), -3, -2)
    return x[..., :planes, :, :].reshape(*x.shape[:-3], planes * kq, LANES)


def code_words(codes):
    """uint8 codes (..., N, d) -> (..., N*kq, 128) int32: byte j of word w
    in word-row t is dimension 512t + 128j + w (sq8_distance); int32 input
    is taken as already packed."""
    if codes.dtype == jnp.int32:
        return codes
    *lead, n, d = codes.shape
    kq = pl.cdiv(d, 4 * LANES)
    c = _pad_to(codes, 4 * LANES, codes.ndim - 1, 0).astype(jnp.int32)
    c = c.reshape(*lead, n, kq, 4, LANES)
    words = c[..., 0, :] | (c[..., 1, :] << 8) | (c[..., 2, :] << 16) \
        | (c[..., 3, :] << 24)
    return words.reshape(*lead, n * kq, LANES)


def _lane_mask(mask, nbrs, n_rows):
    """int32 [B, L] flags: the caller's mask (default all) AND id in range —
    the kernels DMA row ids unchecked, so an out-of-range id would be an
    out-of-bounds HBM read on TPU."""
    in_range = (nbrs >= 0) & (nbrs < n_rows)
    if mask is not None:
        in_range = in_range & (mask != 0)
    return in_range.astype(jnp.int32)


def l2_distance(q, x, mode: str = "l2", bq: int = 128, bc: int = 256,
                bd: int = 512):
    """Distance matrix [Q, C]; pads freely, slices back.  Blocks are rounded
    to the TPU tiling: query rows to a sublane tile (8 rows of 32-bit, 16
    of 16-bit), candidates and dimensions to 128 lanes."""
    Q, d = q.shape
    C = x.shape[0]
    sub = ROW_BLOCK * 4 // q.dtype.itemsize
    bq_ = min(_round_up(bq, sub), _round_up(Q, sub))
    bc_ = min(_round_up(bc, LANES), _round_up(C, LANES))
    bd_ = min(_round_up(bd, LANES), _round_up(d, LANES))
    qp = _pad_to(q, bq_, 0, 0.0)
    xp = _pad_to(x, bc_, 0, 0.0)
    qp = _pad_to(qp, bd_, 1, 0.0)
    xp = _pad_to(xp, bd_, 1, 0.0)
    out = l2_distance_pallas(qp, xp, bq=bq_, bc=bc_, bd=bd_, mode=mode,
                             interpret=interpret_mode())
    return out[:Q, :C]


def crouting_prune(ed, dcq, bound2, valid, cos_theta):
    """Fused estimate + prune mask; pads B to the row-block, M to lanes.

    dcq/bound2 may be [B] (classic one-node expansion, broadcast over lanes)
    or per-lane [B, M] (beam tiles, where each lane's expansion node — and
    for non-L2 metrics its rank-space bound — differs)."""
    B, M = ed.shape
    bb = ROW_BLOCK
    if dcq.ndim == 1:
        dcq = jnp.broadcast_to(dcq[:, None], (B, M))
    if bound2.ndim == 1:
        bound2 = jnp.broadcast_to(bound2[:, None], (B, M))
    edp = _pad_to(_pad_to(ed, 128, 1, jnp.inf), bb, 0, jnp.inf)
    vp = _pad_to(_pad_to(valid.astype(jnp.int8), 128, 1, 0), bb, 0, 0)
    dcqp = _pad_to(_pad_to(dcq, 128, 1, 0.0), bb, 0, 0.0)
    b2p = _pad_to(_pad_to(bound2, 128, 1, 0.0), bb, 0, 0.0)
    est2, mask = crouting_prune_pallas(edp, dcqp, b2p, vp, cos_theta,
                                       bb=bb, interpret=interpret_mode())
    return est2[:B, :M], mask[:B, :M]


def gather_distance(indices, queries, table, fetch=None):
    """Masked gather + squared-L2 distance [B, L]: lanes with ``fetch == 0``
    (default: none) or an out-of-range id issue no row DMA and report +inf.
    ``table`` is (N, d) or already a ``row_table``."""
    B, d = indices.shape[0], queries.shape[1]
    indices = indices.astype(jnp.int32)
    table = row_table(table)
    fetch = _lane_mask(fetch, indices, table.shape[0] // pl.cdiv(d, LANES))
    d2 = gather_distance_pallas(_rows(indices), _rows(fetch),
                                row_table(_rows(queries)), table, d=d,
                                bb=ROW_BLOCK, interpret=interpret_mode())
    return d2[:B]


def gather_distance_pruned(nbr_ids, prune_mask, queries, table):
    """CRouting-integrated exact path: pruned lanes (``prune_mask != 0``)
    skip their row DMA and report +inf."""
    return gather_distance(nbr_ids, queries, table, fetch=prune_mask == 0)


def pool_merge(pool_d, pool_i, new_d, new_i):
    """Merge new candidates into sorted pools, keep best P."""
    B = pool_d.shape[0]
    args = [pool_d, pool_i.astype(jnp.int32), new_d, new_i.astype(jnp.int32)]
    args = [_rows(a, v) for a, v in zip(args, (jnp.inf, -1, jnp.inf, -1))]
    d, i = pool_merge_pallas(*args, bb=ROW_BLOCK, interpret=interpret_mode())
    return d[:B], i[:B]


def sq8_estimate(nbrs, queries, eval_mask, codes, lo, scale, eps):
    """Stage-1 quantized distance estimate + conservative lower bound over a
    neighbor tile (two-stage engine, core/search.py).

    nbrs [B, L] rows of the code table — uint8 (N, d) or already packed by
    ``code_words``; lanes with eval_mask == 0 (or out-of-range ids) skip
    the code-row DMA and report +inf for both outputs.  Returns (ad2, lb2)
    in squared-Euclidean space.
    """
    B, d = nbrs.shape[0], queries.shape[1]
    nbrs = nbrs.astype(jnp.int32)
    words = code_words(codes)
    evalm = _lane_mask(eval_mask, nbrs,
                       words.shape[0] // _plane_counts(d)[0])
    ad2, lb2 = sq8_distance_pallas(
        _rows(nbrs), plane_rows(_rows(queries)).reshape(-1, LANES),
        plane_rows(lo), plane_rows(scale), plane_rows(eps), _rows(evalm),
        words, d=d, bb=ROW_BLOCK, interpret=interpret_mode())
    return ad2[:B], lb2[:B]


def fused_expand(nbrs, queries, ed, dcq, bound2, cos_theta, table,
                 eval_mask=None, prune_eligible=None):
    """Fused CRouting expansion: estimate + prune + conditional gather +
    exact distance in one kernel (the paper's Alg. 2 inner loop).

    dcq/bound2 may be [B] (broadcast over lanes) or per-lane [B, L] for the
    beam engine's [B, W*M] tiles.  eval_mask marks lanes to evaluate exactly
    when not pruned; prune_eligible marks lanes the estimate test applies
    to.  Both default to "neighbor id in range" (the standalone semantics)
    and are always intersected with it.  ``table`` is (N, d) or already a
    ``row_table``.  Returns (dist2 [B, L], prune [B, L] int8).
    """
    nbrs = nbrs.astype(jnp.int32)
    B, L = nbrs.shape
    if dcq.ndim == 1:
        dcq = jnp.broadcast_to(dcq[:, None], (B, L))
    if bound2.ndim == 1:
        bound2 = jnp.broadcast_to(bound2[:, None], (B, L))
    d = queries.shape[1]
    table = row_table(table)
    n_rows = table.shape[0] // pl.cdiv(d, LANES)
    ed, dcq, bound2 = (_rows(a.astype(jnp.float32)) for a in (ed, dcq, bound2))
    d2, prune = fused_expand_pallas(
        _rows(nbrs), row_table(_rows(queries)), ed, dcq, bound2, cos_theta,
        _rows(_lane_mask(eval_mask, nbrs, n_rows)),
        _rows(_lane_mask(prune_eligible, nbrs, n_rows)), table, d=d,
        bb=ROW_BLOCK, interpret=interpret_mode())
    return d2[:B], prune[:B].astype(jnp.int8)
