"""Data for a cell, and the plain reference it is held to.

``make_base_and_queries`` is a copy of ``make_dataset`` in
``src/repro/data/vectors.py`` (the hierarchical Gaussian mixture: unit
super-cluster directions, sub-cluster centres around them, points around the
centres), kept here so that a change to the program cannot move the
benchmark's inputs.  ``exact_top_k`` is the brute-force top-k of
``exact_ground_truth`` in the same file, computed here in float64 so that it
is the exact answer for the float32 data, and ``recall_at_k`` is that file's
recall.  None of this imports the program.
"""
from __future__ import annotations

import numpy as np


def make_base_and_queries(n_base: int, n_query: int, dim: int, n_clusters: int,
                          cluster_std: float, sub_spread: float, seed: int):
    """(base [n_base, dim] f32, queries [n_query, dim] f32) from ``seed``."""
    rng = np.random.default_rng(seed)
    n_super = max(1, int(np.sqrt(n_clusters)))
    n_sub = max(1, n_clusters // n_super)
    sup = rng.normal(size=(n_super, dim)).astype(np.float32)
    sup /= np.linalg.norm(sup, axis=1, keepdims=True)
    centers = (sup[:, None, :] + sub_spread
               * rng.normal(size=(n_super, n_sub, dim)).astype(np.float32))
    centers = centers.reshape(n_super * n_sub, dim)

    def sample(n, salt):
        r = np.random.default_rng(seed * 1_000_003 + salt)
        which = r.integers(0, centers.shape[0], size=n)
        x = centers[which] + cluster_std * r.normal(size=(n, dim)).astype(np.float32)
        return x.astype(np.float32)

    return sample(n_base, 1), sample(n_query, 2)


def squared_l2_f64(queries: np.ndarray, base64: np.ndarray) -> np.ndarray:
    """[q, n] squared Euclidean distances in float64."""
    q = queries.astype(np.float64)
    d = (np.sum(q * q, axis=1)[:, None] + np.sum(base64 * base64, axis=1)[None, :]
         - 2.0 * (q @ base64.T))
    return np.maximum(d, 0.0)


def exact_top_k(queries: np.ndarray, base: np.ndarray, k: int,
                lookup: np.ndarray | None = None, block: int = 256):
    """Brute-force float64 top-k, in blocks of query rows.

    Returns (ids [q, k] ordered by (distance, id), distances [q, k], and,
    where ``lookup`` [q, j] holds base ids, their exact distances [q, j]
    (ids outside the base read NaN))."""
    base64 = base.astype(np.float64)
    n = base.shape[0]
    ids = np.empty((queries.shape[0], k), np.int64)
    dists = np.empty((queries.shape[0], k), np.float64)
    looked = None if lookup is None else np.full(lookup.shape, np.nan)
    for s in range(0, queries.shape[0], block):
        d = squared_l2_f64(queries[s:s + block], base64)
        part = np.argpartition(d, kth=k - 1, axis=1)[:, :k]
        pd = np.take_along_axis(d, part, axis=1)
        order = np.lexsort((part, pd), axis=1)
        ids[s:s + block] = np.take_along_axis(part, order, axis=1)
        dists[s:s + block] = np.take_along_axis(pd, order, axis=1)
        if lookup is not None:
            lk = lookup[s:s + block]
            ok = (lk >= 0) & (lk < n)
            got = np.take_along_axis(d, np.where(ok, lk, 0), axis=1)
            looked[s:s + block] = np.where(ok, got, np.nan)
    return ids, dists, looked


def recall_at_k(found_ids: np.ndarray, gt_ids: np.ndarray, k: int) -> float:
    """|found ∩ true| / k, averaged over queries."""
    hits = 0
    for f, g in zip(found_ids[:, :k], gt_ids[:, :k]):
        hits += len(set(int(i) for i in f if i >= 0) & set(int(i) for i in g))
    return hits / (len(gt_ids) * k)
