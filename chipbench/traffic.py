"""The one traffic generator: a mix is a JSON file of parameters.

Keys of a mix file (``chipbench/traffic/<name>.json``):

* ``loop`` — ``"closed"``: ``clients`` callers, each sending its next
  request when the previous one has resolved; ``"open"``: requests sent on
  a schedule whatever the server does, at ``rate_per_s`` on average;
* ``rows_per_request`` — query rows in each request;
* ``buckets`` — the frontend's batch-shape ladder, all of it warmed in set-up;
* ``sample_requests`` — finished requests held against the reference.

An open-loop window of ``seconds`` sends exactly ``round(rate * seconds)``
requests.  Their gaps are one fixed sequence of exponential draws (from
``GAP_SEED``), scaled so that the last one is due before the window closes,
and cut into blocks of ``BLOCK`` consecutive gaps; ``--seed`` only permutes
the blocks.  So every seed offers the same load and the same bursts, in
another order, and the run-to-run spread is neither the spread of a Poisson
count nor that of a different set of bursts.

The query rows are fixed the same way: the pool is cut into blocks of
``rows_per_request`` consecutive rows, one block per request, and ``--seed``
only orders the blocks (the same order in each pass over the pool).  So a
batch holds the same queries, and costs the same hop-loop iterations,
whatever the seed.
"""
from __future__ import annotations

import numpy as np

BLOCK = 256
GAP_SEED = 0

KEYS = {"closed": {"loop", "clients", "rows_per_request", "buckets",
                   "sample_requests", "why"},
        "open": {"loop", "rate_per_s", "rows_per_request", "buckets",
                 "sample_requests", "why"}}


def validate(mix: dict) -> dict:
    loop = mix.get("loop")
    if loop not in KEYS:
        raise ValueError(f"traffic loop must be one of {sorted(KEYS)}, got {loop!r}")
    missing = KEYS[loop] - set(mix)
    extra = set(mix) - KEYS[loop]
    if missing or extra:
        raise ValueError(f"traffic keys: missing {sorted(missing)}, "
                         f"unknown {sorted(extra)}")
    rows, buckets = int(mix["rows_per_request"]), list(mix["buckets"])
    if not 1 <= rows <= buckets[-1]:
        raise ValueError(f"rows_per_request {rows} outside [1, {buckets[-1]}]")
    return mix


def gaps(rate_per_s: float, seconds: float, seed: int) -> np.ndarray:
    """The window's inter-arrival gaps: one fixed sequence, its blocks of
    ``BLOCK`` gaps in the seed's order."""
    n = max(1, int(round(rate_per_s * seconds)))
    g = np.random.default_rng(GAP_SEED).exponential(1.0, size=n)
    g *= seconds * (1.0 - 0.5 / n) / g.sum()
    blocks = [g[i:i + BLOCK] for i in range(0, n, BLOCK)]
    order = np.random.default_rng(seed).permutation(len(blocks))
    return np.concatenate([blocks[i] for i in order])


def arrival_offsets(rate_per_s: float, seconds: float, seed: int) -> np.ndarray:
    """Send times in seconds from the window's start, ascending, all < seconds."""
    g = gaps(rate_per_s, seconds, seed)
    return np.cumsum(g) - g[0]


def block_order(n_query: int, rows_per_request: int, seed: int) -> np.ndarray:
    """The seed's order of the pool's blocks of ``rows_per_request`` rows."""
    return np.random.default_rng(seed).permutation(max(1, n_query // rows_per_request))


def request_rows(index: int, rows_per_request: int, n_query: int,
                 order: np.ndarray) -> np.ndarray:
    """Query-pool rows of request ``index``: the block that ``order`` puts
    in its place, cycling through the pool."""
    start = int(order[index % len(order)]) * rows_per_request
    return np.arange(start, start + rows_per_request) % n_query
