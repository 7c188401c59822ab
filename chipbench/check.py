"""The comparison that decides ``correct``.

The served answers of a sample of finished requests (ids and squared-L2
distances, as the timed path returned them) are held against the float64
brute force of ``data.exact_top_k`` over the same base vectors and query rows.
Four numbers are compared, each with a limit from the configuration file's
``limits``:

* ``recall_miss`` — 1 - recall@k of the served ids against the exact top-k;
* ``dist_gap`` — the widest gap between a served distance and the exact
  distance of the id served with it, over the exact k-th distance of that
  query (a distance computed in a lower precision, or an id altered after
  its distance was taken, reads here; rows with no valid id are
  ``bad_rows``' to catch);
* ``bad_rows`` — rows with an id outside the base, a repeated id, a
  non-finite distance or distances out of order (exact: limit 0);
* ``unanswered`` — requests sent in the window whose future never resolved,
  a minute past the close at the latest (exact: limit 0).
"""
from __future__ import annotations

import numpy as np

from chipbench.data import exact_top_k, recall_at_k

NUMBERS = ("recall_miss", "dist_gap", "bad_rows", "unanswered")


def bad_rows(ids: np.ndarray, dists: np.ndarray, n_base: int) -> int:
    bad = 0
    for row_ids, row_d in zip(ids, dists):
        if (np.any(row_ids < 0) or np.any(row_ids >= n_base)
                or len(set(row_ids.tolist())) != len(row_ids)
                or not np.all(np.isfinite(row_d))
                or np.any(np.diff(row_d) < 0)):
            bad += 1
    return bad


def compare(queries: np.ndarray, base: np.ndarray, ids: np.ndarray,
            dists: np.ndarray, k: int, unanswered: int) -> dict:
    """The four numbers for served ``ids``/``dists`` [q, k] of ``queries``."""
    ref_ids, ref_d, served_exact = exact_top_k(queries, base, k, lookup=ids)
    scale = np.maximum(ref_d[:, k - 1], np.finfo(np.float64).tiny)
    gap = np.abs(dists.astype(np.float64) - served_exact) / scale[:, None]
    gap = gap[np.isfinite(gap)]
    return {
        "recall_miss": 1.0 - recall_at_k(ids, ref_ids, k),
        "dist_gap": float(gap.max()) if gap.size else 0.0,
        "bad_rows": bad_rows(ids, dists, base.shape[0]),
        "unanswered": int(unanswered),
    }


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(all within their limits, {name: {"value", "limit"}})."""
    table = {name: {"value": numbers[name], "limit": limits[name]}
             for name in NUMBERS}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in table.values())
    return bool(ok), table
