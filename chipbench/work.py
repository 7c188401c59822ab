"""The bytes the search algorithm moves, from its counters and the shapes.

One exact distance call reads one float32 base row (``d * 4`` bytes); one
node expansion reads the node's layer-0 adjacency and stored edge lengths
(``M`` int32 ids and ``M`` float32 lengths, ``M * 8`` bytes), where ``M`` is
HNSW's layer-0 degree, ``2 * m``.  This counts what the algorithm needs, not
how a kernel lays its tables out: padding a row to a lane multiple, or a
second copy of a table, is not work.
"""
from __future__ import annotations


def layer0_degree(m: int) -> int:
    return 2 * m


def algorithm_bytes(dist_calls: int, hops: int, dim: int, m: int) -> int:
    return int(dist_calls) * dim * 4 + int(hops) * layer0_degree(m) * 8
