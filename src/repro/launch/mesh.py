"""Production mesh construction (DESIGN.md §6).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init; smoke tests
see the real single device).
"""
from __future__ import annotations

import jax


def _auto(n_axes: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def data_axes(mesh) -> tuple:
    """The axes the batch dimension shards over ('pod' folds into data)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_local_mesh(n: int = 1, name: str = "data"):
    """Mesh over whatever devices exist (tests / examples)."""
    n = min(n, len(jax.devices()))
    return jax.make_mesh((n,), (name,), axis_types=_auto(1))
