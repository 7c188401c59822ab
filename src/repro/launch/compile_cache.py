"""Persistent XLA compilation cache for the entry points.

Called at the start of ``main()`` by every program entry point
(``python -m repro.launch.serve``, ``python -m benchmarks.run``,
``chip_smoke.py``) — never at import, and never from tests.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
changed here; otherwise the cache lives at the fixed path
``<checkout>/.cache/jax_compile`` (git-ignored), so a later run of the same
checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".cache" / "jax_compile"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
