"""Kernel micro-benchmarks (interpret-mode timings are NOT TPU performance —
they validate call overhead and feed the us_per_call column; TPU kernel times
come from the chip benchmark's profiler trace, ``chipbench/``)."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from benchmarks.common import emit, timed
from repro.kernels import ops, ref


def kernels_micro():
    rng = np.random.default_rng(0)
    derived = {}

    q = jnp.asarray(rng.normal(size=(128, 128)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(1024, 128)).astype(np.float32))
    dt, _ = timed(lambda: ops.l2_distance(q, x).block_until_ready())
    dt_ref, _ = timed(lambda: ref.l2_distance_ref(q, x).block_until_ready())
    derived["l2_distance"] = {"us": round(dt * 1e6, 1),
                              "ref_us": round(dt_ref * 1e6, 1),
                              "gflops": round(2 * 128 * 1024 * 128 / dt / 1e9, 2)}

    ed = jnp.asarray(rng.uniform(0.1, 2, size=(64, 128)).astype(np.float32))
    dcq = jnp.asarray(rng.uniform(0.1, 2, size=(64,)).astype(np.float32))
    b2 = jnp.asarray(rng.uniform(1, 4, size=(64,)).astype(np.float32))
    va = jnp.ones((64, 128), jnp.int8)
    dt, _ = timed(lambda: ops.crouting_prune(ed, dcq, b2, va, 0.15)[0]
                  .block_until_ready())
    derived["crouting_prune"] = {"us": round(dt * 1e6, 1)}

    table = jnp.asarray(rng.normal(size=(4096, 128)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, 4096, size=(8, 16)).astype(np.int32))
    qs = jnp.asarray(rng.normal(size=(8, 128)).astype(np.float32))
    dt, _ = timed(lambda: ops.gather_distance(idx, qs, table)
                  .block_until_ready())
    derived["gather_distance"] = {"us": round(dt * 1e6, 1)}

    pd = jnp.sort(jnp.asarray(rng.uniform(0, 5, size=(16, 64)).astype(np.float32)), axis=1)
    pi = jnp.asarray(rng.integers(0, 9999, size=(16, 64)).astype(np.int32))
    nd = jnp.asarray(rng.uniform(0, 5, size=(16, 32)).astype(np.float32))
    ni = jnp.asarray(rng.integers(0, 9999, size=(16, 32)).astype(np.int32))
    dt, _ = timed(lambda: ops.pool_merge(pd, pi, nd, ni)[0].block_until_ready())
    derived["pool_merge"] = {"us": round(dt * 1e6, 1)}

    # beam-shaped fused expansion tile ([B, W*M] = [8, 64], per-lane dcq)
    nb = jnp.asarray(rng.integers(0, 4096, size=(8, 64)).astype(np.int32))
    edl = jnp.asarray(rng.uniform(0.1, 2, size=(8, 64)).astype(np.float32))
    dcl = jnp.asarray(rng.uniform(0.1, 2, size=(8, 64)).astype(np.float32))
    b2l = jnp.asarray(rng.uniform(1, 4, size=(8, 64)).astype(np.float32))
    dt, _ = timed(lambda: ops.fused_expand(nb, qs, edl, dcl, b2l, 0.15,
                                           table)[0].block_until_ready())
    derived["fused_expand"] = {"us": round(dt * 1e6, 1)}

    for name, d in derived.items():
        emit(f"kernel_{name}", d["us"], d)
    return derived
