"""The Pallas hot path compiles for a TPU v5e, at real widths, without one.

The TPU compiler is installed with JAX, and compiles for a chip that is
described (``topologies.get_topology_desc``) and not attached: a kernel the
chip would refuse — a block not aligned to the (8, 128) tiling, a one-row
slice of a tiled HBM table, an unsupported shape cast — fails here, at no
chip time.  Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library.  The ops wrappers pick
interpret mode from ``jax.default_backend()``, which is the CPU here, so
each test steers them to the compiled kernels with ``monkeypatch``.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

N = 100_001           # 10^5 vectors + the pad row
L = 256               # beam tile W * M = 8 * 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def compiled_kernels(monkeypatch):
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _kernel_args(kernel, B, d):
    f32, i32 = jnp.float32, jnp.int32
    lanes_f = ((B, L), f32)
    rows = ((N * -(-d // 128), 128), f32)               # ops.row_table
    if kernel == "fused_expand":
        return (lambda n, q, ed, dcq, b2, ct, t, e, p: ops.fused_expand(
            n, q, ed, dcq, b2, ct, t, e, p),
            [((B, L), i32), ((B, d), f32), lanes_f, lanes_f, lanes_f,
             ((), f32), rows, ((B, L), jnp.bool_), ((B, L), jnp.bool_)])
    if kernel == "gather_distance":
        return (ops.gather_distance_pruned,
                [((B, L), i32), ((B, L), jnp.int8), ((B, d), f32), rows])
    words = ((N * -(-d // 512), 128), i32)              # ops.code_words
    return (ops.sq8_estimate,
            [((B, L), i32), ((B, d), f32), ((B, L), jnp.bool_), words,
             ((d,), f32), ((d,), f32), ((d,), f32)])


@pytest.mark.parametrize("B", [8, 128])
@pytest.mark.parametrize("d", [128, 960])
@pytest.mark.parametrize("kernel", ["fused_expand", "gather_distance",
                                    "sq8_distance"])
def test_kernel_compiles_for_v5e(one_chip, compiled_kernels, kernel, d, B):
    fn, shapes = _kernel_args(kernel, B, d)
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("B", [8, 128])
def test_pool_merge_compiles_for_v5e(one_chip, compiled_kernels, B):
    efs = 100
    _compile(ops.pool_merge, one_chip, ((B, efs), jnp.float32),
             ((B, efs), jnp.int32), ((B, L), jnp.float32), ((B, L), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_l2_distance_compiles_for_v5e(one_chip, compiled_kernels, dtype):
    """Ragged shapes and small requested blocks, as in test_kernels' sweep:
    the wrapper rounds them to the (8, 128) tiling."""
    _compile(lambda q, x: ops.l2_distance(q, x, bq=32, bc=64, bd=64),
             one_chip, ((33, 200), dtype), ((257, 200), dtype))


def test_pallas_search_step_compiles_for_v5e(one_chip, compiled_kernels):
    """One whole engine="pallas" search call (hop loop, fused expansion,
    bitonic merge) at n = 10^5, d = 128, B = 128 fits one v5e."""
    from repro.core.search import _search_batch
    from repro.core.spec import SearchSpec

    n, d, M, B = N - 1, 128, 32, 128
    spec = SearchSpec(efs=100, router="crouting", engine="pallas",
                      beam_width=1, use_hierarchy=False)

    def step(vectors, neighbors, edge_eu, norms, entry, queries, ct):
        arrays = {"vectors": vectors, "vectors_rows": vectors,
                  "neighbors": neighbors, "edge_eu": edge_eu,
                  "norms": norms, "entry": entry, "n": n}
        return _search_batch(arrays, queries, ct, spec)

    compiled = _compile(step, one_chip, ((N, d), jnp.float32),
                        ((N, M), jnp.int32), ((N, M), jnp.float32),
                        ((N,), jnp.float32), ((), jnp.int32),
                        ((B, d), jnp.float32), ((), jnp.float32))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30
