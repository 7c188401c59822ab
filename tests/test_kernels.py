"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("q_n,c_n,d", [(8, 16, 32), (70, 130, 96),
                                       (128, 256, 128), (33, 257, 200)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["l2", "ip"])
def test_l2_distance_sweep(q_n, c_n, d, dtype, mode):
    q = jnp.asarray(RNG.normal(size=(q_n, d)), dtype)
    x = jnp.asarray(RNG.normal(size=(c_n, d)), dtype)
    out = ops.l2_distance(q, x, mode=mode, bq=32, bc=64, bd=64)
    exp = ref.l2_distance_ref(q, x, mode=mode)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=tol, atol=tol * d)


@pytest.mark.parametrize("b,m", [(1, 7), (13, 37), (32, 64), (5, 130)])
def test_crouting_prune_sweep(b, m):
    ed = jnp.asarray(RNG.uniform(0.1, 2.0, size=(b, m)), jnp.float32)
    dcq = jnp.asarray(RNG.uniform(0.1, 2.0, size=(b,)), jnp.float32)
    b2 = jnp.asarray(RNG.uniform(0.5, 4.0, size=(b,)), jnp.float32)
    valid = jnp.asarray(RNG.integers(0, 2, size=(b, m)), jnp.int8)
    for ct in (-0.3, 0.0, 0.156, 0.9):
        e1, m1 = ops.crouting_prune(ed, dcq, b2, valid, ct)
        e2, m2 = ref.crouting_prune_ref(ed, dcq, b2, valid, ct)
        np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), rtol=1e-5)
        assert (np.asarray(m1) == np.asarray(m2)).all()


@pytest.mark.parametrize("b,m,n,d", [(2, 5, 50, 16), (7, 31, 300, 64),
                                     (4, 16, 128, 128)])
def test_gather_distance_sweep(b, m, n, d):
    table = jnp.asarray(RNG.normal(size=(n, d)), jnp.float32)
    idx = jnp.asarray(RNG.integers(0, n, size=(b, m)), jnp.int32)
    qs = jnp.asarray(RNG.normal(size=(b, d)), jnp.float32)
    out = ops.gather_distance(idx, qs, table)
    exp = ref.gather_distance_ref(idx, qs, table)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-5,
                               atol=1e-5)


def test_gather_distance_pruned_lanes():
    table = jnp.asarray(RNG.normal(size=(64, 32)), jnp.float32)
    idx = jnp.asarray(RNG.integers(0, 64, size=(3, 8)), jnp.int32)
    qs = jnp.asarray(RNG.normal(size=(3, 32)), jnp.float32)
    mask = jnp.asarray(RNG.integers(0, 2, size=(3, 8)), jnp.int8)
    out = ops.gather_distance_pruned(idx, mask, qs, table)
    exp = ref.gather_distance_ref(idx, qs, table)
    m = np.asarray(mask) != 0
    assert np.isinf(np.asarray(out)[m]).all()
    np.testing.assert_allclose(np.asarray(out)[~m], np.asarray(exp)[~m],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("p,m", [(8, 4), (16, 12), (50, 30), (64, 64)])
def test_pool_merge_sweep(p, m):
    b = 6
    pd = jnp.sort(jnp.asarray(RNG.uniform(0, 5, size=(b, p)), jnp.float32), axis=1)
    pi = jnp.asarray(RNG.permutation(10_000)[: b * p].reshape(b, p), jnp.int32)
    nd = jnp.asarray(RNG.uniform(0, 5, size=(b, m)), jnp.float32)
    ni = jnp.asarray((RNG.permutation(10_000)[: b * m] + 20_000).reshape(b, m),
                     jnp.int32)
    d1, i1 = ops.pool_merge(pd, pi, nd, ni)
    d2, i2 = ref.pool_merge_ref(pd, pi, nd, ni)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2))
    assert (np.asarray(i1) == np.asarray(i2)).all()


def test_pool_merge_randomized_with_ties():
    """Quantized distances force ties across pool/new; the kernel must agree
    with an explicit np.sort of the union under the (dist, id) order."""
    rng = np.random.default_rng(11)
    for _ in range(5):
        b, p, m = 4, 12, 9
        pd = np.sort(rng.integers(0, 6, size=(b, p)).astype(np.float32) / 2.0,
                     axis=1)
        nd = rng.integers(0, 6, size=(b, m)).astype(np.float32) / 2.0
        perm = rng.permutation(5000)
        pi = perm[: b * p].reshape(b, p).astype(np.int32)
        ni = (perm[b * p: b * (p + m)] + 10_000).reshape(b, m).astype(np.int32)
        d1, i1 = ops.pool_merge(jnp.asarray(pd), jnp.asarray(pi),
                                jnp.asarray(nd), jnp.asarray(ni))
        for r in range(b):
            union = sorted(zip(np.concatenate([pd[r], nd[r]]),
                               np.concatenate([pi[r], ni[r]])))
            exp_d = np.asarray([u[0] for u in union[:p]], np.float32)
            exp_i = np.asarray([u[1] for u in union[:p]], np.int32)
            np.testing.assert_array_equal(np.asarray(d1)[r], exp_d)
            np.testing.assert_array_equal(np.asarray(i1)[r], exp_i)


@pytest.mark.parametrize("b,m", [(3, 17), (6, 64)])
def test_crouting_prune_per_lane_dcq(b, m):
    """Beam tiles carry a per-lane expansion-node distance and bound."""
    ed = jnp.asarray(RNG.uniform(0.1, 2.0, size=(b, m)), jnp.float32)
    dcq = jnp.asarray(RNG.uniform(0.1, 2.0, size=(b, m)), jnp.float32)
    b2 = jnp.asarray(RNG.uniform(0.5, 4.0, size=(b, m)), jnp.float32)
    valid = jnp.asarray(RNG.integers(0, 2, size=(b, m)), jnp.int8)
    e1, m1 = ops.crouting_prune(ed, dcq, b2, valid, 0.3)
    e2, m2 = ref.crouting_prune_ref(ed, dcq, b2, valid, 0.3)
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), rtol=1e-5)
    assert (np.asarray(m1) == np.asarray(m2)).all()


def test_fused_expand_masks_and_per_lane():
    """eval/prune-eligible masks + per-lane dcq/bound2 (the beam-engine
    calling convention)."""
    b, m, n, d = 4, 12, 120, 16
    table = jnp.asarray(RNG.normal(size=(n, d)), jnp.float32)
    nbrs = jnp.asarray(RNG.integers(0, n + 2, size=(b, m)), jnp.int32)
    qs = jnp.asarray(RNG.normal(size=(b, d)), jnp.float32)
    ed = jnp.asarray(RNG.uniform(0.5, 3.0, size=(b, m)), jnp.float32)
    dcq = jnp.asarray(RNG.uniform(0.5, 3.0, size=(b, m)), jnp.float32)
    b2 = jnp.asarray(RNG.uniform(2.0, 9.0, size=(b, m)), jnp.float32)
    evalm = jnp.asarray(RNG.integers(0, 2, size=(b, m)), jnp.int8) \
        & (nbrs < n).astype(jnp.int8)
    elig = evalm & jnp.asarray(RNG.integers(0, 2, size=(b, m)), jnp.int8)
    d1, m1 = ops.fused_expand(nbrs, qs, ed, dcq, b2, 0.2, table,
                              eval_mask=evalm, prune_eligible=elig)
    d2, m2 = ref.fused_expand_ref(nbrs, qs, ed, dcq, b2, 0.2, table,
                                  eval_mask=evalm, prune_eligible=elig)
    assert (np.asarray(m1) == np.asarray(m2)).all()
    fin = np.isfinite(np.asarray(d2))
    assert (np.isfinite(np.asarray(d1)) == fin).all()
    np.testing.assert_allclose(np.asarray(d1)[fin], np.asarray(d2)[fin],
                               rtol=1e-5, atol=1e-5)


def test_gather_distance_pruned_uses_pad_row_sentinel():
    """Lanes naming the table's LAST row (the engine pad row,
    graph_device_arrays) compute normally when unpruned; pruned lanes skip
    their row DMA and report +inf."""
    table = jnp.asarray(RNG.normal(size=(32, 8)), jnp.float32)
    qs = jnp.asarray(RNG.normal(size=(2, 8)), jnp.float32)
    idx = jnp.full((2, 4), 31, jnp.int32)   # all lanes point at the pad row
    mask = jnp.asarray([[1, 1, 0, 1], [1, 0, 1, 1]], jnp.int8)
    out = np.asarray(ops.gather_distance_pruned(idx, mask, qs, table))
    exp = np.asarray(ref.gather_distance_ref(idx, qs, table))
    m = np.asarray(mask) != 0
    assert np.isinf(out[m]).all()
    np.testing.assert_allclose(out[~m], exp[~m], rtol=1e-5, atol=1e-5)


def _sq8_fixture(b, m, n, d, seed=0):
    from repro.quant import sq8 as SQ

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    p = SQ.sq8_train(x)
    codes = jnp.asarray(SQ.sq8_encode(x, p))
    nbrs = jnp.asarray(rng.integers(0, n + 2, size=(b, m)), jnp.int32)
    qs = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    evalm = jnp.asarray(rng.integers(0, 2, size=(b, m)), jnp.int8)
    return (nbrs, qs, evalm, codes, jnp.asarray(p.lo), jnp.asarray(p.scale),
            jnp.asarray(p.eps))


@pytest.mark.parametrize("b,m,n,d", [(3, 8, 100, 16), (5, 16, 400, 64),
                                     (2, 33, 128, 128)])
def test_sq8_estimate_kernel_matches_oracle(b, m, n, d):
    """Stage-1 SQ8 kernel (uint8 row gather + dequantized accumulate +
    lower-bound emit) == the repro.quant.sq8 oracle, bit-for-bit masks."""
    args = _sq8_fixture(b, m, n, d, seed=b)
    d1, l1 = ops.sq8_estimate(*args)
    d2, l2 = ref.sq8_estimate_ref(*args)
    fin = np.isfinite(np.asarray(d2))
    assert (np.isfinite(np.asarray(d1)) == fin).all()
    np.testing.assert_allclose(np.asarray(d1)[fin], np.asarray(d2)[fin],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(l1)[fin], np.asarray(l2)[fin],
                               rtol=1e-5, atol=1e-5)


def test_sq8_estimate_masked_lanes_report_inf():
    nbrs, qs, _, codes, lo, scale, eps = _sq8_fixture(4, 12, 64, 32)
    evalm = jnp.zeros((4, 12), jnp.int8).at[:, ::3].set(1)
    d1, l1 = ops.sq8_estimate(nbrs, qs, evalm, codes, lo, scale, eps)
    dead = ~(np.asarray(evalm) != 0) | ~(np.asarray(nbrs) < 64)
    assert np.isinf(np.asarray(d1)[dead]).all()
    assert np.isinf(np.asarray(l1)[dead]).all()


def test_sq8_estimate_lower_bound_holds_on_true_rows():
    """lb2 from the kernel never exceeds the true fp32 distance."""
    from repro.quant import sq8 as SQ

    rng = np.random.default_rng(5)
    n, d, b, m = 150, 48, 4, 20
    x = rng.normal(size=(n, d)).astype(np.float32)
    p = SQ.sq8_train(x)
    codes = jnp.asarray(SQ.sq8_encode(x, p))
    nbrs = jnp.asarray(rng.integers(0, n, size=(b, m)), jnp.int32)
    qs = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    _, lb2 = ops.sq8_estimate(nbrs, qs, jnp.ones((b, m), jnp.int8), codes,
                              jnp.asarray(p.lo), jnp.asarray(p.scale),
                              jnp.asarray(p.eps))
    true = np.asarray(ref.gather_distance_ref(nbrs, qs, jnp.asarray(x)))
    assert (np.asarray(lb2) <= true + 1e-4 * (1 + true)).all()


def test_pool_merge_with_inf_padding():
    pd = jnp.asarray([[0.1, 0.5, jnp.inf, jnp.inf]], jnp.float32)
    pi = jnp.asarray([[3, 7, -1, -1]], jnp.int32)
    nd = jnp.asarray([[0.3, jnp.inf]], jnp.float32)
    ni = jnp.asarray([[9, -1]], jnp.int32)
    d, i = ops.pool_merge(pd, pi, nd, ni)
    assert list(np.asarray(i)[0][:3]) == [3, 9, 7]


@pytest.mark.parametrize("b,m,n,d", [(3, 8, 100, 16), (5, 16, 400, 64)])
def test_fused_expand_sweep(b, m, n, d):
    """Fused estimate+prune+conditional-gather kernel == composed oracle."""
    table = jnp.asarray(RNG.normal(size=(n, d)), jnp.float32)
    nbrs = jnp.asarray(RNG.integers(0, n + 2, size=(b, m)), jnp.int32)  # some pads
    qs = jnp.asarray(RNG.normal(size=(b, d)), jnp.float32)
    ed = jnp.asarray(RNG.uniform(0.5, 3.0, size=(b, m)), jnp.float32)
    dcq = jnp.asarray(RNG.uniform(0.5, 3.0, size=(b,)), jnp.float32)
    b2 = jnp.asarray(RNG.uniform(2.0, 9.0, size=(b,)), jnp.float32)
    d1, m1 = ops.fused_expand(nbrs, qs, ed, dcq, b2, 0.156, table)
    d2, m2 = ref.fused_expand_ref(nbrs, qs, ed, dcq, b2, 0.156, table)
    assert (np.asarray(m1) == np.asarray(m2)).all()
    fin = np.isfinite(np.asarray(d2))
    assert (np.isfinite(np.asarray(d1)) == fin).all()
    np.testing.assert_allclose(np.asarray(d1)[fin], np.asarray(d2)[fin],
                               rtol=1e-5, atol=1e-5)
