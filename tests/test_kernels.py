"""Per-kernel allclose vs the pure-jnp oracles, swept over shapes/dtypes."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.types import PAD_INDEX
from repro.kernels import ops, ref

RNG = np.random.default_rng(7)


def _sparse_rows(n, k, pad_frac=0.3, vocab=64):
    idx = RNG.integers(0, vocab, (n, k)).astype(np.uint32)
    val = RNG.random((n, k)).astype(np.float32) + 0.1
    pad = RNG.random((n, k)) < pad_frac
    idx[pad] = PAD_INDEX
    val[pad] = 0.0
    order = np.argsort(idx, axis=-1)
    return (jnp.asarray(np.take_along_axis(idx, order, -1)),
            jnp.asarray(np.take_along_axis(val, order, -1)))


@pytest.mark.parametrize("b,m,c,n", [(1, 4, 16, 64), (3, 8, 256, 1000),
                                     (2, 16, 256, 333)])
def test_pq_score(b, m, c, n):
    lut = jnp.asarray(RNG.normal(size=(b, m, c)), jnp.float32)
    codes = jnp.asarray(RNG.integers(0, c, (n, m)), jnp.uint8)
    # atol covers near-zero sums where f32 accumulation order differs
    # between the kernel and the oracle
    np.testing.assert_allclose(ops.pq_score(lut, codes),
                               ref.pq_score_ref(lut, codes), rtol=1e-5,
                               atol=1e-5)


def test_pq_score_batched():
    b, m, c, n = 3, 8, 256, 500
    lut = jnp.asarray(RNG.normal(size=(b, m, c)), jnp.float32)
    codes = jnp.asarray(RNG.integers(0, c, (b, n, m)), jnp.uint8)
    got = ops.pq_score_batched(lut, codes)
    want = jnp.stack([ref.pq_score_ref(lut[i:i+1], codes[i])[0]
                      for i in range(b)])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bq,kq,n,kd", [(1, 4, 32, 4), (5, 13, 777, 13),
                                        (2, 8, 129, 16)])
def test_sparse_dot(bq, kq, n, kd):
    qi, qv = _sparse_rows(bq, kq)
    di, dv = _sparse_rows(n, kd)
    np.testing.assert_allclose(ops.sparse_dot(qi, qv, di, dv),
                               ref.sparse_dot_ref(qi, qv, di, dv), rtol=1e-5)


def test_sparse_dot_bf16_values():
    qi, qv = _sparse_rows(3, 8)
    di, dv = _sparse_rows(100, 8)
    got = ops.sparse_dot(qi, qv.astype(jnp.bfloat16), di,
                         dv.astype(jnp.bfloat16))
    want = ref.sparse_dot_ref(qi, qv.astype(jnp.bfloat16), di,
                              dv.astype(jnp.bfloat16))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=1e-2)


def test_sparse_dot_batched():
    b, r, k = 4, 50, 8
    qi, qv = _sparse_rows(b, k)
    di, dv = _sparse_rows(b * r, k)
    di = di.reshape(b, r, k)
    dv = dv.reshape(b, r, k)
    got = ops.sparse_dot_batched(qi, qv, di, dv)
    want = jnp.stack([ref.sparse_dot_ref(qi[i:i+1], qv[i:i+1],
                                         di[i], dv[i])[0] for i in range(b)])
    np.testing.assert_allclose(got, want, rtol=1e-5)


@pytest.mark.parametrize("b,n,k", [(1, 16, 1), (4, 333, 7), (2, 64, 64)])
def test_topk_select(b, n, k):
    scores = jnp.asarray(RNG.normal(size=(b, n)), jnp.float32)
    gv, gi = ops.topk_select(scores, k)
    wv, wi = ref.topk_ref(scores, k)
    np.testing.assert_allclose(gv, wv, rtol=1e-6)
    np.testing.assert_array_equal(gi, wi)


def test_topk_with_ties_matches_lax():
    scores = jnp.asarray(np.repeat(RNG.normal(size=(2, 8)), 4, axis=1),
                         jnp.float32)
    gv, gi = ops.topk_select(scores, 5)
    wv, wi = ref.topk_ref(scores, 5)
    np.testing.assert_array_equal(gi, wi)


@pytest.mark.parametrize("b,r,kq,kd", [(1, 1, 3, 5), (3, 7, 13, 9),
                                       (2, 129, 8, 8), (5, 31, 1, 17)])
def test_sparse_dot_batched_odd_shapes(b, r, kq, kd):
    # odd rank counts exercise the kernel's block_n padding of the R axis
    qi, qv = _sparse_rows(b, kq)
    di, dv = _sparse_rows(b * r, kd)
    di = di.reshape(b, r, kd)
    dv = dv.reshape(b, r, kd)
    got = ops.sparse_dot_batched(qi, qv, di, dv)
    want = jnp.stack([ref.sparse_dot_ref(qi[i:i+1], qv[i:i+1],
                                         di[i], dv[i])[0] for i in range(b)])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_sparse_dot_batched_all_padded_rows():
    # fully-padded query and candidate rows (how the multimodal retrieve
    # stage encodes absent candidates) must score exactly 0, not NaN
    b, r, k = 3, 6, 8
    qi, qv = _sparse_rows(b, k)
    qi = qi.at[1].set(PAD_INDEX)
    qv = qv.at[1].set(0.0)
    di, dv = _sparse_rows(b * r, k)
    di = di.reshape(b, r, k).at[:, -2:].set(PAD_INDEX)
    dv = dv.reshape(b, r, k).at[:, -2:].set(0.0)
    got = np.asarray(ops.sparse_dot_batched(qi, qv, di, dv))
    want = np.stack([ref.sparse_dot_ref(qi[i:i+1], qv[i:i+1],
                                        di[i], dv[i])[0] for i in range(b)])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert np.all(got[1] == 0.0)
    assert np.all(got[:, -2:] == 0.0)


def _mlp_params(f, h):
    return {"w0": jnp.asarray(RNG.normal(size=(f, h)), jnp.float32),
            "b0": jnp.asarray(RNG.normal(size=(h,)), jnp.float32),
            "w1": jnp.asarray(RNG.normal(size=(h, h)), jnp.float32),
            "b1": jnp.asarray(RNG.normal(size=(h,)), jnp.float32),
            "w2": jnp.asarray(RNG.normal(size=(h, 1)), jnp.float32),
            "b2": jnp.asarray(RNG.normal(size=(1,)), jnp.float32)}


@pytest.mark.parametrize("b,f,h", [(1, 1, 3), (7, 5, 8), (33, 17, 13),
                                   (130, 9, 6)])
def test_scorer_mlp_matches_ref_odd_shapes(b, f, h):
    # hidden widths off the pad boundary (3, 13, 6) exercise the
    # kernel's hidden-dim padding; ref.scorer_mlp_ref is the oracle
    params = _mlp_params(f, h)
    feats = jnp.asarray(RNG.normal(size=(b, f)), jnp.float32)
    got = ops.scorer_mlp(feats, params)
    want = ref.scorer_mlp_ref(feats, params["w0"], params["b0"],
                              params["w1"], params["b1"],
                              params["w2"], params["b2"])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------
# interpret-vs-compiled parity: every kernel module defaults to
# interpret=False (compiled is the production path); interpret mode is
# kept for tests and CPU validation. The compiled half needs a TPU.


def _both_modes(fn, rtol=None):
    """Run fn(interpret) for both modes, asserting bitwise equality (or
    ``rtol`` for a kernel whose f32 matmuls Mosaic and XLA round
    differently)."""
    if jax.default_backend() == "cpu":
        pytest.skip("compiled Pallas TPU kernels need a TPU; the CPU "
                    "backend only runs them interpreted")
    interp = [np.asarray(a) for a in jax.tree_util.tree_leaves(fn(True))]
    compiled = [np.asarray(a) for a in jax.tree_util.tree_leaves(fn(False))]
    for a, b in zip(interp, compiled):
        if rtol is None:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=rtol, atol=1e-6)


def test_pq_score_interpret_vs_compiled():
    lut = jnp.asarray(RNG.normal(size=(2, 8, 256)), jnp.float32)
    codes = jnp.asarray(RNG.integers(0, 256, (300, 8)), jnp.uint8)
    _both_modes(lambda i: ops.pq_score(lut, codes, interpret=i))
    bcodes = jnp.asarray(RNG.integers(0, 256, (2, 300, 8)), jnp.uint8)
    _both_modes(lambda i: ops.pq_score_batched(lut, bcodes, interpret=i))


def test_sparse_dot_interpret_vs_compiled():
    qi, qv = _sparse_rows(3, 8)
    di, dv = _sparse_rows(200, 8)
    _both_modes(lambda i: ops.sparse_dot(qi, qv, di, dv, interpret=i))
    bi = di[:150].reshape(3, 50, 8)
    bv = dv[:150].reshape(3, 50, 8)
    _both_modes(
        lambda i: ops.sparse_dot_batched(qi, qv, bi, bv, interpret=i))


def test_topk_select_interpret_vs_compiled():
    scores = jnp.asarray(RNG.normal(size=(4, 256)), jnp.float32)
    _both_modes(lambda i: ops.topk_select(scores, 16, interpret=i))


def test_scorer_mlp_interpret_vs_compiled():
    params = _mlp_params(16, 10)
    feats = jnp.asarray(RNG.normal(size=(64, 16)), jnp.float32)
    # not bitwise: interpret mode runs the MLP's f32 matmuls through XLA,
    # compiled mode through Mosaic, which round differently; on a TPU v5e
    # they differed by up to 6.6e-6 relative over 32 random inputs
    _both_modes(lambda i: ops.scorer_mlp(feats, params, interpret=i),
                rtol=1e-5)


def test_fused_query_interpret_vs_compiled():
    lut = jnp.asarray(RNG.normal(size=(2, 4, 16)), jnp.float32)
    codes = jnp.asarray(RNG.integers(0, 16, (2, 100, 4)), jnp.uint8)
    ids = jnp.asarray(RNG.integers(0, 40, (2, 100)), jnp.int32)
    valid = jnp.asarray(RNG.random((2, 100)) > 0.2)
    for quantized in (False, True):
        _both_modes(lambda i: ops.pq_score_dedup_topk(
            lut, codes, ids, 20, valid=valid, quantized=quantized,
            use_kernel=True, interpret=i))


def test_kernel_modules_default_to_compiled():
    """interpret=True must be opt-in everywhere; compiled is production."""
    import inspect
    from repro.kernels import (fused_query, pq_score, scorer_mlp,
                               sparse_dot, topk_select)
    fns = [pq_score.pq_score, pq_score.pq_score_batched,
           sparse_dot.sparse_dot, sparse_dot.sparse_dot_batched,
           topk_select.topk_select, scorer_mlp.scorer_mlp,
           fused_query.fused_query_kernel,
           fused_query.fused_query_kernel_int8]
    for fn in fns:
        sig = inspect.signature(fn)
        assert sig.parameters["interpret"].default is False, fn


def test_topk_kernel_all_neg_inf_matches_lax():
    """Regression: rows of pure -inf (tombstones) must yield ascending
    distinct indices from the kernel, exactly like lax.top_k."""
    scores = jnp.full((2, 32), -jnp.inf, jnp.float32)
    scores = scores.at[1, 7].set(1.0)
    gv, gi = ops.topk_select(scores, 5, interpret=True)
    wv, wi = ref.topk_ref(scores, 5)
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


def test_scorer_mlp_matches_core_scorer():
    from repro.core.scorer import scorer_apply, scorer_init
    from repro.core.types import FeatureSpec
    spec = FeatureSpec(dense={"a": 8}, sets={"s": 4}, scalars=("x",))
    params = scorer_init(jax.random.PRNGKey(0), spec)
    feats = jnp.asarray(RNG.normal(size=(130, params["w0"].shape[0])),
                        jnp.float32)
    got = ops.scorer_mlp(feats, params)
    np.testing.assert_allclose(got, scorer_apply(params, feats),
                               rtol=1e-5, atol=1e-6)
