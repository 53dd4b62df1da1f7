"""Property-test parity harness for the fused query kernel.

``ops.pq_score_dedup_topk`` (both the Pallas interpret kernel and the
single-jit XLA twin, f32 and int8) must match the composed oracle
``ref.fused_query_ref`` **bitwise** — values including -inf placement and
indices including tie-break order — across randomized shapes, duplicate
SOAR copies, all-tombstone rows, score ties, and k >= live-rows edges.
This is the pin that lets the serving path default to the fused op.
"""
import functools

import numpy as np
import jax.numpy as jnp
import pytest

from _hypo_compat import given, settings, st
from repro.ann.scann import ScannConfig, ScannIndex
from repro.core.types import SparseBatch
from repro.kernels import fused_query as fq
from repro.kernels import ops, ref


def _check(got, want):
    gv, gi = got
    wv, wi = want
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


def _all_routes(lut, codes, ids, k, valid, bias, quantized=False):
    """Both production routes: XLA twin (CPU default) + Pallas interpret."""
    want = ref.fused_query_ref(lut, codes, ids, k, valid=valid, bias=bias,
                               quantized=quantized)
    for use_kernel in (False, True):
        got = ops.pq_score_dedup_topk(
            lut, codes, ids, k, valid=valid, bias=bias,
            quantized=quantized, use_kernel=use_kernel)
        _check(got, want)


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_fused_matches_ref_bitwise(data):
    """Randomized sweep: shapes, SOAR dup ids, tombstones, ties, big k."""
    b = data.draw(st.integers(1, 4))
    n = data.draw(st.integers(4, 160))
    m = data.draw(st.integers(1, 6))
    c = data.draw(st.integers(2, 24))
    k = data.draw(st.integers(1, n))
    id_pool = data.draw(st.integers(2, max(2, n)))  # small pool -> dups
    tomb_pct = data.draw(st.floats(0.0, 0.9))
    quantized = data.draw(st.integers(0, 1)) == 1
    seed = data.draw(st.integers(0, 2 ** 31 - 1))
    rng = np.random.default_rng(seed)

    # draw LUT entries from a tiny value set so score ties are common
    lut = jnp.asarray(
        rng.choice(np.asarray([-1.5, -0.25, 0.0, 0.5, 2.0], np.float32),
                   size=(b, m, c)))
    codes = jnp.asarray(rng.integers(0, c, (b, n, m)), jnp.uint8)
    ids = jnp.asarray(rng.integers(0, id_pool, (b, n)), jnp.int32)
    valid = jnp.asarray(rng.random((b, n)) >= tomb_pct)
    bias = jnp.asarray(
        rng.choice(np.asarray([0.0, 0.75], np.float32), size=(b, n)))
    _all_routes(lut, codes, ids, k, valid, bias, quantized=quantized)


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_fused_uint32_wraparound_ids(data):
    """uint32 ids past 2^31 (PAD_ID territory) wrap deterministically;
    equality among valid rows is preserved under the int32 cast."""
    seed = data.draw(st.integers(0, 2 ** 31 - 1))
    rng = np.random.default_rng(seed)
    b, n, m, c, k = 2, 40, 3, 8, 12
    lut = jnp.asarray(rng.normal(size=(b, m, c)), jnp.float32)
    codes = jnp.asarray(rng.integers(0, c, (b, n, m)), jnp.uint8)
    big = np.uint32(0xFFFFFF00)
    ids_np = (rng.integers(0, 8, (b, n)).astype(np.uint32) + big)
    valid = jnp.asarray(rng.random((b, n)) > 0.3)
    bias = jnp.zeros((b, n), jnp.float32)
    ids_i32 = jnp.asarray(ids_np.astype(np.int64).astype(np.int32))
    want = ref.fused_query_ref(lut, codes, ids_i32, k, valid=valid,
                               bias=bias)
    for use_kernel in (False, True):
        got = ops.pq_score_dedup_topk(lut, codes, jnp.asarray(ids_np), k,
                                      valid=valid, bias=bias,
                                      use_kernel=use_kernel)
        _check(got, want)


def test_all_tombstone_rows_yield_ascending_indices():
    """Fully-invalid rows: vals all -inf, idxs 0..k-1 like lax.top_k."""
    b, n, m, c, k = 2, 17, 2, 4, 17
    lut = jnp.zeros((b, m, c), jnp.float32)
    codes = jnp.zeros((b, n, m), jnp.uint8)
    ids = jnp.zeros((b, n), jnp.int32)
    valid = jnp.zeros((b, n), jnp.bool_)
    for use_kernel in (False, True):
        vals, idxs = ops.pq_score_dedup_topk(lut, codes, ids, k,
                                             valid=valid,
                                             use_kernel=use_kernel)
        assert np.all(np.isneginf(np.asarray(vals)))
        np.testing.assert_array_equal(
            np.asarray(idxs), np.tile(np.arange(k, dtype=np.int32), (b, 1)))


def test_k_exceeds_live_rows():
    """k > live rows: dead tail selects remaining indices ascending and
    every live id still surfaces exactly once before the -inf tail."""
    rng = np.random.default_rng(3)
    b, n, m, c, k = 1, 12, 2, 4, 12
    lut = jnp.asarray(rng.normal(size=(b, m, c)), jnp.float32)
    codes = jnp.asarray(rng.integers(0, c, (b, n, m)), jnp.uint8)
    ids = jnp.asarray([[5, 5, 7, 7, 9, 9, 1, 1, 2, 2, 3, 3]], jnp.int32)
    valid = jnp.asarray([[1, 1, 1, 1, 0, 0, 1, 1, 0, 0, 1, 1]]) == 1
    want = ref.fused_query_ref(lut, codes, ids, k, valid=valid)
    for use_kernel in (False, True):
        got = ops.pq_score_dedup_topk(lut, codes, ids, k, valid=valid,
                                      use_kernel=use_kernel)
        _check(got, want)
    vals, idxs = want
    finite = np.isfinite(np.asarray(vals[0]))
    surviving = np.asarray(ids[0])[np.asarray(idxs[0])[finite]]
    # one copy per live id survives the dedup
    assert sorted(surviving.tolist()) == [1, 3, 5, 7]


def test_all_ties_shortlist_order_is_candidate_order():
    """Uniform scores: shortlist = candidate order, later same-id -inf."""
    b, n, m, c, k = 1, 8, 1, 2, 8
    lut = jnp.ones((b, m, c), jnp.float32)
    codes = jnp.zeros((b, n, m), jnp.uint8)
    ids = jnp.asarray([[4, 4, 4, 2, 2, 8, 8, 8]], jnp.int32)
    valid = jnp.ones((b, n), jnp.bool_)
    for use_kernel in (False, True):
        vals, idxs = ops.pq_score_dedup_topk(lut, codes, ids, k,
                                             valid=valid,
                                             use_kernel=use_kernel)
        np.testing.assert_array_equal(np.asarray(idxs[0]), np.arange(n))
        np.testing.assert_array_equal(
            np.isfinite(np.asarray(vals[0])),
            [True, False, False, True, False, True, False, False])


def test_composed_ops_match_fused_bitwise():
    """The fused=False escape hatch (pq_scores -> topk_select ->
    dedup_mask) reproduces the fused op bitwise."""
    rng = np.random.default_rng(11)
    b, n, m, c, k = 3, 90, 4, 16, 32
    lut = jnp.asarray(rng.normal(size=(b, m, c)), jnp.float32)
    codes = jnp.asarray(rng.integers(0, c, (b, n, m)), jnp.uint8)
    ids = jnp.asarray(rng.integers(0, 30, (b, n)), jnp.int32)
    valid = jnp.asarray(rng.random((b, n)) > 0.2)
    bias = jnp.asarray(rng.normal(size=(b, n)), jnp.float32)
    for quantized in (False, True):
        fv, fi = ops.pq_score_dedup_topk(lut, codes, ids, k, valid=valid,
                                         bias=bias, quantized=quantized)
        s = ops.pq_scores(lut, codes, quantized=quantized)
        s = jnp.where(valid, s + bias, -jnp.inf)
        cv, ci = ops.topk_select(s, k)
        cv = ops.dedup_mask(cv, ci, ids, valid)
        _check((cv, ci), (fv, fi))


def _small_corpus(n=160, k_dims=8, vocab=512, seed=0):
    rng = np.random.default_rng(seed)
    idx = np.sort(rng.integers(0, vocab, (n, k_dims)), axis=-1)
    val = (rng.random((n, k_dims)) + 0.1).astype(np.float32)
    return np.arange(1, n + 1, dtype=np.int64), \
        SparseBatch(jnp.asarray(idx.astype(np.uint32)), jnp.asarray(val))


def test_scann_fused_matches_unfused_search():
    """End-to-end pin: all four (fused, use_kernels) combos return the
    same ids and dists on a live two-copy SOAR index."""
    ids, emb = _small_corpus()
    results = []
    for fused in (True, False):
        for use_kernels in (False, True):
            cfg = ScannConfig(n_partitions=8, nprobe=4, reorder=48,
                              soar_lambda=1.0, fused=fused,
                              use_kernels=use_kernels)
            ix = ScannIndex(emb.indices.shape[1], cfg)
            ix.build(ids, emb)
            results.append(ix.search(emb[:16], 10))
    base_ids, base_d = results[0]
    for got_ids, got_d in results[1:]:
        np.testing.assert_array_equal(got_ids, base_ids)
        np.testing.assert_array_equal(got_d, base_d)
    # SOAR dedup survived the fusion: no id appears twice in a row
    for row in base_ids:
        live = row[row >= 0]
        assert len(set(live.tolist())) == len(live)


def test_scann_int8_recall_sane():
    """pq_int8 changes shortlist scores by quantisation only; exact
    rescoring still dominates, so self-recall stays near-perfect."""
    ids, emb = _small_corpus(seed=5)
    cfg = ScannConfig(n_partitions=8, nprobe=8, reorder=64,
                      soar_lambda=1.0, pq_int8=True)
    ix = ScannIndex(emb.indices.shape[1], cfg)
    ix.build(ids, emb)
    got, _ = ix.search(emb[:32], 1)
    hits = sum(int(got[i, 0] == ids[i]) for i in range(32))
    assert hits >= 30, f"int8 self-recall {hits}/32"


def test_quantize_lut_roundtrip_bounds():
    rng = np.random.default_rng(2)
    lut = jnp.asarray(rng.normal(size=(4, 8, 256)) * 3.0, jnp.float32)
    qlut, scale = ops.quantize_lut(lut)
    assert qlut.dtype == jnp.int8
    deq = np.asarray(qlut, np.float32) * np.asarray(scale)[..., None]
    err = np.abs(deq - np.asarray(lut))
    assert np.all(err <= np.asarray(scale)[..., None] * 0.5 + 1e-6)
    # zero rows quantise to zero with unit scale (no div-by-zero)
    q0, s0 = ops.quantize_lut(jnp.zeros((1, 2, 16), jnp.float32))
    assert np.all(np.asarray(q0) == 0) and np.all(np.asarray(s0) == 1.0)


@pytest.mark.parametrize("quantized", [False, True])
def test_fused_k_equals_n_full_permutation(quantized):
    """k == n returns a full permutation of indices."""
    rng = np.random.default_rng(9)
    b, n, m, c = 2, 33, 3, 8
    lut = jnp.asarray(rng.normal(size=(b, m, c)), jnp.float32)
    codes = jnp.asarray(rng.integers(0, c, (b, n, m)), jnp.uint8)
    ids = jnp.asarray(rng.integers(0, 10, (b, n)), jnp.int32)
    valid = jnp.asarray(rng.random((b, n)) > 0.4)
    _all_routes(lut, codes, ids, n, valid,
                jnp.zeros((b, n), jnp.float32), quantized=quantized)
    _, idxs = ops.pq_score_dedup_topk(lut, codes, ids, n, valid=valid,
                                      quantized=quantized)
    for row in np.asarray(idxs):
        assert sorted(row.tolist()) == list(range(n))


# ----------------------------------------------------------- block flags
# The kernel scores and merges only the 1,024-lane blocks that hold a
# valid slot (and block 0). Slabs fill from the front, so the served
# masks have empty blocks behind every partition's cursor; each case
# mixes such patterns and a real row count (rows pad to 8 in the kernel).

def _block_mask(case, rng):
    """(rows, n, valid bool [rows, n]) of one flag-parity case."""
    def live(rows, n, lo, hi, share=0.5):
        v = np.zeros((rows, n), bool)
        v[:, lo:hi] = rng.random((rows, hi - lo)) < share
        return v

    if case == "block0_only":
        return 1, 4096, live(1, 4096, 0, 300)
    if case == "empty_middle":
        v = live(2, 4096, 0, 1024) | live(2, 4096, 3072, 4096)
        return 2, 4096, v
    if case == "last_block_only":           # last block partly padding
        return 4, 3500, live(4, 3500, 3072, 3500)
    if case == "few_valid_row":             # row 5: 5 valid, k is 16
        v = live(8, 4000, 0, 700) | live(8, 4000, 2048, 2600)
        v[5] = False
        v[5, rng.choice(np.arange(2048, 3072), 5, replace=False)] = True
        return 8, 4000, v
    if case == "invalid_row":
        v = live(8, 4096, 0, 200) | live(8, 4096, 3500, 4096, 0.1)
        v[3] = False
        return 8, 4096, v
    if case == "two_row_blocks":            # different flags per 8 rows
        v = live(16, 4096, 0, 1024) | live(16, 4096, 2048, 2100)
        v[8:] = live(8, 4096, 3072, 4096, 0.2)
        v[12] = False
        return 16, 4096, v
    raise ValueError(case)


BLOCK_CASES = ["block0_only", "empty_middle", "last_block_only",
               "few_valid_row", "invalid_row", "two_row_blocks"]


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("case", BLOCK_CASES)
def test_fused_block_flags_match_ref(case, quantized):
    """The kernel with its block flags equals the oracle bit for bit."""
    rng = np.random.default_rng(BLOCK_CASES.index(case))
    b, n, valid_np = _block_mask(case, rng)
    m, c, k = 3, 8, 16
    lut = jnp.asarray(rng.choice(np.asarray([-1.5, -0.25, 0.0, 0.5, 2.0],
                                            np.float32), size=(b, m, c)))
    codes = jnp.asarray(rng.integers(0, c, (b, n, m)), jnp.uint8)
    ids = jnp.asarray(rng.integers(0, 400, (b, n)), jnp.int32)
    bias = jnp.asarray(rng.choice(np.asarray([0.0, 0.75], np.float32),
                                  size=(b, n)))
    valid = jnp.asarray(valid_np)
    want = ref.fused_query_ref(lut, codes, ids, k, valid=valid, bias=bias,
                               quantized=quantized)
    got = ops.pq_score_dedup_topk(lut, codes, ids, k, valid=valid,
                                  bias=bias, quantized=quantized,
                                  use_kernel=True)
    _check(got, want)
    flags = np.asarray(ops.shortlist_block_live(valid, k))
    blocks = -(-n // 1024)
    want_flags = [valid_np[r:r + 8, j * 1024:(j + 1) * 1024].any()
                  for r in range(0, -(-b // 8) * 8, 8)
                  for j in range(blocks)]
    np.testing.assert_array_equal(flags, np.asarray(want_flags, np.int32))


def test_fused_block_flags_none_is_all_live():
    """``block_live=None`` scores every block, like all-ones flags; flags
    that leave a live block out drop its candidates (they are obeyed)."""
    rng = np.random.default_rng(21)
    b, n, m, c, k = 3, 3072, 2, 8, 8
    lut = jnp.asarray(rng.normal(size=(b, m, c)), jnp.float32)
    codes = jnp.asarray(rng.integers(0, c, (b, n, m)), jnp.uint8)
    ids = jnp.asarray(rng.integers(0, 50, (b, n)), jnp.int32)
    valid_np = rng.random((b, n)) < 0.3
    valid = jnp.asarray(valid_np, jnp.int32)
    bias = jnp.zeros((b, n), jnp.float32)
    run = functools.partial(fq.fused_query_kernel, lut, codes, ids, valid,
                            bias, k, interpret=True)
    _check(run(None), run(jnp.ones((3,), jnp.int32)))
    only0 = np.where(np.arange(n) < 1024, valid_np, False)
    want = ref.fused_query_ref(lut, codes, ids, k, valid=jnp.asarray(only0),
                               bias=bias)
    _check(run(jnp.zeros((3,), jnp.int32)), want)
