"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth its kernel is tested against
(tests/test_kernels.py sweeps shapes + dtypes and asserts allclose).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import PAD_INDEX


def pq_score_ref(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """LUT accumulation. lut f32 [B, M, C]; codes u8 [N, M] -> [B, N]."""
    m = lut.shape[1]
    idx = codes.astype(jnp.int32)                               # [N, M]
    per = lut[:, jnp.arange(m)[None, :], idx]                   # [B, N, M]
    return jnp.sum(per, axis=-1)


def sparse_dot_ref(q_idx, q_val, db_idx, db_val) -> jax.Array:
    """Padded sparse-sparse scores. q [B,Kq], db [N,Kd] -> [B, N]."""
    eq = (q_idx[:, None, :, None] == db_idx[None, :, None, :]) \
        & (q_idx[:, None, :, None] != PAD_INDEX)
    prod = q_val[:, None, :, None].astype(jnp.float32) \
        * db_val[None, :, None, :].astype(jnp.float32)
    return jnp.sum(jnp.where(eq, prod, 0.0), axis=(2, 3))


def topk_ref(scores: jax.Array, k: int):
    """Row-wise top-k: (values [B,k], indices [B,k]), ties by lower index."""
    return jax.lax.top_k(scores, k)


def scorer_mlp_ref(feats, w0, b0, w1, b1, w2, b2) -> jax.Array:
    """Fused 2-hidden-layer tanh MLP + sigmoid head. feats [B,F] -> [B].
    f32 matmuls at HIGHEST precision, on every backend."""
    def dot(a, w):
        return jnp.dot(a, w.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)

    h = jnp.tanh(dot(feats.astype(jnp.float32), w0) + b0)
    h = jnp.tanh(dot(h, w1) + b1)
    return jax.nn.sigmoid((dot(h, w2) + b2)[..., 0])


def pq_score_seq_ref(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """Ordered (left-to-right over subspaces) LUT accumulation — the
    bitwise contract of the fused-query kernel's scoring stage.

    lut f32 [B, M, C]; codes u8 [B, N, M] -> [B, N].
    """
    acc = jnp.zeros(codes.shape[:2], jnp.float32)
    for mi in range(lut.shape[1]):
        acc = acc + jnp.take_along_axis(
            lut[:, mi, :], codes[:, :, mi].astype(jnp.int32), axis=1)
    return acc


def pq_score_seq_int8_ref(qlut, scale, codes) -> jax.Array:
    """Quantised scoring oracle: dequantise the int8 LUT back to f32 with
    its per-(query, subspace) scale, then run the ordered f32 loop — the
    scale multiply stays out of the accumulation chain by contract."""
    deq = qlut.astype(jnp.float32) * scale[..., None]
    return pq_score_seq_ref(deq, codes)


def shortlist_dedup_ref(vals, idxs, ids, valid):
    """Dedup-after-cut oracle: shortlist entry i is neutralised to -inf iff
    some earlier entry j < i selected the same point id with both slots
    valid.  ``idxs`` are untouched so gathers stay aligned."""
    sid = jnp.take_along_axis(ids, idxs, axis=1)
    sv = jnp.take_along_axis(valid, idxs, axis=1)
    same = (sid[:, :, None] == sid[:, None, :]) \
        & sv[:, :, None] & sv[:, None, :]
    k = vals.shape[1]
    earlier = jnp.arange(k)[None, :, None] > jnp.arange(k)[None, None, :]
    dup = jnp.any(same & earlier, axis=2)
    return jnp.where(dup, -jnp.inf, vals)


def fused_query_ref(lut, codes, ids, k: int, *, valid=None, bias=None,
                    quantized: bool = False):
    """Composed oracle for ``ops.pq_score_dedup_topk``: ordered PQ scores
    (+bias), invalid rows to -inf, ``lax.top_k`` (ties -> lowest index),
    then the triangular same-id dedup over the cut shortlist."""
    b, n = codes.shape[0], codes.shape[1]
    if valid is None:
        valid = jnp.ones((b, n), jnp.bool_)
    if bias is None:
        bias = jnp.zeros((b, n), jnp.float32)
    if quantized:
        from repro.kernels.fused_query import quantize_lut
        qlut, scale = quantize_lut(lut)
        acc = pq_score_seq_int8_ref(qlut, scale, codes)
    else:
        acc = pq_score_seq_ref(lut, codes)
    scores = jnp.where(valid, acc + bias, -jnp.inf)
    vals, idxs = jax.lax.top_k(scores, k)
    return shortlist_dedup_ref(vals, idxs, ids, valid), idxs
