"""Public jit'd entry points for the Pallas kernels.

The platform picks the path, in one place (``on_tpu``): on a TPU every
entry point lowers its Pallas kernel through Mosaic, and the fused
shortlist ops always run the kernel. On any other backend (the CPU test
runs) the kernels execute in interpret mode, and ``use_kernel=None``
routes the shortlist ops to their bitwise-identical single-jit XLA twins.
Explicit ``interpret=`` / ``use_kernel=`` arguments exist for the parity
tests only. The kernel wrappers pad to the hardware grain (8 query rows,
128 lanes) themselves, so callers never need to know it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import fused_query as _fq
from repro.kernels import pq_score as _pq
from repro.kernels import scorer_mlp as _mlp
from repro.kernels import sparse_dot as _sd
from repro.kernels import topk_select as _tk

quantize_lut = _fq.quantize_lut


def on_tpu() -> bool:
    """Whether the default backend compiles Pallas TPU kernels."""
    return jax.default_backend() == "tpu"


def _interpret(interpret: bool | None) -> bool:
    return not on_tpu() if interpret is None else interpret


def pq_score(lut: jax.Array, codes: jax.Array, *,
             interpret: bool | None = None) -> jax.Array:
    """LUT scoring: lut f32 [B, M, C]; codes u8 [N, M] -> f32 [B, N]."""
    return _pq.pq_score(lut, codes, interpret=_interpret(interpret))


def pq_score_batched(lut, codes, *, interpret: bool | None = None
                     ) -> jax.Array:
    """Per-query slabs: lut f32 [B, M, C]; codes u8 [B, N, M] -> [B, N]."""
    return _pq.pq_score_batched(lut, codes, interpret=_interpret(interpret))


def sparse_dot(q_idx, q_val, db_idx, db_val, *,
               interpret: bool | None = None) -> jax.Array:
    """Exact sparse-sparse scores: q [B,Kq] vs db [N,Kd] -> f32 [B, N]."""
    return _sd.sparse_dot(q_idx, q_val, db_idx, db_val,
                          interpret=_interpret(interpret))


def sparse_dot_batched(q_idx, q_val, db_idx, db_val, *,
                       interpret: bool | None = None) -> jax.Array:
    """Shortlist rescoring: q [B,Kq] vs db [B,R,Kd] -> f32 [B, R]."""
    return _sd.sparse_dot_batched(q_idx, q_val, db_idx, db_val,
                                  interpret=_interpret(interpret))


def topk_select(scores: jax.Array, k: int, *, interpret: bool | None = None):
    """Row-wise top-k (vals, idxs). Kernel path for k <= 64, else lax."""
    if k > 64:
        return jax.lax.top_k(scores, k)
    return _tk.topk_select(scores, k, interpret=_interpret(interpret))


def pq_scores(lut, codes, *, quantized: bool = False,
              use_kernel: bool | None = None,
              interpret: bool | None = None) -> jax.Array:
    """Raw shortlist scores with the fused-path ordering contract:
    lut f32 [B, M, C]; codes u8 [B, N, M] -> f32 [B, N].

    ``use_kernel=None`` runs the Pallas kernel on a TPU and the single-jit
    XLA twin elsewhere; both are bitwise identical. The quantised variant
    always scores through the XLA twin (the int8 pallas path only exists
    fused, inside pq_score_dedup_topk).
    """
    if use_kernel is None:
        use_kernel = on_tpu()
    if quantized:
        qlut, scale = _fq.quantize_lut(lut)
        return _pq_scores_seq_int8_jit(qlut, scale, codes)
    if use_kernel:
        return _pq.pq_score_batched(lut, codes,
                                    interpret=_interpret(interpret))
    return _pq_scores_seq_jit(lut, codes)


@jax.jit
def _pq_scores_seq_jit(lut, codes):
    return _fq.pq_scores_seq(lut, codes)


@jax.jit
def _pq_scores_seq_int8_jit(qlut, scale, codes):
    return _fq.pq_scores_seq_int8(qlut, scale, codes)


@jax.jit
def dedup_mask(vals, idxs, ids, valid) -> jax.Array:
    """SOAR dedup over a cut shortlist: -inf the later of any two valid
    entries sharing a point id.  vals/idxs [B, k]; ids/valid [B, N]."""
    return _fq.dedup_mask_xla(vals, idxs, ids, valid.astype(jnp.bool_))


def pq_score_dedup_topk(lut, codes, ids, k: int, *, valid=None, bias=None,
                        quantized: bool = False,
                        use_kernel: bool | None = None,
                        interpret: bool | None = None):
    """Fused query shortlist: PQ-LUT scores (+bias), invalid rows -> -inf,
    top-k with lax.top_k tie-break, SOAR dedup-after-cut in-register.

    lut f32 [B, M, C]; codes u8 [B, N, M]; ids [B, N] (any integer dtype;
    uint32 wraps deterministically — dedup only compares equality among
    valid rows, so PAD sentinels are harmless as long as they are invalid)
    -> (vals f32 [B, k], idxs i32 [B, k]).  See kernels/fused_query.py for
    the full result contract.

    ``use_kernel=None`` runs the pallas_call on a TPU and the
    bitwise-identical single-jit XLA twin elsewhere. ``use_kernel=True``
    forces the pallas_call (interpreted off a TPU) — what the parity tests
    exercise.
    """
    if use_kernel is None:
        use_kernel = on_tpu()
    if not use_kernel:
        # normalization (astype, default masks) happens inside the jit —
        # eager per-call conversions here cost more than the op itself
        return _fq.fused_query_xla(lut, codes, ids, valid, bias, k,
                                   quantized=quantized)
    b, n = codes.shape[0], codes.shape[1]
    ids = jnp.asarray(ids).astype(jnp.int32)
    valid = (jnp.ones((b, n), jnp.bool_) if valid is None
             else jnp.asarray(valid).astype(jnp.bool_))
    bias = (jnp.zeros((b, n), jnp.float32) if bias is None
            else jnp.asarray(bias).astype(jnp.float32))
    interpret = _interpret(interpret)
    # the flags are their own program, outside the kernel's jit: the trace
    # reader counts every op named after the kernel as one of its calls
    block_live = shortlist_block_live(valid, k)
    valid_i = valid.astype(jnp.int32)
    if quantized:
        qlut, scale = _fq.quantize_lut(lut)
        return _fq.fused_query_kernel_int8(qlut, scale, codes, ids, valid_i,
                                           bias, k, block_live,
                                           interpret=interpret)
    return _fq.fused_query_kernel(lut, codes, ids, valid_i, bias, k,
                                  block_live, interpret=interpret)


@functools.partial(jax.jit, static_argnames="k")
def shortlist_block_live(valid, k: int) -> jax.Array:
    """The fused shortlist kernel's block flags: 1 for each (8-row,
    block-lane) block of its grid that holds a valid slot, which are the
    blocks it scores (block 0 always runs). valid bool [B, N] -> i32
    [Bp/8 * Np/bn], row-block major."""
    b, n = valid.shape
    bp, np_, bn = _fq.block_grid(b, n, k)
    v = _pq.pad_axis(_pq.pad_axis(valid, 0, bp), 1, np_)
    live = jnp.any(v.reshape(bp // _pq.BLOCK_B, _pq.BLOCK_B, np_ // bn, bn),
                   axis=(1, 3))
    return live.astype(jnp.int32).reshape(-1)


def scorer_mlp(feats, params: dict, *, interpret: bool | None = None):
    """Fused paper-scorer: feats [B, F] + core.scorer params -> f32 [B].

    Pads hidden dims to the 128-lane grain (8 when interpreted).
    """
    interpret = _interpret(interpret)
    w0, b0 = params["w0"], params["b0"]
    w1, b1 = params["w1"], params["b1"]
    w2, b2 = params["w2"], params["b2"]
    h = w0.shape[1]
    h_pad = -h % 8 if interpret else -h % 128
    if h_pad:
        w0 = jnp.pad(w0, ((0, 0), (0, h_pad)))
        b0 = jnp.pad(b0, ((0, h_pad),))
        w1 = jnp.pad(w1, ((0, h_pad), (0, h_pad)))
        b1 = jnp.pad(b1, ((0, h_pad),))
        w2 = jnp.pad(w2, ((0, h_pad), (0, 0)))
    return _mlp.scorer_mlp(feats, w0, b0, w1, b1, w2, b2,
                           interpret=interpret)
