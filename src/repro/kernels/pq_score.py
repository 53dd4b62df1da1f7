"""Pallas TPU kernel: PQ lookup-table scoring (the ScaNN hot loop).

CPU ScaNN does LUT scoring with AVX shuffle gathers; the TPU-native
formulation (DESIGN.md §2) turns the per-subspace gather into a one-hot
matmul so the inner loop runs on the MXU:

    scores[b, n] = sum_m lut[b, m, codes[n, m]]
                 = sum_m lut[b, m, :] . onehot(codes[n, m], C)

Layout for Mosaic: the wrappers transpose the LUT to ``[M, B, C]`` and the
codes to ``[B, M, N]`` (``[M, N]`` when every query scores the same
codes), so one grid step holds ``BLOCK_B`` (8) query rows x ``block_n``
(a multiple of 128) candidates and every block's last two dims are
(sublane, lane)-aligned. B pads to 8 and N to ``block_n`` inside the
wrappers; the padding is sliced away.

Per grid step and subspace m, ``lut[m]`` ``[8, C]`` times the one-hot
``[C, block_n]`` of one query row's codes yields that row's gathered LUT
values. The gather is exact: the LUT is split into three bf16 parts whose
one-hot matmuls each return their part unchanged (the one-hot adds exact
zeros), and the parts sum back to the f32 value. Subspaces accumulate
left to right — the ordered contract of ``kernels/fused_query.py``. VMEM
per step ~= 8*M*block_n (codes) + M*8*C*4 (LUTs) + C*block_n*2 (one-hot):
about 1.1 MiB at M=16, C=256, block_n=1024, inside the 16 MiB v5e
scoped-VMEM default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_B = 8          # query rows per grid step (the f32 sublane grain)
LANE = 128


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def pad_axis(x, axis: int, size: int, value=0):
    """Pad ``x`` along ``axis`` up to ``size`` with ``value``."""
    extra = size - x.shape[axis]
    if not extra:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, extra)
    return jnp.pad(x, widths, constant_values=value)


def compiler_params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def split_bf16(x):
    """Exact 3-way bf16 split of f32 ``x``: ``(hi + mid) + lo == x`` in
    f32. Each part is cut by truncating the low 16 bits, so it holds 8 of
    x's 24 significand bits and is exactly representable in bf16: no
    rounding mode or excess-precision rewrite can change it. A one-hot
    bf16 matmul of each part is therefore an exact gather on any MXU."""
    def trunc(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.int32) & -65536
        return jax.lax.bitcast_convert_type(bits, jnp.float32)

    hi = trunc(x)
    r = x - hi
    mid = trunc(r)
    return (hi.astype(jnp.bfloat16), mid.astype(jnp.bfloat16),
            (r - mid).astype(jnp.bfloat16))


def score_block(lut_ref, codes_ref, *, n_centers: int, shared_codes: bool,
                scale_ref=None, live_rows: int | None = None):
    """Ordered one-hot-matmul LUT scores of one grid step -> f32 [rows, bn].

    lut_ref [M, rows, C] f32 — or i8 with ``scale_ref`` f32 [M, rows, 1],
    dequantised per subspace before scoring; codes_ref [rows, M, bn], or
    [M, bn] with ``shared_codes``. Row r accumulates m = 0..M-1 left to
    right: bitwise ``acc += lut[r, m, codes[r, :, m]]``. Per-row codes
    score only the first ``live_rows`` rows (default all); the rest stay
    0."""
    n_sub, rows, _ = lut_ref.shape
    bn = codes_ref.shape[-1]
    centers = jax.lax.broadcasted_iota(jnp.int32, (n_centers, bn), 0)

    def table(m):
        if scale_ref is None:
            return lut_ref[m]
        return lut_ref[m].astype(jnp.float32) * scale_ref[m]

    def gathered(m, codes_row):                    # codes_row [1, bn]
        onehot = (codes_row.astype(jnp.int32) == centers).astype(jnp.bfloat16)
        out = None
        for part in split_bf16(table(m)):
            g = jnp.dot(part, onehot, preferred_element_type=jnp.float32)
            out = g if out is None else out + g
        return out                                 # [rows, bn]

    acc = jnp.zeros((rows, bn), jnp.float32)
    if shared_codes:
        for m in range(n_sub):
            acc = acc + gathered(m, codes_ref[pl.ds(m, 1), :])
        return acc

    row = jax.lax.broadcasted_iota(jnp.int32, (rows, bn), 0)

    def per_row(r, acc):
        for m in range(n_sub):                     # fixed l-to-r order
            part = gathered(m, codes_ref[r, pl.ds(m, 1), :])
            acc = jnp.where(row == r, acc + part, acc)
        return acc

    return jax.lax.fori_loop(0, rows if live_rows is None else live_rows,
                             per_row, acc)


def _pq_score_kernel(lut_ref, codes_ref, out_ref, *, n_centers: int,
                     shared_codes: bool):
    out_ref[...] = score_block(lut_ref, codes_ref, n_centers=n_centers,
                               shared_codes=shared_codes)


def _pq_call(lut, codes_t, n: int, block_n: int, interpret: bool):
    """lut f32 [B, M, C]; codes_t [B, M, N] or [M, N] -> f32 [B, N]."""
    b, m, c = lut.shape
    shared = codes_t.ndim == 2
    bn = min(round_up(block_n, LANE), round_up(n, LANE))
    bp, np_ = round_up(b, BLOCK_B), round_up(n, bn)
    lut_t = pad_axis(lut, 0, bp).transpose(1, 0, 2)          # [M, Bp, C]
    codes_t = pad_axis(codes_t, codes_t.ndim - 1, np_)
    if shared:
        codes_spec = pl.BlockSpec((m, bn), lambda i, j: (0, j))
    else:
        codes_t = pad_axis(codes_t, 0, bp)
        codes_spec = pl.BlockSpec((BLOCK_B, m, bn), lambda i, j: (i, 0, j))
    out = pl.pallas_call(
        functools.partial(_pq_score_kernel, n_centers=c,
                          shared_codes=shared),
        grid=(bp // BLOCK_B, np_ // bn),
        in_specs=[pl.BlockSpec((m, BLOCK_B, c), lambda i, j: (0, i, 0)),
                  codes_spec],
        out_specs=pl.BlockSpec((BLOCK_B, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, np_), jnp.float32),
        compiler_params=compiler_params("parallel", "parallel"),
        interpret=interpret,
    )(lut_t, codes_t)
    return out[:b, :n]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def pq_score_batched(lut: jax.Array, codes: jax.Array, *, block_n: int = 1024,
                     interpret: bool = False) -> jax.Array:
    """Per-query candidate slabs: lut f32 [B, M, C]; codes u8 [B, N, M]
    -> scores f32 [B, N]. (The serving path gathers a different partition
    slab per query, so codes carry a batch dim here.)"""
    return _pq_call(lut, codes.transpose(0, 2, 1), codes.shape[1], block_n,
                    interpret)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def pq_score(lut: jax.Array, codes: jax.Array, *, block_n: int = 1024,
             interpret: bool = False) -> jax.Array:
    """lut f32 [B, M, C]; codes u8 [N, M] -> scores f32 [B, N]."""
    return _pq_call(lut, codes.T, codes.shape[0], block_n, interpret)
