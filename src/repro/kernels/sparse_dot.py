"""Pallas TPU kernel: batched sparse-sparse dot products (exact rescoring).

The paper's exact similarity Dist(p,q) = -M(p).M(q) over fixed-nnz padded
rows. The CPU idiom is a sorted-list merge per pair; merges are branchy and
serialize badly on vector hardware, so the TPU formulation compares every
query nonzero against every candidate nonzero as a dense equality mask and
reduces — VPU-shaped compute with zero data-dependent control flow
(DESIGN.md §2).

Layout for Mosaic: the wrappers transpose the candidate rows to
``[Kd, N]`` (``[B, Kd, R]`` for per-query shortlists), so one grid step
holds 8 query rows x ``block_n`` (a multiple of 128) candidates with the
candidates on lanes; the query rows sit in SMEM and are read as scalars.
Per query row and query nonzero j, the ``[Kd, block_n]`` equality mask
reduces over sublanes, and the nonzeros accumulate in order j = 0..Kq-1 —
the order of ``ann/sparse.py::sparse_dot_one_many``, so the two agree
bitwise (a row's indices are distinct: each term holds one product).
Indices travel as int32 bit patterns (equality is unchanged; PAD_INDEX
becomes -1) and values as f32. VMEM per step ~= 2*Kd*block_n*4 (x8 rows
for shortlists) — about 0.5 MiB at Kd=16, block_n=512.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.types import PAD_INDEX
from repro.kernels.pq_score import (BLOCK_B, LANE, compiler_params, pad_axis,
                                    round_up)

_PAD = int(np.asarray(PAD_INDEX, np.uint32).view(np.int32))


def _sparse_dot_kernel(q_idx_ref, q_val_ref, db_idx_ref, db_val_ref,
                       out_ref, *, shared_db: bool):
    rows, kq = q_idx_ref.shape
    bn = out_ref.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, bn), 0)

    def per_row(r, acc):
        db_idx = db_idx_ref[...] if shared_db else db_idx_ref[r]  # [Kd, bn]
        db_val = db_val_ref[...] if shared_db else db_val_ref[r]
        score = jnp.zeros((1, bn), jnp.float32)
        for j in range(kq):
            qi = q_idx_ref[r, j]                                  # SMEM scalar
            hit = (db_idx == qi) & (qi != _PAD)
            score = score + jnp.sum(
                jnp.where(hit, q_val_ref[r, j] * db_val, 0.0),
                axis=0, keepdims=True)
        return jnp.where(row == r, score, acc)

    out_ref[...] = jax.lax.fori_loop(
        0, rows, per_row, jnp.zeros((rows, bn), jnp.float32))


def _as_i32(idx):
    return jax.lax.bitcast_convert_type(idx.astype(jnp.uint32), jnp.int32)


def _sparse_call(q_idx, q_val, db_idx_t, db_val_t, n: int, block_n: int,
                 interpret: bool):
    """q [B, Kq]; db_*_t [Kd, N] or [B, Kd, N] -> f32 [B, N]."""
    b, kq = q_idx.shape
    shared = db_idx_t.ndim == 2
    kd = db_idx_t.shape[-2]
    bn = min(round_up(block_n, LANE), round_up(n, LANE))
    bp, np_ = round_up(b, BLOCK_B), round_up(n, bn)
    q_idx = pad_axis(_as_i32(q_idx), 0, bp, _PAD)
    q_val = pad_axis(q_val.astype(jnp.float32), 0, bp)
    last = db_idx_t.ndim - 1
    db_idx_t = pad_axis(_as_i32(db_idx_t), last, np_, _PAD)
    db_val_t = pad_axis(db_val_t.astype(jnp.float32), last, np_)
    if shared:
        db_spec = pl.BlockSpec((kd, bn), lambda i, j: (0, j))
    else:
        db_idx_t = pad_axis(db_idx_t, 0, bp, _PAD)
        db_val_t = pad_axis(db_val_t, 0, bp)
        db_spec = pl.BlockSpec((BLOCK_B, kd, bn), lambda i, j: (i, 0, j))
    q_spec = pl.BlockSpec((BLOCK_B, kq), lambda i, j: (i, 0),
                          memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_sparse_dot_kernel, shared_db=shared),
        grid=(bp // BLOCK_B, np_ // bn),
        in_specs=[q_spec, q_spec, db_spec, db_spec],
        out_specs=pl.BlockSpec((BLOCK_B, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, np_), jnp.float32),
        compiler_params=compiler_params("parallel", "parallel"),
        interpret=interpret,
    )(q_idx, q_val, db_idx_t, db_val_t)
    return out[:b, :n]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def sparse_dot_batched(q_idx, q_val, db_idx, db_val, *, block_n: int = 512,
                       interpret: bool = False) -> jax.Array:
    """Per-query candidate rows (rescoring a shortlist): q [B, Kq] vs
    db [B, R, Kd] -> scores f32 [B, R]."""
    return _sparse_call(q_idx, q_val, db_idx.transpose(0, 2, 1),
                        db_val.transpose(0, 2, 1), db_idx.shape[1], block_n,
                        interpret)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def sparse_dot(q_idx: jax.Array, q_val: jax.Array, db_idx: jax.Array,
               db_val: jax.Array, *, block_n: int = 512,
               interpret: bool = False) -> jax.Array:
    """q [B, Kq] (u32/f32); db [N, Kd] -> scores f32 [B, N]."""
    return _sparse_call(q_idx, q_val, db_idx.T, db_val.T, db_idx.shape[0],
                        block_n, interpret)
