"""Dense-friendly ops over the fixed-nnz padded sparse format.

The paper's embeddings are sparse vectors in a 2^32-dim bucket space; on TPU
we keep them as (indices[K], values[K]) rows (see DESIGN.md §2). The two
workhorse ops:

* ``sparse_dot_one_many`` — one query row against a database block. The
  pure-jnp form materializes a K_q × K_d equality mask per pair, which maps
  onto the VPU as a dense compare+reduce; the Pallas kernel
  (``repro.kernels.sparse_dot``) tiles the same computation through VMEM.
* ``count_sketch`` — feature-hashing projection into a d_proj-dim dense
  space (unbiased inner-product estimator), used to run the partitioner and
  the PQ codebooks in a space where centroids are representable.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import hashing
from repro.core.types import PAD_INDEX, SparseBatch


def sparse_dot_pair(qi, qv, di, dv) -> jax.Array:
    """Dot of two padded sparse rows: sum over matching indices."""
    eq = (qi[:, None] == di[None, :]) & (qi[:, None] != PAD_INDEX)
    return jnp.sum(jnp.where(eq, qv[:, None] * dv[None, :], 0.0))


def sparse_dot_one_many(qi, qv, db_idx, db_val) -> jax.Array:
    """One query row vs a database block.

    qi,qv: [Kq]; db_idx,db_val: [N, Kd] -> scores f32 [N].

    Query nonzeros accumulate in order (j = 0..Kq-1), each one's matches
    summed first. A row's indices are distinct, so each term holds at most
    one product and the result does not depend on how a backend orders a
    reduction; ``kernels/sparse_dot`` sums the same way, bitwise.
    """
    out = jnp.zeros(db_idx.shape[:1], jnp.result_type(qv, db_val))
    for j in range(qi.shape[0]):
        hit = (db_idx == qi[j]) & (qi[j] != PAD_INDEX)
        out = out + jnp.sum(jnp.where(hit, qv[j] * db_val, 0.0), axis=-1)
    return out


def sparse_dot_many_many(q: SparseBatch, db: SparseBatch) -> jax.Array:
    """All-pairs scores f32 [Bq, N] (vmapped one-many)."""
    return jax.vmap(lambda i, v: sparse_dot_one_many(i, v, db.indices, db.values))(
        q.indices, q.values)


def count_sketch(sp: SparseBatch, d_proj: int, seed: int = 7) -> jax.Array:
    """CountSketch projection to a dense d_proj space, f32 [B, d_proj].

    h(b) picks the output coordinate, s(b) in {±1} the sign — inner products
    are preserved in expectation, so partitioning/PQ in sketch space ranks
    candidates consistently with the sparse space (final scores are always
    exact-rescored in sparse space).
    """
    h = hashing.uhash(seed, sp.indices) % jnp.uint32(d_proj)
    s = jnp.where((hashing.uhash(seed + 1, sp.indices) & 1) == 1, 1.0, -1.0)
    vals = jnp.where(sp.indices == PAD_INDEX, 0.0, sp.values * s)
    out = jnp.zeros((sp.batch, d_proj), jnp.float32)
    rows = jnp.broadcast_to(jnp.arange(sp.batch)[:, None], sp.indices.shape)
    return out.at[rows, h.astype(jnp.int32)].add(vals)
