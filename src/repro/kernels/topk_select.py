"""Pallas TPU kernel: small-k top-k selection over scored candidates.

ScaNN-NN is small (10-1000) while the scored candidate set is large; the
selection is bandwidth-bound. The kernel streams each row through VMEM in
``BLOCK_N``-lane blocks and keeps a running top-k: per block it runs k
rounds of (max, lowest-index argmax, retire) over the running set plus the
block — O(kN) VPU work with no sort, the standard TPU idiom for k << N.

Ties resolve to the lowest candidate index, exactly like
``jax.lax.top_k``: blocks arrive in index order and every candidate
carries its global index, so the streamed selection equals the selection
over the whole row. Retired candidates are marked by setting their index
to ``RETIRED`` rather than masking their score, so rows that hold
legitimate -inf scores (tombstones, padding) still yield distinct
ascending indices.

Grid: (row blocks of 8, N blocks); the outputs stay resident across the N
axis and double as the running state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.pq_score import (BLOCK_B, LANE, compiler_params, pad_axis,
                                    round_up)

NEG_INF = float("-inf")
RETIRED = 2 ** 31 - 1        # index of a retired (or not yet filled) slot
BLOCK_N = 1024               # candidate lanes streamed per grid step


def select_topk(parts, k: int):
    """k rounds of lowest-index argmax over several candidate arrays.

    ``parts``: sequence of ``(vals f32 [R, W], idx i32 [R, W], payload)``
    where ``payload`` is a tuple of i32 [R, W] arrays carried along with
    each pick and ``idx == RETIRED`` marks a dead candidate. Returns
    ``(vals [R, k], idx [R, k], payload tuple of [R, k])`` in pick order.
    Every row must hold at least k live candidates."""
    rows = parts[0][0].shape[0]
    n_pay = len(parts[0][2])
    slot_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, k), 1)
    vals = [v for v, _, _ in parts]
    pays = [p for _, _, p in parts]

    def body(i, carry):
        idxs, out_v, out_i, out_p = carry
        live = [ix != RETIRED for ix in idxs]
        masked = [jnp.where(lv, v, NEG_INF) for lv, v in zip(live, vals)]
        best = functools.reduce(jnp.maximum, [
            jnp.max(mv, axis=1, keepdims=True) for mv in masked])
        pick = functools.reduce(jnp.minimum, [
            jnp.min(jnp.where(lv & (mv == best), ix, RETIRED), axis=1,
                    keepdims=True)
            for lv, mv, ix in zip(live, masked, idxs)])
        hits = [ix == pick for ix in idxs]
        picked = [functools.reduce(jnp.add, [
            jnp.sum(jnp.where(h, p[t], 0), axis=1, keepdims=True)
            for h, p in zip(hits, pays)]) for t in range(n_pay)]
        slot = slot_iota == i
        out_v = jnp.where(slot, best, out_v)
        out_i = jnp.where(slot, pick, out_i)
        out_p = tuple(jnp.where(slot, pv, o) for pv, o in zip(picked, out_p))
        idxs = [jnp.where(h, RETIRED, ix) for h, ix in zip(hits, idxs)]
        return idxs, out_v, out_i, out_p

    init = ([ix for _, ix, _ in parts],
            jnp.full((rows, k), NEG_INF, jnp.float32),
            jnp.full((rows, k), RETIRED, jnp.int32),
            tuple(jnp.zeros((rows, k), jnp.int32) for _ in range(n_pay)))
    _, out_v, out_i, out_p = jax.lax.fori_loop(0, k, body, init)
    return out_v, out_i, out_p


def block_index(shape, block_n: int):
    """Global candidate index of every lane of the current N block."""
    return (pl.program_id(1) * block_n
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _topk_kernel(scores_ref, vals_ref, idxs_ref, *, k: int, block_n: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        vals_ref[...] = jnp.full(vals_ref.shape, NEG_INF, jnp.float32)
        idxs_ref[...] = jnp.full(idxs_ref.shape, RETIRED, jnp.int32)

    blk = scores_ref[...].astype(jnp.float32)
    vals, idxs, _ = select_topk(
        [(vals_ref[...], idxs_ref[...], ()),
         (blk, block_index(blk.shape, block_n), ())], k)
    vals_ref[...] = vals
    idxs_ref[...] = idxs


def block_n_for(n: int, k: int) -> int:
    """Lane-aligned N block: at most ``BLOCK_N`` (or N rounded up), never
    below k, so the first block alone fills the running top-k."""
    return max(min(BLOCK_N, round_up(n, LANE)), round_up(k, LANE))


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_select(scores: jax.Array, k: int, *, interpret: bool = False):
    """scores f32 [B, N] -> (values f32 [B, k], indices i32 [B, k]).

    Needs k <= N. Padding sits after the real candidates with -inf
    scores, so the lowest-index tie-break never prefers it."""
    b, n = scores.shape
    assert k <= n, f"k={k} exceeds candidate count n={n}"
    bn = block_n_for(n, k)
    bp, np_ = round_up(b, BLOCK_B), round_up(n, bn)
    scores = pad_axis(pad_axis(scores, 0, bp), 1, np_, NEG_INF)
    out_spec = pl.BlockSpec((BLOCK_B, k), lambda i, j: (i, 0))
    vals, idxs = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, block_n=bn),
        grid=(bp // BLOCK_B, np_ // bn),
        in_specs=[pl.BlockSpec((BLOCK_B, bn), lambda i, j: (i, j))],
        out_specs=(out_spec, out_spec),
        out_shape=(jax.ShapeDtypeStruct((bp, k), jnp.float32),
                   jax.ShapeDtypeStruct((bp, k), jnp.int32)),
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(scores)
    return vals[:b], idxs[:b]
