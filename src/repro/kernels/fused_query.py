"""Pallas TPU kernel: fused query shortlist (PQ-score -> SOAR-dedup -> top-k).

The serving shortlist path used to run three separately-jitted ops with HBM
round-trips between them: ``pq_score_batched`` (LUT scoring), an
``argsort(id)``-based SOAR dedup, and ``topk_select``.  This kernel fuses
all three: each grid step takes 8 query rows x ``BLOCK_N`` candidates,
accumulates their PQ lookup scores on the MXU (``pq_score.score_block``,
ordered per-subspace accumulation, see below), masks invalid rows, and
merges them into a running top-k held in VMEM (``topk_select.
select_topk``: k rounds of max, lowest-index argmax, retire). The point
id and validity of every kept candidate ride along, and after the last
block the SOAR duplicate check runs **in-register** over the final
shortlist.

Result contract (pinned bitwise by tests/test_kernels_fused.py):

* ``idxs`` are exactly ``jax.lax.top_k(scores, k)[1]`` where
  ``scores = where(valid, pq + bias, -inf)`` — ties resolve to the lowest
  candidate index, and fully-invalid rows yield ``idxs == 0, 1, ... k-1``.
* ``vals[i]`` is ``scores[idxs[i]]`` unless some earlier shortlist entry
  ``j < i`` carries the same point id with both entries valid, in which
  case ``vals[i] = -inf`` (the duplicate SOAR copy is neutralised but keeps
  its slot, so downstream gathers stay aligned with ``idxs``).

Dedup therefore happens AFTER the top-k cut ("dedup-after-cut"): the
shortlist ranking is by raw approximate score, and the best-scoring copy of
each point survives.  The old path deduped after exact rescoring by
id-sorted order; both keep exactly one copy per id and copies share exact
scores, so final (id, distance) results are unchanged — only the internal
tie-break moved, and it is documented here and in docs/ARCHITECTURE.md.

Work follows ``valid``: ``ops.shortlist_block_live`` flags each (8-row,
``BLOCK_N``-lane) grid block that holds a valid slot, the flags ride in
as scalar prefetch, and a step scores and merges only when its block is
flagged or is block 0; the dedup runs at the last step regardless. This
is exact: ``BLOCK_N >= k``, so block 0 alone fills the running top-k, and
a skipped block holds only -inf at indices above every kept entry, which
the lowest-index tie-break never lets in (rows with fewer than k valid
candidates included). Served slabs fill from the front, so every block
past a partition's append cursor is skipped. A row block scores its
first ``min(B, 8)`` rows, so a batch under 8 scores only its real rows;
padded rows stay -inf through ``valid = 0``.

Ordered accumulation: f32 addition is not associative, so the kernel, the
single-jit XLA twin (``fused_query_xla``) and the oracle
(``ref.fused_query_ref``) all accumulate subspaces left-to-right
(``acc += gather(lut[m])`` for m = 0..M-1).  The one-hot matmul form used
on the MXU (over an exact bf16 split of the LUT, ``pq_score.split_bf16``)
adds exact zeros to the gathered value, which is bitwise neutral, so
kernel == twin == oracle bitwise.  LUT and bias values must be
finite (0 * inf would poison the one-hot matmul).

The int8 variant quantises the LUT per (query, subspace) with a symmetric
scale (``quantize_lut``), dequantises in-register, and scores through the
same ordered f32 loop (the scale multiply never sits in the accumulation
chain, so XLA cannot FMA-contract it); its twin and oracle mirror the op
order exactly so the quantised path is bitwise reproducible too (against
its own oracle — quantisation changes scores vs the f32 path by
construction).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pq_score import (BLOCK_B, compiler_params, pad_axis,
                                    round_up, score_block)
from repro.kernels.topk_select import (NEG_INF, RETIRED, block_index,
                                       block_n_for, select_topk)


# ---------------------------------------------------------------------------
# kernel


def _dedup_cut(vals, ids, ok):
    """Dedup-after-cut over the final shortlist: slot i -> -inf iff some
    earlier slot j < i holds the same point id with both slots valid."""
    rows, k = vals.shape
    slot_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, k), 1)

    def body(i, dup):
        slot = slot_iota == i
        id_i = jnp.sum(jnp.where(slot, ids, 0), axis=1, keepdims=True)
        ok_i = jnp.sum(jnp.where(slot, ok, 0), axis=1, keepdims=True)
        same = (slot_iota < i) & (ids == id_i) & (ok != 0)
        hit = jnp.max(jnp.where(same, 1, 0), axis=1, keepdims=True)
        return jnp.where(slot & (hit > 0) & (ok_i != 0), 1, dup)

    dup = jax.lax.fori_loop(0, k, body, jnp.zeros((rows, k), jnp.int32))
    return jnp.where(dup != 0, NEG_INF, vals)


def _fused_kernel(block_live_ref, *refs, n_centers: int, k: int,
                  block_n: int, live_rows: int, quantized: bool):
    if quantized:
        (lut_ref, scale_ref, codes_ref, ids_ref, valid_ref, bias_ref,
         vals_ref, idxs_ref, sel_ids, sel_ok) = refs
    else:
        (lut_ref, codes_ref, ids_ref, valid_ref, bias_ref,
         vals_ref, idxs_ref, sel_ids, sel_ok) = refs
        scale_ref = None
    i, j, n_j = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
    # grid positions are read outside the branches below: the interpreter
    # cannot lower program_id inside a pl.when
    live = block_live_ref[i * n_j + j]
    idx = block_index(valid_ref.shape, block_n)

    @pl.when(j == 0)
    def _():
        vals_ref[...] = jnp.full(vals_ref.shape, NEG_INF, jnp.float32)
        idxs_ref[...] = jnp.full(idxs_ref.shape, RETIRED, jnp.int32)
        sel_ids[...] = jnp.zeros(sel_ids.shape, jnp.int32)
        sel_ok[...] = jnp.zeros(sel_ok.shape, jnp.int32)

    # block 0 fills the running top-k (block_n >= k); a later block with
    # no valid slot holds only -inf at higher indices, which can never
    # displace an entry, so skipping it leaves the result bit for bit
    @pl.when((j == 0) | (live != 0))
    def _():
        valid = valid_ref[...]
        acc = score_block(lut_ref, codes_ref, n_centers=n_centers,
                          shared_codes=False, scale_ref=scale_ref,
                          live_rows=live_rows)
        scores = jnp.where(valid != 0, acc + bias_ref[...], NEG_INF)
        vals, idxs, (ids, ok) = select_topk(
            [(vals_ref[...], idxs_ref[...], (sel_ids[...], sel_ok[...])),
             (scores, idx, (ids_ref[...], valid))], k)
        vals_ref[...] = vals
        idxs_ref[...] = idxs
        sel_ids[...] = ids
        sel_ok[...] = ok

    @pl.when(j == n_j - 1)
    def _():
        vals_ref[...] = _dedup_cut(vals_ref[...], sel_ids[...], sel_ok[...])


# ---------------------------------------------------------------------------
# quantisation


@jax.jit
def quantize_lut(lut: jax.Array):
    """Symmetric per-(query, subspace) int8 quantisation of an f32 LUT.

    lut f32 [B, M, C] -> (qlut i8 [B, M, C], scale f32 [B, M]).
    """
    amax = jnp.max(jnp.abs(lut), axis=-1)                       # [B, M]
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    qlut = jnp.round(lut / scale[..., None]).astype(jnp.int8)
    return qlut, scale


# ---------------------------------------------------------------------------
# pallas_call wrappers


def block_grid(b: int, n: int, k: int) -> tuple[int, int, int]:
    """(padded rows, padded candidates, lanes per block) of the kernel's
    grid over ``b`` query rows x ``n`` candidates at top-``k``."""
    bn = block_n_for(n, k)
    return round_up(b, BLOCK_B), round_up(n, bn), bn


def _fused_call(lut, scale, codes, ids, valid, bias, block_live, k: int,
                interpret: bool):
    """lut [B, M, C] (i8 when ``scale`` f32 [B, M] is given); codes u8
    [B, N, M]; ids i32, valid i32, bias f32 [B, N]; block_live i32
    [Bp/8 * Np/bn], row-block major, non-zero where a block may hold a
    valid slot (None: every block). B pads to 8 and N to the block;
    padded candidates sit after the real ones (valid=0, id=-1) so the
    lowest-index tie-break never prefers them while k <= n. A row block
    scores its first ``min(B, 8)`` rows: the padding of a batch under 8
    stays -inf unscored."""
    b, m, c = lut.shape
    n = codes.shape[1]
    assert k <= n, f"k={k} exceeds candidate count n={n}"
    bp, np_, bn = block_grid(b, n, k)
    grid = (bp // BLOCK_B, np_ // bn)
    if block_live is None:
        block_live = jnp.ones((grid[0] * grid[1],), jnp.int32)

    def rows(x, value=0):
        return pad_axis(pad_axis(x, 0, bp), 1, np_, value)

    args = [pad_axis(lut, 0, bp).transpose(1, 0, 2)]          # [M, Bp, C]
    specs = [pl.BlockSpec((m, BLOCK_B, c), lambda i, j, _: (0, i, 0))]
    if scale is not None:
        args.append(pad_axis(scale, 0, bp, 1.0).T[:, :, None])  # [M, Bp, 1]
        specs.append(pl.BlockSpec((m, BLOCK_B, 1),
                                  lambda i, j, _: (0, i, 0)))
    row_spec = pl.BlockSpec((BLOCK_B, bn), lambda i, j, _: (i, j))
    args += [rows(codes).transpose(0, 2, 1),                   # [Bp, M, Np]
             rows(ids, -1), rows(valid), rows(bias)]
    specs += [pl.BlockSpec((BLOCK_B, m, bn), lambda i, j, _: (i, 0, j)),
              row_spec, row_spec, row_spec]
    out_spec = pl.BlockSpec((BLOCK_B, k), lambda i, j, _: (i, 0))
    vals, idxs = pl.pallas_call(
        functools.partial(_fused_kernel, n_centers=c, k=k, block_n=bn,
                          live_rows=min(b, BLOCK_B),
                          quantized=scale is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=specs,
            out_specs=(out_spec, out_spec),
            scratch_shapes=[pltpu.VMEM((BLOCK_B, k), jnp.int32),
                            pltpu.VMEM((BLOCK_B, k), jnp.int32)]),
        out_shape=(jax.ShapeDtypeStruct((bp, k), jnp.float32),
                   jax.ShapeDtypeStruct((bp, k), jnp.int32)),
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(block_live, *args)
    return vals[:b], idxs[:b]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def fused_query_kernel(lut, codes, ids, valid, bias, k: int,
                       block_live=None, *, interpret: bool = False):
    """Single pallas_call: lut f32 [B,M,C]; codes u8 [B,N,M]; ids i32 [B,N];
    valid i32 [B,N]; bias f32 [B,N]; optional block flags (``_fused_call``)
    -> (vals f32 [B,k], idxs i32 [B,k])."""
    return _fused_call(lut, None, codes, ids, valid, bias, block_live, k,
                       interpret)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def fused_query_kernel_int8(qlut, scale, codes, ids, valid, bias, k: int,
                            block_live=None, *, interpret: bool = False):
    """Quantised variant: qlut i8 [B,M,C]; scale f32 [B,M]; rest as above."""
    return _fused_call(qlut, scale, codes, ids, valid, bias, block_live, k,
                       interpret)


# ---------------------------------------------------------------------------
# single-jit XLA twins — bitwise-identical semantics without a pallas_call,
# the production route on backends where Mosaic lowering is unavailable
# (this CPU container) and the composed escape hatch's building blocks.


def pq_scores_seq(lut, codes):
    """Ordered-accumulation LUT scoring (gather form): lut f32 [B, M, C];
    codes u8 [B, N, M] -> f32 [B, N].  Bitwise-matches the kernel's
    one-hot matmul (adding exact zeros is neutral in f32)."""
    acc = jnp.zeros(codes.shape[:2], jnp.float32)
    for mi in range(lut.shape[1]):
        acc = acc + jnp.take_along_axis(
            lut[:, mi, :], codes[:, :, mi].astype(jnp.int32), axis=1)
    return acc


def pq_scores_seq_int8(qlut, scale, codes):
    """Quantised twin: dequantise the LUT then run the f32 ordered loop
    (keeps the scale multiply out of the accumulation chain — no FMA)."""
    deq = qlut.astype(jnp.float32) * scale[..., None]
    return pq_scores_seq(deq, codes)


def dedup_mask_xla(vals, idxs, ids, valid):
    """Dedup-after-cut: neutralise later shortlist entries whose point id
    already appeared at an earlier (higher-ranked) valid slot.

    vals f32 [B, k]; idxs i32 [B, k]; ids i32 [B, N]; valid bool [B, N]
    -> vals with duplicate slots set to -inf (idxs unchanged)."""
    sid = jnp.take_along_axis(ids, idxs, axis=1)                 # [B, k]
    sv = jnp.take_along_axis(valid, idxs, axis=1)
    same = (sid[:, :, None] == sid[:, None, :]) \
        & sv[:, :, None] & sv[:, None, :]                        # [B, k, k]
    k = vals.shape[1]
    earlier = jnp.arange(k)[None, :, None] > jnp.arange(k)[None, None, :]
    dup = jnp.any(same & earlier, axis=2)
    return jnp.where(dup, NEG_INF, vals)


@functools.partial(jax.jit, static_argnames=("k", "quantized"))
def fused_query_xla(lut, codes, ids, valid, bias, k: int, *,
                    quantized: bool = False):
    """Single-jit fusion with semantics bitwise-identical to the kernel.

    ``valid``/``bias`` may be None (all-live / zero) — jit treats None as
    an empty pytree, so defaults materialise inside the trace instead of
    costing eager dispatches per call."""
    ids = jnp.asarray(ids).astype(jnp.int32)
    valid = (jnp.ones(codes.shape[:2], jnp.bool_) if valid is None
             else jnp.asarray(valid).astype(jnp.bool_))
    bias = (jnp.zeros(codes.shape[:2], jnp.float32) if bias is None
            else jnp.asarray(bias).astype(jnp.float32))
    if quantized:
        qlut, scale = quantize_lut(lut)
        acc = pq_scores_seq_int8(qlut, scale, codes)
    else:
        acc = pq_scores_seq(lut, codes)
    scores = jnp.where(valid, acc + bias, NEG_INF)
    vals, idxs = jax.lax.top_k(scores, k)
    return dedup_mask_xla(vals, idxs, ids, valid), idxs
