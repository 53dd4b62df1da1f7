"""Pallas TPU kernel: fused pair-scorer MLP (paper's 2-layer similarity NN).

Serving scores |Q| x ScaNN-NN candidate pairs per neighborhood RPC; the
model is tiny (F -> H -> H -> 1, H = 10 in the paper), so the win is not
FLOPs but *fusion*: one VMEM-resident pass instead of five HBM round trips
for the intermediate activations. Weights are broadcast to every grid step
(index_map pins them to block 0) and the feature matrix streams through in
``block_b`` rows.

Note the hardware-alignment padding in ops.py: H=10 is far off the 128-lane
VPU grain, so the wrapper zero-pads the hidden dims once at load time —
padding weights, not activations, costs nothing per query.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _scorer_kernel(feats_ref, w0_ref, b0_ref, w1_ref, b1_ref,
                   w2_ref, b2_ref, out_ref):
    def dot(a, w_ref):
        return jnp.dot(a, w_ref[...], precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)

    x = feats_ref[...].astype(jnp.float32)           # [BB, F]
    h = jnp.tanh(dot(x, w0_ref) + b0_ref[...])
    h = jnp.tanh(dot(h, w1_ref) + b1_ref[...])
    logit = dot(h, w2_ref) + b2_ref[...]             # [BB, 1]
    out_ref[...] = jax.nn.sigmoid(logit)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def scorer_mlp(feats, w0, b0, w1, b1, w2, b2, *, block_b: int = 256,
               interpret: bool = False) -> jax.Array:
    """feats [B, F] + MLP params -> sigmoid scores f32 [B].

    Every operand is 2-D (biases as [1, H] rows, scores as a [B, 1]
    column): Mosaic tiles only the last two dims of a block."""
    b, f = feats.shape
    h = w0.shape[1]
    b_pad = -b % block_b
    if b_pad:
        feats = jnp.pad(feats, ((0, b_pad), (0, 0)))
    grid = ((b + b_pad) // block_b,)
    fixed = lambda bb: (0, 0)
    out = pl.pallas_call(
        _scorer_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, f), lambda bb: (bb, 0)),
            pl.BlockSpec((f, h), fixed),
            pl.BlockSpec((1, h), fixed),
            pl.BlockSpec((h, h), fixed),
            pl.BlockSpec((1, h), fixed),
            pl.BlockSpec((h, 1), fixed),
            pl.BlockSpec((1, 1), fixed),
        ],
        out_specs=pl.BlockSpec((block_b, 1), lambda bb: (bb, 0)),
        out_shape=jax.ShapeDtypeStruct((b + b_pad, 1), jnp.float32),
        interpret=interpret,
    )(feats, w0.astype(jnp.float32), b0.astype(jnp.float32).reshape(1, h),
      w1.astype(jnp.float32), b1.astype(jnp.float32).reshape(1, h),
      w2.astype(jnp.float32), b2.astype(jnp.float32).reshape(1, 1))
    return out[:b, 0]
