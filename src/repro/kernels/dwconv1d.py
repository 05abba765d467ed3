"""Causal depthwise 1-D convolution Pallas kernel (SSM/Mamba conv preact).

This is the paper's DWConv design re-specialized to the sequence axis, which
is where depthwise convolution actually appears in the assigned LM
architectures (hymba's Mamba heads, xLSTM conv preactivation; K = 3..5).

Design (same levers as dwconv2d.py):
* grid ``(B, D/Db, L/Lb)`` — channel blocks parallel (paper's channel-outer
  loop), sequence blocks innermost & sequential.
* filter tile (K, Db) resident in VMEM for the whole sequence sweep.
* causal halo: instead of overlapping input blocks (not expressible with
  blocked BlockSpecs), a ``(K-1, Db)`` VMEM scratch carries the last K-1
  input rows across sequence steps — zero-initialized at l==0 (causal
  zero-pad). Grid iteration on a TensorCore is sequential over the
  ``arbitrary`` axis, so the carry is well-defined.
* output block written exactly once (store-once, Alg. 4 lines 29-34).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dw1d_kernel(x_ref, f_ref, out_ref, carry_ref, *, k: int, out_dtype):
    """Blocks: x (1, Lb, Db); f (K, Db); out (1, Lb, Db); carry (K-1, Db)."""
    l_idx = pl.program_id(2)

    @pl.when(l_idx == 0)
    def _reset():  # causal zero left-pad at sequence start
        carry_ref[...] = jnp.zeros_like(carry_ref)

    x = x_ref[0].astype(jnp.float32)                    # (Lb, Db)
    f = f_ref[...].astype(jnp.float32)                  # (K, Db) resident
    lb = x.shape[0]
    xp = jnp.concatenate([carry_ref[...], x], axis=0)   # (Lb + K - 1, Db)
    acc = jnp.zeros_like(x)
    for i in range(k):                                  # unrolled taps
        acc = acc + xp[i : i + lb, :] * f[i][None, :]
    out_ref[0] = acc.astype(out_dtype)                  # single store
    if k > 1:
        carry_ref[...] = x[lb - (k - 1) :, :]           # halo for next block


@functools.partial(
    jax.jit, static_argnames=("block_l", "block_d", "interpret")
)
def dwconv1d_causal_pallas(
    x: jax.Array,
    f: jax.Array,
    *,
    block_l: int = 1024,
    block_d: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """x: (B, L, D); f: (K, D) -> (B, L, D), causal (zero left-pad)."""
    b, l, d = x.shape
    k, df = f.shape
    assert d == df, (x.shape, f.shape)

    bl = min(block_l, l)
    bd = min(block_d, d)
    pad_l = (-l) % bl
    pad_d = (-d) % bd
    if pad_l or pad_d:
        x = jnp.pad(x, ((0, 0), (0, pad_l), (0, pad_d)))
        f = jnp.pad(f, ((0, 0), (0, pad_d)))
    lp, dp = l + pad_l, d + pad_d

    kernel = functools.partial(_dw1d_kernel, k=k, out_dtype=x.dtype)
    out = pl.pallas_call(
        kernel,
        grid=(b, dp // bd, lp // bl),
        in_specs=[
            pl.BlockSpec((1, bl, bd), lambda i, j, s: (i, s, j)),
            pl.BlockSpec((k, bd), lambda i, j, s: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bl, bd), lambda i, j, s: (i, s, j)),
        out_shape=jax.ShapeDtypeStruct((b, lp, dp), x.dtype),
        scratch_shapes=[pltpu.VMEM((max(k - 1, 1), bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, f)
    return out[:, :l, :d]
