"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernel tests ``assert_allclose`` against, and
the XLA fallback path used on hosts without a TPU (this container). They are
written for clarity, not speed; the jitted dispatch in :mod:`repro.kernels.ops`
picks between these and the Pallas implementations.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.epilogue import apply_epilogue

# ---------------------------------------------------------------------------
# Depthwise 2-D convolution (paper Alg. 1/4), NHWC, filter (Hf, Wf, C).
# ---------------------------------------------------------------------------


def dwconv2d_ref(
    x: jax.Array,
    f: jax.Array,
    *,
    stride: int = 1,
    padding: str = "valid",
) -> jax.Array:
    """Depthwise conv. x: (B, Hi, Wi, C); f: (Hf, Wf, C) -> (B, Ho, Wo, C)."""
    assert x.ndim == 4 and f.ndim == 3 and x.shape[-1] == f.shape[-1]
    c = x.shape[-1]
    # lax depthwise: rhs (Hf, Wf, 1, C) with feature_group_count=C, NHWC/HWIO/NHWC.
    rhs = f[:, :, None, :]
    out = jax.lax.conv_general_dilated(
        x.astype(jnp.float32),
        rhs.astype(jnp.float32),
        window_strides=(stride, stride),
        padding=padding.upper(),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=c,
    )
    return out.astype(x.dtype)


def dwconv2d_loops_ref(
    x: np.ndarray, f: np.ndarray, *, stride: int = 1
) -> np.ndarray:
    """Paper Alg. 1 (unoptimized 5-nested-loop MAC), VALID padding, numpy.

    Deliberately literal — used to cross-check the lax oracle itself.
    """
    b, hi, wi, c = x.shape
    hf, wf, _ = f.shape
    ho = (hi - hf) // stride + 1
    wo = (wi - wf) // stride + 1
    out = np.zeros((b, ho, wo, c), dtype=np.float64)
    for bb in range(b):
        for l in range(ho):
            for k in range(wo):
                for i in range(c):
                    for n in range(hf):
                        for m in range(wf):
                            out[bb, l, k, i] += (
                                x[bb, l * stride + n, k * stride + m, i] * f[n, m, i]
                            )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Pointwise convolution == GEMM (paper Alg. 3/5/6).
# ---------------------------------------------------------------------------


# The bias+activation tail is shared package-wide (kernels/epilogue.py);
# `_epilogue` stays as an alias for old call sites.
_epilogue = apply_epilogue


@jax.custom_vjp
def _mm(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _mm_fwd(x, w):
    return _mm(x, w), (x, w)


def _mm_bwd(res, g):
    """Grads cast to the *param dtype before* any cross-device reduction:
    with bf16 weights the partial-dW all-reduce/reduce-scatter moves half
    the bytes (Megatron-style bf16 gradient reduction). Microbatch
    accumulation upstream still sums in fp32."""
    x, w = res
    dx = jnp.dot(g, w.T, preferred_element_type=jnp.float32).astype(x.dtype)
    ci = x.shape[-1]
    x2 = x.reshape(-1, ci)
    g2 = g.reshape(-1, g.shape[-1])
    dw = jnp.dot(x2.T, g2, preferred_element_type=jnp.float32).astype(w.dtype)
    return dx, dw


_mm.defvjp(_mm_fwd, _mm_bwd)


def pwconv_ref(
    x: jax.Array,
    w: jax.Array,
    *,
    bias: Optional[jax.Array] = None,
    activation: Optional[str] = None,
) -> jax.Array:
    """Pointwise conv / GEMM. x: (..., Ci); w: (Ci, Co) -> (..., Co).

    fp32 accumulation regardless of input dtype (matches MXU semantics);
    backward reduces gradients in the param dtype (see _mm_bwd).
    """
    y = _mm(x, w)
    y = _epilogue(y, bias, activation)
    return y.astype(x.dtype)


def separable_fused_ref(
    x: jax.Array,
    dw_f: jax.Array,
    pw_w: jax.Array,
    dw_bias: Optional[jax.Array] = None,
    pw_bias: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
    *,
    expand_w: Optional[jax.Array] = None,
    expand_activation: Optional[str] = "relu6",
    stride: int = 1,
    padding: str = "valid",
    dw_activation: Optional[str] = "relu6",
    activation: Optional[str] = None,
) -> jax.Array:
    """Oracle for the fused DW+PW block (kernels/separable_fused.py).

    Same math as the fused kernel: the DW output stays fp32 into the GEMM
    (the unfused composition rounds it to the activation dtype in between).
    With ``expand_w`` (Ci, C) the bias-free PW-expand stage runs first, also
    kept fp32 into the DW stage (the 3-stage chain's numerics).
    """
    y = x.astype(jnp.float32)
    if expand_w is not None:
        y = jnp.dot(y, expand_w.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
        y = _epilogue(y, None, expand_activation)
    y = dwconv2d_ref(
        y, dw_f.astype(jnp.float32),
        stride=stride, padding=padding,
    )
    if dw_bias is not None:
        y = y + dw_bias.astype(jnp.float32)
    y = _epilogue(y, None, dw_activation)
    out = jnp.dot(
        y, pw_w.astype(jnp.float32), preferred_element_type=jnp.float32
    )
    out = _epilogue(out, pw_bias, activation)
    if residual is not None:
        out = out + residual.astype(jnp.float32)
    return out.astype(x.dtype)


def conv2d_ref(
    x: jax.Array,
    f: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    stride: int = 1,
    padding: str = "valid",
    activation: Optional[str] = None,
) -> jax.Array:
    """Full dense conv (the FusedMB stage).  x: (B, Hi, Wi, Ci);
    f: (Hf, Wf, Ci, Co) -> (B, Ho, Wo, Co), fp32 accumulation."""
    assert x.ndim == 4 and f.ndim == 4 and x.shape[-1] == f.shape[2]
    y = jax.lax.conv_general_dilated(
        x.astype(jnp.float32),
        f.astype(jnp.float32),
        window_strides=(stride, stride),
        padding=padding.upper(),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    y = _epilogue(y, bias, activation)
    return y.astype(x.dtype)


def fused_mbconv_ref(
    x: jax.Array,
    mb_f: jax.Array,
    pw_w: jax.Array,
    mb_bias: Optional[jax.Array] = None,
    pw_bias: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
    *,
    stride: int = 1,
    padding: str = "valid",
    mb_activation: Optional[str] = "relu6",
    activation: Optional[str] = None,
) -> jax.Array:
    """Oracle for the fused-MBConv block (kernels/fused_mbconv.py): full
    conv -> act -> PW-project, the conv output kept fp32 into the GEMM
    (the unfused composition rounds it to the activation dtype between)."""
    y = jax.lax.conv_general_dilated(
        x.astype(jnp.float32),
        mb_f.astype(jnp.float32),
        window_strides=(stride, stride),
        padding=padding.upper(),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    y = _epilogue(y, mb_bias.astype(jnp.float32)
                  if mb_bias is not None else None, mb_activation)
    out = jnp.dot(
        y, pw_w.astype(jnp.float32), preferred_element_type=jnp.float32
    )
    out = _epilogue(out, pw_bias, activation)
    if residual is not None:
        out = out + residual.astype(jnp.float32)
    return out.astype(x.dtype)


def se_ref(
    x: jax.Array,
    w1: jax.Array,
    b1: jax.Array,
    w2: jax.Array,
    b2: jax.Array,
    *,
    activation: str = "relu",
) -> jax.Array:
    """Squeeze-excite oracle: global-avg-pool -> FC-reduce (``activation``)
    -> FC-expand -> sigmoid -> channelwise scale.  x: (B, H, W, C);
    w1: (C, Cse); w2: (Cse, C) -> (B, H, W, C), all fp32 internally."""
    xf = x.astype(jnp.float32)
    pooled = jnp.mean(xf, axis=(1, 2))                       # (B, C)
    hid = _epilogue(jnp.dot(pooled, w1.astype(jnp.float32),
                            preferred_element_type=jnp.float32),
                    b1.astype(jnp.float32), activation)
    gate = jax.nn.sigmoid(jnp.dot(hid, w2.astype(jnp.float32),
                                  preferred_element_type=jnp.float32)
                          + b2.astype(jnp.float32))          # (B, C)
    return (xf * gate[:, None, None, :]).astype(x.dtype)


def dw_se_ref(
    x: jax.Array,
    dw_f: jax.Array,
    w1: jax.Array,
    b1: jax.Array,
    w2: jax.Array,
    b2: jax.Array,
    dw_bias: Optional[jax.Array] = None,
    *,
    stride: int = 1,
    padding: str = "valid",
    dw_activation: Optional[str] = "relu6",
    se_activation: str = "relu",
) -> jax.Array:
    """Oracle for the fused DW + SE-epilogue pass
    (kernels/se_epilogue.py): the DW output stays fp32 into the pool, the
    two gate FCs and the final scale (the unfused composition rounds it to
    the activation dtype in between)."""
    y = dwconv2d_ref(x.astype(jnp.float32), dw_f.astype(jnp.float32),
                     stride=stride, padding=padding)
    if dw_bias is not None:
        y = y + dw_bias.astype(jnp.float32)
    y = _epilogue(y, None, dw_activation)
    pooled = jnp.mean(y, axis=(1, 2))                        # (B, C)
    hid = _epilogue(jnp.dot(pooled, w1.astype(jnp.float32),
                            preferred_element_type=jnp.float32),
                    b1.astype(jnp.float32), se_activation)
    gate = jax.nn.sigmoid(jnp.dot(hid, w2.astype(jnp.float32),
                                  preferred_element_type=jnp.float32)
                          + b2.astype(jnp.float32))
    return (y * gate[:, None, None, :]).astype(x.dtype)


def matmul_rtra_ref(
    a: jax.Array, b: jax.Array, *, block_k: int = 128
) -> jax.Array:
    """Paper Alg. 5 loop structure (A-stationary, k-outermost): the BLAS/RTRA
    baseline. Semantically identical to ``a @ b``; the loop embodies the
    output-tile round-trip per reduction block that the paper identifies as
    the AI flaw of BLAS kernels. Used for traffic modeling + as a second oracle.
    """
    g, ci = a.shape
    ci2, co = b.shape
    assert ci == ci2
    nk = max(1, (ci + block_k - 1) // block_k)
    pad = nk * block_k - ci
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad)))
        b = jnp.pad(b, ((0, pad), (0, 0)))
    a3 = a.reshape(g, nk, block_k).transpose(1, 0, 2)  # (nk, G, bk)
    b3 = b.reshape(nk, block_k, co)

    def body(k, acc):  # out tile is re-read and re-written every k step (RTRA)
        return acc + jnp.dot(
            a3[k], b3[k], preferred_element_type=jnp.float32
        )

    out = jax.lax.fori_loop(0, nk, body, jnp.zeros((g, co), jnp.float32))
    return out.astype(a.dtype)
