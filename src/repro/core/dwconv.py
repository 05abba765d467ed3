"""DWConv — the paper's depthwise-convolution contribution as a framework op.

:func:`depthwise2d` runs NHWC spatial DWConv (MobileNet/MnasNet workloads) as
one standalone op under a ``KernelPolicy``; the chain engine fuses the same
stage into its separable kernels instead.
"""
from __future__ import annotations

import jax

from repro.core.pwconv import DEFAULT_POLICY, KernelPolicy
from repro.kernels import ops


def depthwise2d(
    x: jax.Array,
    f: jax.Array,
    *,
    stride: int = 1,
    padding: str = "same",
    policy: KernelPolicy = DEFAULT_POLICY,
) -> jax.Array:
    """x (B, H, W, C) * f (Hf, Wf, C) -> (B, Ho, Wo, C)."""
    return ops.dwconv2d(
        x, f, stride=stride, padding=padding,
        impl=policy.impl, interpret=policy.interpret,
    )

