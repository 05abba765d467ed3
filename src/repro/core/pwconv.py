"""PWConv — the paper's pointwise-convolution contribution as a framework op.

:func:`pointwise` runs the paper's output-stationary GEMM as one standalone
1x1 conv under a ``KernelPolicy``; the chain engine fuses the same stage into
its separable kernels instead.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import ops

# KernelPolicy lives at the kernel layer now (kernels/policy.py — the single
# owner of backend resolution and the VMEM budget); re-exported here because
# this module was its historical home.  Fusion is no longer a policy field
# but a planner decision (core/chain.plan, DESIGN.md §5).
from repro.kernels.policy import (  # noqa: F401  (re-export)
    DEFAULT_POLICY,
    KernelPolicy,
    resolve_impl,
)


def pointwise(
    x: jax.Array,
    w: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    activation: Optional[str] = None,
    policy: KernelPolicy = DEFAULT_POLICY,
) -> jax.Array:
    """Pointwise conv (1x1) / GEMM over the trailing axis, fp32 accumulate."""
    return ops.pwconv(
        x, w, bias,
        activation=activation,
        impl=policy.impl,
        interpret=policy.interpret,
        block_g=policy.block_g,
        block_co=policy.block_co,
        block_ci=policy.block_ci,
    )
