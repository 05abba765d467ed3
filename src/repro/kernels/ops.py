"""Jitted public wrappers for the kernel package.

Every op has two execution paths:

* ``impl="xla"``      — the pure-jnp oracle (ref.py), used on CPU hosts and as
                        the comparison baseline;
* ``impl="pallas"``   — the TPU Pallas kernel (compiled on TPU, or
                        ``interpret=True`` on CPU for validation).

``impl="auto"`` picks pallas on TPU backends and xla elsewhere, so the same
model code runs in this CPU container and on a real pod.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import blocking, ref
from repro.kernels.dwconv2d import dwconv2d_pallas
from repro.kernels.epilogue import apply_epilogue
from repro.kernels.policy import resolve_impl
from repro.kernels.pwconv import pwconv_pallas
from repro.kernels.separable_fused import separable_fused_pallas

# Single source of the "auto -> pallas on TPU else xla" rule
# (kernels/policy.py); `_resolve` stays as an alias for old call sites.
_resolve = resolve_impl


def pad_same(x: jax.Array, hf: int, wf: int, stride: int) -> jax.Array:
    """Explicit SAME padding in HBM (so the Pallas kernels only see VALID).

    Public: the chain lowering (kernels/lowering.py) applies it before a
    ``fused2``/``fused3`` segment of more than one row slab (one slab makes
    its halo in the kernel) and before ``fusedmb`` and ``dw_se`` segments.
    """
    (top, bottom), (left, right) = blocking.same_pads(
        x.shape[1], x.shape[2], hf, wf, stride)
    # compile-time only: names the pad in op metadata
    with jax.named_scope("same_pad"):
        return jnp.pad(x, ((0, 0), (top, bottom), (left, right), (0, 0)))


_pad_same = pad_same


def dwconv2d(
    x: jax.Array,
    f: jax.Array,
    *,
    stride: int = 1,
    padding: str = "same",
    impl: str = "auto",
    interpret: bool = False,
    block_c: int | None = None,
    vmem_budget: int = blocking.DEFAULT_VMEM_BUDGET,
    out_dtype: str | None = None,
) -> jax.Array:
    """Depthwise 2-D conv, NHWC. x (B,Hi,Wi,C), f (Hf,Wf,C).

    ``block_c`` executes the kernel at an explicit channel block (the chain
    lowering passes its ``ChainSegment.plan`` here so a planned — or
    measured — ``ChainPlan`` runs verbatim); ``None`` defers to the
    dtype-aware planner at ``vmem_budget``.  ``out_dtype`` (dtype NAME)
    selects the store width of the output (DESIGN.md §7); ``None`` keeps
    ``x.dtype``.
    """
    impl = _resolve(impl)
    if impl == "xla":
        y = ref.dwconv2d_ref(x, f, stride=stride, padding=padding)
        return y if out_dtype is None else y.astype(out_dtype)
    if padding.lower() == "same":
        x = _pad_same(x, f.shape[0], f.shape[1], stride)
    elif padding.lower() != "valid":
        raise ValueError(padding)
    return dwconv2d_pallas(x, f, stride=stride, block_c=block_c,
                           vmem_budget=vmem_budget, interpret=interpret,
                           out_dtype=out_dtype)


def separable_fused(
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
    padding: str = "same",
    dw_activation: Optional[str] = "relu6",
    activation: Optional[str] = None,
    impl: str = "auto",
    interpret: bool = False,
    vmem_budget: int = blocking.DEFAULT_VMEM_BUDGET,
) -> jax.Array:
    """Fused depthwise-separable block: [PW-expand ->] DW -> act -> PW in
    one kernel pass.

    x (B,Hi,Wi,C); dw_f (Hf,Wf,C); pw_w (C,Co) -> (B,Ho,Wo,Co); with
    ``expand_w`` (Ci, C) the input is (B,Hi,Wi,Ci) and the bias-free
    expansion GEMM is computed on the fly inside the kernel.  On the pallas
    path neither the expanded tensor nor the DW intermediate ever touches
    HBM (DESIGN.md §3/§5).  Block shapes — including the row-slab dimension
    that keeps the accumulator VMEM-sized at any resolution — come from
    :func:`repro.kernels.blocking.plan_separable` /
    :func:`~repro.kernels.blocking.plan_separable3`.  When a plan does not
    fit the budget the op degrades exactly like the chain planner
    (DESIGN.md §5): 3-stage fused -> standalone expand + 2-stage fused ->
    unfused Pallas composition.  The unfused fallback is semantically the
    same block but rounds the intermediates to the activation dtype between
    kernels (the fused paths keep them fp32), so sub-fp32 dtypes can differ
    by intermediate-rounding error across the VMEM-feasibility boundary.

    Prefer the declarative chain API (``core/chain.py``) for new code; this
    wrapper remains the kernel-level entry the lowering maps onto.
    """
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.separable_fused_ref(
            x, dw_f, pw_w, dw_bias, pw_bias, residual,
            expand_w=expand_w, expand_activation=expand_activation,
            stride=stride, padding=padding,
            dw_activation=dw_activation, activation=activation,
        )
    hf, wf = dw_f.shape[0], dw_f.shape[1]
    if padding.lower() == "same":
        x = pad_same(x, hf, wf, stride)
    elif padding.lower() != "valid":
        raise ValueError(padding)
    hi, wi = x.shape[1], x.shape[2]
    ho = (hi - hf) // stride + 1
    wo = (wi - wf) // stride + 1
    if expand_w is not None:
        plan3 = blocking.plan_separable3(
            ho, wo, expand_w.shape[0], expand_w.shape[1], pw_w.shape[-1],
            stride=stride, hf=hf, wf=wf, dtype=x.dtype,
            vmem_budget=vmem_budget, residual=residual is not None)
        if plan3 is not None:
            return separable_fused_pallas(
                x, dw_f, pw_w, dw_bias, pw_bias, residual,
                expand_w=expand_w, expand_activation=expand_activation,
                stride=stride, dw_activation=dw_activation,
                activation=activation, block_c=plan3.block_c,
                block_co=plan3.block_co, slab_h=plan3.slab_h,
                interpret=interpret,
            )
        # Degrade to the 2-stage path: standalone expansion GEMM (its output
        # rounds to the activation dtype), then DW -> PW below.
        x = pwconv(x, expand_w, activation=expand_activation,
                   impl="pallas", interpret=interpret,
                   vmem_budget=vmem_budget)
    plan = blocking.plan_separable(
        ho, wo, x.shape[-1], pw_w.shape[-1], stride=stride, hf=hf, wf=wf,
        dtype=x.dtype, vmem_budget=vmem_budget,
        residual=residual is not None)
    if plan is None:
        # Even the minimal (cb=1, cob=1, slab_h=1) plan exceeds the budget:
        # compose the standalone kernels instead (correct, just not fused).
        y = dwconv2d_pallas(x, dw_f, stride=stride,
                            vmem_budget=vmem_budget, interpret=interpret)
        if dw_bias is not None:
            y = y + dw_bias
        y = apply_epilogue(y, None, dw_activation).astype(x.dtype)
        out = pwconv(
            y, pw_w, pw_bias, activation=activation,
            impl="pallas", interpret=interpret, vmem_budget=vmem_budget,
        )
        if residual is not None:
            out = out + residual
        return out
    return separable_fused_pallas(
        x, dw_f, pw_w, dw_bias, pw_bias, residual,
        stride=stride, dw_activation=dw_activation, activation=activation,
        block_c=plan.block_c, block_co=plan.block_co, slab_h=plan.slab_h,
        interpret=interpret,
    )


def pwconv(
    x: jax.Array,
    w: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    activation: Optional[str] = None,
    impl: str = "auto",
    interpret: bool = False,
    block_g: int | None = None,
    block_co: int | None = None,
    block_ci: int | None = None,
    vmem_budget: int = blocking.DEFAULT_VMEM_BUDGET,
    out_dtype: str | None = None,
) -> jax.Array:
    """Pointwise conv / GEMM over the last axis. x (..., Ci), w (Ci, Co).

    Block shapes default to :func:`repro.kernels.blocking.plan_pwconv`
    (dtype-aware MXU-aligned grid, sized against ``vmem_budget``); explicit
    overrides win.  ``out_dtype`` (dtype NAME) selects the store width of
    the output (DESIGN.md §7); ``None`` keeps ``x.dtype``.
    """
    impl = _resolve(impl)
    if impl == "xla":
        y = ref.pwconv_ref(x, w, bias=bias, activation=activation)
        return y if out_dtype is None else y.astype(out_dtype)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if block_g is None or block_co is None or block_ci is None:
        plan = blocking.plan_pwconv(x2.shape[0], w.shape[0], w.shape[1],
                                    dtype=x.dtype,
                                    vmem_budget=vmem_budget)
        block_g = block_g or plan.block_g
        block_co = block_co or plan.block_co
        block_ci = block_ci or plan.block_c
    y = pwconv_pallas(
        x2, w, bias,
        activation=activation,
        block_g=block_g, block_co=block_co, block_ci=block_ci,
        interpret=interpret, out_dtype=out_dtype,
    )
    return y.reshape(*lead, w.shape[1])
