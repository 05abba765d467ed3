"""DW + squeeze-excite epilogue Pallas kernel: DW conv -> global-avg-pool
-> FC-reduce -> act -> FC-expand -> sigmoid -> channelwise scale, in ONE
pass (the MnasNet-A1 SE placement, DESIGN.md §10).

MnasNet puts SE directly after the DW stage, and the SE gate consumes
exactly the tensor the DW kernel just produced — composed through HBM the
DW output takes a full round-trip (store by DW, re-load by the pool AND
re-load by the scale) purely to compute two tiny FCs over its spatial
mean.  This kernel keeps the DW output VMEM-resident and applies the whole
gate as an in-kernel epilogue: it is stored exactly once, already scaled.

Residency contract — and why there is NO block ladder here: the squeeze FC
mixes ALL channels of the pooled vector, and the pool itself spans ALL
spatial positions, so the kernel requires full-channel (``block_c == C``)
full-spatial (``n_slabs == 1``) residency per batch image.  A
partial-channel or slabbed variant would compute the gate from a partial
mean — a WRONG answer, not a slower one — so ``blocking.plan_dw_se``
either fits the whole working set or returns None and ``core/chain.plan``
degrades to a standalone DW + the standalone two-GEMM SE pass (segment
kinds ``dw`` + ``se``).  The static analyzer enforces the same contract as
rule PL114.

Grid: ``(B,)``, fully parallel — one grid cell owns one image's whole DW
output.  Zero-padding safety for the sigmoid (which does NOT map 0 -> 0
and therefore can never join ``kernels/epilogue.ACTIVATIONS``): padded
channels would carry zero DW output, and ``0 * sigmoid(anything) == 0`` —
but with ``block_c == C`` there is no channel padding in the first place.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import blocking, taps
from repro.kernels.epilogue import apply_epilogue as _epilogue
from repro.kernels.gridspec import (BlockRef, KernelModel,
                                    compiler_params, in_specs_from_model,
                                    out_spec_from_model)
from repro.kernels.policy import contract_precision


def dw_se_kernel_model(*, b: int, hiu: int, wiu: int, ho: int, wo: int,
                       c: int, c_se: int, hf: int, wf: int, stride: int,
                       itemsize: int, out_itemsize: int,
                       has_dw_bias: bool) -> KernelModel:
    """The exact grid/BlockSpec geometry ``dw_se_pallas`` lowers to —
    consumed by BOTH the kernel and ``repro.analysis`` (DESIGN.md §8).
    Full-channel, full-spatial blocks by construction (see module doc);
    the gate weights are tiny and fetched whole."""
    inputs = [BlockRef(
        "x", (b, hiu, wiu, c), (1, hiu, wiu, c),
        lambda i: (i, 0, 0, 0), itemsize)]
    inputs.append(BlockRef("dw_f", (hf, wf, c), (hf, wf, c),
                           lambda i: (0, 0, 0), itemsize))
    if has_dw_bias:
        inputs.append(BlockRef("dw_bias", (1, c), (1, c),
                               lambda i: (0, 0), itemsize))
    inputs.append(BlockRef("w1", (c, c_se), (c, c_se),
                           lambda i: (0, 0), itemsize))
    inputs.append(BlockRef("b1", (1, c_se), (1, c_se),
                           lambda i: (0, 0), itemsize))
    inputs.append(BlockRef("w2", (c_se, c), (c_se, c),
                           lambda i: (0, 0), itemsize))
    inputs.append(BlockRef("b2", (1, c), (1, c),
                           lambda i: (0, 0), itemsize))
    out_ref = BlockRef("out", (b, ho, wo, c), (1, ho, wo, c),
                       lambda i: (i, 0, 0, 0), out_itemsize)
    return KernelModel(
        name="dw_se",
        grid=(b,),
        dimension_semantics=("parallel",),
        inputs=tuple(inputs),
        output=out_ref,
        scratch_bytes=taps.stage_bytes((hiu, wiu, c), stride > 1),
        value_bytes=ho * wo * c * 4,          # DW intermediate (fp32)
        reshapes=(((ho, wo, c), (ho * wo, c)),),
    )


def _dw_se_kernel(*refs, hf: int, wf: int, stride: int,
                  dw_activation, se_activation, has_dwb: bool, out_dtype):
    """refs = (x, dw_f, [dw_bias,] w1, b1, w2, b2, out, [stage]).

    Blocks: x (1, Hiu, Wiu, C) — one image's whole (VALID) input window;
    dw_f (Hf, Wf, C); dw_bias (1, C); w1 (C, Cse); b1 (1, Cse);
    w2 (Cse, C); b2 (1, C); out (1, Ho, Wo, C); stage: x's fp32
    lane-chunked copy when strided (``taps.py``).
    """
    it = iter(refs)
    x_ref = next(it)
    f_ref = next(it)
    dwb_ref = next(it) if has_dwb else None
    w1_ref = next(it)
    b1_ref = next(it)
    w2_ref = next(it)
    b2_ref = next(it)
    out_ref = next(it)
    stage_ref = next(it, None)
    if stage_ref is not None:
        taps.stage_input(stage_ref, x_ref)

    _, ho, wo, c = out_ref.shape
    f = f_ref[...].astype(jnp.float32)

    # --- DW stage: shift-and-FMA over ALL channels (dwconv2d Alg. 4) ---
    dw = jnp.zeros((ho, wo, c), jnp.float32)
    for n in range(hf):
        for m in range(wf):
            win = taps.tap(x_ref, stage_ref, n, m, ho, wo, stride, c)
            dw = dw + win * f[n, m][None, None, :]
    dw = _epilogue(
        dw, dwb_ref[0][None, None, :] if dwb_ref is not None else None,
        dw_activation,
    )

    # --- SE epilogue: pool -> reduce FC -> act -> expand FC -> sigmoid ---
    # (every intermediate is a VMEM value; the DW output is never stored
    # unscaled)
    pooled = jnp.mean(dw.reshape(ho * wo, c), axis=0, keepdims=True)
    prec = contract_precision(x_ref.dtype)
    hid = jnp.dot(pooled, w1_ref[...].astype(jnp.float32),
                  preferred_element_type=jnp.float32, precision=prec)
    hid = _epilogue(hid, b1_ref[0][None, :].astype(jnp.float32),
                    se_activation)
    gate = jnp.dot(hid, w2_ref[...].astype(jnp.float32),
                   preferred_element_type=jnp.float32, precision=prec)
    gate = jax.nn.sigmoid(gate + b2_ref[0][None, :].astype(jnp.float32))

    out_ref[0] = (dw * gate.reshape(1, 1, c)).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("stride", "dw_activation", "se_activation",
                     "interpret", "out_dtype"),
)
def dw_se_pallas(
    x: jax.Array,
    dw_f: jax.Array,
    w1: jax.Array,
    b1: jax.Array,
    w2: jax.Array,
    b2: jax.Array,
    dw_bias: Optional[jax.Array] = None,
    *,
    stride: int = 1,
    dw_activation: Optional[str] = "relu6",
    se_activation: str = "relu",
    interpret: bool = False,
    out_dtype: Optional[str] = None,
) -> jax.Array:
    """Fused DW + squeeze-excite pass.  x (B,Hi,Wi,C); dw_f (Hf,Wf,C);
    w1 (C,Cse); b1 (Cse,); w2 (Cse,C); b2 (C,) [+ dw_bias (C,)]
    -> (B,Ho,Wo,C), the DW output channelwise-scaled by the SE gate.

    VALID geometry — SAME padding is applied by the wrapper (lowering.py).
    Raises ValueError when the full-channel full-spatial working set
    exceeds the VMEM budget (callers should have consulted
    ``blocking.plan_dw_se`` and degraded to standalone DW + SE instead).
    """
    b, hi, wi, c = x.shape
    odt = jnp.dtype(out_dtype) if out_dtype is not None else x.dtype
    hf, wf, cf = dw_f.shape
    c1, c_se = w1.shape
    assert c == cf == c1 and w2.shape == (c_se, c), (
        x.shape, dw_f.shape, w1.shape, w2.shape)
    ho = (hi - hf) // stride + 1
    wo = (wi - wf) // stride + 1
    assert ho >= 1 and wo >= 1, "input smaller than filter"
    hiu = (ho - 1) * stride + hf
    wiu = (wo - 1) * stride + wf

    plan = blocking.plan_dw_se(hiu, wiu, ho, wo, c, c_se, hf, wf,
                               dtype=x.dtype)
    if plan is None:
        raise ValueError(
            f"dw_se working set exceeds VMEM for {(hi, wi, c, c_se)}; "
            "use the standalone DW + SE composition")

    x = x[:, :hiu, :wiu, :]
    model = dw_se_kernel_model(
        b=b, hiu=hiu, wiu=wiu, ho=ho, wo=wo, c=c, c_se=c_se, hf=hf, wf=wf,
        stride=stride, itemsize=x.dtype.itemsize, out_itemsize=odt.itemsize,
        has_dw_bias=dw_bias is not None,
    )
    inputs = [x, dw_f]
    if dw_bias is not None:
        inputs.append(dw_bias.reshape(1, -1))
    inputs.extend([w1, b1.reshape(1, -1), w2, b2.reshape(1, -1)])
    for arr, br in zip(inputs, model.inputs):
        assert arr.shape == br.array_shape, (br.name, arr.shape,
                                             br.array_shape)

    kernel = functools.partial(
        _dw_se_kernel, hf=hf, wf=wf, stride=stride,
        dw_activation=dw_activation, se_activation=se_activation,
        has_dwb=dw_bias is not None, out_dtype=odt,
    )
    return pl.pallas_call(
        kernel,
        grid=model.grid,
        in_specs=in_specs_from_model(model),
        out_specs=out_spec_from_model(model),
        out_shape=jax.ShapeDtypeStruct(model.output.array_shape, odt),
        scratch_shapes=taps.stage_shapes((hiu, wiu, c), stride > 1),
        compiler_params=compiler_params(model),
        interpret=interpret,
    )(*inputs)
