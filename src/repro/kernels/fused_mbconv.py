"""Fused-MBConv Pallas kernel: full HfxWf conv -> act -> PW-project GEMM in
ONE pass (the EfficientNet-Lite edge block, DESIGN.md §10).

The fused-MBConv block replaces PW-expand + DW with a single dense
convolution straight to the expanded width, then projects back down with a
1x1 conv.  Composed through HBM the expanded tensor — ``expand`` times the
input — takes a full round-trip purely as an artifact of op granularity,
exactly the paper's argument for the separable pair.  This kernel computes

    conv(HfxWf, stride, Ci -> C) (+ bias) -> activation -> PW GEMM
    (+ PW bias, activation, optional residual add)

in one grid pass: each reduction step materializes one conv-output channel
slab as a VMEM fp32 value and immediately feeds it to the output-stationary
projection GEMM; the expanded tensor never exists in HBM.

Grid and residency (mirrors ``separable_fused_pallas``'s expand-on-the-fly
structure):

* grid ``(B, n_slabs, Co/Cob, C/Cb)`` with the conv-output channel
  reduction **innermost** and the output BlockSpec ignoring it — the fp32
  accumulator ``(slab_h*Wo, Cob)`` stays VMEM-resident across the whole
  reduction and is stored exactly once.
* the input window carries ALL ``Ci`` raw channels (it is every conv tap's
  A-operand), fetched with element-offset indexing per row slab —
  adjacent slabs re-read the ``Hf - stride`` row halo.  Strided taps read
  an fp32 lane-chunked stage of that window (``kernels/taps.py``).
* per reduction step, the conv runs as ``Hf*Wf`` tap GEMMs:
  ``window(slab_h, Wo, Ci) . f[n, m] (Ci, Cb)`` accumulated in fp32 (MXU
  work — unlike the depthwise taps these contract over ``Ci``), then
  bias + activation, then the ``(slab_h*Wo, Cb) @ (Cb, Cob)`` projection.

Unlike the 3-stage separable fusion, a conv **bias is allowed**: SAME
padding is consumed by the conv taps BEFORE the bias is added to the conv
output, so padded input pixels never meet the bias (the bias-free
restriction on fused PW-expansions does not apply here).

All block choices come from ``kernels.blocking.plan_fused_mb``; when even
the minimal plan exceeds the budget the planner returns None and
``core/chain.plan`` degrades to a standalone XLA conv (segment kind
``mb``) + standalone PW.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import blocking, taps
from repro.kernels.epilogue import apply_epilogue as _epilogue
from repro.kernels.gridspec import (BlockRef, KernelModel,
                                    compiler_params, in_specs_from_model,
                                    out_spec_from_model)
from repro.kernels.policy import contract_precision


def fused_mb_kernel_model(*, b: int, ho: int, wo: int, c_in: int, c: int,
                          co: int, hf: int, wf: int, stride: int,
                          block_c: int, block_co: int, slab_h: int,
                          itemsize: int, out_itemsize: int,
                          has_mb_bias: bool, has_pw_bias: bool,
                          has_residual: bool) -> KernelModel:
    """The exact grid/BlockSpec geometry ``fused_mbconv_pallas`` lowers to
    at these blocks — consumed by BOTH the kernel and ``repro.analysis``
    (DESIGN.md §8).  ``c_in`` is the raw input width, ``c`` the conv-output
    (expanded) width, ``co`` the projected width.  Shapes are the PADDED
    shapes handed to ``pl.pallas_call``."""
    cb, cob = block_c, block_co
    sh = min(slab_h, ho)
    n_slabs = -(-ho // sh)
    ho_p = n_slabs * sh
    slab_hi = (sh - 1) * stride + hf
    wiu = (wo - 1) * stride + wf
    pad_c = (-c) % cb
    pad_co = (-co) % cob
    cp, cop = c + pad_c, co + pad_co
    nk = cp // cb
    rows_in = (ho_p - 1) * stride + hf

    inputs = [BlockRef(
        "x", (b, rows_in, wiu, c_in), (1, slab_hi, wiu, c_in),
        lambda i, s, j, k, sh=sh, st=stride: (i, s * sh * st, 0, 0),
        itemsize, unblocked=True)]
    inputs.append(BlockRef("mb_f", (hf, wf, c_in, cp), (hf, wf, c_in, cb),
                           lambda i, s, j, k: (0, 0, 0, k), itemsize))
    if has_mb_bias:
        inputs.append(BlockRef("mb_bias", (1, cp), (1, cb),
                               lambda i, s, j, k: (0, k), itemsize))
    inputs.append(BlockRef("pw_w", (cp, cop), (cb, cob),
                           lambda i, s, j, k: (k, j), itemsize))
    if has_pw_bias:
        inputs.append(BlockRef("pw_bias", (1, cop), (1, cob),
                               lambda i, s, j, k: (0, j), itemsize))
    if has_residual:
        inputs.append(BlockRef("residual", (b, ho_p, wo, cop),
                               (1, sh, wo, cob),
                               lambda i, s, j, k: (i, s, 0, j), itemsize))
    out_ref = BlockRef("out", (b, ho_p, wo, cop), (1, sh, wo, cob),
                       lambda i, s, j, k: (i, s, 0, j), out_itemsize)
    return KernelModel(
        name="fused_mbconv",
        grid=(b, n_slabs, cop // cob, nk),
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"),
        inputs=tuple(inputs),
        output=out_ref,
        scratch_bytes=(sh * wo * cob * 4           # fp32 accumulator
                       + taps.stage_bytes((slab_hi, wiu, c_in),
                                          stride > 1)),
        value_bytes=sh * wo * cb * 4,              # conv intermediate (fp32)
        reshapes=(((sh, wo, c_in), (sh * wo, c_in)),
                  ((sh, wo, cb), (sh * wo, cb))),
    )


def _fused_mb_kernel(*refs, hf: int, wf: int, stride: int, nk: int,
                     mb_activation, activation, has_mbb: bool,
                     has_pwb: bool, has_res: bool, out_dtype):
    """refs = (x, mb_f, [mb_bias,] pw_w, [pw_bias,] [residual,] out, acc,
    [stage]).

    Blocks: x (1, slab_hi, Wiu, Ci) — the overlapping raw-input window of
    this row slab, identical for every reduction step; mb_f
    (Hf, Wf, Ci, Cb); mb_bias (1, Cb); pw_w (Cb, Cob); pw_bias (1, Cob);
    residual / out (1, slab_h, Wo, Cob); acc VMEM scratch (slab_h*Wo, Cob)
    fp32; stage: x's fp32 lane-chunked copy when strided (``taps.py``).
    """
    it = iter(refs)
    x_ref = next(it)
    f_ref = next(it)
    mbb_ref = next(it) if has_mbb else None
    w_ref = next(it)
    pwb_ref = next(it) if has_pwb else None
    res_ref = next(it) if has_res else None
    out_ref = next(it)
    acc_ref = next(it)
    stage_ref = next(it, None)

    _, slab_h, wo, cob = out_ref.shape
    cb = f_ref.shape[3]
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if stage_ref is not None:
        taps.stage_input(stage_ref, x_ref)
    ci = x_ref.shape[3]
    f = f_ref[...].astype(jnp.float32)
    prec = contract_precision(x_ref.dtype)

    # --- conv stage: Hf*Wf tap GEMMs contracting over the raw channels ---
    conv = jnp.zeros((slab_h * wo, cb), jnp.float32)
    for n in range(hf):
        for m in range(wf):
            win = taps.tap(x_ref, stage_ref, n, m, slab_h, wo, stride, ci)
            conv = conv + jnp.dot(
                win.reshape(slab_h * wo, ci), f[n, m],
                preferred_element_type=jnp.float32, precision=prec,
            )
    conv = _epilogue(
        conv, mbb_ref[0][None, :] if mbb_ref is not None else None,
        mb_activation,
    )

    # --- projection: conv tile (VMEM value, never stored) is the A-operand
    acc_ref[...] += jnp.dot(
        conv, w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32, precision=prec,
    )

    @pl.when(k == nk - 1)
    def _store():  # single store of the slab's output block
        acc = _epilogue(
            acc_ref[...],
            pwb_ref[...] if pwb_ref is not None else None,
            activation,
        )
        y = acc.reshape(slab_h, wo, cob)
        if res_ref is not None:
            y = y + res_ref[0].astype(jnp.float32)
        out_ref[0] = y.astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("stride", "mb_activation", "activation", "block_c",
                     "block_co", "slab_h", "interpret", "out_dtype"),
)
def fused_mbconv_pallas(
    x: jax.Array,
    mb_f: jax.Array,
    pw_w: jax.Array,
    mb_bias: Optional[jax.Array] = None,
    pw_bias: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
    *,
    stride: int = 1,
    mb_activation: Optional[str] = "relu6",
    activation: Optional[str] = None,
    block_c: int | None = None,
    block_co: int | None = None,
    slab_h: int | None = None,
    interpret: bool = False,
    out_dtype: Optional[str] = None,
) -> jax.Array:
    """Fused-MBConv block.  x (B,Hi,Wi,Ci); mb_f (Hf,Wf,Ci,C); pw_w (C,Co)
    [+ mb_bias (C,), pw_bias (Co,), residual (B,Ho,Wo,Co)] -> (B,Ho,Wo,Co).

    VALID geometry — SAME padding is applied by the wrapper (lowering.py).
    ``out_dtype`` (a dtype NAME, static) selects the store width of the
    single output write; the accumulator is fp32 VMEM scratch regardless.
    Block shapes not given explicitly come from
    :func:`repro.kernels.blocking.plan_fused_mb`; raises ValueError when
    even the minimal plan exceeds the VMEM budget (callers should have
    consulted the planner and degraded to the standalone conv instead).
    """
    b, hi, wi, c_in = x.shape
    odt = jnp.dtype(out_dtype) if out_dtype is not None else x.dtype
    hf, wf, ci_f, c = mb_f.shape
    cw, co = pw_w.shape
    assert ci_f == c_in and c == cw, (x.shape, mb_f.shape, pw_w.shape)
    ho = (hi - hf) // stride + 1
    wo = (wi - wf) // stride + 1
    assert ho >= 1 and wo >= 1, "input smaller than filter"
    hiu = (ho - 1) * stride + hf
    wiu = (wo - 1) * stride + wf

    if block_c is None or block_co is None or slab_h is None:
        plan = blocking.plan_fused_mb(
            ho, wo, c_in, c, co, stride=stride, hf=hf, wf=wf,
            dtype=x.dtype, residual=residual is not None)
        if plan is None and (block_c is None or block_co is None):
            raise ValueError(
                f"no fused-MBConv plan fits VMEM for {(hi, wi, c, co)}; "
                "use the standalone conv + PW composition")
        cb = block_c or plan.block_c
        cob = block_co or plan.block_co
        sh = slab_h or (plan.slab_h if plan is not None else ho)
    else:
        cb, cob, sh = block_c, block_co, slab_h
    sh = min(sh, ho)
    n_slabs = -(-ho // sh)
    ho_p = n_slabs * sh

    # Conv-output channel / Co padding: zero filter columns make padded conv
    # channels compute act(bias-padding) = act(0) = 0, and the matching zero
    # pw_w rows nullify them regardless.
    pad_c = (-c) % cb
    pad_co = (-co) % cob
    if pad_c:
        mb_f = jnp.pad(mb_f, ((0, 0), (0, 0), (0, 0), (0, pad_c)))
        pw_w = jnp.pad(pw_w, ((0, pad_c), (0, 0)))
        if mb_bias is not None:
            mb_bias = jnp.pad(mb_bias, ((0, pad_c),))
    if pad_co:
        pw_w = jnp.pad(pw_w, ((0, 0), (0, pad_co)))
        if pw_bias is not None:
            pw_bias = jnp.pad(pw_bias, ((0, pad_co),))
        if residual is not None:
            residual = jnp.pad(residual,
                               ((0, 0), (0, 0), (0, 0), (0, pad_co)))
    cp, cop = c + pad_c, co + pad_co
    nk = cp // cb

    # Row padding so the slab grid tiles Ho: the last slab's window reads
    # zero rows past the image and its garbage output rows are cropped.
    rows_in = (ho_p - 1) * stride + hf
    x = x[:, :hiu, :wiu, :]
    if rows_in > hiu:
        x = jnp.pad(x, ((0, 0), (0, rows_in - hiu), (0, 0), (0, 0)))
    if ho_p > ho and residual is not None:
        residual = jnp.pad(residual,
                           ((0, 0), (0, ho_p - ho), (0, 0), (0, 0)))

    model = fused_mb_kernel_model(
        b=b, ho=ho, wo=wo, c_in=c_in, c=c, co=co, hf=hf, wf=wf,
        stride=stride, block_c=cb, block_co=cob, slab_h=sh,
        itemsize=x.dtype.itemsize, out_itemsize=odt.itemsize,
        has_mb_bias=mb_bias is not None, has_pw_bias=pw_bias is not None,
        has_residual=residual is not None,
    )
    inputs = [x, mb_f]
    if mb_bias is not None:
        inputs.append(mb_bias.reshape(1, -1))
    inputs.append(pw_w)
    if pw_bias is not None:
        inputs.append(pw_bias.reshape(1, -1))
    if residual is not None:
        inputs.append(residual)
    for arr, br in zip(inputs, model.inputs):
        assert arr.shape == br.array_shape, (br.name, arr.shape,
                                             br.array_shape)

    kernel = functools.partial(
        _fused_mb_kernel, hf=hf, wf=wf, stride=stride, nk=nk,
        mb_activation=mb_activation, activation=activation,
        has_mbb=mb_bias is not None, has_pwb=pw_bias is not None,
        has_res=residual is not None, out_dtype=odt,
    )
    assert model.output.array_shape == (b, ho_p, wo, cop)
    out = pl.pallas_call(
        kernel,
        grid=model.grid,
        in_specs=in_specs_from_model(model),
        out_specs=out_spec_from_model(model),
        out_shape=jax.ShapeDtypeStruct(model.output.array_shape, odt),
        scratch_shapes=[pltpu.VMEM((sh * wo, cob), jnp.float32)]
        + taps.stage_shapes(((sh - 1) * stride + hf, wiu, c_in), stride > 1),
        compiler_params=compiler_params(model),
        interpret=interpret,
    )(*inputs)
    return out[:, :ho, :, :co]
