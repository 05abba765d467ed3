"""Fused depthwise-separable block Pallas kernel (DW3x3 -> act -> PW GEMM),
with an optional expand-on-the-fly stage (PW-expand -> DW -> PW-project in
ONE pass — the full MobileNetV2 inverted residual).

The paper's thesis one level up (DESIGN.md §3): ``dwconv2d`` and ``pwconv``
are both memory-bound, and composing them through HBM makes the DW output —
a tensor the size of the block's activation — take a full HBM round-trip
(one store by the DW kernel, one load per Co panel by the PW kernel) purely
as an artifact of op granularity. This kernel computes

    DW(HfxWf, stride) (+ folded-BN bias) -> activation -> PW GEMM
    (+ PW bias, activation, optional residual add)

in ONE grid pass. The DW output tile is produced in VMEM and immediately
consumed as the A-operand of the output-stationary PW reduction; it never
exists in HBM.

Grid and residency (mirrors ``pwconv``'s RTRD structure, plus a spatial
slab dimension):

* grid ``(B, n_slabs, Co/Cob, C/Cb)`` with the channel reduction
  **innermost** and the output BlockSpec ignoring it — the fp32 accumulator
  ``(slab_h*Wo, Cob)`` stays VMEM-resident across the whole reduction of
  its slab and is stored exactly once.
* the **row-slab dimension** bounds the accumulator: each grid cell owns
  ``slab_h`` output rows, and the input BlockSpec (element-offset
  indexing) fetches the overlapping
  ``(slab_h-1)*stride + Hf`` input-row window for that slab — adjacent
  slabs re-fetch a ``Hf - stride`` row halo at each interior seam. This is
  what lifts the old ~1.5M-pixel accumulator ceiling (DESIGN.md §3): any
  resolution now fuses, at the cost of the (tiny) halo re-read counted in
  ``core.intensity.separable_traffic_fused``.
* per reduction step, the kernel runs the ``dwconv2d`` shift-and-FMA over
  one channel slab (VPU work), applies bias+activation, reshapes to
  ``(slab_h*Wo, Cb)`` and feeds the MXU matmul against the ``(Cb, Cob)``
  weight tile. DW output lives only as that VMEM value.

Traffic win (``core.intensity.separable_traffic_*``): with a single Co panel
(the common MobileNet case — the planner targets it) the fused block removes
exactly the intermediate round-trip, ``2 * B*Ho*Wo*C * dtype`` bytes, minus
the halo re-reads when slabbed. Channel padding is harmless for any
activation: padded DW channels multiply zero-padded PW weight rows, so their
contribution is exactly zero. Row padding (when ``slab_h`` does not divide
``Ho``) computes zero-input garbage rows that are cropped before return.

Expand-on-the-fly (the 3-stage V2 chain, DESIGN.md §5): with ``expand_w``
``(Ci, C)`` given, the kernel's input is the RAW ``Ci``-channel tensor and
each reduction step first computes its expanded-channel slab, one GEMM per
input row — ``x_window[h] (Wiu, Ci) @ expand_w[:, k*Cb:(k+1)*Cb]`` — into
VMEM fp32 scratch, applies the expand activation, and feeds that
slab to the DW shift-and-FMA in place of the streamed input.  Neither the
expanded tensor (``B*Hi*Wi*C`` — 6x the input at the usual expansion
factor) nor the DW output ever exists in HBM.  Restriction: the expansion
must be bias-free, because SAME padding is applied to the raw input before
the kernel and a bias would make padding pixels expand to ``act(bias) != 0``
(every supported activation maps 0 -> 0, so bias-free expand commutes with
zero padding).  ``core/chain.plan`` degrades to the 2-stage path when the
spec declares an expand bias.

All block choices come from ``kernels.blocking.plan_separable`` /
``plan_separable3`` (dtype-aware VMEM budget, Co-panel and row-slab
enumeration); when even the minimal plan exceeds the budget the planner
returns None and callers fall back to the unfused composition
(``ops.separable_fused``).

TPU note: the overlapping input windows use element-offset indexing
(``pl.Element`` on every block dim, ``gridspec.in_specs_from_model``); the
row offset is un-tiled and free, the lane offset must be provably
128-aligned (MC204).  The DW taps of a strided or expanded block
read an fp32 lane-chunked VMEM stage (``kernels/taps.py``).
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


def fused_kernel_model(*, b: int, ho: int, wo: int, c_in: int, c: int,
                       co: int, hf: int, wf: int, stride: int,
                       block_c: int, block_co: int, slab_h: int,
                       itemsize: int, out_itemsize: int,
                       has_expand: bool, has_dw_bias: bool,
                       has_pw_bias: bool, has_residual: bool) -> KernelModel:
    """The exact grid/BlockSpec geometry ``separable_fused_pallas`` lowers
    to at these blocks — the single source of truth consumed by BOTH the
    kernel (specs built from this model) and the static analyzer
    (``repro.analysis``), so planner<->lowering drift is structurally
    impossible (DESIGN.md §8).

    ``c_in`` is the raw input channel count (== ``c`` without expand).
    Shapes are the PADDED shapes the kernel hands to ``pl.pallas_call``
    after channel/Co/row padding.
    """
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

    # x window: element-offset (unblocked) indexing — adjacent slabs'
    # windows overlap by the (hf - stride)-row halo.  With expand the
    # window carries ALL raw channels; without, one channel slab.  A single
    # channel slab gets a literal 0 lane offset: Mosaic must prove the
    # offset 128-aligned, and cannot for ``k * cb`` with cb < 128 (MC204).
    if has_expand:
        x_ref = BlockRef(
            "x", (b, rows_in, wiu, c_in), (1, slab_hi, wiu, c_in),
            lambda i, s, j, k, sh=sh, st=stride: (i, s * sh * st, 0, 0),
            itemsize, unblocked=True)
    else:
        x_ref = BlockRef(
            "x", (b, rows_in, wiu, cp), (1, slab_hi, wiu, cb),
            lambda i, s, j, k, sh=sh, st=stride, cb=cb, nk=nk:
                (i, s * sh * st, 0, k * cb if nk > 1 else 0),
            itemsize, unblocked=True)
    inputs = [x_ref]
    if has_expand:
        inputs.append(BlockRef("expand_w", (c_in, cp), (c_in, cb),
                               lambda i, s, j, k: (0, k), itemsize))
    inputs.append(BlockRef("dw_f", (hf, wf, cp), (hf, wf, cb),
                           lambda i, s, j, k: (0, 0, k), itemsize))
    if has_dw_bias:
        inputs.append(BlockRef("dw_bias", (1, cp), (1, cb),
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
    reshapes = [((sh, wo, cb), (sh * wo, cb))]
    return KernelModel(
        name="separable_fused3" if has_expand else "separable_fused2",
        grid=(b, n_slabs, cop // cob, nk),
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"),
        inputs=tuple(inputs),
        output=out_ref,
        scratch_bytes=(sh * wo * cob * 4           # fp32 accumulator
                       + taps.stage_bytes((slab_hi, wiu, cb),
                                          has_expand or stride > 1)),
        value_bytes=sh * wo * cb * 4,              # DW intermediate (fp32)
        reshapes=tuple(reshapes),
    )


def _fused_kernel(*refs, hf: int, wf: int, stride: int, nk: int,
                  dw_activation, activation, has_exp: bool,
                  expand_activation, has_dwb: bool, has_pwb: bool,
                  has_res: bool, out_dtype):
    """refs = (x, [expand_w,] f, [dw_bias,] w, [pw_bias,] [residual,] out,
    acc, [stage]).

    Blocks: x (1, slab_hi, Wiu, Cb) — the overlapping input window of this
    row slab (with expand: (1, slab_hi, Wiu, Ci), the RAW input, identical
    for every reduction step); expand_w (Ci, Cb); f (Hf, Wf, Cb); dw_bias
    (1, Cb); w (Cb, Cob); pw_bias (1, Cob); residual (1, slab_h, Wo, Cob);
    out (1, slab_h, Wo, Cob); acc VMEM scratch (slab_h*Wo, Cob) fp32;
    stage: the fp32 lane-chunked DW input (the expanded slab, or x when
    strided — ``taps.py``).
    """
    it = iter(refs)
    x_ref = next(it)
    ew_ref = next(it) if has_exp else None
    f_ref = next(it)
    dwb_ref = next(it) if has_dwb else None
    w_ref = next(it)
    pwb_ref = next(it) if has_pwb else None
    res_ref = next(it) if has_res else None
    out_ref = next(it)
    acc_ref = next(it)
    stage_ref = next(it, None)

    _, slab_h, wo, cob = out_ref.shape
    cb = f_ref.shape[2]
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if ew_ref is not None:
        # --- expand stage: this step's expanded-channel slab, on the fly ---
        # one (Wiu, Ci) @ (Ci, Cb) GEMM per input row into the fp32 stage;
        # never in HBM.  Row by row, because collapsing (slab_hi, Wiu) into
        # GEMM rows is a relayout when Wiu is off the sublane tile.
        ew = ew_ref[...].astype(jnp.float32)
        prec = contract_precision(x_ref.dtype)

        def expand_row(h, carry):
            ex = jnp.dot(x_ref[0, h].astype(jnp.float32), ew,
                         preferred_element_type=jnp.float32, precision=prec)
            taps.stage(stage_ref, _epilogue(ex, None, expand_activation),
                       row=h)
            return carry

        jax.lax.fori_loop(0, x_ref.shape[1], expand_row, 0)
    elif stage_ref is not None:
        taps.stage_input(stage_ref, x_ref)

    # --- DW stage: shift-and-FMA over the channel slab (dwconv2d Alg. 4) ---
    f = f_ref[...].astype(jnp.float32)
    dw = jnp.zeros((slab_h, wo, cb), jnp.float32)
    for n in range(hf):
        for m in range(wf):
            win = taps.tap(x_ref, stage_ref, n, m, slab_h, wo, stride, cb)
            dw = dw + win * f[n, m][None, None, :]
    dw = _epilogue(
        dw, dwb_ref[0][None, None, :] if dwb_ref is not None else None,
        dw_activation,
    )

    # --- PW stage: DW tile (VMEM value, never stored) is the A-operand ---
    a = dw.reshape(slab_h * wo, cb)
    acc_ref[...] += jnp.dot(
        a, w_ref[...].astype(jnp.float32), preferred_element_type=jnp.float32,
        precision=contract_precision(x_ref.dtype),
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
    static_argnames=("stride", "dw_activation", "activation",
                     "expand_activation", "block_c", "block_co", "slab_h",
                     "interpret", "out_dtype"),
)
def separable_fused_pallas(
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
    dw_activation: Optional[str] = "relu6",
    activation: Optional[str] = None,
    block_c: int | None = None,
    block_co: int | None = None,
    slab_h: int | None = None,
    interpret: bool = False,
    out_dtype: Optional[str] = None,
) -> jax.Array:
    """Fused DW+PW block. x (B,Hi,Wi,C); dw_f (Hf,Wf,C); pw_w (C,Co)
    [+ dw_bias (C,), pw_bias (Co,), residual (B,Ho,Wo,Co)] -> (B,Ho,Wo,Co).

    With ``expand_w`` (Ci, C) the input is the RAW (B,Hi,Wi,Ci) tensor and
    the kernel runs the full 3-stage chain — bias-free PW-expand (computed
    on the fly per row slab) -> DW -> PW-project — in one pass.

    ``out_dtype`` (a dtype NAME, static so it participates in the jit key)
    selects the store width of the single output write — the mixed-precision
    chain lowering pins the last pass of a bf16-streamed block to the
    policy's ``out`` dtype (DESIGN.md §7); ``None`` stores at ``x.dtype``.
    The accumulator is fp32 VMEM scratch regardless.

    VALID geometry — SAME padding is applied by the wrapper (ops.py /
    lowering.py).  Block shapes not given explicitly come from
    :func:`repro.kernels.blocking.plan_separable` (or ``plan_separable3``
    with expand); raises ValueError when even the minimal plan exceeds the
    VMEM budget (callers should have consulted the planner and taken a
    degraded path instead).
    """
    b, hi, wi, c_in = x.shape
    odt = jnp.dtype(out_dtype) if out_dtype is not None else x.dtype
    hf, wf, cf = dw_f.shape
    cw, co = pw_w.shape
    if expand_w is not None:
        ci_raw, c = expand_w.shape
        assert ci_raw == c_in and c == cf == cw, (
            x.shape, expand_w.shape, dw_f.shape, pw_w.shape)
    else:
        c = c_in
        assert c == cf == cw, (x.shape, dw_f.shape, pw_w.shape)
    ho = (hi - hf) // stride + 1
    wo = (wi - wf) // stride + 1
    assert ho >= 1 and wo >= 1, "input smaller than filter"
    hiu = (ho - 1) * stride + hf
    wiu = (wo - 1) * stride + wf

    if block_c is None or block_co is None or slab_h is None:
        if expand_w is not None:
            plan = blocking.plan_separable3(
                ho, wo, c_in, c, co, stride=stride, hf=hf, wf=wf,
                dtype=x.dtype, residual=residual is not None)
        else:
            plan = blocking.plan_separable(
                ho, wo, c, co, stride=stride, hf=hf, wf=wf, dtype=x.dtype,
                residual=residual is not None)
        if plan is None and (block_c is None or block_co is None):
            raise ValueError(
                f"no fused block plan fits VMEM for {(hi, wi, c, co)}; "
                "use the unfused composition (ops.separable_fused does this)"
            )
        cb = block_c or plan.block_c
        cob = block_co or plan.block_co
        sh = slab_h or (plan.slab_h if plan is not None else ho)
    else:
        cb, cob, sh = block_c, block_co, slab_h
    sh = min(sh, ho)
    n_slabs = -(-ho // sh)
    ho_p = n_slabs * sh
    slab_hi = (sh - 1) * stride + hf

    # Channel / Co padding (zero rows of pw_w nullify padded DW channels;
    # with expand, zero COLUMNS of expand_w make the padded expanded
    # channels exactly zero — every activation maps 0 -> 0).
    pad_c = (-c) % cb
    pad_co = (-co) % cob
    if pad_c:
        if expand_w is not None:
            expand_w = jnp.pad(expand_w, ((0, 0), (0, pad_c)))
        else:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, pad_c)))
        dw_f = jnp.pad(dw_f, ((0, 0), (0, 0), (0, pad_c)))
        pw_w = jnp.pad(pw_w, ((0, pad_c), (0, 0)))
        if dw_bias is not None:
            dw_bias = jnp.pad(dw_bias, ((0, pad_c),))
    if pad_co:
        pw_w = jnp.pad(pw_w, ((0, 0), (0, pad_co)))
        if pw_bias is not None:
            pw_bias = jnp.pad(pw_bias, ((0, pad_co),))
    if pad_co and residual is not None:
        residual = jnp.pad(residual, ((0, 0), (0, 0), (0, 0), (0, pad_co)))
    cp, cop = c + pad_c, co + pad_co
    nk = cp // cb

    # Row padding so the slab grid tiles Ho: the last slab's window reads
    # zero rows past the image and its garbage output rows are cropped.
    rows_in = (ho_p - 1) * stride + hf
    x = x[:, :hiu, :wiu, :]
    if rows_in > hiu:
        x = jnp.pad(x, ((0, 0), (0, rows_in - hiu), (0, 0), (0, 0)))
    if ho_p > ho and residual is not None:
        residual = jnp.pad(residual, ((0, 0), (0, ho_p - ho), (0, 0), (0, 0)))

    # The grid and every BlockSpec come from the kernel model — the same
    # object the static analyzer (repro.analysis) checks, so what is proven
    # statically is what executes (DESIGN.md §8).  Input windows of adjacent
    # slabs overlap by (hf - stride) halo rows, so the x BlockSpec uses
    # element-offset indexing; with expand the window carries
    # ALL raw channels (Ci is small; the reduction steps slab the EXPANDED
    # channels via the expand_w block instead).
    model = fused_kernel_model(
        b=b, ho=ho, wo=wo, c_in=c_in, c=c, co=co, hf=hf, wf=wf,
        stride=stride, block_c=cb, block_co=cob, slab_h=sh,
        itemsize=x.dtype.itemsize, out_itemsize=odt.itemsize,
        has_expand=expand_w is not None, has_dw_bias=dw_bias is not None,
        has_pw_bias=pw_bias is not None, has_residual=residual is not None,
    )
    inputs = [x]
    if expand_w is not None:
        inputs.append(expand_w)
    inputs.append(dw_f)
    if dw_bias is not None:
        inputs.append(dw_bias.reshape(1, -1))
    inputs.append(pw_w)
    if pw_bias is not None:
        inputs.append(pw_bias.reshape(1, -1))
    if residual is not None:
        inputs.append(residual)
    for arr, br in zip(inputs, model.inputs):
        assert arr.shape == br.array_shape, (br.name, arr.shape,
                                             br.array_shape)
    in_specs = in_specs_from_model(model)

    kernel = functools.partial(
        _fused_kernel, hf=hf, wf=wf, stride=stride, nk=nk,
        dw_activation=dw_activation, activation=activation,
        has_exp=expand_w is not None, expand_activation=expand_activation,
        has_dwb=dw_bias is not None, has_pwb=pw_bias is not None,
        has_res=residual is not None, out_dtype=odt,
    )
    stage = taps.stage_shapes((slab_hi, wiu, cb),
                              expand_w is not None or stride > 1)

    assert model.output.array_shape == (b, ho_p, wo, cop)
    out = pl.pallas_call(
        kernel,
        grid=model.grid,
        in_specs=in_specs,
        out_specs=out_spec_from_model(model),
        out_shape=jax.ShapeDtypeStruct(model.output.array_shape, odt),
        scratch_shapes=[pltpu.VMEM((sh * wo, cob), jnp.float32)] + stage,
        compiler_params=compiler_params(model),
        interpret=interpret,
    )(*inputs)
    return out[:, :ho, :, :co]
