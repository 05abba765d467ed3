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
group of R input rows — ``x[h:h+R] (R*W, Ci) @ expand_w[:, k*Cb:(k+1)*Cb]``
— into VMEM fp32 scratch, applies the expand activation, and feeds that
slab to the DW shift-and-FMA in place of the streamed input.  R > 1 only
where the rows collapse into GEMM rows for free (:func:`plan_expand_rows`:
the unpadded image, its width on the fp32 sublane tile); elsewhere R = 1,
one ``(Wiu, Ci)`` GEMM per row.  Neither the expanded tensor
(``B*Hi*Wi*C`` — 6x the input at the usual expansion factor) nor the DW
output ever exists in HBM.  Restriction: the expansion
must be bias-free, because the SAME halo is zero in the RAW input's
channels — the kernel writes zeros where a pad pixel's expansion would go
(or expands the zero pixels the wrapper padded in), and a bias would make
them ``act(bias) != 0`` (every supported activation maps 0 -> 0, so a
bias-free expand commutes with zero padding).  ``core/chain.plan``
degrades to the 2-stage path when the spec declares an expand bias.

SAME padding (``pads``): a one-slab plan takes the UNPADDED input and makes
the halo in VMEM — no padded copy of the block's input is written to HBM.
The kernel writes its input (or its expanded rows) into the fp32 tap stage
at the pad offset and zeroes the halo around it (``kernels/taps.py``); the
taps then read the stage as they read a padded window.  The values are
those of the padded input, so the output is unchanged.  Without ``pads``
the geometry is VALID: the caller pads in HBM (``ops.pad_same``), as
slabbed plans and the unfused ops still do.

All block choices come from ``kernels.blocking.plan_separable`` /
``plan_separable3`` (dtype-aware VMEM budget, Co-panel and row-slab
enumeration); when even the minimal plan exceeds the budget the planner
returns None and callers fall back to the unfused composition
(``ops.separable_fused``).

Batch-minor entry (``separable_fused_batch_minor``, DESIGN.md §3): the
network's first block reads a (B, H, W, C) body input laid out with the
batch in the lanes — the TPU's default layout at a batch of 128 — as its
(H, W, C, B) view, so no relayout copy of the input precedes the kernel.
The DW taps run over 128 images a vreg; the turn to channel-minor is one
MXU contraction and one strided store per output pixel, in VMEM.

TPU note: the overlapping input windows use element-offset indexing
(``pl.Element`` on every block dim, ``gridspec.in_specs_from_model``); the
row offset is un-tiled and free, the lane offset must be provably
128-aligned (MC204).  The DW taps of a strided or expanded block, and of
one that makes its SAME halo, read an fp32 lane-chunked VMEM stage
(``kernels/taps.py``).
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


def _staged(has_expand: bool, stride: int, pads) -> bool:
    """Whether the DW taps read an fp32 stage (``taps.py``): the expanded
    slab, a strided window, or a window whose SAME halo is made in VMEM."""
    return has_expand or stride > 1 or pads is not None


#: Rows of an fp32 (8, 128) tile: rows of the image collapse into GEMM rows
#: without a relayout when their width is a multiple of it.
_F32_SUBLANES = 8


def plan_expand_rows(x_rows: int, x_cols: int, dw_pixels: int,
                     pads) -> int:
    """Input rows R that one expand GEMM of the fused3 kernel takes.

    Where the kernel reads the unpadded image (``pads``: the SAME halo made
    in VMEM, one row slab) and its width ``x_cols`` is a multiple of the
    fp32 sublane tile, ``x[h:h+R]`` collapses to ``(R*x_cols, Ci)`` for
    free: R is then the largest divisor of ``x_rows`` whose expanded value
    ``(R*x_cols, Cb)`` is no larger than the DW intermediate ``(dw_pixels,
    Cb)`` the kernel models already count.  Elsewhere R = 1: one GEMM per
    input row, since collapsing a window off the tile is a relayout."""
    if pads is None or x_cols % _F32_SUBLANES:
        return 1
    return max((r for r in range(1, x_rows + 1)
                if x_rows % r == 0 and r * x_cols <= dw_pixels), default=1)


def fused_kernel_model(*, b: int, ho: int, wo: int, c_in: int, c: int,
                       co: int, hf: int, wf: int, stride: int,
                       block_c: int, block_co: int, slab_h: int,
                       itemsize: int, out_itemsize: int,
                       has_expand: bool, has_dw_bias: bool,
                       has_pw_bias: bool, has_residual: bool,
                       pads=None,
                       expand_rows: Optional[int] = None) -> KernelModel:
    """The exact grid/BlockSpec geometry ``separable_fused_pallas`` lowers
    to at these blocks — the single source of truth consumed by BOTH the
    kernel (specs built from this model) and the static analyzer
    (``repro.analysis``), so planner<->lowering drift is structurally
    impossible (DESIGN.md §8).

    ``c_in`` is the raw input channel count (== ``c`` without expand).
    Shapes are the PADDED shapes the kernel hands to ``pl.pallas_call``
    after channel/Co/row padding.  With ``pads`` (the SAME padding made in
    VMEM, one row slab) the input is the unpadded image, one whole block
    per image.  ``expand_rows`` is the fused3 kernel's expand group
    (None: :func:`plan_expand_rows`'s).
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

    if pads is not None:
        assert n_slabs == 1, "the SAME halo is made in VMEM for one slab"
        hin, win = slab_hi - sum(pads[0]), wiu - sum(pads[1])
        if has_expand:
            x_ref = BlockRef("x", (b, hin, win, c_in), (1, hin, win, c_in),
                             lambda i, s, j, k: (i, 0, 0, 0), itemsize)
        else:
            x_ref = BlockRef("x", (b, hin, win, cp), (1, hin, win, cb),
                             lambda i, s, j, k: (i, 0, 0, k), itemsize)
    # x window: element-offset (unblocked) indexing — adjacent slabs'
    # windows overlap by the (hf - stride)-row halo.  With expand the
    # window carries ALL raw channels; without, one channel slab.  A single
    # channel slab gets a literal 0 lane offset: Mosaic must prove the
    # offset 128-aligned, and cannot for ``k * cb`` with cb < 128 (MC204).
    elif has_expand:
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
    if has_expand:
        _, x_rows, x_cols, _ = x_ref.block_shape
        r = expand_rows or plan_expand_rows(x_rows, x_cols, sh * wo, pads)
        assert x_rows % r == 0, (x_ref.block_shape, r)
        if r > 1:
            reshapes.append(((r, x_cols, c_in), (r * x_cols, c_in)))
    return KernelModel(
        name="separable_fused3" if has_expand else "separable_fused2",
        grid=(b, n_slabs, cop // cob, nk),
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"),
        inputs=tuple(inputs),
        output=out_ref,
        scratch_bytes=(sh * wo * cob * 4           # fp32 accumulator
                       + taps.stage_bytes((slab_hi, wiu, cb),
                                          _staged(has_expand, stride, pads))),
        value_bytes=sh * wo * cb * 4,              # DW intermediate (fp32)
        reshapes=tuple(reshapes),
    )


def _fused_kernel(*refs, hf: int, wf: int, stride: int, nk: int,
                  dw_activation, activation, has_exp: bool,
                  expand_activation, expand_rows: Optional[int],
                  has_dwb: bool, has_pwb: bool, has_res: bool, out_dtype,
                  pads):
    """refs = (x, [expand_w,] f, [dw_bias,] w, [pw_bias,] [residual,] out,
    acc, [stage]).

    Blocks: x (1, slab_hi, Wiu, Cb) — the overlapping input window of this
    row slab (with expand: (1, slab_hi, Wiu, Ci), the RAW input, identical
    for every reduction step; with ``pads``: the unpadded image);
    expand_w (Ci, Cb); f (Hf, Wf, Cb); dw_bias (1, Cb); w (Cb, Cob);
    pw_bias (1, Cob); residual (1, slab_h, Wo, Cob); out (1, slab_h, Wo,
    Cob); acc VMEM scratch (slab_h*Wo, Cob) fp32; stage: the fp32
    lane-chunked DW input window (the expanded slab, or x when strided or
    when the kernel makes the SAME halo — ``taps.py``).  ``expand_rows``:
    the input rows one expand GEMM takes (None: :func:`plan_expand_rows`'s
    at these blocks, as ``fused_kernel_model`` records it).
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
    _, x_rows, x_cols, _ = x_ref.shape
    cb = f_ref.shape[2]
    k = pl.program_id(3)
    # where the input sits in the stage: past the halo made here
    top, left = (pads[0][0], pads[1][0]) if pads is not None else (0, 0)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if ew_ref is not None:
        # --- expand stage: this step's expanded-channel slab, on the fly ---
        # one (R*Wiu, Ci) @ (Ci, Cb) GEMM per group of R input rows into the
        # fp32 stage; never in HBM.  R = 1 (row by row) unless the rows
        # collapse into GEMM rows without a relayout (plan_expand_rows).
        ew = ew_ref[...].astype(jnp.float32)
        prec = contract_precision(x_ref.dtype)
        r = expand_rows or plan_expand_rows(x_rows, x_cols, slab_h * wo,
                                            pads)

        def expand(g, carry):
            h = g * r
            xs = x_ref[0, pl.ds(h, r)].astype(jnp.float32)
            ex = jnp.dot(xs.reshape(r * x_cols, xs.shape[-1]), ew,
                         preferred_element_type=jnp.float32, precision=prec)
            taps.stage(stage_ref, _epilogue(ex, None, expand_activation)
                       .reshape(r, x_cols, cb),
                       row=pl.ds(h + top, r), col=left)
            return carry

        jax.lax.fori_loop(0, x_rows // r, expand, 0)
    elif stage_ref is not None:
        taps.stage_input(stage_ref, x_ref, top, left)
    if pads is not None:
        # a pad pixel is zero, expanded or not (the expand is bias-free)
        taps.zero_halo(stage_ref, pads, x_rows, x_cols)

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
                     "interpret", "out_dtype", "pads", "expand_rows"),
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
    pads: Optional[blocking.Pads] = None,
    expand_rows: Optional[int] = None,
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

    ``pads`` ``((top, bottom), (left, right))`` — the SAME padding of the
    unpadded ``x`` (``blocking.same_pads``) — makes the kernel apply it in
    VMEM; the plan must then be one row slab (``blocking.kernel_pads``).
    Without ``pads`` the geometry is VALID and a SAME caller pads ``x``
    first (``ops.pad_same``).  ``expand_rows`` sets the input rows of one
    expand GEMM (a divisor of the rows the kernel reads); None takes
    :func:`plan_expand_rows`'s.  Block shapes not given explicitly come from
    :func:`repro.kernels.blocking.plan_separable` (or ``plan_separable3``
    with expand); raises ValueError when even the minimal plan exceeds the
    VMEM budget (callers should have consulted the planner and taken a
    degraded path instead).
    """
    b, hi, wi, c_in = x.shape
    if pads is not None:
        hi, wi = hi + sum(pads[0]), wi + sum(pads[1])   # the padded extent
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
                dtype=x.dtype, residual=residual is not None, pads=pads)
        else:
            plan = blocking.plan_separable(
                ho, wo, c, co, stride=stride, hf=hf, wf=wf, dtype=x.dtype,
                residual=residual is not None, pads=pads)
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
    if pads is not None and blocking.kernel_pads(pads, ho, sh) is None:
        raise ValueError(
            f"the SAME halo is made in VMEM for one row slab, not {n_slabs};"
            " pad the input in HBM (ops.pad_same) for a slabbed plan")

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
    top, left = (pads[0][0], pads[1][0]) if pads is not None else (0, 0)
    x = x[:, :hiu - top, :wiu - left, :]     # the rows and columns read
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
        pads=pads, expand_rows=expand_rows,
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
        expand_rows=expand_rows, has_dwb=dw_bias is not None,
        has_pwb=pw_bias is not None, has_res=residual is not None,
        out_dtype=odt, pads=pads,
    )
    stage = taps.stage_shapes((slab_hi, wiu, cb),
                              _staged(expand_w is not None, stride, pads))

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
        name="fused3" if expand_w is not None else "fused2",
    )(*inputs)
    return out[:, :ho, :, :co]


# ---------------------------------------------------------------------------
# Batch-minor entry: the body input as it arrives on the TPU at batch 128
# ---------------------------------------------------------------------------

#: Images a grid cell of the batch-minor entry computes: one vreg of lanes.
BATCH_LANES = 128

#: Output columns the batch-minor kernel turns to channel-minor at a time:
#: half a 112-wide row, so its fp32 turn scratch fits the VMEM budget.
TURN_COLS = 56

#: Images whose turned rows one step of the store loop writes out.
_IMAGES_PER_STEP = 8


def _turn_cols(w: int) -> int:
    return TURN_COLS if w % TURN_COLS == 0 else w


def _turn_stride(tc: int) -> int:
    """Rows between two images in the turn scratch: odd, so that the
    strided store of one pixel's 8 images is one vreg store (an even
    stride takes 2-8 stores a vreg on v5e)."""
    return tc | 1


def _pixels_per_step(w: int) -> int:
    return max(p for p in (8, 7, 6, 5, 4, 3, 2, 1) if w % p == 0)


def batch_minor_kernel_model(*, b: int, h: int, w: int, c: int, co: int,
                             hf: int, wf: int, pads, itemsize: int,
                             out_itemsize: int, has_dw_bias: bool,
                             has_pw_bias: bool) -> KernelModel:
    """The grid/BlockSpec geometry of :func:`separable_fused_batch_minor`
    (DESIGN.md §3, §8): a stride-1 SAME DW -> PW over the body input laid
    out ``(H, W, C, B)``, batch in the lanes.

    Grid ``(B/128, H)``: each cell writes output row ``t`` of 128 images.
    The second dim is sequential and carries a ring of the last ``hf``
    input rows in VMEM, with the zero column halo: cell ``t`` brings in
    row ``t + bottom`` only, so every input row crosses HBM once.  The
    first ``bottom`` rows come in with the batch chunk (``head``, one
    buffer)."""
    (top, bottom), (left, right) = pads
    assert hf == top + bottom + 1 and wf == left + right + 1, (hf, wf, pads)
    lanes = BATCH_LANES
    assert b % lanes == 0, b
    last = h - 1
    inputs = [BlockRef(
        "x", (h, w, c, b), (1, w, c, lanes),
        lambda i, t: (jnp.minimum(t + bottom, last), 0, 0, i), itemsize)]
    if bottom:
        inputs.append(BlockRef("head", (h, w, c, b), (bottom, w, c, lanes),
                               lambda i, t: (0, 0, 0, i), itemsize,
                               streamed=False))
    inputs.append(BlockRef("dw_f", (hf, wf, c, lanes), (hf, wf, c, lanes),
                           lambda i, t: (0, 0, 0, 0), itemsize))
    if has_dw_bias:
        inputs.append(BlockRef("dw_bias", (c, lanes), (c, lanes),
                               lambda i, t: (0, 0), itemsize))
    inputs.append(BlockRef("pw_w", (c, co), (c, co),
                           lambda i, t: (0, 0), itemsize))
    if has_pw_bias:
        inputs.append(BlockRef("pw_bias", (1, co), (1, co),
                               lambda i, t: (0, 0), itemsize))
    out_ref = BlockRef("out", (b, h, w, co), (lanes, 1, w, co),
                       lambda i, t: (i, t, 0, 0), out_itemsize)
    tc = _turn_cols(w)
    return KernelModel(
        name="separable_fused2_batch_minor",
        grid=(b // lanes, h),
        dimension_semantics=("parallel", "arbitrary"),
        inputs=tuple(inputs),
        output=out_ref,
        scratch_bytes=(hf * (w + left + right) * c * lanes * itemsize  # ring
                       + lanes * _turn_stride(tc) * co * 4),         # turn
        value_bytes=_pixels_per_step(tc) * c * lanes * 4,   # DW pixels
        reduction_dims=(),
    )


def _batch_minor_kernel(*refs, hf: int, pads, h: int, dw_activation,
                        activation, has_dwb: bool, has_pwb: bool,
                        out_dtype, prec):
    """refs = (x, [head,] f, [dw_bias,] w, [pw_bias,] out, ring, turn).

    x (1, W, C, 128): input row ``t + bottom`` (the last row past the
    image); head (bottom, W, C, 128): rows ``0 .. bottom-1``; f (Hf, Wf,
    C, 128) and dw_bias (C, 128): the DW weights broadcast over the lanes;
    w (C, Co); pw_bias (1, Co); out (128, 1, Wo, Co): output row ``t`` of
    the batch chunk; ring (Hf, W + left + right, C, 128): the last ``Hf``
    input rows with the zero column halo, row ``r`` in slot
    ``(r + top) % Hf``; turn (128 * S, Co) fp32: the PW output of ``Tc``
    columns after its epilogue, image ``b``'s at rows ``b * S ..`` (S
    odd, ``_turn_stride``).
    """
    (top, bottom), (left, _) = pads
    it = iter(refs)
    x_ref = next(it)
    head_ref = next(it) if bottom else None
    f_ref = next(it)
    dwb_ref = next(it) if has_dwb else None
    w_ref = next(it)
    pwb_ref = next(it) if has_pwb else None
    out_ref = next(it)
    ring_ref = next(it)
    turn_ref = next(it)

    _, w, c, lanes = x_ref.shape
    wo, co = out_ref.shape[2], out_ref.shape[3]
    t = pl.program_id(1)
    cols = pl.ds(left, w)

    def slot(row):
        return jax.lax.rem(row + top, hf)

    @pl.when(t == 0)
    def _start():   # a new batch chunk: the halo, then its first rows
        ring_ref[...] = jnp.zeros_like(ring_ref)
        for r in range(bottom):
            ring_ref[(r + top) % hf, cols] = head_ref[r]

    new = t + bottom

    @pl.when(new < h)
    def _row():
        ring_ref[slot(new), cols] = x_ref[0]

    if bottom:
        @pl.when(new >= h)
        def _pad_row():
            ring_ref[slot(new), cols] = jnp.zeros((w, c, lanes),
                                                  ring_ref.dtype)

    slots = [slot(t - top + n) for n in range(hf)]
    wf = f_ref.shape[1]
    pw = w_ref[...].astype(jnp.float32)
    pwb = pwb_ref[...] if pwb_ref is not None else None
    tc = _turn_cols(wo)
    ts = _turn_stride(tc)
    step = _pixels_per_step(tc)

    def chunk(q, carry):
        c0 = pl.multiple_of(q * tc, tc)

        def pixels(j, carry):
            # the DW taps over full 128-image vregs, in fp32
            w0 = c0 + j * step
            dw = jnp.zeros((step, c, lanes), jnp.float32)
            for n in range(hf):
                for m in range(wf):
                    dw = dw + (ring_ref[slots[n], pl.ds(w0 + m, step)]
                               .astype(jnp.float32)
                               * f_ref[n, m].astype(jnp.float32)[None])
            dw = _epilogue(
                dw, dwb_ref[...][None] if dwb_ref is not None else None,
                dw_activation)
            # the turn: (C, 128) -> (128, Co) on the MXU, stored image-major
            for k in range(step):
                y = jax.lax.dot_general(
                    dw[k], pw, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=prec)
                turn_ref[pl.ds(j * step + k, lanes, stride=ts), :] = (
                    _epilogue(y, pwb, activation))
            return carry

        jax.lax.fori_loop(0, tc // step, pixels, 0)

        def images(q, carry):
            for u in range(_IMAGES_PER_STEP):
                b = q * _IMAGES_PER_STEP + u
                out_ref[b, 0, pl.ds(c0, tc), :] = turn_ref[
                    pl.ds(b * ts, tc), :].astype(out_dtype)
            return carry

        jax.lax.fori_loop(0, lanes // _IMAGES_PER_STEP, images, 0)
        return carry

    jax.lax.fori_loop(0, wo // tc, chunk, 0)


@functools.partial(
    jax.jit,
    static_argnames=("pads", "dw_activation", "activation", "interpret",
                     "out_dtype"))
def separable_fused_batch_minor(
    xt: jax.Array,
    dw_f: jax.Array,
    pw_w: jax.Array,
    dw_bias: Optional[jax.Array] = None,
    pw_bias: Optional[jax.Array] = None,
    *,
    pads: blocking.Pads,
    dw_activation: Optional[str] = "relu6",
    activation: Optional[str] = None,
    interpret: bool = False,
    out_dtype: Optional[str] = None,
) -> jax.Array:
    """Fused stride-1 SAME DW -> PW over a batch-minor input: xt (H, W, C,
    B) with B a multiple of 128; dw_f (Hf, Wf, C); pw_w (C, Co) [+ dw_bias
    (C,), pw_bias (Co,)] -> (B, H, W, Co), the layout and values of
    :func:`separable_fused_pallas` on ``xt.transpose(3, 0, 1, 2)``.

    The DW taps run over full 128-image vregs; the turn to channel-minor
    happens per output pixel in VMEM: the pixel's (C, 128) DW tile,
    contracted over C with the PW weights on the MXU, gives (128, Co),
    stored image-major into an fp32 scratch with one strided store a
    vreg, from which each image's row is stored once (DESIGN.md §3)."""
    h, w, c, b = xt.shape
    hf, wf, cf = dw_f.shape
    cw, co = pw_w.shape
    assert c == cf == cw, (xt.shape, dw_f.shape, pw_w.shape)
    odt = jnp.dtype(out_dtype) if out_dtype is not None else xt.dtype
    lanes = BATCH_LANES
    model = batch_minor_kernel_model(
        b=b, h=h, w=w, c=c, co=co, hf=hf, wf=wf, pads=pads,
        itemsize=xt.dtype.itemsize, out_itemsize=odt.itemsize,
        has_dw_bias=dw_bias is not None, has_pw_bias=pw_bias is not None)
    inputs = [xt]
    if pads[0][1]:
        inputs.append(xt)
    inputs.append(jnp.broadcast_to(dw_f[..., None], (hf, wf, c, lanes)))
    if dw_bias is not None:
        inputs.append(jnp.broadcast_to(dw_bias[:, None], (c, lanes)))
    inputs.append(pw_w)
    if pw_bias is not None:
        inputs.append(pw_bias.reshape(1, co))
    for arr, br in zip(inputs, model.inputs):
        assert arr.shape == br.array_shape, (br.name, arr.shape,
                                             br.array_shape)
    kernel = functools.partial(
        _batch_minor_kernel, hf=hf, pads=pads, h=h,
        dw_activation=dw_activation, activation=activation,
        has_dwb=dw_bias is not None, has_pwb=pw_bias is not None,
        out_dtype=odt, prec=contract_precision(xt.dtype))
    (left, right) = pads[1]
    return pl.pallas_call(
        kernel,
        grid=model.grid,
        in_specs=in_specs_from_model(model),
        out_specs=out_spec_from_model(model),
        out_shape=jax.ShapeDtypeStruct(model.output.array_shape, odt),
        scratch_shapes=[
            pltpu.VMEM((hf, w + left + right, c, lanes), xt.dtype),
            pltpu.VMEM((lanes * _turn_stride(_turn_cols(w)), co),
                       jnp.float32)],
        compiler_params=compiler_params(model),
        interpret=interpret,
        name="fused2",
    )(*inputs)
