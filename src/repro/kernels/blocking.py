"""Unified dtype-aware block planner for the Pallas kernels (DESIGN.md §4).

The paper's core argument — pick blockings that pin the working set at the
fastest memory level and write each output exactly once — used to be
re-derived separately by ``dwconv2d._block_c``, ``separable_fused._snap`` /
``_co_candidates`` / ``_block_sizes`` and ``pwconv``'s fixed grid defaults,
each budgeting at fp32 widths.  This module is the single owner of that
logic:

* **dtype-aware VMEM budgeting** — streamed operands (input slabs, filter
  and weight tiles, output tiles) are costed at ``dtype.itemsize`` bytes;
  only the accumulators are pinned at fp32 (``ACC_BYTES``), matching what
  the kernels actually allocate.  bf16 working sets therefore claim ~2x
  less than the old fp32-only math and the planner can afford larger
  blocks.
* **channel / Co-panel enumeration** — ``snap_channels`` and
  ``co_candidates`` (strictly descending, deduplicated) shared by every
  consumer.
* **spatial row-slab blocking with halo** — ``plan_separable`` adds an
  output-row slab dimension: when the full ``(Ho·Wo, Cob)`` accumulator
  panel cannot fit VMEM, the image is cut into ``n_slabs`` slabs of
  ``slab_h`` output rows whose *input* fetches overlap by
  ``halo_rows = Hf - stride`` rows at each interior seam.  This lifts the
  old ~1.5M-pixel fused-kernel ceiling: any resolution now yields a real
  :class:`BlockPlan` instead of the unfused fallback.

* **whole-chain budgeting** — ``plan_separable3`` budgets the full
  MobileNetV2 inverted residual (PW-expand -> DW -> PW-project) as ONE
  kernel: the expansion GEMM is computed on the fly per row slab inside the
  fused kernel, so the budget adds the raw-input window (at ``Ci``
  channels), the expand-weight tile and the fp32 expanded value to the
  2-stage working set.  ``ChainPlan`` / ``ChainSegment`` are the planner's
  answer for a whole declared stage chain (``core/chain.plan``): which
  contiguous stages fuse, at which blocks — a frozen, hashable, comparable
  unit (the cache key for measured autotuning later).

Consumers: ``kernels/dwconv2d.py`` (``plan_dwconv2d``),
``kernels/separable_fused.py`` + ``kernels/ops.py`` (``plan_separable``,
``plan_separable3``), ``kernels/ops.py::pwconv`` (``plan_pwconv``),
``core/chain.py`` + ``kernels/lowering.py`` (``ChainPlan``), and the
analysis layer (``benchmarks/kernel_vmem.py``,
``benchmarks/roofline_table.py``, ``core/intensity.py`` consumers report
the planner's choices).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

#: Default HBM->VMEM working-set budget a single kernel may claim. 12 MiB of
#: the ~16 MiB/core leaves headroom for Mosaic's own spills and semaphores.
DEFAULT_VMEM_BUDGET = 12 * 1024 * 1024

#: Accumulators are always fp32 scratch regardless of the activation dtype.
ACC_BYTES = 4

#: TPU lane count — the minor-dim vector width every block snaps to.
LANES = 128


def dtype_bytes(dtype) -> int:
    """Element width the planner budgets streamed operands at."""
    return jnp.dtype(dtype).itemsize


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """One kernel invocation's block choices + the VMEM claim behind them.

    Which fields a kernel consumes (DESIGN.md §4):

    * ``dwconv2d``          — ``block_c`` only (``slab_h`` == Ho, one slab).
    * ``separable_fused``   — ``block_c``, ``block_co``, ``slab_h`` /
      ``n_slabs`` / ``halo_rows`` (the row-slab grid dimension).
    * ``pwconv``            — ``block_g``, ``block_c`` (= Ci block),
      ``block_co``.

    ``vmem_bytes`` is the claimed working set at these blocks and
    ``dtype_bytes`` the streamed-element width it was budgeted at; both are
    reported by ``benchmarks/kernel_vmem.py``.
    """
    block_c: int            # channel slab (DW lanes / GEMM reduction block)
    block_co: int           # output-channel panel (0: op has no Co dim)
    slab_h: int             # output rows per spatial slab
    n_slabs: int            # ceil(Ho / slab_h)
    halo_rows: int          # input rows re-fetched per interior slab seam
    vmem_bytes: int         # claimed working set at these blocks
    dtype_bytes: int        # streamed-element width budgeted
    block_g: int = 0        # GEMM row-panel (pwconv only)

    def co_panels(self, co: int) -> int:
        """Number of output-channel panels this plan splits ``co`` into."""
        return -(-co // self.block_co) if self.block_co else 1


def snap_channels(cb: int, c: int) -> int:
    """Snap a raw channel-count budget to a usable block: all of ``c``, a
    multiple of 128 lanes, or the tiny-VMEM power-of-two fallback (correct
    everywhere; only lane utilization suffers — DESIGN.md §2)."""
    if c <= cb:
        return c
    if cb >= LANES:
        return (cb // LANES) * LANES
    p = 1
    while p * 2 <= cb:
        p *= 2
    return p


def co_candidates(co: int) -> list[int]:
    """Strictly descending, deduplicated Co-panel candidates: all of Co
    first (single panel — the traffic-optimal case), then multiples of 128,
    then powers of two.  Replaces ``separable_fused._co_candidates``, which
    could emit interleaved/duplicate entries."""
    cands = {co}
    k = ((co - 1) // LANES) * LANES
    while k >= LANES:
        cands.add(k)
        k -= LANES
    p = 64
    while p >= 1:
        if p < co:
            cands.add(p)
        p //= 2
    return sorted(cands, reverse=True)


def slab_candidates(ho: int) -> list[int]:
    """Descending output-row slab heights: the whole image first (no
    slabbing, no halo), then powers of two.  Strictly descending and
    deduplicated like :func:`co_candidates`."""
    cands = {ho}
    p = 1
    while p * 2 < ho:
        p *= 2
    while p >= 1:
        cands.add(p)
        p //= 2
    return sorted(cands, reverse=True)


# ---------------------------------------------------------------------------
# dwconv2d
# ---------------------------------------------------------------------------

def dwconv2d_vmem_bytes(hi: int, wi: int, ho: int, wo: int, cb: int,
                        hf: int = 3, wf: int = 3,
                        itemsize: int = 4) -> int:
    """Working set of ``dwconv2d`` at channel block ``cb``: 2x double-
    buffered input slab + filter tile (streamed at ``itemsize``), fp32
    output accumulator."""
    return cb * (2 * hi * wi * itemsize + hf * wf * itemsize
                 + ho * wo * ACC_BYTES)


def plan_dwconv2d(hi: int, wi: int, ho: int, wo: int, c: int,
                  hf: int = 3, wf: int = 3, *,
                  dtype=jnp.float32,
                  vmem_budget: int = DEFAULT_VMEM_BUDGET) -> BlockPlan:
    """Channel-block plan for the depthwise kernel (replaces
    ``dwconv2d._block_c``, now budgeting at ``dtype.itemsize``)."""
    nb = dtype_bytes(dtype)
    per_c = dwconv2d_vmem_bytes(hi, wi, ho, wo, 1, hf, wf, nb)
    cb = snap_channels(max(1, vmem_budget // max(per_c, 1)), c)
    return BlockPlan(
        block_c=cb, block_co=0, slab_h=ho, n_slabs=1, halo_rows=0,
        vmem_bytes=dwconv2d_vmem_bytes(hi, wi, ho, wo, cb, hf, wf, nb),
        dtype_bytes=nb,
    )


# ---------------------------------------------------------------------------
# fused separable block (DW -> act -> PW)
# ---------------------------------------------------------------------------

def fused_vmem_bytes(wo: int, slab_h: int, cb: int, cob: int,
                     hf: int = 3, wf: int = 3, stride: int = 1,
                     itemsize: int = 4, residual: bool = False) -> int:
    """Working-set bytes of the fused kernel at blocks
    ``(cb, cob, slab_h)``: fp32 accumulator + output tile (+ 2x residual
    tile), and per channel slab the 2x double-buffered input slab, the DW
    intermediate (fp32 value), the filter tile and 2x the PW weight tile.
    The single source of truth for :func:`plan_separable` and
    ``benchmarks/kernel_vmem.py``."""
    slab_hi = (slab_h - 1) * stride + hf
    wiu = (wo - 1) * stride + wf
    out_side = slab_h * wo * cob * (ACC_BYTES + itemsize)
    if residual:
        out_side += 2 * slab_h * wo * cob * itemsize
    per_c = (2 * slab_hi * wiu * itemsize       # input slab, double-buffered
             + hf * wf * itemsize               # DW filter tile
             + slab_h * wo * ACC_BYTES          # DW intermediate (fp32 value)
             + 2 * cob * itemsize)              # PW weight tile, dbl-buffered
    return out_side + cb * per_c


def _fused_plan_at(ho: int, wo: int, c: int, slab_h: int, cob: int,
                   hf: int, wf: int, stride: int, itemsize: int,
                   residual: bool, vmem_budget: int,
                   min_cb: int) -> Optional[int]:
    """Largest snapped channel block >= min_cb fitting the budget, or None."""
    base = fused_vmem_bytes(wo, slab_h, 0, cob, hf, wf, stride, itemsize,
                            residual)
    per_c = fused_vmem_bytes(wo, slab_h, 1, cob, hf, wf, stride, itemsize,
                             residual) - base
    rem = vmem_budget - base
    if rem < per_c:
        return None
    cb = snap_channels(int(rem // per_c), c)
    return cb if cb >= min_cb else None


def plan_separable_at(ho: int, wo: int, c: int, co: int, *,
                      block_co: int, slab_h: int,
                      stride: int = 1, hf: int = 3, wf: int = 3,
                      dtype=jnp.float32,
                      vmem_budget: int = DEFAULT_VMEM_BUDGET,
                      residual: bool = False) -> Optional[BlockPlan]:
    """Feasibility probe at an EXPLICIT ``(block_co, slab_h)`` point: the
    largest channel block that fits the budget there, or None.  This is the
    autotuner's candidate constructor (``kernels/autotune.py``) — the
    analytic :func:`plan_separable` walks the same ladder but stops at the
    first hit; the tuner instead measures several feasible points."""
    nb = dtype_bytes(dtype)
    cb = _fused_plan_at(ho, wo, c, slab_h, block_co, hf, wf, stride, nb,
                        residual, vmem_budget, 1)
    if cb is None:
        return None
    n_slabs = -(-ho // slab_h)
    return BlockPlan(
        block_c=cb, block_co=block_co, slab_h=slab_h, n_slabs=n_slabs,
        halo_rows=max(hf - stride, 0) if n_slabs > 1 else 0,
        vmem_bytes=fused_vmem_bytes(wo, slab_h, cb, block_co, hf, wf,
                                    stride, nb, residual),
        dtype_bytes=nb,
    )


def plan_separable(ho: int, wo: int, c: int, co: int, *,
                   stride: int = 1, hf: int = 3, wf: int = 3,
                   dtype=jnp.float32,
                   vmem_budget: int = DEFAULT_VMEM_BUDGET,
                   residual: bool = False) -> Optional[BlockPlan]:
    """Block plan for the fused separable kernel, or None when nothing fits.

    Preference order (traffic-motivated, DESIGN.md §3):

    1. a **single Co panel** — splitting Co replays the input stream and the
       DW compute per panel, the costliest re-read;
    2. the **largest row slab** — slabbing only re-fetches
       ``halo_rows = Hf - stride`` input rows per interior seam, the
       cheapest re-read, so it is the dimension of last resort *within* a
       Co choice but always preferred over splitting Co;
    3. the **largest channel slab** that still fits, full-lane (>= 128 or
       all of C) if possible, power-of-two fallback otherwise.

    Returns None only when even ``(cb=1, cob=1, slab_h=1)`` exceeds the
    budget — with row slabs there is no resolution-driven ceiling anymore.
    """
    nb = dtype_bytes(dtype)
    halo = max(hf - stride, 0)
    # Co outermost so a single panel always wins over splitting Co; within a
    # panel choice, prefer a full-lane channel block (min_cb pass 1) over a
    # larger slab with degenerate lanes, then take anything that fits.
    for cob in co_candidates(co):
        for min_cb in (min(c, LANES), 1):
            for slab_h in slab_candidates(ho):
                cb = _fused_plan_at(ho, wo, c, slab_h, cob, hf, wf, stride,
                                    nb, residual, vmem_budget, min_cb)
                if cb is None:
                    continue
                n_slabs = -(-ho // slab_h)
                return BlockPlan(
                    block_c=cb, block_co=cob, slab_h=slab_h,
                    n_slabs=n_slabs,
                    halo_rows=halo if n_slabs > 1 else 0,
                    vmem_bytes=fused_vmem_bytes(
                        wo, slab_h, cb, cob, hf, wf, stride, nb, residual),
                    dtype_bytes=nb,
                )
    return None


# ---------------------------------------------------------------------------
# 3-stage fused chain (PW-expand -> DW -> PW-project): expand-on-the-fly
# ---------------------------------------------------------------------------

def fused3_vmem_bytes(wo: int, slab_h: int, ci: int, cb: int, cob: int,
                      hf: int = 3, wf: int = 3, stride: int = 1,
                      itemsize: int = 4, residual: bool = False) -> int:
    """Working-set bytes of the 3-stage fused kernel (expand-on-the-fly) at
    blocks ``(cb, cob, slab_h)`` with raw-input channels ``ci``.

    Relative to :func:`fused_vmem_bytes` the input slab is the RAW input at
    ``ci`` channels (fetched whole per grid cell — it is the expand GEMM's
    A-operand), and each expanded-channel slab adds the expand-weight tile
    ``(ci, cb)`` plus the fp32 expanded value ``(slab_hi, wiu, cb)`` that
    replaces the streamed input as the DW stage's operand.  Single source of
    truth for :func:`plan_separable3` and ``benchmarks/kernel_vmem.py``.
    """
    slab_hi = (slab_h - 1) * stride + hf
    wiu = (wo - 1) * stride + wf
    out_side = slab_h * wo * cob * (ACC_BYTES + itemsize)
    if residual:
        out_side += 2 * slab_h * wo * cob * itemsize
    out_side += 2 * slab_hi * wiu * ci * itemsize  # raw input, dbl-buffered
    per_c = (2 * ci * itemsize                 # expand W tile, dbl-buffered
             + slab_hi * wiu * ACC_BYTES       # expanded value (fp32, VMEM)
             + hf * wf * itemsize              # DW filter tile
             + slab_h * wo * ACC_BYTES         # DW intermediate (fp32 value)
             + 2 * cob * itemsize)             # PW weight tile, dbl-buffered
    return out_side + cb * per_c


def _fused3_plan_at(c: int, ci: int, slab_h: int, cob: int, wo: int,
                    hf: int, wf: int, stride: int, itemsize: int,
                    residual: bool, vmem_budget: int,
                    min_cb: int) -> Optional[int]:
    """Largest snapped expanded-channel block >= min_cb that fits, or None."""
    base = fused3_vmem_bytes(wo, slab_h, ci, 0, cob, hf, wf, stride,
                             itemsize, residual)
    per_c = fused3_vmem_bytes(wo, slab_h, ci, 1, cob, hf, wf, stride,
                              itemsize, residual) - base
    rem = vmem_budget - base
    if rem < per_c:
        return None
    cb = snap_channels(int(rem // per_c), c)
    return cb if cb >= min_cb else None


def plan_separable3_at(ho: int, wo: int, ci: int, c: int, co: int, *,
                       block_co: int, slab_h: int,
                       stride: int = 1, hf: int = 3, wf: int = 3,
                       dtype=jnp.float32,
                       vmem_budget: int = DEFAULT_VMEM_BUDGET,
                       residual: bool = False) -> Optional[BlockPlan]:
    """3-stage analogue of :func:`plan_separable_at`: feasibility probe for
    the expand-on-the-fly kernel at an explicit ``(block_co, slab_h)``."""
    nb = dtype_bytes(dtype)
    cb = _fused3_plan_at(c, ci, slab_h, block_co, wo, hf, wf, stride, nb,
                         residual, vmem_budget, 1)
    if cb is None:
        return None
    n_slabs = -(-ho // slab_h)
    return BlockPlan(
        block_c=cb, block_co=block_co, slab_h=slab_h, n_slabs=n_slabs,
        halo_rows=max(hf - stride, 0) if n_slabs > 1 else 0,
        vmem_bytes=fused3_vmem_bytes(wo, slab_h, ci, cb, block_co, hf, wf,
                                     stride, nb, residual),
        dtype_bytes=nb,
    )


def plan_separable3(ho: int, wo: int, ci: int, c: int, co: int, *,
                    stride: int = 1, hf: int = 3, wf: int = 3,
                    dtype=jnp.float32,
                    vmem_budget: int = DEFAULT_VMEM_BUDGET,
                    residual: bool = False) -> Optional[BlockPlan]:
    """Block plan for the 3-stage fused chain (expand -> DW -> project), or
    None when nothing fits (callers degrade to the 2-stage plan:
    standalone expand GEMM + :func:`plan_separable`, then to unfused).

    ``ci`` is the raw-input channel count, ``c`` the expanded (DW) width and
    ``co`` the projected output width.  Same preference order as
    :func:`plan_separable`: single Co panel > largest row slab > largest
    expanded-channel slab, full-lane if possible.  The expanded intermediate
    dominates the budget (fp32 ``(slab_hi, wiu, cb)`` per reduction step),
    so high resolutions slab earlier than the 2-stage kernel does.
    """
    nb = dtype_bytes(dtype)
    halo = max(hf - stride, 0)
    for cob in co_candidates(co):
        for min_cb in (min(c, LANES), 1):
            for slab_h in slab_candidates(ho):
                cb = _fused3_plan_at(c, ci, slab_h, cob, wo, hf, wf, stride,
                                     nb, residual, vmem_budget, min_cb)
                if cb is None:
                    continue
                n_slabs = -(-ho // slab_h)
                return BlockPlan(
                    block_c=cb, block_co=cob, slab_h=slab_h,
                    n_slabs=n_slabs,
                    halo_rows=halo if n_slabs > 1 else 0,
                    vmem_bytes=fused3_vmem_bytes(
                        wo, slab_h, ci, cb, cob, hf, wf, stride, nb,
                        residual),
                    dtype_bytes=nb,
                )
    return None


# ---------------------------------------------------------------------------
# fused MBConv (full conv -> act -> PW-project): conv-on-the-fly
# ---------------------------------------------------------------------------

def fused_mb_vmem_bytes(wo: int, slab_h: int, ci: int, cb: int, cob: int,
                        hf: int = 3, wf: int = 3, stride: int = 1,
                        itemsize: int = 4, residual: bool = False) -> int:
    """Working-set bytes of the fused-MBConv kernel (full ``hf x wf`` conv
    -> act -> PW-project in one pass) at blocks ``(cb, cob, slab_h)`` with
    raw-input channels ``ci``.

    Like :func:`fused3_vmem_bytes` the raw input window is fetched whole
    (all ``ci`` channels — it is every conv tap's A-operand), but there is
    no expanded-value slab: each reduction step computes the conv
    intermediate directly at ``(slab_h, wo, cb)`` and feeds it to the
    projection GEMM.  The conv filter tile is ``(hf, wf, ci, cb)`` — the
    dense filter replaces the depthwise one + expand weight.  Single source
    of truth for :func:`plan_fused_mb` and the static analyzer.
    """
    slab_hi = (slab_h - 1) * stride + hf
    wiu = (wo - 1) * stride + wf
    out_side = slab_h * wo * cob * (ACC_BYTES + itemsize)
    if residual:
        out_side += 2 * slab_h * wo * cob * itemsize
    out_side += 2 * slab_hi * wiu * ci * itemsize  # raw input, dbl-buffered
    per_c = (2 * hf * wf * ci * itemsize       # conv filter tile, dbl-buffered
             + slab_h * wo * ACC_BYTES         # conv intermediate (fp32 value)
             + 2 * cob * itemsize)             # PW weight tile, dbl-buffered
    return out_side + cb * per_c


def _fused_mb_plan_at(c: int, ci: int, slab_h: int, cob: int, wo: int,
                      hf: int, wf: int, stride: int, itemsize: int,
                      residual: bool, vmem_budget: int,
                      min_cb: int) -> Optional[int]:
    """Largest snapped conv-output channel block >= min_cb that fits."""
    base = fused_mb_vmem_bytes(wo, slab_h, ci, 0, cob, hf, wf, stride,
                               itemsize, residual)
    per_c = fused_mb_vmem_bytes(wo, slab_h, ci, 1, cob, hf, wf, stride,
                                itemsize, residual) - base
    rem = vmem_budget - base
    if rem < per_c:
        return None
    cb = snap_channels(int(rem // per_c), c)
    return cb if cb >= min_cb else None


def plan_fused_mb_at(ho: int, wo: int, ci: int, c: int, co: int, *,
                     block_co: int, slab_h: int,
                     stride: int = 1, hf: int = 3, wf: int = 3,
                     dtype=jnp.float32,
                     vmem_budget: int = DEFAULT_VMEM_BUDGET,
                     residual: bool = False) -> Optional[BlockPlan]:
    """Feasibility probe for the fused-MBConv kernel at an explicit
    ``(block_co, slab_h)`` — the autotuner's candidate constructor."""
    nb = dtype_bytes(dtype)
    cb = _fused_mb_plan_at(c, ci, slab_h, block_co, wo, hf, wf, stride, nb,
                           residual, vmem_budget, 1)
    if cb is None:
        return None
    n_slabs = -(-ho // slab_h)
    return BlockPlan(
        block_c=cb, block_co=block_co, slab_h=slab_h, n_slabs=n_slabs,
        halo_rows=max(hf - stride, 0) if n_slabs > 1 else 0,
        vmem_bytes=fused_mb_vmem_bytes(wo, slab_h, ci, cb, block_co, hf, wf,
                                       stride, nb, residual),
        dtype_bytes=nb,
    )


def plan_fused_mb(ho: int, wo: int, ci: int, c: int, co: int, *,
                  stride: int = 1, hf: int = 3, wf: int = 3,
                  dtype=jnp.float32,
                  vmem_budget: int = DEFAULT_VMEM_BUDGET,
                  residual: bool = False) -> Optional[BlockPlan]:
    """Block plan for the fused-MBConv pass (full conv -> act -> PW-project
    in ONE kernel), or None when nothing fits (callers degrade to a
    standalone XLA conv + standalone PW).  ``ci`` is the raw-input width,
    ``c`` the conv-output (expanded) width, ``co`` the projected width.
    Same preference order as :func:`plan_separable3`."""
    nb = dtype_bytes(dtype)
    halo = max(hf - stride, 0)
    for cob in co_candidates(co):
        for min_cb in (min(c, LANES), 1):
            for slab_h in slab_candidates(ho):
                cb = _fused_mb_plan_at(c, ci, slab_h, cob, wo, hf, wf,
                                       stride, nb, residual, vmem_budget,
                                       min_cb)
                if cb is None:
                    continue
                n_slabs = -(-ho // slab_h)
                return BlockPlan(
                    block_c=cb, block_co=cob, slab_h=slab_h,
                    n_slabs=n_slabs,
                    halo_rows=halo if n_slabs > 1 else 0,
                    vmem_bytes=fused_mb_vmem_bytes(
                        wo, slab_h, ci, cb, cob, hf, wf, stride, nb,
                        residual),
                    dtype_bytes=nb,
                )
    return None


def plan_mb(ho: int, wo: int, ci: int, c: int, hf: int = 3, wf: int = 3, *,
            stride: int = 1, dtype=jnp.float32,
            vmem_budget: int = DEFAULT_VMEM_BUDGET) -> BlockPlan:
    """Standalone dense-conv segment (the fused-MBConv degradation target).
    It lowers to the XLA convolution — the dense conv is MXU-shaped as-is;
    the Pallas win is fusing the projection — so the plan records geometry
    for traffic/telemetry and claims zero Pallas VMEM."""
    return BlockPlan(
        block_c=c, block_co=0, slab_h=ho, n_slabs=1, halo_rows=0,
        vmem_bytes=0, dtype_bytes=dtype_bytes(dtype),
    )


# ---------------------------------------------------------------------------
# squeeze-excite: DW + SE-epilogue fused pass, and the standalone two-GEMM
# ---------------------------------------------------------------------------

def dw_se_vmem_bytes(hiu: int, wiu: int, ho: int, wo: int, c: int,
                     c_se: int, hf: int = 3, wf: int = 3,
                     itemsize: int = 4) -> int:
    """Working set of the DW + SE-epilogue kernel.  The SE gate mixes ALL
    channels of the pooled DW output, so the pass requires full-channel,
    full-spatial residency: 2x input window + filter at all ``c`` channels,
    the fp32 DW accumulator + output tile, and the (tiny) gate weights."""
    return (c * (2 * hiu * wiu * itemsize + hf * wf * itemsize
                 + ho * wo * (ACC_BYTES + itemsize))
            + 4 * c * c_se * itemsize          # w1 + w2 tiles, dbl-buffered
            + 2 * (c_se + c) * itemsize)       # b1 + b2 vectors


def plan_dw_se(hiu: int, wiu: int, ho: int, wo: int, c: int, c_se: int,
               hf: int = 3, wf: int = 3, *,
               dtype=jnp.float32,
               vmem_budget: int = DEFAULT_VMEM_BUDGET
               ) -> Optional[BlockPlan]:
    """Plan for the fused DW + SE-epilogue pass, or None when the
    full-channel working set exceeds the budget (callers degrade to a
    standalone DW + a standalone SE two-GEMM pass).  Unlike the other fused
    planners there is no block ladder to walk: the squeeze FC needs the
    whole pooled channel vector, so partial-channel residency is not a
    degraded plan — it is a wrong one.  ``block_g`` carries ``c_se``."""
    nb = dtype_bytes(dtype)
    need = dw_se_vmem_bytes(hiu, wiu, ho, wo, c, c_se, hf, wf, nb)
    if need > vmem_budget:
        return None
    return BlockPlan(
        block_c=c, block_co=0, slab_h=ho, n_slabs=1, halo_rows=0,
        vmem_bytes=need, dtype_bytes=nb, block_g=c_se,
    )


def plan_se(b: int, c: int, c_se: int, *, dtype=jnp.float32,
            vmem_budget: int = DEFAULT_VMEM_BUDGET) -> BlockPlan:
    """Standalone squeeze-excite segment: global pool + two tiny GEMMs
    (reduce, expand) + sigmoid scale.  The GEMMs run through the pwconv
    kernel at its own planned blocks; the claim here is the larger of the
    two GEMM working sets.  ``block_g`` carries ``c_se``."""
    nb = dtype_bytes(dtype)
    p1 = plan_pwconv(b, c, c_se, dtype=dtype, vmem_budget=vmem_budget)
    p2 = plan_pwconv(b, c_se, c, dtype=dtype, vmem_budget=vmem_budget)
    return BlockPlan(
        block_c=c, block_co=0, slab_h=1, n_slabs=1, halo_rows=0,
        vmem_bytes=max(p1.vmem_bytes, p2.vmem_bytes),
        dtype_bytes=nb, block_g=c_se,
    )


# ---------------------------------------------------------------------------
# whole-chain plan schema (core/chain.plan -> kernels/lowering.lower)
# ---------------------------------------------------------------------------

#: Segment kinds a chain lowers to.  ``fused3`` = one kernel pass for
#: PW-expand -> DW -> PW-project (expand-on-the-fly); ``fused2`` = one pass
#: for DW -> PW (the PR-2 kernel); ``fusedmb`` = one pass for a full
#: ``hf x wf`` conv -> act -> PW-project (the fused-MBConv block);
#: ``dw_se`` = one pass for DW with the squeeze-excite gate applied as an
#: in-kernel epilogue; ``pw`` / ``dw`` = standalone kernels; ``se`` = the
#: standalone squeeze-excite two-GEMM pass; ``mb`` = a standalone dense
#: conv (XLA-lowered — the fused-MBConv degradation target).
SEGMENT_KINDS = ("fused3", "fused2", "fusedmb", "dw_se", "pw", "dw", "se",
                 "mb")

#: Segment kinds whose kernels take a residual operand (the chain residual
#: can fold into their final store).
FUSED_KINDS = ("fused3", "fused2", "fusedmb")


@dataclasses.dataclass(frozen=True)
class ChainSegment:
    """One lowering unit of a stage chain: which contiguous spec stages run
    as one kernel pass, and at which block shapes."""
    kind: str                      # one of SEGMENT_KINDS
    stages: tuple[int, ...]        # indices into the spec's stage tuple
    plan: BlockPlan                # block choices for this segment's kernel

    def __post_init__(self):
        assert self.kind in SEGMENT_KINDS, self.kind


@dataclasses.dataclass(frozen=True)
class ChainPlan:
    """The planner's answer for a whole declared stage chain (DESIGN.md §5).

    Produced by ``core/chain.plan`` and consumed by
    ``kernels/lowering.lower``; frozen + hashable so it is a cacheable,
    comparable unit (the key for measured autotuning later).

    ``residual``: the spec's residual connection is active at these shapes
    (stride product 1, c_out == c_in).  ``residual_fused``: it is folded
    into the final fused segment's kernel pass (otherwise the lowering adds
    it as a separate elementwise op).
    """
    segments: tuple[ChainSegment, ...]
    residual: bool
    residual_fused: bool
    dtype_bytes: int
    vmem_budget: int

    @property
    def n_kernel_passes(self) -> int:
        # a standalone SE segment runs two GEMM passes (reduce + expand);
        # a standalone "mb" conv lowers to XLA but still counts as one pass
        # of HBM round-trip; every other segment is one kernel pass.
        n = sum(2 if s.kind == "se" else 1 for s in self.segments)
        return n + (1 if self.residual and not self.residual_fused else 0)

    @property
    def n_pallas_calls(self) -> int:
        """The kernel passes that lower to a Pallas call: all but the
        XLA-lowered ones (a standalone ``mb`` conv, a standalone residual
        add) — what a compiled program counts as ``tpu_custom_call``s."""
        return sum({"se": 2, "mb": 0}.get(s.kind, 1) for s in self.segments)

    @property
    def fully_fused(self) -> bool:
        """The whole chain (incl. any residual) runs as ONE kernel pass."""
        return len(self.segments) == 1 and self.segments[0].kind in (
            FUSED_KINDS) and (self.residual_fused or not self.residual)


# ---------------------------------------------------------------------------
# pwconv (output-stationary GEMM)
# ---------------------------------------------------------------------------

def pwconv_vmem_bytes(bg: int, bci: int, bco: int, itemsize: int = 4) -> int:
    """Working set of the RTRD GEMM: fp32 accumulator + 2x double-buffered
    streamed A/B tiles at the activation width."""
    return bg * bco * ACC_BYTES + 2 * (bg * bci + bci * bco) * itemsize


#: G-panel ladder the GEMM planner walks (and the autotuner measures over).
PW_G_CANDIDATES = (1024, 512, 256, 128, 64, 32, 16, 8)


def plan_pwconv(g: int, ci: int, co: int, *,
                dtype=jnp.float32,
                vmem_budget: int = DEFAULT_VMEM_BUDGET) -> BlockPlan:
    """Grid plan for the pointwise GEMM (owns what used to be ``pwconv``'s
    hard-coded 256^3 defaults).  Co/Ci blocks stay MXU-aligned multiples of
    128; the G panel grows when the dtype is narrow (bf16 tiles cost half,
    so the same budget affords a 2x taller output panel)."""
    nb = dtype_bytes(dtype)
    bco = bci = 2 * LANES
    for bg in PW_G_CANDIDATES:
        if pwconv_vmem_bytes(bg, bci, bco, nb) <= vmem_budget:
            break
    return BlockPlan(
        block_c=bci, block_co=bco, slab_h=0, n_slabs=1, halo_rows=0,
        vmem_bytes=pwconv_vmem_bytes(bg, bci, bco, nb),
        dtype_bytes=nb, block_g=bg,
    )
