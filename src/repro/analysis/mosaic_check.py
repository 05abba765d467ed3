"""mosaic_check — TPU tiling lint over derived KernelModels (DESIGN.md §8).

Mosaic lays VMEM out in (sublane, lane) tiles: the minor dimension in
128-lane vectors, the second-minor in sublanes whose count depends on the
element width (fp32 8, bf16 16, int8/fp8 32).  Interpret-mode parity tests
(the whole test suite on CPU) cannot see these constraints; the v5e compile
guard (``tests/test_tpu_compile.py``) can, at the cost of a compile.  This
pass encodes the statically checkable half as lint rules:

* MC201 (warning) — a block's minor dimension is not a multiple of 128
  lanes (legal, but pads every vector: lane utilization cost).
* MC202 (info) — second-minor dimension off the sublane count for the
  element width (Mosaic pads; cheap but worth seeing).
* MC203 (warning) — an in-kernel collapsing reshape
  (``(Sh, Wo, Cb) -> (Sh·Wo, Cb)``) whose collapsed second-minor is not
  sublane-aligned, or that changes the minor dimension — the shapes Mosaic
  may refuse or spill on.
* MC204 (error) — an element-offset window (``BlockRef.unblocked``) whose
  offset in a TILED dimension is not PROVABLY a multiple of the tile (128
  lanes minor, the sublane count second-minor).  Mosaic proves this over
  the index map with the grid indices unknown and refuses the kernel when
  it cannot ("Couldn't prove that tiling (128) divides index"): ``k * 32``
  is refused even when ``k`` only ever takes the value 0.
* MC205 (error) — an "arbitrary" (reduction) grid dimension that is not
  innermost: the revisiting-accumulator pattern every kernel here relies
  on requires reduction dims after all parallel dims.

The dtype->sublane table is :data:`SUBLANES`; rules receive the SAME
``KernelModel`` the kernels build their BlockSpecs from.
"""
from __future__ import annotations

import math
from typing import List, Sequence, Tuple

from repro.analysis.diagnostics import (ERROR, INFO, WARNING, Diagnostic)
from repro.kernels.gridspec import BlockRef, KernelModel

#: itemsize (bytes) -> minimum sublane count of the second-minor dimension.
SUBLANES = {4: 8, 2: 16, 1: 32}

LANES = 128


def _sublanes(itemsize: int) -> int:
    return SUBLANES.get(itemsize, 8)


def _check_block_alignment(br: BlockRef, segment: str) -> List[Diagnostic]:
    """MC201/MC202 for one operand's block shape."""
    diags: List[Diagnostic] = []
    shape = br.block_shape
    geo = f"{br.name} block={shape}"
    if len(shape) >= 1 and shape[-1] % LANES:
        sev = WARNING if shape[-1] != br.array_shape[-1] else INFO
        diags.append(Diagnostic(
            "MC201", sev,
            f"minor dim {shape[-1]} is not a multiple of {LANES} lanes",
            segment, geo,
            "lane utilization drops; prefer 128-multiples (or all of the "
            "dim when it is small)"))
    sub = _sublanes(br.itemsize)
    if len(shape) >= 2 and shape[-2] % sub:
        diags.append(Diagnostic(
            "MC202", INFO,
            f"second-minor dim {shape[-2]} off the {sub}-sublane tile for "
            f"{br.itemsize}-byte elements", segment, geo))
    return diags


def check_reshapes(reshapes: Sequence[Tuple[Tuple[int, ...],
                                            Tuple[int, ...]]],
                   itemsize: int, segment: str = "") -> List[Diagnostic]:
    """MC203 over the in-kernel reshape list a model records."""
    diags: List[Diagnostic] = []
    sub = _sublanes(itemsize)
    for src, dst in reshapes:
        geo = f"reshape {src} -> {dst}"
        if src[-1] != dst[-1]:
            diags.append(Diagnostic(
                "MC203", WARNING,
                "reshape changes the minor (lane) dimension — Mosaic "
                "lowers this as a relayout", segment, geo,
                "keep the channel dim minor through in-kernel reshapes"))
        elif len(dst) < len(src) and src[-2] % sub:
            diags.append(Diagnostic(
                "MC203", WARNING,
                f"sublane-collapsing reshape with second-minor {src[-2]} "
                f"off the {sub}-sublane tile", segment, geo,
                "Mosaic may refuse or pad the collapse; pick Wo-aligned "
                "blocks or validate on hardware"))
    return diags


class _GridIndex:
    """A grid index as Mosaic sees it when it checks a window offset: an
    unknown integer.  Arithmetic on it tracks the largest constant known to
    divide the expression, which is all the proof can use."""

    def __init__(self, divisor: int = 1):
        self.divisor = divisor

    def __add__(self, other):
        return _GridIndex(math.gcd(self.divisor, _divisor(other)))

    __radd__ = __add__

    def __mul__(self, other):
        if not isinstance(other, _GridIndex) and other == 0:
            return 0
        return _GridIndex(self.divisor * _divisor(other))

    __rmul__ = __mul__


def _divisor(v) -> int:
    """Largest constant known to divide ``v`` (0 for a literal zero)."""
    return v.divisor if isinstance(v, _GridIndex) else abs(int(v))


def check_unblocked(model: KernelModel, segment: str = "",
                    ) -> List[Diagnostic]:
    """MC204 for every element-offset operand: evaluate the index map over
    unknown grid indices and require each tiled-dim offset to be provably
    a multiple of its tile, as Mosaic does before it accepts the window."""
    diags: List[Diagnostic] = []
    for br in model.inputs:
        if not br.unblocked:
            continue
        pos = br.index_map(*(_GridIndex() for _ in model.grid))
        tiles = (("minor", LANES), ("second-minor", _sublanes(br.itemsize)))
        for off, (dim, tile) in zip(reversed(pos), tiles):
            if _divisor(off) % tile:
                diags.append(Diagnostic(
                    "MC204", ERROR,
                    f"element offset in the {dim} dim is not provably a "
                    f"multiple of its {tile}-element tile", segment,
                    f"{br.name} block={br.block_shape}",
                    "Mosaic refuses the window; make the offset a literal "
                    "0 or a tile multiple of the grid index"))
    return diags


def check_semantics(model: KernelModel, segment: str = "",
                    ) -> List[Diagnostic]:
    """MC205: reduction ("arbitrary") dims must be innermost."""
    sem = model.dimension_semantics
    seen_arbitrary = False
    for s in sem:
        if s == "arbitrary":
            seen_arbitrary = True
        elif seen_arbitrary:
            return [Diagnostic(
                "MC205", ERROR,
                f"dimension_semantics {sem} has a parallel dim after an "
                "arbitrary (reduction) dim", segment, f"grid={model.grid}",
                "the VMEM accumulator is only revisited when reduction "
                "dims are innermost (RTRD)")]
    return []


def lint_model(model: KernelModel, segment: str = "") -> List[Diagnostic]:
    """All mosaic rules over one derived kernel model."""
    diags: List[Diagnostic] = []
    for br in list(model.inputs) + [model.output]:
        diags.extend(_check_block_alignment(br, segment))
    diags.extend(check_reshapes(model.reshapes, model.inputs[0].itemsize,
                                segment))
    diags.extend(check_unblocked(model, segment))
    diags.extend(check_semantics(model, segment))
    return diags
