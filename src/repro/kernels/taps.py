"""Tap-window reads shared by the four depthwise-family kernels.

Every DW-style kernel (``dwconv2d``, ``separable_fused``, ``se_epilogue``,
``fused_mbconv``) runs the same unrolled ``Hf x Wf`` tap loop: tap
``(n, m)`` reads the input rows ``n, n+s, ...`` and columns ``m, m+s, ...``
of its VMEM window and upcasts them to fp32.

Mosaic constrains how such a window may be taken:

* a strided slice of a VALUE is refused (``vector.extract_strided_slice``
  takes unit strides only), so a strided tap is a strided READ of a ref;
* strided loads exist for 32-bit data only, and only from a memref whose
  minor dimension is at most one 128-lane vreg wide.

So a unit-stride tap reads the ``(1, rows, cols, lanes)`` input block
directly, and a strided one reads an fp32 **stage**: a VMEM scratch of shape
``(ceil(lanes/128), rows, cols, min(lanes, 128))`` that holds the window
split into lane chunks, written once per grid cell.  The staged values are
exactly the fp32 upcast the taps would read, so the arithmetic — and the
interpret-mode output — does not depend on which path a kernel takes.

SAME padding made in VMEM (the fused separable kernel, DESIGN.md §3) stages
the window at any stride: the kernel writes its unpadded input into the
stage at the pad offset and zeroes the halo around it (:func:`zero_halo`),
so out-of-image taps read exactly the zeros that padding in HBM put there.
A unit-stride tap of a staged window reads the stage too: unaligned fp32
rows, which ran faster on v5e than the packed bf16 input block (PERF.md).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _chunked(window) -> tuple:
    rows, cols, lanes = window
    width = min(lanes, LANES)
    return (-(-lanes // width), rows, cols, width)


def stage_shapes(window, staged: bool) -> list:
    """Scratch shapes of the stage for a ``(rows, cols, lanes)`` window;
    empty when the taps read the input block directly."""
    return [pltpu.VMEM(_chunked(window), jnp.float32)] if staged else []


def stage_bytes(window, staged: bool) -> int:
    """VMEM bytes of :func:`stage_shapes`, for the kernel models."""
    if not staged:
        return 0
    n, rows, cols, width = _chunked(window)
    return 4 * n * rows * cols * width


def _chunks(stage_ref, lanes: int):
    width = stage_ref.shape[-1]
    for j in range(stage_ref.shape[0]):
        yield j, j * width, min(width, lanes - j * width)


def stage(stage_ref, value, row=slice(None), col: int = 0) -> None:
    """Write an fp32 ``(rows, cols, lanes)`` value — or, given a ``row``
    index, one ``(cols, lanes)`` row — into the lane chunks, from column
    ``col`` (the tail chunk's lanes past ``lanes`` are left unwritten and
    never leave :func:`tap`)."""
    cols = pl.ds(col, value.shape[-2])
    for j, lo, w in _chunks(stage_ref, value.shape[-1]):
        stage_ref[j, row, cols, :w] = value[..., lo:lo + w]


def stage_input(stage_ref, x_ref, top: int = 0, left: int = 0) -> None:
    """:func:`stage` for the ``(1, rows, cols, lanes)`` input block, read
    and upcast one lane chunk at a time, at row ``top`` and column
    ``left`` of the stage."""
    _, rows, cols, lanes = x_ref.shape
    rs, cs = pl.ds(top, rows), pl.ds(left, cols)
    for j, lo, w in _chunks(stage_ref, lanes):
        stage_ref[j, rs, cs, :w] = x_ref[0, :, :, lo:lo + w].astype(
            jnp.float32)


def zero_halo(stage_ref, pads, rows: int, cols: int) -> None:
    """Zero the SAME halo ``pads`` ``((top, bottom), (left, right))``
    around the ``rows x cols`` input written at ``(top, left)``: every
    stage row above and below it, and the columns beside it."""
    (top, bottom), (left, right) = pads
    n, hp, wp, width = stage_ref.shape
    for r0, nr in ((0, top), (top + rows, bottom)):
        if nr:
            stage_ref[:, pl.ds(r0, nr)] = jnp.zeros((n, nr, wp, width),
                                                    jnp.float32)
    for c0, nc in ((0, left), (left + cols, right)):
        if nc:
            stage_ref[:, :, pl.ds(c0, nc)] = jnp.zeros((n, hp, nc, width),
                                                       jnp.float32)


def tap(x_ref, stage_ref, n: int, m: int, rows: int, cols: int,
        stride: int, lanes: int):
    """The fp32 ``(rows, cols, lanes)`` window of tap ``(n, m)``: from the
    stage when there is one, else straight from the input block."""
    if stage_ref is None:
        assert stride == 1, "strided taps read the stage"
        return x_ref[0, pl.ds(n, rows), pl.ds(m, cols), :].astype(
            jnp.float32)
    rs, cs = pl.ds(n, rows, stride=stride), pl.ds(m, cols, stride=stride)
    pieces = [stage_ref[j, rs, cs, :] for j in range(stage_ref.shape[0])]
    win = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, -1)
    return win if win.shape[-1] == lanes else win[..., :lanes]
