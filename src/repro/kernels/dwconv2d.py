"""Depthwise 2-D convolution Pallas kernel (paper Alg. 4, TPU adaptation).

Paper mechanism → TPU mapping (DESIGN.md §2):

* channel-outermost parallel loop (``i'``)  → grid over channel blocks, with
  ``dimension_semantics="parallel"`` — each TensorCore owns a channel slab, so
  its filter working set is ``Hf·Wf·Cblk`` (the 1/p scalability argument).
* filter register tile pinned across all output blocks → the ``(Hf, Wf, Cblk)``
  filter tile is fetched to VMEM once per grid cell and reused for the whole
  spatial extent.
* output block loaded/stored once (Alg. 4 lines 14-19 / 29-34) → the output
  tile is accumulated in a VMEM fp32 buffer and written to HBM exactly once.
* the 4-channel NEON SIMD dimension → the 128-lane minor dimension (NHWC).

DWConv has no matmul structure, so this is a pure-VPU kernel: an unrolled
``Hf×Wf`` shift-and-FMA over the spatial extent, vectorized across lanes
(channels) and sublanes (rows). HBM traffic is the information floor: input
read once, filter once, output written once — AI = Hf·Wf/(1+1/…) FLOPs/byte,
the paper's T^DW bound with the block terms at their VMEM-scale limits.

Stride > 1 is handled with strided tap reads on the H/W (non-minor) dims,
from an fp32 lane-chunked stage of the input block (``kernels/taps.py``).
Padding is applied by the wrapper (ops.py) so the kernel sees VALID geometry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import blocking, taps
from repro.kernels.gridspec import (BlockRef, KernelModel,
                                    compiler_params, in_specs_from_model,
                                    out_spec_from_model)


def dw_kernel_model(*, b: int, hiu: int, wiu: int, ho: int, wo: int, c: int,
                    block_c: int, hf: int, wf: int, stride: int,
                    itemsize: int, out_itemsize: int) -> KernelModel:
    """The exact grid/BlockSpec geometry ``dwconv2d_pallas`` lowers to —
    consumed by both the kernel and the static analyzer (DESIGN.md §8).
    ``hiu``/``wiu`` are the input rows/cols actually consumed; shapes are
    the channel-padded shapes handed to ``pl.pallas_call``."""
    cb = block_c
    cp = c + (-c) % cb
    return KernelModel(
        name="dwconv2d",
        grid=(b, cp // cb),
        dimension_semantics=("parallel", "parallel"),
        inputs=(
            BlockRef("x", (b, hiu, wiu, cp), (1, hiu, wiu, cb),
                     lambda i, j: (i, 0, 0, j), itemsize),
            BlockRef("f", (hf, wf, cp), (hf, wf, cb),
                     lambda i, j: (0, 0, j), itemsize),
        ),
        output=BlockRef("out", (b, ho, wo, cp), (1, ho, wo, cb),
                        lambda i, j: (i, 0, 0, j), out_itemsize),
        scratch_bytes=taps.stage_bytes((hiu, wiu, cb), stride > 1),
        value_bytes=ho * wo * cb * 4,              # fp32 jnp accumulator
    )


def _dw2d_kernel(x_ref, f_ref, out_ref, *stage, hf: int, wf: int,
                 stride: int, out_dtype):
    """Blocks: x (1, Hi, Wi, Cb); f (Hf, Wf, Cb); out (1, Ho, Wo, Cb);
    ``stage``: x's fp32 lane-chunked copy when strided (``taps.py``)."""
    _, ho, wo, cb = out_ref.shape
    stage_ref = stage[0] if stage else None
    if stage_ref is not None:
        taps.stage_input(stage_ref, x_ref)
    f = f_ref[...].astype(jnp.float32)         # filter tile: VMEM-resident
    acc = jnp.zeros(out_ref.shape[1:], jnp.float32)
    for n in range(hf):                        # unrolled taps (Hf·Wf ≤ 25)
        for m in range(wf):
            win = taps.tap(x_ref, stage_ref, n, m, ho, wo, stride, cb)
            acc = acc + win * f[n, m][None, None, :]
    out_ref[0] = acc.astype(out_dtype)         # single store (lines 29-34)


@functools.partial(jax.jit, static_argnames=("stride", "interpret", "block_c",
                                             "vmem_budget", "out_dtype"))
def dwconv2d_pallas(
    x: jax.Array,
    f: jax.Array,
    *,
    stride: int = 1,
    block_c: int | None = None,
    vmem_budget: int = blocking.DEFAULT_VMEM_BUDGET,
    interpret: bool = False,
    out_dtype: str | None = None,
) -> jax.Array:
    """x: (B, Hi, Wi, C); f: (Hf, Wf, C) -> (B, Ho, Wo, C). VALID geometry.

    An explicit ``block_c`` (e.g. a ``ChainSegment.plan``'s or a measured
    autotuner winner's) is executed verbatim; ``None`` re-plans at
    ``vmem_budget``.  ``out_dtype`` (dtype NAME, static) selects the store
    width of the single output write (DESIGN.md §7); ``None`` stores at
    ``x.dtype``; accumulation is fp32 either way."""
    odt = jnp.dtype(out_dtype) if out_dtype is not None else x.dtype
    b, hi, wi, c = x.shape
    hf, wf, cf = f.shape
    assert c == cf, (x.shape, f.shape)
    ho = (hi - hf) // stride + 1
    wo = (wi - wf) // stride + 1
    assert ho >= 1 and wo >= 1, "input smaller than filter"

    if block_c is None:
        # dtype-aware channel-block plan (kernels/blocking.py owns the math)
        block_c = blocking.plan_dwconv2d(
            hi, wi, ho, wo, c, hf, wf, dtype=x.dtype,
            vmem_budget=vmem_budget).block_c
    cb = block_c
    pad = (-c) % cb
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, pad)))
        f = jnp.pad(f, ((0, 0), (0, 0), (0, pad)))
    cp = c + pad

    # Input rows/cols actually consumed (drop the VALID remainder so block
    # shapes match exactly).
    hiu = (ho - 1) * stride + hf
    wiu = (wo - 1) * stride + wf
    x = x[:, :hiu, :wiu, :]

    # Grid and BlockSpecs come from the kernel model — the same object the
    # static analyzer (repro.analysis) checks (DESIGN.md §8).
    model = dw_kernel_model(
        b=b, hiu=hiu, wiu=wiu, ho=ho, wo=wo, c=c, block_c=cb, hf=hf, wf=wf,
        stride=stride, itemsize=x.dtype.itemsize, out_itemsize=odt.itemsize,
    )
    for arr, br in zip((x, f), model.inputs):
        assert arr.shape == br.array_shape, (br.name, arr.shape,
                                             br.array_shape)

    kernel = functools.partial(
        _dw2d_kernel, hf=hf, wf=wf, stride=stride, out_dtype=odt
    )
    out = pl.pallas_call(
        kernel,
        grid=model.grid,
        in_specs=in_specs_from_model(model),
        out_specs=out_spec_from_model(model),
        out_shape=jax.ShapeDtypeStruct(model.output.array_shape, odt),
        scratch_shapes=taps.stage_shapes((hiu, wiu, cb), stride > 1),
        compiler_params=compiler_params(model),
        interpret=interpret,
    )(x, f)
    return out[..., :c]
