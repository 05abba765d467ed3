"""Output-stationary pointwise-conv / GEMM Pallas kernel (paper Alg. 6, RTRD).

The paper's PWConv contribution: make the GEMM kernel *output-stationary* —
the output tile ``D`` stays in fast storage across the entire reduction (Ci)
loop and is stored exactly once, instead of the BLAS/RTRA pattern where ``D``
round-trips per reduction block.

TPU adaptation (DESIGN.md §2): "registers" become a VMEM-resident fp32
accumulator tile. The Pallas grid is ``(G/Gb, Co/Cob, Ci/Cib)`` with the
reduction axis **innermost** and the output BlockSpec index map ignoring it,
so the accumulator tile is revisited across all Ci steps and written back to
HBM once — RTRD at the VMEM level. The RTRA pathology (reduction outermost)
would spill/refetch the accumulator tile to HBM ``Ci/Cib`` times.

Epilogue fusion (bias + activation) is a beyond-paper addition: it removes an
extra HBM round-trip of the output that a separate bias/act op would cost.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Shared bias+activation tail (kernels/epilogue.py) — the same jnp ops trace
# inside the kernel body; `_epilogue` stays as an alias for old call sites.
from repro.kernels.epilogue import apply_epilogue as _epilogue
from repro.kernels.gridspec import (BlockRef, KernelModel,
                                    compiler_params, in_specs_from_model,
                                    out_spec_from_model)
from repro.kernels.policy import contract_precision


def pw_clamp_blocks(g: int, ci: int, co: int, block_g: int, block_co: int,
                    block_ci: int) -> tuple[int, int, int]:
    """Clamp requested block sizes to the problem (never below the fp32
    (8, 128) tile) — the kernel and the analyzer apply the same rule."""
    bg = min(block_g, max(8, g))
    bco = min(block_co, max(128, co))
    bci = min(block_ci, max(128, ci))
    return bg, bco, bci


def pw_kernel_model(*, g: int, ci: int, co: int, bg: int, bci: int, bco: int,
                    has_bias: bool, itemsize: int,
                    out_itemsize: int) -> KernelModel:
    """The exact grid/BlockSpec geometry ``pwconv_pallas`` lowers to at the
    (already clamped) blocks — consumed by both the kernel and the static
    analyzer (DESIGN.md §8).  Shapes are the padded shapes handed to
    ``pl.pallas_call``."""
    gp = g + (-g) % bg
    cip = ci + (-ci) % bci
    cop = co + (-co) % bco
    inputs = [
        BlockRef("x", (gp, cip), (bg, bci),
                 lambda i, j, k: (i, k), itemsize),
        BlockRef("w", (cip, cop), (bci, bco),
                 lambda i, j, k: (k, j), itemsize),
    ]
    if has_bias:
        inputs.append(BlockRef("bias", (1, cop), (1, bco),
                               lambda i, j, k: (0, j), itemsize))
    return KernelModel(
        name="pwconv",
        grid=(gp // bg, cop // bco, cip // bci),
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        inputs=tuple(inputs),
        output=BlockRef("out", (gp, cop), (bg, bco),
                        lambda i, j, k: (i, j), out_itemsize),
        scratch_bytes=bg * bco * 4,                # fp32 accumulator
    )


def _rtrd_kernel(*refs, nk: int, activation, out_dtype):
    """Grid (g, j, k); k innermost. acc_ref: VMEM (Gb, Cob) fp32 scratch.

    refs = (x_ref, w_ref, [bias_ref,] out_ref, acc_ref).
    """
    if len(refs) == 5:
        x_ref, w_ref, bias_ref, out_ref, acc_ref = refs
    else:
        x_ref, w_ref, out_ref, acc_ref = refs
        bias_ref = None
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # The output tile (acc) stays resident; only A/B tiles stream. == RTRD.
    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32,
        precision=contract_precision(x_ref.dtype),
    )

    @pl.when(k == nk - 1)
    def _store():  # single store of the output tile (paper lines 29-34)
        acc = acc_ref[...]
        acc = _epilogue(acc, bias_ref[...] if bias_ref is not None else None,
                        activation)
        out_ref[...] = acc.astype(out_dtype)


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=(
        "activation", "block_g", "block_co", "block_ci", "interpret",
        "out_dtype",
    ),
)
def pwconv_pallas(
    x: jax.Array,
    w: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    activation: Optional[str] = None,
    block_g: int = 256,
    block_co: int = 256,
    block_ci: int = 256,
    interpret: bool = False,
    out_dtype: Optional[str] = None,
) -> jax.Array:
    """x: (G, Ci) @ w: (Ci, Co) [+ bias (Co,)] -> (G, Co), fp32 accumulate.

    Block sizes are multiples of the (8, 128) fp32 tile; defaults sized so
    x/w/acc tiles (3 * 256*256*4B = 768 KiB) leave VMEM room for
    double-buffering the streamed A/B tiles.

    ``out_dtype`` (dtype NAME, static): store width of the single output
    write — used by the mixed-precision chain lowering (DESIGN.md §7);
    ``None`` stores at ``x.dtype``.  Accumulation is fp32 either way.
    """
    g, ci = x.shape
    ci2, co = w.shape
    assert ci == ci2, (x.shape, w.shape)
    out_dtype = jnp.dtype(out_dtype) if out_dtype is not None else x.dtype

    bg, bco, bci = pw_clamp_blocks(g, ci, co, block_g, block_co, block_ci)

    xp = _pad_to(_pad_to(x, 0, bg), 1, bci)
    wp = _pad_to(_pad_to(w, 0, bci), 1, bco)
    gp, cip = xp.shape
    cop = wp.shape[1]
    nk = cip // bci

    # Grid and BlockSpecs come from the kernel model — the same object the
    # static analyzer (repro.analysis) checks (DESIGN.md §8).
    model = pw_kernel_model(
        g=g, ci=ci, co=co, bg=bg, bci=bci, bco=bco, has_bias=bias is not None,
        itemsize=x.dtype.itemsize, out_itemsize=out_dtype.itemsize,
    )
    inputs = [xp, wp]
    if bias is not None:
        inputs.append(_pad_to(bias.reshape(1, -1), 1, bco))
    for arr, br in zip(inputs, model.inputs):
        assert arr.shape == br.array_shape, (br.name, arr.shape,
                                             br.array_shape)

    kernel = functools.partial(
        _rtrd_kernel, nk=nk, activation=activation, out_dtype=out_dtype
    )
    out = pl.pallas_call(
        kernel,
        grid=model.grid,
        in_specs=in_specs_from_model(model),
        out_specs=out_spec_from_model(model),
        out_shape=jax.ShapeDtypeStruct(model.output.array_shape, out_dtype),
        scratch_shapes=[pltpu.VMEM((bg, bco), jnp.float32)],
        compiler_params=compiler_params(model),
        interpret=interpret,
        name="pwconv",
    )(*inputs)
    return out[:g, :co]
