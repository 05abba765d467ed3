"""Declarative grid/BlockSpec geometry shared by the kernels and the static
analyzer (DESIGN.md §8).

Every Pallas kernel in this package lowers to a grid plus a set of
BlockSpecs.  Before this module those were built inline inside each
``pl.pallas_call`` call site, which meant the planner (``blocking.py``), the
lowering and any analysis each re-derived the same padding / index-map
arithmetic — exactly the planner<->lowering drift PR 4 had to fix by hand.

Now each kernel module exposes a pure ``*_kernel_model(...)`` builder that
returns a :class:`KernelModel`: the grid, the dimension semantics, and one
:class:`BlockRef` per operand (padded array shape, block shape, index map,
indexing mode).  The kernel constructs its actual ``pl.BlockSpec``s FROM the
model (:func:`in_specs_from_model` / :func:`out_spec_from_model`), and
``repro.analysis`` statically checks the SAME model — so what the verifier
proves (VMEM residency, halo in-bounds, disjoint output tiling, lane/sublane
alignment) is what the hardware will execute, not a parallel re-derivation.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Tuple

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Ceiling the derived working set must never exceed (the planner budgets
#: 12 MiB of this); it is Mosaic's default scoped-VMEM limit.
VMEM_HARD_BYTES = 16 * 1024 * 1024

#: Scoped-VMEM limit every kernel compiles under.  The derived working set
#: counts operands, scratch and the named fp32 values, but Mosaic also
#: spills the in-kernel temporaries (per-tap windows, the pre-stage
#: expanded value) to VMEM; at 112x112 those pushed V2's first stride-2
#: inverted residual past the 16 MiB default on v5e.  A v5e TensorCore has
#: 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class BlockRef:
    """One operand's block geometry: the (padded) array the kernel is passed,
    the VMEM block shape, and the grid -> block index map.

    ``unblocked`` marks element-offset indexing (every block dim a
    ``pl.Element``) — the index map then returns ELEMENT offsets, not block
    indices (the fused kernels' overlapping halo windows).  ``streamed``
    operands are pipelined HBM<->VMEM by Mosaic and therefore
    double-buffered in the VMEM accounting.
    """
    name: str
    array_shape: Tuple[int, ...]
    block_shape: Tuple[int, ...]
    index_map: Callable[..., Tuple[int, ...]]
    itemsize: int
    unblocked: bool = False
    streamed: bool = True

    @property
    def block_elems(self) -> int:
        return math.prod(self.block_shape)

    @property
    def block_bytes(self) -> int:
        return self.block_elems * self.itemsize

    def buffer_bytes(self) -> int:
        """VMEM footprint of this operand: 2x when pipelined/double-buffered."""
        return (2 if self.streamed else 1) * self.block_bytes


@dataclasses.dataclass(frozen=True)
class KernelModel:
    """A kernel invocation's complete lowering geometry — what
    ``pl.pallas_call`` will be handed, in checkable form.

    ``scratch_bytes`` covers explicit VMEM scratch allocations (fp32
    accumulators, the tap stage of ``kernels/taps.py``); ``value_bytes``
    the persistent in-kernel fp32 values the planner budgets (the DW or
    conv intermediate) that are neither operands nor scratch.  ``reshapes`` records in-kernel reshape shapes for
    the Mosaic sublane-collapse lint (``analysis/mosaic_check.py``).
    ``reduction_dims`` are the "arbitrary" dims that reduce into a
    revisited output block; None means every arbitrary dim.  An arbitrary
    dim left out of it carries VMEM state from one step to the next and
    writes its own output block at each.
    """
    name: str
    grid: Tuple[int, ...]
    dimension_semantics: Tuple[str, ...]
    inputs: Tuple[BlockRef, ...]
    output: BlockRef
    scratch_bytes: int = 0
    value_bytes: int = 0
    reshapes: Tuple[Tuple[Tuple[int, ...], Tuple[int, ...]], ...] = ()
    reduction_dims: Optional[Tuple[int, ...]] = None

    @property
    def reductions(self) -> Tuple[int, ...]:
        if self.reduction_dims is not None:
            return self.reduction_dims
        return tuple(i for i, s in enumerate(self.dimension_semantics)
                     if s == "arbitrary")

    @property
    def grid_points(self) -> int:
        return math.prod(self.grid)

    def vmem_bytes(self) -> int:
        """Derived VMEM working set of one grid cell: every streamed operand
        double-buffered, plus the output buffer, scratch and in-kernel
        values."""
        return (sum(br.buffer_bytes() for br in self.inputs)
                + self.output.buffer_bytes()
                + self.scratch_bytes + self.value_bytes)


def in_specs_from_model(model: KernelModel) -> list:
    """The ``pl.BlockSpec`` list the kernel passes as ``in_specs``."""
    specs = []
    for br in model.inputs:
        if br.unblocked:
            # JAX refuses a mix: every dim of an element-offset window is
            # an Element, the batch dim included
            shape = tuple(pl.Element(d) for d in br.block_shape)
            specs.append(pl.BlockSpec(shape, br.index_map))
        elif br.streamed:
            specs.append(pl.BlockSpec(br.block_shape, br.index_map))
        else:
            specs.append(pl.BlockSpec(br.block_shape, br.index_map,
                                      pipeline_mode=pl.Buffered(1)))
    return specs


def out_spec_from_model(model: KernelModel) -> pl.BlockSpec:
    return pl.BlockSpec(model.output.block_shape, model.output.index_map)


def compiler_params(model: KernelModel) -> pltpu.CompilerParams:
    """The Mosaic parameters the kernel passes as ``compiler_params``."""
    return pltpu.CompilerParams(
        dimension_semantics=model.dimension_semantics,
        vmem_limit_bytes=VMEM_LIMIT_BYTES)
