"""planlint — static verification of ChainPlans against the ACTUAL kernel
lowering geometry (DESIGN.md §8).

Parity tests catch wrong *values*; this pass catches infeasible or silently
degraded *plans* before anything runs — the class of planner<->lowering
drift PR 4 had to fix by hand.  Three layers of checks per segment:

1. **Plan-field checks** (PL101-PL113): the planner's own VMEM model
   recomputed at the plan's block fields must match ``BlockPlan.vmem_bytes``
   exactly (drift detection), stay within the policy budget, and every
   block field must be a value the §4 ladders can produce (snapped channel
   blocks, valid Co panels, consistent slab fields).
2. **Derived-VMEM check** (PL103): the working set re-derived from the
   BlockSpecs the lowering will emit — via the same ``*_kernel_model``
   builders the kernels construct their ``pl.BlockSpec``s from
   (``kernels/gridspec.py``) — must stay under the 16 MiB physical ceiling
   (error) and the soft planner budget (warning).  Because the kernels
   consume the identical model, this is not a parallel re-derivation.
3. **Grid enumeration** (PL120-PL123): statically enumerate the grid and
   evaluate every ``index_map`` to prove halo input windows stay in-bounds,
   output blocks cover every output tile exactly once (no gaps), tile
   disjointly across parallel grid coordinates (write-race detection), and
   the output map never depends on a reduction ("arbitrary") dimension —
   the RTRD accumulator contract.

Entry point: :func:`lint_chain`; :func:`chain_models` exposes the derived
``KernelModel``s for the mosaic pass; :func:`check_grid` is public so the
seeded-violation tests can corrupt a model directly.
"""
from __future__ import annotations

import itertools
from typing import List, Optional, Sequence, Tuple

from repro.analysis.diagnostics import (ERROR, INFO, WARNING, Diagnostic)
from repro.kernels import blocking
from repro.kernels.autotune import _SegGeom, _segment_geoms
from repro.kernels.blocking import BlockPlan, ChainPlan
from repro.kernels.dwconv2d import dw_kernel_model
from repro.kernels.fused_mbconv import fused_mb_kernel_model
from repro.kernels.gridspec import VMEM_HARD_BYTES, KernelModel
from repro.kernels.pwconv import pw_clamp_blocks, pw_kernel_model
from repro.kernels.se_epilogue import dw_se_kernel_model
from repro.kernels.separable_fused import fused_kernel_model

#: Segment kinds with no Pallas kernel of their own: ``se`` lowers to two
#: pwconv passes (linted as GEMMs at their own geometry would be, but
#: composed by the lowering) + XLA pool/scale; ``mb`` lowers to the XLA
#: convolution on every impl.  ``segment_kernel_model`` returns None for
#: these BY DESIGN — not plan corruption.
XLA_COMPOSED_KINDS = ("se", "mb")

#: Grid-cell ceiling for exhaustive enumeration; larger grids are checked at
#: per-dimension boundary samples (first/last/middle) and coverage checks
#: are skipped with an INFO diagnostic — never silently.
MAX_GRID_POINTS = 200_000


def walk_segments(spec, chain_plan: ChainPlan,
                  x_shape: Sequence[int]) -> List[_SegGeom]:
    """Per-segment kernel geometry — the same shape walk the autotuner's
    candidate enumeration uses (duck-typed on the stage objects)."""
    return _segment_geoms(spec.stages, chain_plan, x_shape)


def _geom_str(geom: _SegGeom) -> str:
    if geom.kind == "pw":
        return f"pw g={geom.g} ci={geom.ci} co={geom.co}"
    return (f"{geom.kind} ho={geom.ho} wo={geom.wo} ci={geom.ci} "
            f"c={geom.c} co={geom.co} stride={geom.stride} "
            f"hf={geom.hf}x{geom.wf}")


def segment_kernel_model(geom: _SegGeom, plan: BlockPlan,
                         b: int) -> Optional[KernelModel]:
    """The KernelModel this segment's kernel will lower to — built by the
    SAME ``*_kernel_model`` function the kernel itself consumes.  The
    output itemsize is taken at the stream width (``plan.dtype_bytes``);
    a wider final store only grows the output buffer, which PL103's hard
    ceiling still bounds via the fp32 accumulator/value terms.  Returns
    None for :data:`XLA_COMPOSED_KINDS` (no single Pallas kernel)."""
    nb = plan.dtype_bytes
    if geom.kind in XLA_COMPOSED_KINDS:
        return None
    if geom.kind == "fusedmb":
        return fused_mb_kernel_model(
            b=b, ho=geom.ho, wo=geom.wo, c_in=geom.ci, c=geom.c,
            co=geom.co, hf=geom.hf, wf=geom.wf, stride=geom.stride,
            block_c=plan.block_c, block_co=plan.block_co,
            slab_h=plan.slab_h, itemsize=nb, out_itemsize=nb,
            has_mb_bias=True, has_pw_bias=True,
            has_residual=geom.residual,
        )
    if geom.kind == "dw_se":
        hiu = (geom.ho - 1) * geom.stride + geom.hf
        wiu = (geom.wo - 1) * geom.stride + geom.wf
        return dw_se_kernel_model(
            b=b, hiu=hiu, wiu=wiu, ho=geom.ho, wo=geom.wo, c=geom.c,
            c_se=geom.g, hf=geom.hf, wf=geom.wf, stride=geom.stride,
            itemsize=nb, out_itemsize=nb, has_dw_bias=True,
        )
    if geom.kind in ("fused3", "fused2"):
        return fused_kernel_model(
            b=b, ho=geom.ho, wo=geom.wo, c_in=geom.ci, c=geom.c, co=geom.co,
            hf=geom.hf, wf=geom.wf, stride=geom.stride,
            block_c=plan.block_c, block_co=plan.block_co,
            slab_h=plan.slab_h, itemsize=nb, out_itemsize=nb,
            has_expand=geom.kind == "fused3", has_dw_bias=True,
            has_pw_bias=True, has_residual=geom.residual,
        )
    if geom.kind == "dw":
        hiu = (geom.ho - 1) * geom.stride + geom.hf
        wiu = (geom.wo - 1) * geom.stride + geom.wf
        return dw_kernel_model(
            b=b, hiu=hiu, wiu=wiu, ho=geom.ho, wo=geom.wo, c=geom.c,
            block_c=plan.block_c, hf=geom.hf, wf=geom.wf,
            stride=geom.stride, itemsize=nb, out_itemsize=nb,
        )
    assert geom.kind == "pw", geom.kind
    bg, bco, bci = pw_clamp_blocks(geom.g, geom.ci, geom.co,
                                   plan.block_g, plan.block_co, plan.block_c)
    return pw_kernel_model(
        g=geom.g, ci=geom.ci, co=geom.co, bg=bg, bci=bci, bco=bco,
        has_bias=True, itemsize=nb, out_itemsize=nb,
    )


# ---------------------------------------------------------------------------
# PL101-PL113: plan-field checks
# ---------------------------------------------------------------------------

def _claimed_vmem(geom: _SegGeom, plan: BlockPlan,
                  b: Optional[int] = None, budget: Optional[int] = None,
                  ) -> int:
    """The planner's own model recomputed at the plan's block fields."""
    nb = plan.dtype_bytes
    if geom.kind == "fused3":
        return blocking.fused3_vmem_bytes(
            geom.wo, plan.slab_h, geom.ci, plan.block_c, plan.block_co,
            geom.hf, geom.wf, geom.stride, nb, geom.residual)
    if geom.kind == "fused2":
        return blocking.fused_vmem_bytes(
            geom.wo, plan.slab_h, plan.block_c, plan.block_co,
            geom.hf, geom.wf, geom.stride, nb, geom.residual)
    if geom.kind == "fusedmb":
        return blocking.fused_mb_vmem_bytes(
            geom.wo, plan.slab_h, geom.ci, plan.block_c, plan.block_co,
            geom.hf, geom.wf, geom.stride, nb, geom.residual)
    if geom.kind == "dw_se":
        hiu = (geom.ho - 1) * geom.stride + geom.hf
        wiu = (geom.wo - 1) * geom.stride + geom.wf
        return blocking.dw_se_vmem_bytes(
            hiu, wiu, geom.ho, geom.wo, geom.c, geom.g,
            geom.hf, geom.wf, nb)
    if geom.kind == "mb":
        # lowers to the XLA convolution on every impl: no Pallas working
        # set to claim (plan_mb)
        return 0
    if geom.kind == "se":
        # the claim is the larger inner pwconv plan's working set; the
        # GEMM's G dimension is the BATCH, which the shape walk does not
        # carry — recompute only when the caller supplies it
        if b is None:
            return plan.vmem_bytes
        dtype = "bfloat16" if nb == 2 else "float32"
        kw = {} if budget is None else {"vmem_budget": budget}
        return blocking.plan_se(b, geom.c, geom.g, dtype=dtype,
                                **kw).vmem_bytes
    if geom.kind == "dw":
        hiu = (geom.ho - 1) * geom.stride + geom.hf
        wiu = (geom.wo - 1) * geom.stride + geom.wf
        return blocking.dwconv2d_vmem_bytes(
            hiu, wiu, geom.ho, geom.wo, plan.block_c, geom.hf, geom.wf, nb)
    return blocking.pwconv_vmem_bytes(
        plan.block_g, plan.block_c, plan.block_co, nb)


def lint_segment_fields(geom: _SegGeom, plan: BlockPlan, budget: int,
                        segment: str,
                        b: Optional[int] = None) -> List[Diagnostic]:
    """PL101/PL102 (VMEM claim), PL110-PL114 (block-field validity).
    ``b`` (the batch) tightens the PL102 recompute for ``se`` segments,
    whose GEMM rows are the batch dimension."""
    diags: List[Diagnostic] = []
    geo = _geom_str(geom)

    def err(rule, msg, hint=""):
        diags.append(Diagnostic(rule, ERROR, msg, segment, geo, hint))

    if geom.kind == "pw":
        # PL113: splitting G/Ci/Co at a boundary the (8, 128) tile cannot
        # express (the kernel clamps oversized blocks, so only
        # misaligned SPLITS are wrong, not large requests).
        bg, bco, bci = pw_clamp_blocks(geom.g, geom.ci, geom.co,
                                       plan.block_g, plan.block_co,
                                       plan.block_c)
        if bg <= 0 or bco <= 0 or bci <= 0:
            err("PL113", f"degenerate GEMM blocks (bg={bg}, bco={bco}, "
                f"bci={bci})", "use plan_pwconv / PW_G_CANDIDATES")
        else:
            if bg < geom.g and bg % 8:
                err("PL113", f"G panel {bg} splits g={geom.g} off the "
                    "8-sublane tile", "pick block_g from PW_G_CANDIDATES")
            if bci < geom.ci and bci % blocking.LANES:
                err("PL113", f"Ci block {bci} splits the reduction off the "
                    f"{blocking.LANES}-lane tile",
                    "use a multiple of 128 for block_ci")
            if bco < geom.co and bco % blocking.LANES:
                err("PL113", f"Co block {bco} splits co={geom.co} off the "
                    f"{blocking.LANES}-lane tile",
                    "use a multiple of 128 for block_co")
    elif geom.kind in XLA_COMPOSED_KINDS:
        # se / mb compose XLA (+pwconv) passes — no kernel blocks to
        # validate, but degenerate slab fields must still hold.
        if plan.n_slabs != 1 or plan.halo_rows != 0:
            err("PL112", f"{geom.kind} segment carries slab fields "
                f"(n_slabs={plan.n_slabs}, halo_rows={plan.halo_rows})",
                "XLA-composed segments have no spatial slab dimension")
    elif geom.kind == "dw_se":
        # PL114: the SE gate mixes ALL channels of a pool over ALL spatial
        # positions — partial residency is a WRONG answer, not a slower
        # one (kernels/se_epilogue.py residency contract).
        if plan.block_c != geom.c:
            err("PL114", f"block_c={plan.block_c} != C={geom.c} on a dw_se "
                "segment — the SE gate would be computed from a partial "
                "channel set",
                "dw_se requires full-channel residency; degrade to "
                "standalone dw + se instead of shrinking block_c")
        if plan.n_slabs != 1 or plan.halo_rows != 0 or plan.slab_h != geom.ho:
            err("PL114", f"spatial slabbing (slab_h={plan.slab_h}, "
                f"n_slabs={plan.n_slabs}, halo_rows={plan.halo_rows}) on a "
                "dw_se segment — the pooled mean would span one slab, not "
                "the image",
                "dw_se requires full-spatial residency (slab_h=ho, "
                "n_slabs=1); degrade to standalone dw + se")
        if plan.block_g != geom.g:
            err("PL114", f"block_g={plan.block_g} does not carry the SE "
                f"reduced width c_se={geom.g}",
                "dw_se plans store c_se in block_g (blocking.plan_dw_se)")
    else:
        # PL110: channel block must be a value snap_channels can produce.
        cb = plan.block_c
        if cb <= 0 or cb != blocking.snap_channels(cb, geom.c):
            err("PL110", f"block_c={cb} is not snapped for c={geom.c} "
                f"(want {blocking.snap_channels(max(cb, 1), geom.c)})",
                "channel blocks must be all-of-C, a multiple of 128, or a "
                "power of two (blocking.snap_channels)")
        if geom.kind in ("fused2", "fused3", "fusedmb"):
            # PL111: Co panel must come from the co_candidates ladder.
            if plan.block_co not in blocking.co_candidates(geom.co):
                err("PL111", f"block_co={plan.block_co} is not a valid Co "
                    f"panel for co={geom.co}",
                    "panels are all-of-Co, multiples of 128, or powers of "
                    "two (blocking.co_candidates)")
            # PL112: slab fields must be mutually consistent.
            sh = plan.slab_h
            if sh <= 0 or sh > geom.ho:
                err("PL112", f"slab_h={sh} outside [1, ho={geom.ho}]")
            else:
                n_slabs = -(-geom.ho // sh)
                if plan.n_slabs != n_slabs:
                    err("PL112", f"n_slabs={plan.n_slabs} but ceil(ho/"
                        f"slab_h)={n_slabs}")
                halo = max(geom.hf - geom.stride, 0) if n_slabs > 1 else 0
                if plan.halo_rows != halo:
                    err("PL112", f"halo_rows={plan.halo_rows}, expected "
                        f"{halo} (hf-stride at interior seams)")
        else:  # dw
            if plan.n_slabs != 1 or plan.halo_rows != 0:
                err("PL112", f"dw segment carries slab fields (n_slabs="
                    f"{plan.n_slabs}, halo_rows={plan.halo_rows})",
                    "dwconv2d has no spatial slab dimension")

    if not diags:
        # PL102 only when the fields themselves are coherent — recomputing
        # the model at corrupted fields would double-report.
        claimed = _claimed_vmem(geom, plan, b, budget)
        if plan.vmem_bytes != claimed:
            diags.append(Diagnostic(
                "PL102", ERROR,
                f"vmem_bytes={plan.vmem_bytes} but the planner model at "
                f"these blocks gives {claimed}", segment, geo,
                "the plan was hand-edited or the VMEM model changed under "
                "a persisted plan — re-plan or re-tune"))
    if plan.vmem_bytes > budget:
        diags.append(Diagnostic(
            "PL101", ERROR,
            f"claimed vmem_bytes={plan.vmem_bytes} exceeds the policy "
            f"budget {budget}", segment, geo,
            "shrink blocks (smaller slab_h / block_co) or raise "
            "policy.vmem_budget"))
    return diags


# ---------------------------------------------------------------------------
# PL103 + PL120-PL123: derived VMEM and grid enumeration
# ---------------------------------------------------------------------------

def check_vmem_derived(model: KernelModel, budget: int,
                       segment: str = "", geometry: str = "",
                       ) -> List[Diagnostic]:
    """PL103: the working set derived from the actual BlockSpecs (every
    streamed operand double-buffered + output + scratch + in-kernel values)
    against the 16 MiB physical ceiling (error) and the soft budget
    (warning — the derived count adds double-buffering terms the planner's
    model intentionally amortizes, so near-budget plans are legal)."""
    derived = model.vmem_bytes()
    if derived > VMEM_HARD_BYTES:
        return [Diagnostic(
            "PL103", ERROR,
            f"derived working set {derived} B exceeds physical VMEM "
            f"({VMEM_HARD_BYTES} B)", segment, geometry,
            "this plan cannot lower on real hardware — shrink blocks")]
    if derived > budget:
        return [Diagnostic(
            "PL103", WARNING,
            f"derived working set {derived} B exceeds the soft budget "
            f"{budget} B (physical ceiling ok)", segment, geometry,
            "Mosaic headroom is reduced; consider smaller blocks")]
    return []


def _grid_samples(grid: Tuple[int, ...]):
    """Full enumeration when affordable, else per-dim boundary samples."""
    total = 1
    for g in grid:
        total *= g
    if total <= MAX_GRID_POINTS:
        return itertools.product(*(range(g) for g in grid)), True
    dims = []
    for g in grid:
        pts = {0, g - 1, g // 2, min(1, g - 1), max(g - 2, 0)}
        dims.append(sorted(p for p in pts if 0 <= p < g))
    return itertools.product(*dims), False


def check_grid(model: KernelModel, *, segment: str = "",
               geometry: str = "") -> List[Diagnostic]:
    """PL120-PL123 by static grid enumeration.

    For every (sampled) grid point, every input ``index_map`` is evaluated:
    block-mode maps return block indices (in-bounds iff
    ``(idx+1)*block <= array``), element-offset maps return ELEMENT
    offsets (in-bounds iff ``offset + block <= array``) — this is what
    proves the overlapping halo windows never read past the padded input.
    The output map must tile the output exactly: every output block
    covered (PL121), no two distinct parallel coordinates writing the same
    block (PL122 — a write race), and no dependence on reduction
    dimensions (PL123 — the accumulator contract).
    """
    diags: List[Diagnostic] = []
    geometry = geometry or f"grid={model.grid}"
    points, full = _grid_samples(model.grid)
    if not full:
        diags.append(Diagnostic(
            "PL121", INFO,
            f"grid {model.grid} too large for exhaustive coverage check; "
            "bounds checked at boundary samples only", segment, geometry))
    red_dims = [i for i, s in enumerate(model.dimension_semantics)
                if s == "arbitrary"]

    out = model.output
    out_blocks = tuple(-(-a // blk) for a, blk
                       in zip(out.array_shape, out.block_shape))
    seen: dict = {}
    oob_reported = set()
    overlap = gap_possible = red_dep = False
    for idx in points:
        for br in model.inputs:
            if br.name in oob_reported:
                continue
            pos = br.index_map(*idx)
            for d, (p, blk, arr) in enumerate(zip(pos, br.block_shape,
                                                  br.array_shape)):
                start = p if br.unblocked else p * blk
                if start < 0 or start + blk > arr:
                    diags.append(Diagnostic(
                        "PL120", ERROR,
                        f"input '{br.name}' window out of bounds at grid "
                        f"{idx}: dim {d} reads [{start}, {start + blk}) of "
                        f"array extent {arr}", segment, geometry,
                        "the index_map or the operand padding is wrong"))
                    oob_reported.add(br.name)
                    break
        opos = out.index_map(*idx)
        for d, (p, blk, arr) in enumerate(zip(opos, out.block_shape,
                                              out.array_shape)):
            if p < 0 or p * blk + blk > arr:
                if "out" not in oob_reported:
                    diags.append(Diagnostic(
                        "PL120", ERROR,
                        f"output block out of bounds at grid {idx}: dim "
                        f"{d} writes block {p} of {arr // blk}",
                        segment, geometry))
                    oob_reported.add("out")
        par = tuple(v for i, v in enumerate(idx) if i not in red_dims)
        prev = seen.get(opos)
        if prev is None:
            seen[opos] = par
        elif prev != par:
            if not overlap:
                diags.append(Diagnostic(
                    "PL122", ERROR,
                    f"output block {opos} written by distinct parallel "
                    f"coordinates {prev} and {par} — a write race",
                    segment, geometry,
                    "output blocks must tile disjointly across parallel "
                    "grid dimensions"))
                overlap = True
        # PL123: reduction-dim dependence — vary each reduction dim by one.
        if not red_dep:
            for rd in red_dims:
                if idx[rd] + 1 < model.grid[rd]:
                    bumped = tuple(v + 1 if i == rd else v
                                   for i, v in enumerate(idx))
                    if out.index_map(*bumped) != opos:
                        diags.append(Diagnostic(
                            "PL123", ERROR,
                            f"output index map depends on reduction dim "
                            f"{rd}: grid {idx} -> {opos} but {bumped} -> "
                            f"{out.index_map(*bumped)}", segment, geometry,
                            "the accumulator tile must be revisited across "
                            "the whole reduction (RTRD)"))
                        red_dep = True
                    break
    if full:
        n_out = 1
        for nb_ in out_blocks:
            n_out *= nb_
        if len(seen) < n_out and not gap_possible:
            missing = next(
                idx for idx in itertools.product(*(range(nb_)
                                                   for nb_ in out_blocks))
                if idx not in seen)
            diags.append(Diagnostic(
                "PL121", ERROR,
                f"output coverage gap: block {missing} of {out_blocks} is "
                "never written", segment, geometry,
                "the grid does not tile the output — check n_slabs / "
                "panel counts"))
    return diags


# ---------------------------------------------------------------------------
# lint_chain: the whole pass over one planned chain
# ---------------------------------------------------------------------------

def chain_models(spec, chain_plan: ChainPlan, x_shape: Sequence[int],
                 ) -> List[Tuple[str, _SegGeom, Optional[KernelModel]]]:
    """(segment label, geometry, derived KernelModel) per segment; the model
    is None when the plan's fields are too corrupted to derive one — or,
    for :data:`XLA_COMPOSED_KINDS` (se, mb), by design."""
    b = int(x_shape[0])
    out = []
    for si, (geom, seg) in enumerate(zip(
            walk_segments(spec, chain_plan, x_shape), chain_plan.segments)):
        label = f"seg{si}/{seg.kind}"
        try:
            model = segment_kernel_model(geom, seg.plan, b)
        except (AssertionError, ArithmeticError, ValueError):
            model = None
        out.append((label, geom, model))
    return out


def lint_chain(spec, chain_plan: ChainPlan, x_shape: Sequence[int], *,
               label: str = "chain") -> List[Diagnostic]:
    """The full planlint pass: field checks, derived VMEM, grid proofs."""
    diags: List[Diagnostic] = []
    budget = chain_plan.vmem_budget
    b = int(x_shape[0])
    for (seg_label, geom, model), seg in zip(
            chain_models(spec, chain_plan, x_shape), chain_plan.segments):
        segment = f"{label}/{seg_label}"
        field_diags = lint_segment_fields(geom, seg.plan, budget, segment,
                                          b=b)
        diags.extend(field_diags)
        if any(d.severity == ERROR for d in field_diags):
            continue  # grid checks on corrupted fields would only cascade
        if model is None:
            if geom.kind not in XLA_COMPOSED_KINDS:
                diags.append(Diagnostic(
                    "PL112", ERROR,
                    "cannot derive the kernel geometry from this plan",
                    segment, _geom_str(geom)))
            continue
        diags.extend(check_vmem_derived(model, budget, segment,
                                        _geom_str(geom)))
        diags.extend(check_grid(model, segment=segment,
                                geometry=_geom_str(geom)))
    return diags
