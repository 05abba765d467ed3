"""The separable-block fusion accounting table (fused vs unfused HBM bytes,
with the removed DW-intermediate term broken out — DESIGN.md §3) and the
per-block ChainPlan table of whole MobileNetV2 inverted residuals."""
from __future__ import annotations

from repro.core import intensity as it


def separable_fusion_rows(dtype=None) -> list[dict]:
    """Per-block HBM accounting: unfused = fused + intermediate round-trip
    - halo re-reads.

    ``intermediate_mb`` is the term the fused kernel removes (the DW output's
    HBM store + per-Co-panel loads) and ``halo_mb`` the (much smaller) term
    row-slab blocking adds back at slab seams; fused bytes must be strictly
    lower for every block the planner can fuse — including the hires suite,
    which was fallback-only before slabs (asserted by tests/test_intensity.py).
    """
    try:
        from benchmarks.layers import SEP_SUITES, sep_geometry
    except ModuleNotFoundError:  # run as `python benchmarks/roofline_table.py`
        from layers import SEP_SUITES, sep_geometry
    from repro.kernels import blocking

    import jax.numpy as jnp
    dtype = dtype or jnp.float32
    nb = blocking.dtype_bytes(dtype)
    rows = []
    for suite, blks in SEP_SUITES.items():
        for blk in blks:
            s = blk.stride
            hi, wi, ho, wo = sep_geometry(blk)
            plan = blocking.plan_separable(
                ho, wo, blk.c_in, blk.c_out, stride=s, hf=blk.hf,
                wf=blk.hf, dtype=dtype)
            bco = plan.block_co if plan else blk.c_out
            slab_h = plan.slab_h if plan else None
            unf = it.separable_traffic_unfused(
                1, hi, wi, blk.c_in, blk.c_out, blk.hf, blk.hf, s,
                dtype_bytes=nb)
            fus = it.separable_traffic_fused(
                1, hi, wi, blk.c_in, blk.c_out, blk.hf, blk.hf, s,
                block_co=bco, slab_h=slab_h, dtype_bytes=nb)
            inter = it.separable_intermediate_bytes(
                1, hi, wi, blk.c_in, blk.c_out, blk.hf, blk.hf, s,
                dtype_bytes=nb)
            halo = it.separable_slab_halo_bytes(
                1, wi, blk.c_in, blk.hf, s, plan.n_slabs if plan else 1,
                -(-blk.c_out // bco), dtype_bytes=nb)
            rows.append({
                "suite": suite,
                "name": blk.name,
                "fusible": plan is not None,
                "blocks": (f"c{plan.block_c}xco{plan.block_co}"
                           f"xs{plan.slab_h}" if plan else "-"),
                "n_slabs": plan.n_slabs if plan else 0,
                "unfused_mb": unf.bytes_hbm / 1e6,
                "fused_mb": fus.bytes_hbm / 1e6,
                "intermediate_mb": inter / 1e6,
                "halo_mb": halo / 1e6,
                "saved_mb": (unf.bytes_hbm - fus.bytes_hbm) / 1e6,
                "ai_unfused": unf.intensity,
                "ai_fused": fus.intensity,
            })
    return rows


def chain_fusion_rows(dtype=None) -> list[dict]:
    """Per-block ChainPlan traffic table for whole MobileNetV2 inverted
    residuals: what the chain planner actually lowers (its ChainPlan and
    the modeled HBM bytes) vs the PR-2 two-stage lowering (standalone
    expansion GEMM + fused DW->PW) vs fully unfused.  The CI dry-run gate
    asserts every block plans to a single fused3 pass with strictly
    decreasing bytes across the three strategies (DESIGN.md §5)."""
    try:
        from benchmarks.layers import MOBILENET_V2_IR
    except ModuleNotFoundError:  # run as `python benchmarks/roofline_table.py`
        from layers import MOBILENET_V2_IR

    import jax.numpy as jnp
    from repro.core import chain
    from repro.kernels import blocking

    dtype = dtype or jnp.float32
    nb = blocking.dtype_bytes(dtype)
    rows = []
    for blk in MOBILENET_V2_IR:
        spec = chain.inverted_residual_spec(
            blk.c_in, blk.c_out, expand=blk.expand, stride=blk.stride,
            hf=blk.hf)
        shape = (1, blk.h, blk.h, blk.c_in)
        cp = chain.plan(spec, shape, dtype=dtype)
        t_chain = chain.chain_traffic(spec, cp, shape)
        ho = -(-blk.h // blk.stride)
        p2 = blocking.plan_separable(ho, ho, blk.c_mid, blk.c_out,
                                     stride=blk.stride, hf=blk.hf,
                                     wf=blk.hf, dtype=dtype,
                                     residual=cp.residual)
        t_2stage = it.separable_traffic_2stage(
            1, blk.h, blk.h, blk.c_in, blk.c_mid, blk.c_out, blk.hf,
            blk.hf, blk.stride, block_co=p2.block_co if p2 else None,
            slab_h=p2.slab_h if p2 else None, dtype_bytes=nb)
        t_unf = it.separable_traffic_unfused3(
            1, blk.h, blk.h, blk.c_in, blk.c_mid, blk.c_out, blk.hf,
            blk.hf, blk.stride, dtype_bytes=nb)
        mb_2stage = t_2stage.bytes_hbm
        mb_unf = t_unf.bytes_hbm
        if cp.residual:
            # keep the comparison symmetric with chain_traffic's residual
            # terms: the 2-stage lowering folds the residual into its fused
            # tail (one streamed read); the unfused one pays a separate
            # elementwise add (read y, read res, write sum)
            mb_2stage += nb * blk.h * blk.h * blk.c_out
            mb_unf += nb * 3 * blk.h * blk.h * blk.c_out
        seg = cp.segments[0]
        rows.append({
            "name": blk.name,
            "plan": "+".join(s.kind for s in cp.segments),
            "single_pass": cp.fully_fused,
            "residual": cp.residual,
            "blocks": (f"c{seg.plan.block_c}xco{seg.plan.block_co}"
                       f"xs{seg.plan.slab_h}"),
            "mb_3stage": t_chain.bytes_hbm / 1e6,
            "mb_2stage": mb_2stage / 1e6,
            "mb_unfused": mb_unf / 1e6,
            "saved_vs_2stage_mb": (mb_2stage - t_chain.bytes_hbm) / 1e6,
            "ai_3stage": t_chain.intensity,
            "ai_2stage": t_2stage.flops / max(mb_2stage, 1.0),
        })
    return rows


def chain_fusion_markdown() -> str:
    lines = [
        "| block | plan | single pass | blocks | 3-stage HBM (MB) | "
        "2-stage HBM (MB) | unfused HBM (MB) | saved vs 2-stage (MB) | "
        "AI 3-stage | AI 2-stage |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in chain_fusion_rows():
        lines.append(
            f"| {r['name']} | {r['plan']} | {r['single_pass']} | "
            f"{r['blocks']} | {r['mb_3stage']:.2f} | {r['mb_2stage']:.2f} | "
            f"{r['mb_unfused']:.2f} | {r['saved_vs_2stage_mb']:.2f} | "
            f"{r['ai_3stage']:.2f} | {r['ai_2stage']:.2f} |")
    return "\n".join(lines)


def separable_fusion_markdown() -> str:
    lines = [
        "| block | fused blocks | slabs | unfused HBM (MB) | fused HBM (MB) |"
        " intermediate term (MB) | halo term (MB) | saved (MB) | AI unfused |"
        " AI fused |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in separable_fusion_rows():
        lines.append(
            f"| {r['suite']}/{r['name']} | {r['blocks']} | {r['n_slabs']} | "
            f"{r['unfused_mb']:.2f} | {r['fused_mb']:.2f} | "
            f"{r['intermediate_mb']:.2f} | {r['halo_mb']:.2f} | "
            f"{r['saved_mb']:.2f} | "
            f"{r['ai_unfused']:.2f} | {r['ai_fused']:.2f} |")
    return "\n".join(lines)


if __name__ == "__main__":
    print(separable_fusion_markdown())
    print()
    print(chain_fusion_markdown())
