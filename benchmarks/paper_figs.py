"""Paper figures 4-7: per-layer DWConv/PWConv benchmarks + core scaling.

This container has no ARM core and no TPU, so each figure has two honest
components:

1. **measured**   — CPU wall-time of the *runnable* implementations: the
   XLA-compiled reference ops (the framework's CPU execution path), with the
   unoptimized 5-loop Algorithm-1 oracle timed on the smallest layer to
   anchor the "Unoptimized" point of the paper's Fig. 1.
2. **modeled**    — the paper's own analytical machinery (core/intensity.py):
   per-layer arithmetic intensity of TF-Lite's loop structure vs ours
   (DWConv: T_tf vs eq. 1; PWConv: RTRA vs RTRD), and the TPU-v5e roofline
   time of each variant's HBM traffic. The modeled speedup column is the
   reproduction of the paper's figure bars; the paper's measured ARM
   speedups (2.9-9x over TF-Lite, up to 5.5x over TVM for DWConv) are
   quoted alongside for validation.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.layers import SEP_SUITES, SUITES, sep_geometry
from repro.core import intensity as it
from repro.kernels import blocking, ref

# v5e single-chip constants: bf16 peak FLOP/s and HBM bytes/s
PEAK = 197e12
HBM = 819e9
# quad-core Cortex-A57 (paper fig. 1): ~32 GFLOP/s fp32 peak, ~25.6 GB/s LPDDR4
ARM_PEAK = 32e9
ARM_BW = 25.6e9


def _time_jit(fn, *args, reps=5, measure=True) -> float:
    """Wall-time ``fn`` in us; with ``measure=False`` (the --dry-run path)
    skip compilation + timing entirely and report 0.0 — the analytical
    columns are the dry-run deliverable."""
    if not measure:
        return 0.0
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6  # us


def bench_dw_layer(layer, rng, measure=True) -> dict:
    us = 0.0
    if measure:   # dry-run needs only shapes — never materialize inputs
        x = jnp.asarray(rng.normal(size=(1, layer.h, layer.w, layer.c))
                        .astype(np.float32))
        f = jnp.asarray(rng.normal(size=(layer.hf, layer.hf, layer.c))
                        .astype(np.float32))
        xla = jax.jit(lambda x, f: ref.dwconv2d_ref(
            x, f, stride=layer.stride, padding="valid"))
        us = _time_jit(xla, x, f)

    # paper-model AI + roofline times (per-variant HBM traffic)
    ours = it.dwconv2d_traffic(1, layer.h, layer.w, layer.c, layer.hf,
                               layer.hf, layer.stride)
    tf4 = it.dwconv2d_traffic_rowpar(1, layer.h, layer.w, layer.c, layer.hf,
                                     layer.hf, layer.stride, p=4)
    t_ours = max(ours.time_s(PEAK, HBM))
    t_tf = max(tf4.time_s(PEAK, HBM))
    ai_ours = it.t_ours_dw_asymptotic(layer.hf, layer.hf)
    ai_tf = it.t_tf_dw(4)
    return {
        "name": layer.name,
        "us_xla_cpu": us,
        "ai_ours": ai_ours,
        "ai_tflite": ai_tf,
        "ai_ratio": ai_ours / ai_tf,
        "bytes_ours": ours.bytes_hbm,
        "bytes_rowpar4": tf4.bytes_hbm,
        "modeled_speedup": t_tf / t_ours,
    }


def bench_pw_layer(layer, rng, measure=True) -> dict:
    g = layer.h * layer.w
    us = us_rtra = 0.0
    if measure:   # dry-run needs only shapes — never materialize inputs
        a = jnp.asarray(rng.normal(size=(g, layer.c_in)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(layer.c_in, layer.c_out))
                        .astype(np.float32))
        xla = jax.jit(lambda a, b: ref.pwconv_ref(a, b))
        us = _time_jit(xla, a, b)
        rtra_fn = jax.jit(lambda a, b: ref.matmul_rtra_ref(a, b, block_k=128))
        us_rtra = _time_jit(rtra_fn, a, b)

    # (3) model AI/roofline at the planner's blocks — the grid every default
    # ops.pwconv call actually runs at (keeps this table consistent with
    # benchmarks/kernel_vmem.py)
    pw_plan = blocking.plan_pwconv(g, layer.c_in, layer.c_out)
    bg, bco, bci = pw_plan.block_g, pw_plan.block_co, pw_plan.block_c
    rtrd = it.pwconv_traffic_rtrd(g, layer.c_in, layer.c_out, bg, bci, bco)
    rtra = it.pwconv_traffic_rtra(g, layer.c_in, layer.c_out, bg, bci, bco)
    t_rtrd = max(rtrd.time_s(PEAK, HBM))
    t_rtra = max(rtra.time_s(PEAK, HBM))
    return {
        "name": layer.name,
        "us_xla_cpu": us,
        "us_rtra_loop_cpu": us_rtra,
        "ai_rtrd": it.t_rtrd_pw(ci=layer.c_in),
        "ai_rtra": it.t_rtra_pw(co=layer.c_out),
        "bytes_rtrd": rtrd.bytes_hbm,
        "bytes_rtra": rtra.bytes_hbm,
        "modeled_speedup": t_rtra / t_rtrd,
    }


def bench_separable_block(blk, rng, measure=True) -> dict:
    """Fused vs unfused separable block: measured CPU wall-time of both XLA
    paths, plus the modeled HBM traffic of the two kernel strategies — the
    'saved' column is the DW intermediate round-trip (DESIGN.md §3)."""
    us_unfused = us_fused = 0.0
    if measure:   # dry-run needs only shapes — never materialize inputs
        x = jnp.asarray(rng.normal(size=(1, blk.h, blk.w, blk.c_in))
                        .astype(np.float32))
        f = jnp.asarray(rng.normal(size=(blk.hf, blk.hf, blk.c_in))
                        .astype(np.float32) / blk.hf)
        w = jnp.asarray(rng.normal(size=(blk.c_in, blk.c_out))
                        .astype(np.float32) * blk.c_in ** -0.5)
        db = jnp.zeros((blk.c_in,), jnp.float32)
        pb = jnp.zeros((blk.c_out,), jnp.float32)

        def unfused(x, f, w, db, pb):
            y = ref.dwconv2d_ref(x, f, stride=blk.stride, padding="same")
            y = jnp.clip(y + db, 0.0, 6.0)
            return ref.pwconv_ref(y, w, bias=pb, activation="relu6")

        def fused(x, f, w, db, pb):
            return ref.separable_fused_ref(
                x, f, w, db, pb, stride=blk.stride, padding="same",
                dw_activation="relu6", activation="relu6")

        us_unfused = _time_jit(jax.jit(unfused), x, f, w, db, pb)
        us_fused = _time_jit(jax.jit(fused), x, f, w, db, pb)

    # modeled traffic at the fused kernel's chooser-picked blocks, on the
    # SAME-padded (VALID-equivalent) geometry the kernels actually see
    s = blk.stride
    hi, wi, ho, wo = sep_geometry(blk)
    plan = blocking.plan_separable(ho, wo, blk.c_in, blk.c_out, stride=s,
                                   hf=blk.hf, wf=blk.hf)
    bco_fused = plan.block_co if plan else blk.c_out
    unf = it.separable_traffic_unfused(
        1, hi, wi, blk.c_in, blk.c_out, blk.hf, blk.hf, s)
    fus = it.separable_traffic_fused(
        1, hi, wi, blk.c_in, blk.c_out, blk.hf, blk.hf, s,
        block_co=bco_fused, slab_h=plan.slab_h if plan else None)
    t_unf = max(unf.time_s(PEAK, HBM))
    t_fus = max(fus.time_s(PEAK, HBM))
    return {
        "name": blk.name,
        "us_unfused_xla_cpu": us_unfused,
        "us_fused_xla_cpu": us_fused,
        "bytes_unfused": unf.bytes_hbm,
        "bytes_fused": fus.bytes_hbm,
        "bytes_saved": unf.bytes_hbm - fus.bytes_hbm,
        "bytes_intermediate": it.separable_intermediate_bytes(
            1, hi, wi, blk.c_in, blk.c_out, blk.hf, blk.hf, s),
        "fusible": plan is not None,
        "block_co": bco_fused,
        "slab_h": plan.slab_h if plan else 0,
        "n_slabs": plan.n_slabs if plan else 0,
        "ai_unfused": unf.intensity,
        "ai_fused": fus.intensity,
        "modeled_speedup": t_unf / t_fus,
    }


def fig_unoptimized_anchor(measure=True) -> dict:
    """Paper Fig. 1 'Unoptimized' point: Algorithm-1 naive loops vs XLA,
    on a small layer (numpy loops are too slow for the big ones)."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(1, 16, 16, 32)).astype(np.float32)
    f = rng.normal(size=(3, 3, 32)).astype(np.float32)
    if not measure:
        return {"name": "unoptimized-anchor-16x16x32",
                "us_naive_loops": 0.0, "us_xla_cpu": 0.0, "speedup": 0.0}
    t0 = time.perf_counter()
    ref.dwconv2d_loops_ref(x, f, stride=1)
    t_naive = time.perf_counter() - t0
    xj, fj = jnp.asarray(x), jnp.asarray(f)
    fn = jax.jit(lambda x, f: ref.dwconv2d_ref(x, f, padding="valid"))
    us = _time_jit(fn, xj, fj)
    return {"name": "unoptimized-anchor-16x16x32",
            "us_naive_loops": t_naive * 1e6,
            "us_xla_cpu": us,
            "speedup": t_naive * 1e6 / us}


def fig7_scalability() -> list[dict]:
    """Fig. 7: modeled core scaling — ours (channel-parallel) vs TF-Lite-
    style (row-parallel) on MobileNetV1 D3 (56x56x128) under the paper's
    L1-thrash model; per-core compute + shared-bandwidth roofline."""
    rows = []
    layer = dict(b=1, hi=56, wi=56, c=128, hf=3, wf=3, stride=1)
    ours1 = it.dwconv2d_traffic(**{k: v for k, v in layer.items()})
    for p in (1, 2, 4):
        t_ours = max(ours1.flops / (ARM_PEAK * p / 4),
                     ours1.bytes_hbm / ARM_BW)
        tf = it.dwconv2d_traffic_rowpar(
            layer["b"], layer["hi"], layer["wi"], layer["c"], layer["hf"],
            layer["wf"], layer["stride"], p=p)
        t_tf = max(tf.flops / (ARM_PEAK * p / 4), tf.bytes_hbm / ARM_BW)
        base_ours = max(ours1.flops / (ARM_PEAK / 4),
                        ours1.bytes_hbm / ARM_BW)
        tf1 = it.dwconv2d_traffic_rowpar(
            layer["b"], layer["hi"], layer["wi"], layer["c"], layer["hf"],
            layer["wf"], layer["stride"], p=1)
        base_tf = max(tf1.flops / (ARM_PEAK / 4), tf1.bytes_hbm / ARM_BW)
        rows.append({
            "threads": p,
            "speedup_ours": base_ours / t_ours,
            "speedup_rowpar": base_tf / t_tf,
        })
    return rows


def run_all(quick: bool = False, dry_run: bool = False):
    """All figure/table rows. ``dry_run`` keeps every analytical column
    (traffic, AI, roofline, planner blocks) but skips compilation and wall-
    clock timing — the CI traffic-model regression gate runs this mode. The
    hires sep suite is only *timed* under --full (its XLA CPU reference
    passes are minutes-slow); its model rows are always present."""
    rng = np.random.default_rng(0)
    measure = not dry_run
    results = {}
    for suite, (dws, pws) in SUITES.items():
        if quick:
            dws, pws = dws[:3], pws[:3]
        results[suite] = {
            "dw": [bench_dw_layer(l, rng, measure=measure) for l in dws],
            "pw": [bench_pw_layer(l, rng, measure=measure) for l in pws],
        }
    for suite, blks in SEP_SUITES.items():
        if quick:
            blks = blks[:3]
        m = measure and (suite != "hires" or not quick)
        results.setdefault(suite, {})["sep"] = [
            bench_separable_block(b, rng, measure=m) for b in blks]
    results["fig1_anchor"] = fig_unoptimized_anchor(measure=measure)
    results["fig7"] = fig7_scalability()
    return results
