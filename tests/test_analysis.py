"""Seeded-violation suite for the static plan/kernel verifier
(repro.analysis, DESIGN.md §8).

Every rule in the catalog gets at least one POSITIVE test (a deliberately
corrupted plan / model / jaxpr that must fire exactly that rule) and at
least one NEGATIVE test (the clean equivalent must not fire it) — the
analyzer is only trustworthy if it both catches seeded bugs and stays
silent on the real plans the planner emits.  Also covers the integration
hooks: the ``KernelPolicy(verify=True)`` knob, tune-cache drop-and-warn,
and network-cache entry validation.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro import analysis
from repro.analysis import jaxpr_audit, mosaic_check, planlint
from repro.analysis.diagnostics import ERROR, INFO, WARNING, Report
from repro.core import chain, network
from repro.kernels import autotune, blocking
from repro.kernels.gridspec import BlockRef, KernelModel
from repro.kernels.policy import KernelPolicy

PAL = KernelPolicy(impl="pallas", interpret=True)

#: Small geometries keep interpret-mode planning/tracing fast.
SEP_SHAPE = (1, 16, 16, 32)      # fused2: DW(32) -> PW(64)
IR_SHAPE = (1, 14, 14, 16)       # fused3: PW(64) -> DW -> PW(16) + residual
PW_SHAPE = (1, 8, 8, 256)        # standalone pointwise GEMM


def _sep():
    return chain.separable_block_spec(64, stride=1)


def _ir():
    return chain.inverted_residual_spec(16, 16, expand=4, stride=1)


def _pw_only():
    return chain.SeparableSpec(stages=(chain.PW(128, bias=True),))


def _with_plan(cp, si, **kw):
    """A copy of ``cp`` with segment ``si``'s BlockPlan fields replaced."""
    seg = cp.segments[si]
    new = dataclasses.replace(seg, plan=dataclasses.replace(seg.plan, **kw))
    return dataclasses.replace(
        cp, segments=cp.segments[:si] + (new,) + cp.segments[si + 1:])


def _rules(diags, severity=ERROR):
    return sorted({d.rule for d in diags if d.severity == severity})


# ---------------------------------------------------------------------------
# planlint PL101-PL113: plan-field checks
# ---------------------------------------------------------------------------

def test_clean_plans_have_no_errors():
    """Negative for every PL rule at once: the analytic planner's own
    answers must lint clean (fused2, fused3-with-residual, pw)."""
    for spec, shape in ((_sep(), SEP_SHAPE), (_ir(), IR_SHAPE),
                        (_pw_only(), PW_SHAPE)):
        cp = chain.plan(spec, shape)
        diags = planlint.lint_chain(spec, cp, shape)
        assert _rules(diags) == [], [d.format() for d in diags]


def test_pl101_claimed_vmem_over_budget():
    spec, shape = _sep(), SEP_SHAPE
    cp = chain.plan(spec, shape)
    assert cp.segments[0].plan.vmem_bytes > 1024
    bad = dataclasses.replace(cp, vmem_budget=1024)
    assert "PL101" in _rules(planlint.lint_chain(spec, bad, shape))
    assert "PL101" not in _rules(planlint.lint_chain(spec, cp, shape))


def test_pl102_vmem_claim_drift():
    spec, shape = _sep(), SEP_SHAPE
    cp = chain.plan(spec, shape)
    bad = _with_plan(cp, 0, vmem_bytes=123)
    rules = _rules(planlint.lint_chain(spec, bad, shape))
    assert rules == ["PL102"]  # coherent fields -> exactly the drift rule


def test_pl110_unsnapped_channel_block():
    spec, shape = _sep(), SEP_SHAPE
    cp = chain.plan(spec, shape)
    bad = _with_plan(cp, 0, block_c=100)  # snap_channels(100, 32) == 32
    assert "PL110" in _rules(planlint.lint_chain(spec, bad, shape))
    zero = _with_plan(cp, 0, block_c=0)
    assert "PL110" in _rules(planlint.lint_chain(spec, zero, shape))


def test_pl111_invalid_co_panel():
    spec, shape = _sep(), SEP_SHAPE
    cp = chain.plan(spec, shape)
    assert 100 not in blocking.co_candidates(64)
    bad = _with_plan(cp, 0, block_co=100)
    assert "PL111" in _rules(planlint.lint_chain(spec, bad, shape))


def test_pl112_inconsistent_slab_fields():
    spec, shape = _sep(), SEP_SHAPE
    cp = chain.plan(spec, shape)
    plan = cp.segments[0].plan
    bad = _with_plan(cp, 0, n_slabs=plan.n_slabs + 1)
    assert "PL112" in _rules(planlint.lint_chain(spec, bad, shape))
    overslab = _with_plan(cp, 0, slab_h=10_000)
    assert "PL112" in _rules(planlint.lint_chain(spec, overslab, shape))
    wrong_halo = _with_plan(cp, 0, slab_h=4, n_slabs=4, halo_rows=7)
    assert "PL112" in _rules(planlint.lint_chain(spec, wrong_halo, shape))


def test_pl113_misaligned_gemm_split():
    spec, shape = _pw_only(), PW_SHAPE
    cp = chain.plan(spec, shape)
    assert cp.segments[0].kind == "pw"
    # bci=100 splits the ci=256 reduction off the 128-lane tile
    bad = _with_plan(cp, 0, block_c=100)
    assert "PL113" in _rules(planlint.lint_chain(spec, bad, shape))
    degenerate = _with_plan(cp, 0, block_g=-8)
    assert "PL113" in _rules(planlint.lint_chain(spec, degenerate, shape))


# ---------------------------------------------------------------------------
# planlint PL103: derived VMEM vs ceiling/budget
# ---------------------------------------------------------------------------

def _dw_model(c=32, block_c=32, ho=8):
    from repro.kernels.dwconv2d import dw_kernel_model
    return dw_kernel_model(b=1, hiu=ho + 2, wiu=ho + 2, ho=ho, wo=ho, c=c,
                           block_c=block_c, hf=3, wf=3, stride=1,
                           itemsize=4, out_itemsize=4)


def test_pl103_derived_vmem():
    small = _dw_model()
    assert planlint.check_vmem_derived(small,
                                       blocking.DEFAULT_VMEM_BUDGET) == []
    # 258x258x1024 fp32 double-buffered blows the 16 MiB physical ceiling
    huge = _dw_model(c=1024, block_c=1024, ho=256)
    diags = planlint.check_vmem_derived(huge, blocking.DEFAULT_VMEM_BUDGET)
    assert _rules(diags) == ["PL103"]
    # between soft budget and ceiling -> warning only
    mid = _dw_model(c=256, block_c=256, ho=50)
    assert blocking.DEFAULT_VMEM_BUDGET < mid.vmem_bytes() <= 16 * 2 ** 20
    diags = planlint.check_vmem_derived(mid, blocking.DEFAULT_VMEM_BUDGET)
    assert _rules(diags) == [] and _rules(diags, WARNING) == ["PL103"]


# ---------------------------------------------------------------------------
# planlint PL120-PL123: grid enumeration on a toy model
# ---------------------------------------------------------------------------

def _toy(out_map=lambda i, k: (i, 0), in_map=lambda i, k: (i, k),
         out_shape=((32, 8), (8, 8)), grid=(4, 2),
         sem=("parallel", "arbitrary")):
    x = BlockRef("x", (32, 16), (8, 8), in_map, 4)
    out = BlockRef("out", out_shape[0], out_shape[1], out_map, 4)
    return KernelModel("toy", grid, sem, (x,), out)


def test_grid_clean_toy_model():
    assert _rules(planlint.check_grid(_toy())) == []


def test_pl120_input_window_oob():
    bad = _toy(in_map=lambda i, k: (i + 1, k))  # last row block over-reads
    assert _rules(planlint.check_grid(bad)) == ["PL120"]


def test_pl120_unblocked_offset_oob():
    x = BlockRef("x", (33, 16), (9, 8), lambda i: (i * 8, 0), 4,
                 unblocked=True)
    out = BlockRef("out", (32, 16), (8, 16), lambda i: (i, 0), 4)
    clean = KernelModel("halo", (4,), ("parallel",), (x,), out)
    assert _rules(planlint.check_grid(clean)) == []
    # shift every halo window 2 rows down: the last reads [26, 35) of 33
    shifted = dataclasses.replace(
        clean, inputs=(dataclasses.replace(x, index_map=lambda i:
                                           (i * 8 + 2, 0)),))
    assert _rules(planlint.check_grid(shifted)) == ["PL120"]


def test_pl121_coverage_gap():
    bad = _toy(out_map=lambda i, k: (0, 0))  # every slab writes block 0
    rules = _rules(planlint.check_grid(bad))
    assert "PL121" in rules      # blocks (1..3, 0) never written
    assert "PL122" in rules      # and all parallel coords race on (0, 0)


def test_pl122_write_race_without_gap():
    # two parallel coords per output block, but full coverage
    bad = _toy(out_map=lambda i, k: (i // 2, 0), out_shape=((16, 8), (8, 8)))
    assert _rules(planlint.check_grid(bad)) == ["PL122"]


def test_pl123_output_depends_on_reduction_dim():
    bad = _toy(out_map=lambda i, k: (i, k), out_shape=((32, 16), (8, 8)))
    assert "PL123" in _rules(planlint.check_grid(bad))


def test_carry_dim_writes_a_block_per_step():
    """An "arbitrary" dim left out of ``reduction_dims`` carries state and
    writes a block per step (the batch-minor kernel's rows): PL123 does
    not apply to it, and two of its steps writing one block race."""
    carry = dataclasses.replace(
        _toy(out_map=lambda i, k: (i, k), out_shape=((32, 16), (8, 8))),
        reduction_dims=())
    assert _rules(planlint.check_grid(carry)) == []
    both = dataclasses.replace(
        _toy(out_map=lambda i, k: (i, 0)), reduction_dims=())
    assert _rules(planlint.check_grid(both)) == ["PL122"]


def test_grid_sampling_on_huge_grids():
    """Above MAX_GRID_POINTS the check degrades to boundary samples and
    says so (INFO PL121) instead of silently passing."""
    big = _toy(out_map=lambda i, k: (i, 0),
               out_shape=((8 * 600, 8), (8, 8)), grid=(600, 600),
               sem=("parallel", "arbitrary"))
    big = dataclasses.replace(
        big, inputs=(BlockRef("x", (8 * 600, 8 * 600), (8, 8),
                              lambda i, k: (i, k), 4),))
    diags = planlint.check_grid(big)
    assert _rules(diags) == []
    assert [d.rule for d in diags if d.severity == INFO] == ["PL121"]


def test_real_fused_model_grid_proofs():
    """The derived fused3 model (overlapping halo windows, RTRD reduction)
    passes the full grid proof — the negative for PL120-123 on the real
    index maps, not the toy."""
    spec, shape = _ir(), IR_SHAPE
    cp = chain.plan(spec, shape)
    (label, geom, model), = planlint.chain_models(spec, cp, shape)
    assert model is not None and geom.kind == "fused3"
    assert _rules(planlint.check_grid(model)) == []


# ---------------------------------------------------------------------------
# mosaic_check MC201-MC205
# ---------------------------------------------------------------------------

def _ref(array, block, itemsize=4, name="x"):
    return BlockRef(name, array, block, lambda *i: tuple(0 for _ in array),
                    itemsize)


def test_mc201_lane_misaligned_block():
    warn = mosaic_check._check_block_alignment(
        _ref((64, 256), (8, 64)), "s")
    assert [d.rule for d in warn if d.severity == WARNING] == ["MC201"]
    # taking ALL of a small minor dim is the planner's documented fallback
    info = mosaic_check._check_block_alignment(_ref((64, 64), (8, 64)), "s")
    assert [d.rule for d in info if d.severity == INFO] == ["MC201"]
    assert mosaic_check._check_block_alignment(
        _ref((64, 256), (8, 128)), "s") == []


def test_mc202_sublane_misaligned_block():
    diags = mosaic_check._check_block_alignment(_ref((64, 128), (7, 128)),
                                                "s")
    assert [d.rule for d in diags] == ["MC202"]
    assert mosaic_check._check_block_alignment(
        _ref((64, 128), (8, 128)), "s") == []
    # bf16 needs 16 sublanes: 8 is now misaligned
    diags = mosaic_check._check_block_alignment(
        _ref((64, 128), (8, 128), itemsize=2), "s")
    assert [d.rule for d in diags] == ["MC202"]


def test_mc203_collapsing_reshape():
    # (14, 14, 512) -> (196, 512): second-minor 14 off the 8-sublane tile
    diags = mosaic_check.check_reshapes([((14, 14, 512), (196, 512))], 4)
    assert [d.rule for d in diags] == ["MC203"]
    # minor-dim change is a relayout regardless of alignment
    diags = mosaic_check.check_reshapes([((8, 16, 32), (8, 512))], 4)
    assert [d.rule for d in diags] == ["MC203"]
    # aligned collapse is clean
    assert mosaic_check.check_reshapes([((16, 128, 512),
                                         (2048, 512))], 4) == []


def _unblocked_model(index_map):
    x = BlockRef("x", (64, 128), (8, 128), index_map, 4, unblocked=True)
    out = BlockRef("o", (64, 128), (8, 128), lambda i: (i, 0), 4)
    return KernelModel("toy", (8,), ("parallel",), (x,), out)


def test_mc204_unblocked_offsets():
    aligned = mosaic_check.check_unblocked(
        _unblocked_model(lambda i: (i * 8, 0)))
    assert aligned == []
    skewed = mosaic_check.check_unblocked(
        _unblocked_model(lambda i: (i * 8 + 1, 0)))
    assert [(d.rule, d.severity) for d in skewed] == [("MC204", ERROR)]
    # the v5e compiler refuses a lane offset it cannot prove 128-aligned,
    # even one that is 0 at every grid point: k * 32 with one channel slab
    lanes = BlockRef("x", (8, 32), (8, 32), lambda i: (0, i * 32), 4,
                     unblocked=True)
    out = BlockRef("o", (8, 32), (8, 32), lambda i: (0, 0), 4)
    unproven = KernelModel("toy", (1,), ("parallel",), (lanes,), out)
    assert _rules(mosaic_check.check_unblocked(unproven)) == ["MC204"]
    literal = dataclasses.replace(
        unproven, inputs=(dataclasses.replace(
            lanes, index_map=lambda i: (0, 0)),))
    assert mosaic_check.check_unblocked(literal) == []


def test_mc205_reduction_dim_not_innermost():
    m = _toy(sem=("arbitrary", "parallel"))
    assert _rules(mosaic_check.check_semantics(m)) == ["MC205"]
    assert mosaic_check.check_semantics(
        _toy(sem=("parallel", "arbitrary"))) == []


def test_real_models_mosaic_clean():
    """Negative at the model level: no MC errors on real derived models."""
    for spec, shape in ((_sep(), SEP_SHAPE), (_ir(), IR_SHAPE)):
        cp = chain.plan(spec, shape)
        for label, _geom, model in planlint.chain_models(spec, cp, shape):
            assert _rules(mosaic_check.lint_model(model, label)) == []


# ---------------------------------------------------------------------------
# jaxpr_audit JX301/JX302/JX310/JX311
# ---------------------------------------------------------------------------

def test_jx301_pass_count():
    spec, shape = _ir(), IR_SHAPE
    cp = chain.plan(spec, shape, policy=PAL)
    jaxpr = jaxpr_audit.trace_chain(spec, cp, shape, jnp.float32, PAL)
    ok = jaxpr_audit.audit_passes(jaxpr, len(cp.segments), cp.fully_fused)
    assert ok == []
    bad = jaxpr_audit.audit_passes(jaxpr, len(cp.segments) + 1,
                                   cp.fully_fused)
    assert _rules(bad) == ["JX301"]


def test_jx302_hbm_intermediate_on_fused_chain():
    spec, shape = _ir(), IR_SHAPE
    cp = chain.plan(spec, shape, policy=PAL)
    assert cp.fully_fused
    run = chain.lower(spec, cp, PAL)
    params = jaxpr_audit.param_structs(spec, shape[-1], jnp.float32)
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    # a compute op outside the kernel materializes an HBM intermediate
    leaky = jax.make_jaxpr(lambda p, a: jnp.tanh(run(p, a)))(params, x)
    diags = jaxpr_audit.audit_passes(leaky, len(cp.segments), True)
    assert _rules(diags) == ["JX302"]
    # the same trace is fine when the plan never claimed full fusion
    assert jaxpr_audit.audit_passes(leaky, len(cp.segments), False) == []


def test_jx310_rogue_cast():
    jaxpr = jax.make_jaxpr(
        lambda a: a.astype(jnp.float16).astype(jnp.float32))(
            jax.ShapeDtypeStruct((4, 4), jnp.float32))
    diags = jaxpr_audit.audit_casts(jaxpr, {"float32"})
    assert _rules(diags) == ["JX310"]
    assert "float16" in diags[0].message
    assert jaxpr_audit.audit_casts(jaxpr, {"float16", "float32"}) == []


def _matmul_jaxpr(pref):
    def kernel(x_ref, y_ref, o_ref):
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], y_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=pref).astype(jnp.float32)
    fn = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((8, 8), jnp.float32),
        interpret=True)
    s = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    return jax.make_jaxpr(fn)(s, s)


def test_jx311_accumulation_width():
    bad = jaxpr_audit.audit_accumulation(_matmul_jaxpr(jnp.bfloat16))
    assert _rules(bad) == ["JX311"]
    assert jaxpr_audit.audit_accumulation(_matmul_jaxpr(jnp.float32)) == []


def test_real_chain_jaxpr_audit_clean():
    for spec, shape in ((_sep(), SEP_SHAPE), (_ir(), IR_SHAPE)):
        cp = chain.plan(spec, shape, policy=PAL)
        diags = jaxpr_audit.lint_chain_jaxpr(spec, cp, shape,
                                             dtype=jnp.float32, policy=PAL)
        assert _rules(diags) == [], [d.format() for d in diags]


# ---------------------------------------------------------------------------
# report plumbing + top-level entry points
# ---------------------------------------------------------------------------

def test_report_serialization():
    spec, shape = _sep(), SEP_SHAPE
    cp = chain.plan(spec, shape)
    r = analysis.analyze_chain(spec, cp, shape, policy=PAL, jaxpr=True)
    assert r.ok
    d = r.to_json()
    assert d["ok"] and set(d) == {"ok", "summary", "diagnostics"}
    assert all(set(x) == {"rule", "severity", "message", "segment",
                          "geometry", "hint"} for x in d["diagnostics"])
    assert "0 error(s)" in r.summary()


def test_verify_or_raise():
    spec, shape = _sep(), SEP_SHAPE
    cp = chain.plan(spec, shape)
    analysis.verify_or_raise(
        analysis.analyze_chain(spec, cp, shape, jaxpr=False))
    bad = _with_plan(cp, 0, vmem_bytes=123)
    with pytest.raises(analysis.PlanVerificationError, match="PL102"):
        analysis.verify_or_raise(
            analysis.analyze_chain(spec, bad, shape, jaxpr=False))


def test_lint_cached_plan():
    spec, shape = _sep(), SEP_SHAPE
    cp = chain.plan(spec, shape)
    assert analysis.lint_cached_plan(spec, cp, shape) is None
    assert analysis.lint_cached_plan(
        spec, _with_plan(cp, 0, vmem_bytes=123), shape) == "PL102"


# ---------------------------------------------------------------------------
# integration: policy.verify knob, tune-cache drop, network-cache validation
# ---------------------------------------------------------------------------

def test_policy_verify_knob():
    spec = _sep()
    params = chain.init_chain(jax.random.PRNGKey(0), spec, SEP_SHAPE[-1])
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=SEP_SHAPE).astype(np.float32))
    verified = chain.execute(spec, params, x,
                             policy=dataclasses.replace(PAL, verify=True))
    plain = chain.execute(spec, params, x, policy=PAL)
    np.testing.assert_allclose(np.asarray(verified), np.asarray(plain))

    bad = _with_plan(chain.plan(spec, x.shape, policy=PAL), 0,
                     vmem_bytes=123)
    with pytest.raises(analysis.PlanVerificationError, match="PL102"):
        chain.execute(spec, params, x,
                      policy=dataclasses.replace(PAL, verify=True),
                      chain_plan=bad)
    # without the knob the corrupted claim executes (values stay right:
    # vmem_bytes is a claim, not an input to the lowering)
    out = chain.execute(spec, params, x, policy=PAL, chain_plan=bad)
    np.testing.assert_allclose(np.asarray(out), np.asarray(plain))


def test_tune_cache_entry_dropped_with_warning(tmp_path):
    spec, x_shape = _sep(), SEP_SHAPE
    pol = dataclasses.replace(PAL, autotune=True,
                              tune_cache=str(tmp_path / "tune.json"))
    good = chain.plan(spec, x_shape,
                      policy=dataclasses.replace(pol, autotune=False))
    key = autotune.problem_key(spec, x_shape, jnp.float32, pol)
    cache = autotune.TuneCache(pol.tune_cache)
    cache.put(key, {"plan": autotune.serialize_chain_plan(
        _with_plan(good, 0, vmem_bytes=123))})
    cache.save()
    with pytest.warns(UserWarning, match=r"planlint \(PL102\)"):
        got = autotune.lookup_cached_plan(spec, x_shape, jnp.float32, pol)
    assert got is None  # caller falls back to the analytic planner

    cache.put(key, {"plan": autotune.serialize_chain_plan(good)})
    cache.save()
    got = autotune.lookup_cached_plan(spec, x_shape, jnp.float32, pol)
    assert got == good  # clean entries replay untouched, no warning


def _tiny_net():
    return network.NetworkSpec(name="tiny", c_in=8, blocks=(
        chain.separable_block_spec(16, stride=1),
        chain.inverted_residual_spec(16, 16, expand=2, stride=1),
    ))


def test_network_cache_entry_validation():
    net = _tiny_net()
    nplan = network.plan_network(net, (1, 8, 8, 8), policy=PAL)
    assert network._validate_network_entry(net, nplan, PAL)
    bad = dataclasses.replace(
        nplan, plans=(_with_plan(nplan.plans[0], 0, vmem_bytes=123),)
        + nplan.plans[1:])
    with pytest.warns(UserWarning, match=r"block 0 failed planlint"):
        assert not network._validate_network_entry(net, bad, PAL)


def test_network_verify_knob():
    net = _tiny_net()
    nplan = network.plan_network(
        net, (1, 8, 8, 8), policy=dataclasses.replace(PAL, verify=True))
    assert analysis.analyze_network(net, nplan, policy=PAL,
                                    jaxpr=False).ok
