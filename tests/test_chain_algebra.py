"""Generalized chain algebra (DESIGN.md §10): SE and fused-MBConv stages
as first-class chain citizens.

Covers the new fusability windows (``dw_se`` epilogue fusion, ``fusedmb``
conv+project fusion) as plan goldens incl. the VMEM-degradation ladders,
fused-vs-unfused-composition parity (fp32 tight, bf16 tolerance) on the
Pallas interpret path, the traffic-model ordering, the MnasNet-A1 /
EfficientNet-Lite0 network specs end to end, and the per-rule seeded
positives/negatives for the new static-analysis surface (PL114, the
XLA-composed model-None contract, grid proofs on the new kernel models).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import jaxpr_audit, planlint
from repro.analysis.diagnostics import ERROR
from repro.core import chain, network
from repro.kernels import blocking, ref
from repro.kernels.policy import DtypePolicy, KernelPolicy

RNG = np.random.default_rng(23)
PAL = KernelPolicy(impl="pallas", interpret=True)

#: Small enough for interpret mode, big enough for a real dw_se/fusedmb
#: plan: the SE pool needs FULL channel+spatial residency (DESIGN.md §10).
SE_SHAPE = (1, 14, 14, 16)       # pw -> dw_se -> pw (+ residual)
FMB_SHAPE = (1, 16, 16, 24)      # one fusedmb pass (+ residual)


def _arr(shape, dtype=np.float32, scale=1.0):
    return jnp.asarray((RNG.normal(size=shape) * scale).astype(dtype))


def _kinds(cp):
    return [s.kind for s in cp.segments]


def _rules(diags, severity=ERROR):
    return sorted({d.rule for d in diags if d.severity == severity})


def _se():
    return chain.mbconv_se_spec(16, 16, expand=4, stride=1)


def _fmb(stride=1, c_in=24, c_out=24):
    return chain.fused_mbconv_spec(c_in, c_out, expand=4, stride=stride)


def _with_plan(cp, si, **kw):
    seg = cp.segments[si]
    new = dataclasses.replace(seg, plan=dataclasses.replace(seg.plan, **kw))
    return dataclasses.replace(
        cp, segments=cp.segments[:si] + (new,) + cp.segments[si + 1:])


# ---------------------------------------------------------------------------
# plan() goldens: the new fusability windows
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_plan_golden_mbconv_se_fuses_dw_se(dtype):
    """The MnasNet MBConv+SE block plans its SE gate as the DW epilogue
    (ONE dw_se pass), never as a standalone stage, whenever the pooled
    tensor is fully VMEM-resident — fp32 and bf16."""
    cp = chain.plan(_se(), SE_SHAPE, dtype=dtype)
    assert _kinds(cp) == ["pw", "dw_se", "pw"], cp
    seg = cp.segments[1]
    # the residency contract the SE pool requires (and PL114 enforces):
    # every channel, every output row, no slabbing
    assert seg.plan.block_c == 16 * 4
    assert seg.plan.n_slabs == 1 and seg.plan.slab_h == 14
    assert seg.plan.block_g == 4  # se_ratio * block INPUT width
    assert cp.residual and not cp.residual_fused
    assert cp.n_kernel_passes == 4  # pw + dw_se + pw + residual add


def test_plan_golden_dw_se_residency_degradation():
    """When the dw_se working set cannot be fully resident the planner must
    fall back to DW + standalone two-GEMM SE — a partial-residency dw_se
    pool would compute the WRONG answer, so there is no slabbed middle
    ground."""
    spec = chain.mbconv_se_spec(16, 16, expand=6)
    cp = chain.plan(spec, (1, 112, 112, 16))
    assert _kinds(cp) == ["pw", "dw", "se", "pw"]
    # the standalone SE is two GEMM passes (pool+reduce, expand+scale)
    assert cp.n_kernel_passes == 6  # pw + dw + 2*se + pw + residual add


def test_plan_golden_fused_mbconv_single_pass():
    """The EfficientNet-Lite edge block (full conv -> PW-project) plans to
    ONE fusedmb pass, with the residual folded in when shapes allow."""
    cp = chain.plan(_fmb(stride=2, c_out=40), (1, 32, 32, 24))
    assert _kinds(cp) == ["fusedmb"]
    assert cp.n_kernel_passes == 1 and not cp.residual

    cp_r = chain.plan(_fmb(), FMB_SHAPE)
    assert _kinds(cp_r) == ["fusedmb"]
    assert cp_r.residual and cp_r.residual_fused
    assert cp_r.n_kernel_passes == 1


def test_plan_golden_fused_mbconv_degrades_to_mb_pw():
    """When even the minimal fusedmb tile blows the budget (the raw-input
    row window alone exceeds it at this geometry) the planner degrades to
    a standalone XLA conv (mb) + pointwise projection."""
    spec = chain.fused_mbconv_spec(256, 256, expand=2)
    cp = chain.plan(spec, (1, 8, 2048, 256))
    assert _kinds(cp) == ["mb", "pw"]
    assert cp.residual and not cp.residual_fused
    # mb executes as one XLA conv pass; vmem claims must stay honest
    assert cp.segments[0].plan.vmem_bytes == 0


def test_plan_legacy_fused_false_unfuses_new_kinds():
    cp = chain.plan(_se(), SE_SHAPE, policy=KernelPolicy(fused=False))
    assert _kinds(cp) == ["pw", "dw", "se", "pw"]
    cp2 = chain.plan(_fmb(), FMB_SHAPE, policy=KernelPolicy(fused=False))
    assert _kinds(cp2) == ["mb", "pw"]


# ---------------------------------------------------------------------------
# parity: fused kernels vs the unfused XLA oracle composition
# ---------------------------------------------------------------------------

def _se_oracle(spec, params, x, cp):
    """Per-stage XLA refs with natural rounding between stages."""
    y = ref.pwconv_ref(x, params[0]["w"], activation="relu")
    y = ref.dwconv2d_ref(y, params[1]["f"], stride=1, padding="same")
    y = jnp.maximum(y, 0.0)
    y = ref.se_ref(y, params[2]["w1"], params[2]["b1"],
                   params[2]["w2"], params[2]["b2"])
    y = ref.pwconv_ref(y, params[3]["w"])
    if cp.residual:
        y = y + x
    return y


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_mbconv_se_parity(dtype):
    """Acceptance gate: the dw_se epilogue pass matches the fully unfused
    oracle chain (fp32 tight; bf16 within rounding — the fused pass keeps
    the DW output fp32 into the pool/gate, the unfused chain rounds it)."""
    spec = _se()
    params = chain.init_chain(jax.random.PRNGKey(3), spec, SE_SHAPE[-1])
    if dtype != np.float32:
        params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    x = _arr((2,) + SE_SHAPE[1:]).astype(dtype)

    cp = chain.plan(spec, x.shape, dtype=x.dtype)
    assert _kinds(cp) == ["pw", "dw_se", "pw"]
    got = chain.execute(spec, params, x, policy=PAL, chain_plan=cp)
    want = _se_oracle(spec, params, x, cp)
    tol = 1e-4 if dtype == np.float32 else 8e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_mbconv_se_parity_across_degradation():
    """The unfused rung (pw+dw+se+pw) computes the same block as the fused
    dw_se plan (fp32)."""
    spec = _se()
    params = chain.init_chain(jax.random.PRNGKey(4), spec, SE_SHAPE[-1])
    x = _arr(SE_SHAPE)
    fused = chain.execute(spec, params, x, policy=PAL)
    unfused = chain.execute(
        spec, params, x,
        policy=KernelPolicy(impl="pallas", interpret=True, fused=False))
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("stride,residual", [(1, True), (2, False)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_fused_mbconv_parity(stride, residual, dtype):
    """The single-pass conv+project kernel matches the unfused composition
    (XLA conv -> rounded activation -> XLA GEMM), stride 1 with residual
    and stride 2 without, fp32 and bf16."""
    c_in = 24
    c_out = c_in if residual else 40
    spec = _fmb(stride=stride, c_in=c_in, c_out=c_out)
    params = chain.init_chain(jax.random.PRNGKey(7), spec, c_in)
    if dtype != np.float32:
        params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    x = _arr((2, 15, 15, c_in)).astype(dtype)

    cp = chain.plan(spec, x.shape, dtype=x.dtype)
    assert _kinds(cp) == ["fusedmb"]
    assert cp.residual == residual
    got = chain.execute(spec, params, x, policy=PAL, chain_plan=cp)

    y = ref.conv2d_ref(x, params[0]["f"], stride=stride, padding="same",
                       activation="relu6")
    y = ref.pwconv_ref(y, params[1]["w"])
    if residual:
        y = y + x
    tol = 1e-4 if dtype == np.float32 else 8e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(y, np.float32),
                               rtol=tol, atol=tol)


def test_fused_mbconv_parity_across_degradation():
    spec = _fmb()
    params = chain.init_chain(jax.random.PRNGKey(9), spec, FMB_SHAPE[-1])
    x = _arr(FMB_SHAPE)
    fused = chain.execute(spec, params, x, policy=PAL)
    unfused = chain.execute(
        spec, params, x,
        policy=KernelPolicy(impl="pallas", interpret=True, fused=False))
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# traffic models: fusion must pay off in modeled HBM bytes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nb", [4, 2])
def test_dw_se_traffic_below_unfused(nb):
    spec = _se()
    cp_f = chain.plan(spec, SE_SHAPE)
    cp_u = chain.plan(spec, SE_SHAPE, policy=KernelPolicy(fused=False))
    assert _kinds(cp_f) == ["pw", "dw_se", "pw"]
    assert _kinds(cp_u) == ["pw", "dw", "se", "pw"]
    t_f = chain.chain_traffic(spec, cp_f, SE_SHAPE, dtype_bytes=nb)
    t_u = chain.chain_traffic(spec, cp_u, SE_SHAPE, dtype_bytes=nb)
    assert t_f.bytes_hbm < t_u.bytes_hbm, nb
    # fusion moves bytes, not arithmetic — except the standalone DW's
    # separate activation-epilogue pass (1 flop/element), which the fused
    # pass absorbs for free
    assert t_u.flops - t_f.flops == 1 * 14 * 14 * 64


@pytest.mark.parametrize("nb", [4, 2])
def test_fused_mbconv_traffic_below_unfused(nb):
    spec = _fmb()
    cp_f = chain.plan(spec, FMB_SHAPE)
    cp_u = chain.plan(spec, FMB_SHAPE, policy=KernelPolicy(fused=False))
    assert _kinds(cp_f) == ["fusedmb"] and _kinds(cp_u) == ["mb", "pw"]
    t_f = chain.chain_traffic(spec, cp_f, FMB_SHAPE, dtype_bytes=nb)
    t_u = chain.chain_traffic(spec, cp_u, FMB_SHAPE, dtype_bytes=nb)
    assert t_f.bytes_hbm < t_u.bytes_hbm, nb


# ---------------------------------------------------------------------------
# the new network specs end to end
# ---------------------------------------------------------------------------

def _hist(nplan):
    from collections import Counter
    return dict(Counter(s.kind for p in nplan.plans for s in p.segments))


def test_mnasnet_a1_plan_golden():
    """Every one of the 8 SE-carrying MBConv blocks fuses its gate onto the
    DW pass; nothing degrades to standalone se/dw at the paper's 112x112."""
    net = network.mnasnet_a1_spec()
    nplan = network.plan_network(net, (1, 112, 112, net.c_in))
    assert len(net.blocks) == 16
    assert _hist(nplan) == {"fused2": 1, "fused3": 7, "pw": 16, "dw_se": 8}


def test_efficientnet_lite0_plan_golden():
    """All 4 fused-MBConv blocks plan single-pass fusedmb; every other
    block stays fused3/fused2 — the whole body is single-pass-per-block."""
    net = network.efficientnet_lite0_spec()
    nplan = network.plan_network(net, (1, 112, 112, net.c_in))
    assert len(net.blocks) == 16
    assert _hist(nplan) == {"fused2": 1, "fused3": 11, "fusedmb": 4}
    assert all(len(p.segments) == 1 for p in nplan.plans)


def _inverted_residual_relu6(c_in, c_out, *, expand, stride, hf=3):
    """``chain.inverted_residual_spec`` as it read before it took the
    activation: relu6 hard-coded."""
    return chain.SeparableSpec(stages=(
        chain.PW(c_in * expand, activation="relu6"),
        chain.DW(stride=stride, activation="relu6", hf=hf, wf=hf),
        chain.PW(c_out),
    ), residual="auto")


@pytest.mark.parametrize("make", [network.mobilenet_v2_spec,
                                  network.efficientnet_lite0_spec])
def test_relu6_bodies_unchanged_by_the_activation_argument(make):
    """MobileNetV2 and EfficientNet-Lite0 keep relu6: every one of their
    inverted residuals equals the block built before
    ``inverted_residual_spec`` took the activation, so the specs (the memo
    and plan keys) are the same objects as before."""
    net = make()
    c, n = net.c_in, 0
    for b in net.blocks:
        pw, dw = b.stages[0], b.stages[1]
        if len(b.stages) == 3 and isinstance(dw, chain.DW):
            old = _inverted_residual_relu6(
                c, b.out_channels(c), expand=pw.features // c,
                stride=dw.stride, hf=dw.hf)
            assert b == old and hash(b) == hash(old)
            n += 1
        c = b.out_channels(c)
    assert n == {network.mobilenet_v2_spec: 16,
                 network.efficientnet_lite0_spec: 11}[make]


def test_mnasnet_a1_spec_is_relu_as_published():
    """ReLU in every activated stage (SepConv DW, every expansion and DW,
    the SE hidden layer), linear projections, SE reduced to a quarter of
    the block input, 5x5 taps in the (3, 40) and (6, 160) rows."""
    net = network.mnasnet_a1_spec(1.0)
    acts, se, taps = set(), [], []
    for b in net.blocks:
        *inner, proj = b.stages
        assert isinstance(proj, chain.PW) and proj.activation is None
        for st in inner:
            acts.add(st.activation)
            if isinstance(st, chain.SE):
                se.append(st.reduce)
            if isinstance(st, chain.DW):
                taps.append(st.hf)
    assert acts == {"relu"}
    assert se == [6, 10, 10, 20, 28, 28, 40, 40]
    assert taps == [3] * 3 + [5] * 3 + [3] * 6 + [5] * 3 + [3]


@pytest.mark.parametrize("make,stream", [
    pytest.param(network.mnasnet_a1_spec, None, id="mnasnet_a1_spec"),
    pytest.param(network.efficientnet_lite0_spec, None,
                 id="efficientnet_lite0_spec"),
    pytest.param(network.mnasnet_a1_spec, "bfloat16",
                 id="mnasnet_a1_spec-pallas-bf16"),
])
def test_execute_network_new_archs(make, stream):
    """Both new bodies run end to end through the network engine and match
    the per-block execute composition.  On the Pallas path with a bf16
    stream, MnasNet-A1 at a 32x32 body input (every one of its 16 blocks
    and SE reduce widths as at 224, the plan too: fused2 1, fused3 7, pw
    16, dw_se 8) matches the fp32 oracle within the bf16 tolerance."""
    net = make()
    params = network.init_network(jax.random.PRNGKey(0), net)
    if stream is not None:
        x = jax.random.uniform(jax.random.PRNGKey(1), (2, 32, 32, net.c_in),
                               maxval=4.0)
        pol = KernelPolicy(impl="pallas", interpret=True, on_failure="raise",
                           dtype_policy=DtypePolicy(stream=stream))
        assert _hist(network.plan_network(net, x.shape, policy=pol)) == {
            "fused2": 1, "fused3": 7, "pw": 16, "dw_se": 8}
        got = np.asarray(network.execute_network(
            net, network.cast_network_params(params, stream), x,
            policy=pol), np.float32)
        want = np.asarray(network.reference_network(net, params, x))
        assert got.shape == want.shape == (2, 2, 2, 320)
        rel = np.abs(got - want).max() / np.abs(want).max()
        assert rel < network.BF16_REL_TOL, rel
        return
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 16, net.c_in))
    pol = KernelPolicy(impl="xla")
    y = network.execute_network(net, params, x, policy=pol)
    o = x
    for spec, p in zip(net.blocks, params):
        o = chain.execute(spec, p, o, policy=pol)
    got, want = np.asarray(y, np.float32), np.asarray(o, np.float32)
    assert np.isfinite(got).all()
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-30)
    assert rel < 1e-5, rel


# ---------------------------------------------------------------------------
# static analysis: PL114 + the XLA-composed contract + grid proofs
# ---------------------------------------------------------------------------

def test_clean_new_plans_lint_clean():
    """Negative for every PL rule on the new kinds at once — including the
    degraded (se/mb-carrying) plans, whose XLA-composed segments have no
    kernel model by design."""
    cases = (
        (_se(), SE_SHAPE, None),
        (_fmb(), FMB_SHAPE, None),
        (chain.mbconv_se_spec(16, 16, expand=6), (1, 112, 112, 16), None),
        (_fmb(), FMB_SHAPE, KernelPolicy(fused=False)),
    )
    for spec, shape, pol in cases:
        cp = chain.plan(spec, shape, policy=pol or KernelPolicy())
        diags = planlint.lint_chain(spec, cp, shape)
        assert _rules(diags) == [], [d.format() for d in diags]


def test_pl114_dw_se_residency_violations():
    """Seeded positives: every way the dw_se residency contract can break
    (partial channels, spatial slabbing, wrong SE width) fires PL114 —
    each would silently compute a WRONG pooled mean, not a slow one."""
    spec = _se()
    cp = chain.plan(spec, SE_SHAPE)
    assert cp.segments[1].kind == "dw_se"

    partial = _with_plan(cp, 1, block_c=32)  # C=64: pool sees half
    assert "PL114" in _rules(planlint.lint_chain(spec, partial, SE_SHAPE))

    slabbed = _with_plan(cp, 1, slab_h=7, n_slabs=2)
    assert "PL114" in _rules(planlint.lint_chain(spec, slabbed, SE_SHAPE))

    wrong_se = _with_plan(cp, 1, block_g=8)  # spec says reduce=4
    assert "PL114" in _rules(planlint.lint_chain(spec, wrong_se, SE_SHAPE))

    # and the clean plan fires none of them
    assert "PL114" not in _rules(planlint.lint_chain(spec, cp, SE_SHAPE))


def test_chain_models_none_for_xla_composed_kinds():
    """se/mb segments have NO single Pallas kernel (model is None by
    design) and lint_chain must not report that as a failure — only an
    unexpectedly missing model on a kernel-backed kind is an error."""
    spec = chain.mbconv_se_spec(16, 16, expand=6)
    shape = (1, 112, 112, 16)
    cp = chain.plan(spec, shape)
    kinds = {g.kind: m for _l, g, m in planlint.chain_models(spec, cp, shape)}
    assert kinds["se"] is None and kinds["dw"] is not None
    assert _rules(planlint.lint_chain(spec, cp, shape)) == []

    spec2 = _fmb()
    cp2 = chain.plan(spec2, FMB_SHAPE, policy=KernelPolicy(fused=False))
    kinds2 = {g.kind: m
              for _l, g, m in planlint.chain_models(spec2, cp2, FMB_SHAPE)}
    assert kinds2["mb"] is None and kinds2["pw"] is not None


def test_new_kernel_models_grid_proofs():
    """The derived dw_se and fusedmb models pass the full grid proof
    (in-bounds halo windows, exact disjoint output coverage) — the
    negative for PL120-123 on the new index maps."""
    for spec, shape, kind in ((_se(), SE_SHAPE, "dw_se"),
                              (_fmb(), FMB_SHAPE, "fusedmb")):
        cp = chain.plan(spec, shape)
        models = [(g, m) for _l, g, m in planlint.chain_models(spec, cp,
                                                               shape)
                  if g.kind == kind]
        assert models and models[0][1] is not None
        assert _rules(planlint.check_grid(models[0][1])) == []


def test_claimed_vmem_honest_for_new_kinds():
    """PL102 drift detection reaches the new kinds: a corrupted vmem claim
    on a dw_se or fusedmb segment is caught."""
    for spec, shape, si in ((_se(), SE_SHAPE, 1), (_fmb(), FMB_SHAPE, 0)):
        cp = chain.plan(spec, shape)
        bad = _with_plan(cp, si, vmem_bytes=123)
        assert "PL102" in _rules(planlint.lint_chain(spec, bad, shape))


# ---------------------------------------------------------------------------
# jaxpr audit on the new kinds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec,shape", [(_se(), SE_SHAPE),
                                        (_fmb(), FMB_SHAPE)])
def test_new_chain_jaxpr_audit_clean(spec, shape):
    cp = chain.plan(spec, shape, policy=PAL)
    diags = jaxpr_audit.lint_chain_jaxpr(spec, cp, shape,
                                         dtype=jnp.float32, policy=PAL)
    assert _rules(diags) == [], [d.format() for d in diags]


def test_jx310_seeded_cast_around_se_chain():
    """A rogue fp16 round-trip wrapped around the SE chain fires the
    cast-ownership rule; the clean trace does not."""
    spec, shape = _se(), SE_SHAPE
    cp = chain.plan(spec, shape, policy=PAL)
    run = chain.lower(spec, cp, PAL)
    params = jaxpr_audit.param_structs(spec, shape[-1], jnp.float32)
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    clean = jax.make_jaxpr(run)(params, x)
    assert _rules(jaxpr_audit.audit_casts(clean, {"float32"})) == []
    leaky = jax.make_jaxpr(
        lambda p, a: run(p, a.astype(jnp.float16).astype(jnp.float32)))(
            params, x)
    assert _rules(jaxpr_audit.audit_casts(leaky, {"float32"})) == ["JX310"]


def test_param_structs_cover_new_stages():
    """The audit's shape-only param mirror matches init_chain exactly for
    SE and FusedMB stages (key set AND shapes), so traces need no real
    weights."""
    for spec, c_in in ((_se(), SE_SHAPE[-1]), (_fmb(), FMB_SHAPE[-1])):
        real = chain.init_chain(jax.random.PRNGKey(0), spec, c_in)
        structs = jaxpr_audit.param_structs(spec, c_in, jnp.float32)
        assert len(real) == len(structs)
        for rp, sp in zip(real, structs):
            assert set(rp) == set(sp)
            for k in rp:
                assert rp[k].shape == sp[k].shape, (k, rp[k].shape)
