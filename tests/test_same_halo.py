"""SAME padding made inside the fused separable kernel (interpret mode).

A one-slab plan hands ``separable_fused_pallas`` the UNPADDED input with
``pads`` and the kernel makes the halo in VMEM; the lowering counts each
fused segment that does (``lowering.halo_in_kernel``) or still pads in HBM
(``lowering.halo_padded``).  The in-kernel path must give what the padded
path gives at the same blocks, and both what the oracle gives.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import analysis
from repro.analysis import planlint
from repro.analysis.jaxpr_audit import param_structs
from repro.core import chain, network
from repro.kernels import blocking, lowering, ops, ref
from repro.kernels.policy import DtypePolicy, KernelPolicy
from repro.kernels.separable_fused import separable_fused_pallas
from repro.runtime import telemetry

BF16_REL_TOL = 5e-2

RNG = np.random.default_rng(14)


def _arr(shape, scale=1.0):
    return jnp.asarray((RNG.normal(size=shape) * scale).astype(np.float32))


def _block(size, stride, hf, expand, residual, dtype):
    """Operands of one block: x is the unpadded input."""
    ci, c = (8, 24) if expand else (16, 16)
    co = ci if residual else 24
    ho = -(-size // stride)
    ops_ = dict(
        x=_arr((2, size, size, ci)),
        expand_w=_arr((ci, c), scale=ci ** -0.5) if expand else None,
        dw_f=_arr((hf, hf, c), scale=1 / hf),
        pw_w=_arr((c, co), scale=c ** -0.5),
        dw_bias=_arr((c,), scale=0.1),
        pw_bias=_arr((co,), scale=0.1),
        residual=_arr((2, ho, ho, co)) if residual else None)
    return {k: None if v is None else v.astype(dtype)
            for k, v in ops_.items()}


def _fused(o, **kw):
    return separable_fused_pallas(
        o["x"], o["dw_f"], o["pw_w"], o["dw_bias"], o["pw_bias"],
        o["residual"], expand_w=o["expand_w"], expand_activation="relu6",
        dw_activation="relu6", activation=None, interpret=True, **kw)


# (size, stride, hf, residual, dtype): even and odd sizes, each at both
# strides with 3x3 and 5x5 filters, the residual at unit stride (where the
# shape is kept); the bf16 stream on the 3x3 cases
GEOMETRIES = [(7, 1, 3, True), (7, 2, 3, False), (7, 1, 5, False),
              (7, 2, 5, False), (14, 1, 3, False), (14, 2, 3, False),
              (14, 1, 5, True), (14, 2, 5, False), (15, 1, 3, False),
              (15, 2, 3, False), (15, 1, 5, True), (15, 2, 5, False),
              (28, 1, 3, True), (28, 2, 3, False), (28, 1, 5, False),
              (28, 2, 5, False)]
CASES = ([(*g, "float32") for g in GEOMETRIES]
         + [(*g, "bfloat16") for g in GEOMETRIES if g[2] == 3])


@pytest.mark.parametrize("expand", [False, True], ids=["fused2", "fused3"])
@pytest.mark.parametrize(
    "size,stride,hf,residual,dtype", CASES,
    ids=[f"{s}-s{st}-{hf}x{hf}{'-res' if r else ''}-{d}"
         for s, st, hf, r, d in CASES])
def test_in_kernel_halo_matches_padded_input(size, stride, hf, residual,
                                             expand, dtype):
    o = _block(size, stride, hf, expand, residual, dtype)
    pads = blocking.same_pads(size, size, hf, hf, stride)
    ho = -(-size // stride)
    blocks = dict(stride=stride, block_c=o["dw_f"].shape[-1],
                  block_co=o["pw_w"].shape[-1], slab_h=ho)
    got = _fused(o, pads=pads, **blocks)
    padded = _fused(dict(o, x=ops.pad_same(o["x"], hf, hf, stride)),
                    **blocks)
    want = ref.separable_fused_ref(
        o["x"], o["dw_f"], o["pw_w"], o["dw_bias"], o["pw_bias"],
        o["residual"], expand_w=o["expand_w"], expand_activation="relu6",
        stride=stride, padding="same", dw_activation="relu6",
        activation=None)
    assert got.shape == padded.shape == want.shape == (
        2, ho, ho, o["pw_w"].shape[-1])
    got, padded, want = (np.asarray(a, np.float32)
                         for a in (got, padded, want))
    scale = np.abs(want).max()
    if dtype == "float32":
        np.testing.assert_allclose(got, padded, rtol=0, atol=1e-6 * scale)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4 * scale)
    else:
        assert np.abs(got - padded).max() <= BF16_REL_TOL * scale
        assert np.abs(got - want).max() <= BF16_REL_TOL * scale


def test_slabbed_plan_refuses_in_kernel_halo():
    """A plan of more than one row slab cannot make the halo in VMEM: the
    kernel refuses ``pads`` there, and the planner budgets it padded."""
    o = _block(14, 1, 3, False, False, "float32")
    pads = blocking.same_pads(14, 14, 3, 3, 1)
    assert blocking.kernel_pads(pads, 14, 14) == pads
    assert blocking.kernel_pads(pads, 14, 4) is None
    with pytest.raises(ValueError, match="one row slab"):
        _fused(o, pads=pads, block_c=16, block_co=24, slab_h=4)


def _counters():
    return {k: v for k, v in telemetry.runtime_report()["counters"].items()
            if k.startswith("lowering.halo")}


def test_slabbed_segment_pads_outside_and_counts_it():
    """Through the lowering: a one-slab segment takes the in-kernel halo, a
    tiny VMEM budget's slabbed segment still pads in HBM, and both agree
    with the oracle."""
    spec = chain.inverted_residual_spec(8, 8, expand=2, stride=1)
    x = _arr((1, 12, 12, 8))
    params = chain.init_chain(jax.random.PRNGKey(3), spec, 8)
    xla = KernelPolicy(impl="xla", on_failure="raise")
    want = lowering.lower(spec, chain.plan(spec, x.shape, policy=xla),
                          xla)(params, x)
    for budget, slabbed, counted in (
            (blocking.DEFAULT_VMEM_BUDGET, False, "lowering.halo_in_kernel"),
            (24 * 1024, True, "lowering.halo_padded")):
        pol = KernelPolicy(impl="pallas", interpret=True,
                           vmem_budget=budget, on_failure="raise")
        cp = chain.plan(spec, x.shape, policy=pol)
        (seg,) = cp.segments
        assert seg.kind == "fused3" and (seg.plan.n_slabs > 1) == slabbed
        telemetry.reset_runtime_telemetry()
        got = lowering.lower(spec, cp, pol)(params, x)
        assert _counters() == {counted: 1}
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


BODIES = {"mobilenet_v1_spec": 13, "mobilenet_v2_spec": 17}


@pytest.mark.parametrize("body", BODIES)
def test_bodies_at_224_make_every_halo_in_kernel(body):
    """Every fused segment of the V1 and V2 bodies at a 224 image (body
    input 112x112, bf16 stream) keeps the plan the padded window gave it —
    all channels, all of Co, one row slab, at batch 1 and 128 — and makes
    its SAME halo in VMEM: one build counts 13 / 17 in-kernel halos and
    no padded one."""
    net = getattr(network, body)(1.0)
    pol = KernelPolicy(impl="pallas", interpret=True, on_failure="raise",
                       dtype_policy=DtypePolicy(stream="bfloat16"))
    for batch in (1, 128):
        shape = (batch, 112, 112, net.c_in)
        nplan = network.plan_network(net, shape, dtype=jnp.bfloat16,
                                     policy=pol)
        for spec, cp, bshape in zip(net.blocks, nplan.plans,
                                    nplan.block_shapes):
            (seg,) = cp.segments
            (g,) = planlint.walk_segments(spec, cp, bshape)
            padded = (blocking.plan_separable3(
                g.ho, g.wo, g.ci, g.c, g.co, stride=g.stride, hf=g.hf,
                wf=g.wf, dtype=jnp.bfloat16, residual=g.residual)
                if seg.kind == "fused3" else blocking.plan_separable(
                g.ho, g.wo, g.c, g.co, stride=g.stride, hf=g.hf, wf=g.wf,
                dtype=jnp.bfloat16, residual=g.residual))
            fields = ("block_c", "block_co", "slab_h", "n_slabs")
            assert [getattr(seg.plan, f) for f in fields] == [
                getattr(padded, f) for f in fields] == [g.c, g.co, g.ho, 1]
            assert seg.plan.vmem_bytes < padded.vmem_bytes
    # the static verifier proves the new kernel models: VMEM (PL103), grid
    # (PL120-PL123), tiling (MC201-MC205) and the pass count (JX301)
    report = analysis.analyze_network(net, nplan, policy=pol)
    assert report.ok, report.summary()
    assert "PL103" not in report.rules()
    params = [[{k: jax.ShapeDtypeStruct(v.shape, jnp.bfloat16)
                for k, v in p.items()}
               for p in param_structs(spec, bshape[-1], jnp.bfloat16)]
              for spec, bshape in zip(net.blocks, nplan.block_shapes)]
    telemetry.reset_runtime_telemetry()
    jax.eval_shape(network.build_network_fn(net, nplan, pol), params,
                   jax.ShapeDtypeStruct(shape, jnp.bfloat16))
    assert _counters() == {"lowering.halo_in_kernel": BODIES[body]}


#: Per build at a 224 image (body input 112x112, bf16 stream): SAME halos
#: made in the kernel / padded in HBM, residuals inside the last kernel /
#: added as a separate op, the body input read as it lies / through a
#: relayout.  ``dw_se`` and ``fusedmb`` segments pad in HBM; the CPU
#: device lays the input out channel-minor.
LOWERING_COUNTS = {
    "mobilenet_v1_spec": (13, 0, 0, 0, 0, 1),
    "mobilenet_v2_spec": (17, 0, 10, 0, 0, 1),
    "mnasnet_a1_spec": (8, 8, 4, 5, 0, 1),
    "efficientnet_lite0_spec": (12, 4, 9, 0, 0, 1),
}


@pytest.mark.parametrize("body", LOWERING_COUNTS)
def test_lowering_counters_per_build_at_224(body):
    net = getattr(network, body)(1.0)
    pol = KernelPolicy(impl="pallas", interpret=True, on_failure="raise",
                       dtype_policy=DtypePolicy(stream="bfloat16"))
    shape = (128, 112, 112, net.c_in)
    nplan = network.plan_network(net, shape, dtype=jnp.bfloat16, policy=pol)
    params = [[{k: jax.ShapeDtypeStruct(v.shape, jnp.bfloat16)
                for k, v in p.items()}
               for p in param_structs(spec, bshape[-1], jnp.bfloat16)]
              for spec, bshape in zip(net.blocks, nplan.block_shapes)]
    telemetry.reset_runtime_telemetry()
    jax.eval_shape(network.build_network_fn(net, nplan, pol), params,
                   jax.ShapeDtypeStruct(shape, jnp.bfloat16))
    counters = telemetry.runtime_report()["counters"]
    names = ("lowering.halo_in_kernel", "lowering.halo_padded",
             "lowering.residual_in_kernel", "lowering.residual_separate",
             "lowering.input_in_place", "lowering.input_relayout")
    assert tuple(counters.get(n, 0) for n in names) == LOWERING_COUNTS[body]
    assert {n for n in counters if n.startswith("lowering.")} <= set(names)
