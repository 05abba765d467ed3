"""The body input read as it arrives: batch-minor at a batch of 128.

On the TPU a (128, 112, 112, 32) body input lies batch-minor (XLA's
default layout puts the batch in the lanes).  Where the first block is a
one-slab stride-1 ``fused2``, ``build_network_fn`` hands it the (H, W, C,
B) view of that array and ``separable_fused_batch_minor`` reads it as it
lies, instead of a channel-minor relayout ``copy`` in HBM.  Here, in
interpret mode: the kernel against today's ``fused2`` and the oracle, the
decision as a pure function of (shape, layout, plan), the counters, and
the fallbacks.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import analysis
from repro.analysis import planlint
from repro.analysis.jaxpr_audit import param_structs
from repro.core import chain, network
from repro.kernels import autotune, blocking, lowering, ref
from repro.kernels.policy import DtypePolicy, KernelPolicy
from repro.kernels.separable_fused import (separable_fused_batch_minor,
                                           separable_fused_pallas)
from repro.runtime import faultinject, quarantine, telemetry

BF16_REL_TOL = 5e-2
B = 128

RNG = np.random.default_rng(17)


@pytest.fixture(autouse=True)
def _clean_runtime():
    faultinject.disarm_all()
    telemetry.reset_runtime_telemetry()
    quarantine.clear_memo()
    network.clear_network_cache()
    yield
    faultinject.disarm_all()
    quarantine.clear_memo()
    network.clear_network_cache()


def _arr(shape, scale=1.0):
    return jnp.asarray((RNG.normal(size=shape) * scale).astype(np.float32))


# (size, Co, DW activation, PW activation, biases, stream): both sizes at
# both widths, each epilogue with and without biases, both streams
KERNEL_CASES = [
    (8, 64, "relu6", "relu6", True, "float32"),
    (8, 64, "relu6", None, False, "bfloat16"),
    (8, 64, "relu", None, True, "bfloat16"),
    (8, 16, "relu6", "relu6", False, "bfloat16"),
    (8, 16, "relu6", None, True, "float32"),
    (8, 16, "relu", None, False, "float32"),
    (15, 64, "relu6", "relu6", False, "float32"),
    (15, 64, "relu6", None, True, "float32"),
    (15, 64, "relu", None, False, "bfloat16"),
    (15, 16, "relu6", "relu6", True, "bfloat16"),
    (15, 16, "relu6", None, False, "bfloat16"),
    (15, 16, "relu", None, True, "float32"),
]


@pytest.mark.parametrize(
    "size,co,dw_act,pw_act,biases,dtype", KERNEL_CASES,
    ids=[f"{s}-co{co}-{a}-{p or 'linear'}-{'bias' if b else 'nobias'}-{d}"
         for s, co, a, p, b, d in KERNEL_CASES])
def test_batch_minor_entry_matches_fused2(size, co, dw_act, pw_act, biases,
                                          dtype):
    """The batch-minor entry on the (H, W, C, B) view gives what today's
    ``fused2`` gives on the (B, H, W, C) array, and both what the oracle
    gives, at ``fused2``'s tolerances."""
    c = 32
    x = _arr((B, size, size, c)).astype(dtype)
    dw_f = _arr((3, 3, c), scale=1 / 3).astype(dtype)
    pw_w = _arr((c, co), scale=c ** -0.5).astype(dtype)
    dw_b = _arr((c,), scale=0.1).astype(dtype) if biases else None
    pw_b = _arr((co,), scale=0.1).astype(dtype) if biases else None
    pads = blocking.same_pads(size, size, 3, 3, 1)
    acts = dict(dw_activation=dw_act, activation=pw_act)
    got = separable_fused_batch_minor(
        jnp.transpose(x, lowering.BATCH_MINOR), dw_f, pw_w, dw_b, pw_b,
        pads=pads, interpret=True, **acts)
    today = separable_fused_pallas(
        x, dw_f, pw_w, dw_b, pw_b, pads=pads, block_c=c, block_co=co,
        slab_h=size, interpret=True, **acts)
    want = ref.separable_fused_ref(x, dw_f, pw_w, dw_b, pw_b,
                                   padding="same", **acts)
    assert got.shape == today.shape == want.shape == (B, size, size, co)
    assert got.dtype == today.dtype == jnp.dtype(dtype)
    got, today, want = (np.asarray(a, np.float32)
                        for a in (got, today, want))
    scale = np.abs(want).max()
    if dtype == "float32":
        np.testing.assert_allclose(got, today, rtol=0, atol=1e-6 * scale)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4 * scale)
    else:
        assert np.abs(got - today).max() <= BF16_REL_TOL * scale
        assert np.abs(got - want).max() <= BF16_REL_TOL * scale


# ---------------------------------------------------------------------------
# The decision: a pure function of (shape, layout, plan)
# ---------------------------------------------------------------------------

PALLAS = KernelPolicy(impl="pallas", interpret=True, on_failure="raise",
                      dtype_policy=DtypePolicy(stream="bfloat16"))


def _first_block(body="mobilenet_v1_spec", shape=(B, 112, 112, 32)):
    net = getattr(network, body)(1.0)
    spec = net.blocks[0]
    return spec, chain.plan(spec, shape, dtype=jnp.bfloat16, policy=PALLAS)


def _slabbed(cp):
    (seg,) = cp.segments
    return dataclasses.replace(cp, segments=(dataclasses.replace(
        seg, plan=dataclasses.replace(seg.plan, slab_h=16, n_slabs=7)),))


V2_B01 = chain.inverted_residual_spec(16, 24, expand=6, stride=2)

DECISIONS = [
    # (case, spec+plan maker, shape, layout, impl, taken)
    ("v1-b128-batch-minor", "v1", (B, 112, 112, 32), (1, 2, 3, 0),
     "pallas", True),
    ("v2-b128-batch-minor", "v2", (B, 112, 112, 32), (1, 2, 3, 0),
     "pallas", True),
    ("mnasnet-b256-batch-minor", "mnasnet", (256, 112, 112, 32),
     (1, 2, 3, 0), "pallas", True),
    ("b1-w-minor", "v1", (1, 112, 112, 32), (0, 1, 3, 2), "pallas", False),
    ("b64-batch-minor", "v1", (64, 112, 112, 32), (1, 2, 3, 0), "pallas",
     False),
    ("b128-channel-minor", "v1", (B, 112, 112, 32), (0, 1, 2, 3), "pallas",
     False),
    ("b128-no-layout", "v1", (B, 112, 112, 32), None, "pallas", False),
    ("b128-xla", "v1", (B, 112, 112, 32), (1, 2, 3, 0), "xla", False),
    ("b128-fused3-first", "fused3", (B, 112, 112, 16), (1, 2, 3, 0),
     "pallas", False),
    ("b128-slabbed-first", "slabbed", (B, 112, 112, 32), (1, 2, 3, 0),
     "pallas", False),
    ("b128-stride2-first", "stride2", (B, 112, 112, 32), (1, 2, 3, 0),
     "pallas", False),
]


def _spec_plan(kind, shape):
    if kind in ("v1", "v2", "mnasnet"):
        body = {"v1": "mobilenet_v1_spec", "v2": "mobilenet_v2_spec",
                "mnasnet": "mnasnet_a1_spec"}[kind]
        return _first_block(body, shape)
    if kind == "fused3":
        return V2_B01, chain.plan(V2_B01, shape, dtype=jnp.bfloat16,
                                  policy=PALLAS)
    if kind == "slabbed":
        spec, cp = _first_block(shape=shape)
        return spec, _slabbed(cp)
    assert kind == "stride2"
    spec = chain.separable_block_spec(64, stride=2)
    return spec, chain.plan(spec, shape, dtype=jnp.bfloat16, policy=PALLAS)


@pytest.mark.parametrize("kind,shape,layout,impl,taken",
                         [d[1:] for d in DECISIONS],
                         ids=[d[0] for d in DECISIONS])
def test_input_in_place_decision(kind, shape, layout, impl, taken):
    spec, cp = _spec_plan(kind, shape)
    assert lowering.input_in_place(spec, cp, shape, layout, impl) is taken


# ---------------------------------------------------------------------------
# Through build_network_fn: counters, numerics, fallbacks
# ---------------------------------------------------------------------------

def _two_blocks(body):
    full = getattr(network, body)(1.0)
    return dataclasses.replace(full, blocks=full.blocks[:2])


def _counts():
    counters = telemetry.runtime_report()["counters"]
    return (counters.get("lowering.input_in_place", 0),
            counters.get("lowering.input_relayout", 0))


@pytest.mark.parametrize("body", ["mobilenet_v1_spec", "mobilenet_v2_spec"])
def test_network_reads_batch_minor_input_and_counts_it(body):
    """At batch 128 the first two blocks give the same output whether the
    input is read batch-minor or channel-minor; each build counts one
    ``lowering.input_in_place`` or one ``lowering.input_relayout``."""
    net = _two_blocks(body)
    shape = (B, 8, 8, net.c_in)
    nplan = network.plan_network(net, shape, dtype=jnp.bfloat16,
                                 policy=PALLAS)
    params = network.cast_network_params(
        network.init_network(jax.random.PRNGKey(0), net), jnp.bfloat16)
    x = _arr(shape).astype(jnp.bfloat16)
    outs = {}
    for layout, counts in ((lowering.BATCH_MINOR, (1, 0)), (None, (0, 1))):
        telemetry.reset_runtime_telemetry()
        fn = jax.jit(network.build_network_fn(net, nplan, PALLAS,
                                              input_layout=layout))
        outs[layout] = np.asarray(fn(params, x), np.float32)
        assert _counts() == counts
        fn(params, x)          # a second call traces nothing
        assert _counts() == counts
    got, today = outs[lowering.BATCH_MINOR], outs[None]
    assert np.abs(got - today).max() <= BF16_REL_TOL * np.abs(today).max()


def test_device_layout_decides_by_default():
    """Without an ``input_layout`` the build takes the default device's
    layout for block 0's input: channel-minor on the CPU."""
    assert network.device_layout((B, 8, 8, 32), jnp.bfloat16) == (0, 1, 2, 3)
    x = jnp.zeros((2, 3, 4, 5))
    assert network.array_layout(x) == (0, 1, 2, 3)
    assert network.array_layout(np.zeros((2, 3))) is None
    net = _two_blocks("mobilenet_v1_spec")
    shape = (B, 8, 8, net.c_in)
    nplan = network.plan_network(net, shape, dtype=jnp.bfloat16,
                                 policy=PALLAS)
    params = [[{k: jax.ShapeDtypeStruct(v.shape, jnp.bfloat16)
                for k, v in p.items()}
               for p in param_structs(spec, bshape[-1], jnp.bfloat16)]
              for spec, bshape in zip(net.blocks, nplan.block_shapes)]
    jax.eval_shape(network.build_network_fn(net, nplan, PALLAS), params,
                   jax.ShapeDtypeStruct(shape, jnp.bfloat16))
    assert _counts() == (0, 1)


def test_quarantined_block0_falls_back(tmp_path):
    """An ``unfused`` ban on block 0 lowers it on the XLA reference: the
    batch-minor input then takes today's path."""
    net = _two_blocks("mobilenet_v1_spec")
    shape = (B, 8, 8, net.c_in)
    pol = dataclasses.replace(PALLAS, on_failure="degrade",
                              tune_cache=str(tmp_path / "tune.json"))
    nplan = network.plan_network(net, shape, dtype=jnp.bfloat16, policy=pol)
    block0 = network.resolve_block_policies(net, pol)[0]
    q = quarantine.Quarantine.load(quarantine.quarantine_path(pol))
    q.add_failure(autotune.problem_key(net.blocks[0], shape,
                                       jnp.dtype(jnp.bfloat16), block0),
                  signature={}, ban="unfused",
                  failure={"kind": "test", "message": "seeded"})
    q.save()
    params = network.cast_network_params(
        network.init_network(jax.random.PRNGKey(0), net), jnp.bfloat16)
    x = _arr(shape).astype(jnp.bfloat16)
    fn = jax.jit(network.build_network_fn(net, nplan, pol,
                                          input_layout=lowering.BATCH_MINOR))
    y = fn(params, x)
    assert _counts() == (0, 1)
    assert y.shape == (B, 4, 4, 128)


def test_per_block_recovery_takes_todays_path(tmp_path):
    """A classified failure of the composed program recovers block by
    block through ``execute_chain``, which never reads the input
    batch-minor."""
    net = _two_blocks("mobilenet_v1_spec")
    shape = (B, 8, 8, net.c_in)
    pol = dataclasses.replace(PALLAS, on_failure="degrade",
                              tune_cache=str(tmp_path / "tune.json"))
    params = network.cast_network_params(
        network.init_network(jax.random.PRNGKey(0), net), jnp.bfloat16)
    x = _arr(shape).astype(jnp.bfloat16)
    faultinject.arm("compile:network", times=1)
    with pytest.warns(RuntimeWarning, match="recovering per-block"):
        y = network.execute_network(net, params, x, policy=pol)
    assert telemetry.runtime_report()["recoveries"] == 1
    assert _counts() == (0, 0)
    assert y.shape == (B, 4, 4, 128)


@pytest.mark.parametrize("body", ["mobilenet_v1_spec", "mobilenet_v2_spec"])
def test_analysis_proves_the_batch_minor_model(body):
    """The static verifier proves block 0's batch-minor kernel model at
    batch 128: VMEM (PL103, under the plan's budget), the grid (PL120-
    PL123) and the tiling (MC201-MC205)."""
    net = getattr(network, body)(1.0)
    shape = (B, 112, 112, net.c_in)
    nplan = network.plan_network(net, shape, dtype=jnp.bfloat16,
                                 policy=PALLAS)
    model = planlint.body_input_model(net.blocks[0], nplan.plans[0], shape)
    assert model.name == "separable_fused2_batch_minor"
    assert model.grid == (1, 112)
    assert model.vmem_bytes() <= nplan.plans[0].vmem_budget
    report = analysis.analyze_network(net, nplan, policy=PALLAS,
                                      jaxpr=False)
    assert report.ok, report.summary()
    assert "PL103" not in report.rules()
    linted = [d for d in report.diagnostics if "batch_minor" in d.segment]
    assert linted and all(d.severity == "info" for d in linted), linted
    assert planlint.body_input_model(net.blocks[0], nplan.plans[0],
                                     (1, 112, 112, net.c_in)) is None
