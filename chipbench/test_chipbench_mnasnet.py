"""The MnasNet-A1 configuration on the CPU: it is the program's spec as
published (ReLU throughout), its plain fp32 body is the program's fp32
oracle, its work is pinned, and the readers of the program's lowering
counters (``halo_in_hbm_share``, ``residual_in_hbm_share``) read what
one build of the body counts."""
import dataclasses
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import body  # noqa: E402
import run  # noqa: E402

MNAS = "mnasnet_a1_1.0_224"


def _mnas_body(hw=None, batch=1):
    cfg = body.load_config(MNAS)
    if hw is not None:
        cfg = dict(cfg, body_input=[hw, hw, cfg["body_input"][2]])
    return body.Body(cfg, batch)


def _reader(name):
    return body.load_module(os.path.join(HERE, "metrics", f"{name}.py"))


def test_config_matches_its_program_spec():
    """ReLU after every stage but the linear projections (the SE's hidden
    layer too), SE reduced to a quarter of the block input, 5x5 taps in
    the (3, 40) and (6, 160) rows: the configuration and the program's
    spec agree."""
    from repro.core import network
    bd = _mnas_body()
    assert bd.cfg["program_spec"] == "mnasnet_a1_spec"
    run.check_program_spec(
        network.mnasnet_a1_spec(bd.cfg["width_multiplier"]), bd)
    for b in bd.blocks:
        *inner, proj = b["stages"]
        assert proj["kind"] == "PW" and proj["act"] is None
        assert {st["act"] for st in inner if st["kind"] != "SE"} == {"relu"}
    se = [st for b in bd.blocks for st in b["stages"] if st["kind"] == "SE"]
    assert [st["reduce"] for st in se] == [6, 10, 10, 20, 28, 28, 40, 40]
    assert {(st["hidden_act"], st["act"]) for st in se} == {("relu", None)}
    assert [st["k"] for b in bd.blocks for st in b["stages"]
            if st["kind"] == "DW"] == [3] * 3 + [5] * 3 + [3] * 6 + \
        [5] * 3 + [3]


@pytest.mark.parametrize("block", [1, 15])
def test_check_program_spec_refuses_relu6_in_a_non_se_block(block):
    """A non-SE MBConv block with ReLU6, as ``inverted_residual_spec``
    builds it by default, is not the published body."""
    from repro.core import chain, network
    net = network.mnasnet_a1_spec(1.0)
    c_in = net.c_in
    for b in net.blocks[:block]:
        c_in = b.out_channels(c_in)
    old = net.blocks[block]
    relu6 = chain.inverted_residual_spec(
        c_in, old.out_channels(c_in), expand=old.stages[0].features // c_in,
        stride=old.stride_product(), hf=old.stages[1].hf)
    assert relu6 != old and [type(s) for s in relu6.stages] == \
        [type(s) for s in old.stages]
    blocks = net.blocks[:block] + (relu6,) + net.blocks[block + 1:]
    with pytest.raises(ValueError, match=f"program block {block} "):
        run.check_program_spec(dataclasses.replace(net, blocks=blocks),
                               _mnas_body())


def test_body_forward_matches_reference_network():
    """The harness's fp32 body is the program's fp32 oracle, at a 32x32
    body input (all 16 blocks, every SE width as at 224) on the
    configuration's own seeded weights."""
    import jax
    from repro.core import network
    bd = _mnas_body(32, 2)
    kw, kx = jax.random.split(jax.random.PRNGKey(2**31 + 161))
    params = jax.jit(bd.init_params)(kw)
    x = bd.make_inputs(kx, 1)[0]
    y = np.asarray(bd.forward(params, x))
    ref = np.asarray(network.reference_network(
        network.mnasnet_a1_spec(1.0), params, x.astype("float32")))
    assert y.shape == ref.shape == (2, 2, 2, 320)
    assert np.abs(y - ref).max() / np.abs(ref).max() < 1e-5


def test_body_totals():
    bd = _mnas_body()
    assert len(bd.blocks) == 16
    assert bd.n_weights() == 2162086
    assert bd.block_shapes()[-1][1] == (7, 7, 320)


@pytest.mark.parametrize("batch,ideal", [(1, 9.108879120879121e-06),
                                         (128, 0.0004953994774114774)])
def test_ideal_s_per_call_pinned(batch, ideal):
    """The body's ideal time on a TPU v5e: memory-bound, and independent
    of the activations (the work model counts none)."""
    bd = _mnas_body(batch=batch)
    peak = body.load_peak("TPU v5 lite")
    assert bd.ideal_s_per_call(peak) == pytest.approx(ideal, rel=1e-12)
    assert bd.bytes_per_call() / peak["hbm_bytes_per_s"] > \
        bd.flops_per_call() / peak["bf16_flops_per_s"]


@pytest.mark.parametrize("name,hbm,kernel", [
    ("halo_in_hbm_share", "lowering.halo_padded", "lowering.halo_in_kernel"),
    ("residual_in_hbm_share", "lowering.residual_separate",
     "lowering.residual_in_kernel"),
])
def test_in_hbm_share_reads_the_counters(monkeypatch, name, hbm, kernel):
    from repro.runtime import telemetry
    monkeypatch.setattr(telemetry, "_COUNTERS",
                        telemetry.collections.Counter())
    reader = _reader(name)
    assert reader.UNIT == "%"
    assert reader.read({}) is None          # no counter: nothing to read
    telemetry.count("network.builds")
    assert reader.read({}) is None          # counted nothing of its own
    telemetry.count(kernel)
    assert reader.read({}) == 0.0
    for _ in range(3):
        telemetry.count(hbm)
    assert reader.read({}) == pytest.approx(75.0)
    # a second trace of the same body counts everything again: same ratio
    telemetry.count(kernel)
    for _ in range(3):
        telemetry.count(hbm)
    assert reader.read({}) == pytest.approx(75.0)


def test_in_hbm_shares_of_the_program_body(monkeypatch):
    """Traced once, the program's MnasNet-A1 body pads 8 of its 16 SAME
    halos in HBM (every ``dw_se``) and adds 5 of its 9 residuals outside
    the kernel."""
    import jax
    import jax.numpy as jnp
    from repro.core import network
    from repro.kernels.policy import DtypePolicy, KernelPolicy
    from repro.runtime import telemetry
    monkeypatch.setattr(telemetry, "_COUNTERS",
                        telemetry.collections.Counter())
    net = network.mnasnet_a1_spec(1.0)
    pol = KernelPolicy(impl="pallas", interpret=True, on_failure="raise",
                       dtype_policy=DtypePolicy(stream="bfloat16"))
    x = jax.ShapeDtypeStruct((2, 32, 32, net.c_in), jnp.bfloat16)
    nplan = network.plan_network(net, x.shape, dtype=x.dtype, policy=pol)
    params = jax.eval_shape(lambda: network.cast_network_params(
        network.init_network(jax.random.PRNGKey(0), net), jnp.bfloat16))
    jax.eval_shape(network.build_network_fn(net, nplan, pol), params, x)
    assert _reader("halo_in_hbm_share").read({}) == pytest.approx(50.0)
    assert _reader("residual_in_hbm_share").read({}) == pytest.approx(
        100 * 5 / 9)
