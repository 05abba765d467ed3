"""The stage contract on the CPU: a program stage of class ``K`` is read
through ``reference/K.py``'s ``describe``, the activation table covers
every activation the program's epilogues accept, a body with
squeeze-excite stages is taken from new files alone and computes what the
program's own fp32 reference computes, and the MobileNetV1/V2 cells read
what they read before the contract moved into ``describe``."""
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import body  # noqa: E402
import run  # noqa: E402

V1 = "mobilenet_v1_1.0_224"
V2 = "mobilenet_v2_1.0_224"


def _mbconv_se(c_in, c_out, t, k, stride, reduce=None, hidden_act="relu"):
    """The stage dicts of ``chain.mbconv_se_spec`` as a configuration's
    ``blocks()`` gives them."""
    return {"residual": stride == 1 and c_in == c_out, "stages": [
        {"kind": "PW", "c_out": c_in * t, "bias": False, "act": "relu"},
        {"kind": "DW", "k": k, "stride": stride, "bias": False,
         "act": "relu"},
        {"kind": "SE", "reduce": max(1, c_in // 4) if reduce is None
         else reduce, "hidden_act": hidden_act, "act": None},
        {"kind": "PW", "c_out": c_out, "bias": False, "act": None},
    ]}


#: Two MnasNet-A1-style SE blocks: 5x5 taps, expansion 3, 24 -> 24 at
#: stride 1 (residual) and 24 -> 40 at stride 2.
SE_BLOCKS = [_mbconv_se(24, 24, 3, 5, 1), _mbconv_se(24, 40, 3, 5, 2)]
SE_CFG = {"name": "se_two_blocks", "body_input": [16, 16, 24],
          "stream_dtype": "float32", "weight_gain": 1.3}


def _se_net(*blocks):
    from repro.core import chain, network
    if not blocks:
        blocks = (chain.mbconv_se_spec(24, 24, expand=3, hf=5, stride=1),
                  chain.mbconv_se_spec(24, 40, expand=3, hf=5, stride=2))
    return network.NetworkSpec(name="se_two_blocks", c_in=24, blocks=blocks)


@pytest.fixture
def config_body(monkeypatch):
    """Builds a Body whose ``blocks()`` is given in the test, as a new
    ``configs/<name>.py`` would give it."""
    load = body.load_module

    def make(blocks):
        path = os.path.join(HERE, "configs", f"{SE_CFG['name']}.py")

        def load_config_module(p):
            if p == path:
                return types.SimpleNamespace(blocks=lambda c: blocks)
            return load(p)

        monkeypatch.setattr(body, "load_module", load_config_module)
        return body.Body(SE_CFG, 2)

    return make


def test_se_body_matches_the_program_spec(config_body):
    bd = config_body(SE_BLOCKS)
    run.check_program_spec(_se_net(), bd)
    assert set(bd.ref) == {"PW", "DW", "SE"}


def test_se_body_forward_matches_reference_network(config_body):
    import jax
    from repro.core import network
    bd = config_body(SE_BLOCKS)
    kw, kx = jax.random.split(jax.random.PRNGKey(2**31 + 15))
    params = jax.jit(bd.init_params)(kw)
    x = bd.make_inputs(kx, 1)[0]
    y = np.asarray(bd.forward(params, x))
    ref = np.asarray(network.reference_network(_se_net(), params, x))
    assert y.shape == ref.shape == (2, 8, 8, 40)
    assert np.abs(y - ref).max() / np.abs(ref).max() < 1e-5


def test_se_gate_is_spread_on_seeded_weights(config_body):
    """The seeded gate neither saturates nor sits at one value, so a
    wrong gate shows."""
    import jax
    bd = config_body(SE_BLOCKS)
    kw, kx = jax.random.split(jax.random.PRNGKey(2**31 + 16))
    params = bd.init_params(kw)
    y = bd.make_inputs(kx, 1)[0].astype("float32")
    stages, stage_params = bd.blocks[0]["stages"], params[0]
    for st, p in zip(stages[:2], stage_params):
        y = body.ACTIVATIONS[st["act"]](
            bd.ref[st["kind"]].apply(st, p, y, body.identity))
    gated = bd.ref["SE"].apply(stages[2], stage_params[2], y, body.identity)
    y_sum = np.asarray(y.sum(axis=(1, 2)))
    gate = np.asarray(gated.sum(axis=(1, 2)))[y_sum > 0] / y_sum[y_sum > 0]
    lo, hi = np.quantile(gate, [0.05, 0.95])
    assert 0.01 < lo < 0.35 and 0.65 < hi < 0.99


@pytest.mark.parametrize("change", ["reduce_off_by_one", "hidden_act"])
def test_check_program_spec_refuses_a_wrong_se(config_body, change):
    kw = ({"reduce": 7} if change == "reduce_off_by_one"
          else {"hidden_act": "relu6"})
    bd = config_body([_mbconv_se(24, 24, 3, 5, 1, **kw), SE_BLOCKS[1]])
    with pytest.raises(ValueError, match="program block 0"):
        run.check_program_spec(_se_net(), bd)


def test_stage_kind_without_reference_file_is_named(config_body):
    from repro.core import chain
    bd = config_body(SE_BLOCKS[:1])
    net = _se_net(chain.fused_mbconv_spec(24, 24, expand=3))
    with pytest.raises(FileNotFoundError,
                       match=r"chipbench/reference/FusedMB\.py"):
        run.check_program_spec(net, bd)


@pytest.mark.parametrize("kw", [{"hf": 3, "wf": 5}, {"padding": "valid"}])
def test_dw_describe_refuses_what_it_cannot_compute(kw):
    from repro.core import chain
    dw = body.load_module(os.path.join(HERE, "reference", "DW.py"))
    with pytest.raises(ValueError, match="square SAME"):
        dw.describe(chain.DW(**kw))


def test_activation_table_covers_the_program():
    from repro.kernels import epilogue
    assert set(epilogue.ACTIVATIONS) <= set(body.ACTIVATIONS)


@pytest.mark.parametrize("name", ["relu", "relu6", "gelu", "silu"])
def test_activation_matches_the_program_epilogue(name):
    import jax.numpy as jnp
    from repro.kernels import epilogue
    y = jnp.linspace(-12.0, 12.0, 4001, dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(body.ACTIVATIONS[name](y)),
        np.asarray(epilogue.apply_epilogue(y, activation=name)),
        rtol=1e-6, atol=1e-6)


# -- the MobileNetV1/V2 cells read as they did ---------------------------

@pytest.mark.parametrize("name,spec", [(V1, "mobilenet_v1_spec"),
                                       (V2, "mobilenet_v2_spec")])
def test_v1_v2_configs_match_their_program_specs(name, spec):
    from repro.core import network
    cfg = body.load_config(name)
    assert cfg["program_spec"] == spec
    run.check_program_spec(getattr(network, spec)(cfg["width_multiplier"]),
                           body.Body(cfg, 1))


#: ``ideal_s_per_call`` on a TPU v5e, taken before the stage contract
#: moved into ``describe``.
IDEAL_S = [(V1, 1, 2.1893470085470084e-05), (V1, 128, 0.0018114428717948719),
           (V2, 1, 8.08220757020757e-06), (V2, 128, 0.0004827018315018315)]


@pytest.mark.parametrize("name,batch,ideal", IDEAL_S)
def test_ideal_s_per_call_pinned(name, batch, ideal):
    bd = body.Body(body.load_config(name), batch)
    assert bd.ideal_s_per_call(body.load_peak("TPU v5 lite")) == \
        pytest.approx(ideal, rel=1e-12)


#: ``init_params`` for one key, taken before the stage contract moved into
#: ``describe``: leaves, numbers, and sums of all values, of their squares
#: and of the values weighted by position, which a change of draw, leaf
#: order or scale moves by far more than the tolerance.
WEIGHT_PINS = [
    (V1, 52, 3195136, 39.45263575940458, 21809.30510430141,
     523.3981432766626),
    (V2, 50, 1779296, -103.86983688926796, 22680.81260467985,
     -258.18541548152825),
]


@pytest.mark.parametrize("name,n_leaves,size,total,squares,weighted",
                         WEIGHT_PINS)
def test_init_params_pinned(name, n_leaves, size, total, squares, weighted):
    import jax
    bd = body.Body(body.load_config(name), 1)
    p = jax.jit(bd.init_params)(jax.random.PRNGKey(2**31 + 77))
    leaves = [a for blk in p for d in blk for a in d.values()]
    v = np.concatenate([np.asarray(a, np.float64).ravel() for a in leaves])
    w = (np.arange(v.size) % 7) - 3.0
    assert (len(leaves), v.size) == (n_leaves, size)
    assert v.sum() == pytest.approx(total, abs=1e-3)
    assert (v * v).sum() == pytest.approx(squares, rel=1e-6)
    assert (v * w).sum() == pytest.approx(weighted, abs=1e-3)
