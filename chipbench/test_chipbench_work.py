"""The benchmark's yardstick for work: FLOPs, bytes and weights of the
bodies, counted from the published tables, and the table of peaks."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import body  # noqa: E402

V1 = "mobilenet_v1_1.0_224"
V2 = "mobilenet_v2_1.0_224"


def _body(name, batch=1):
    return body.Body(body.load_config(name), batch)


def _block_work(bd, i):
    """(MACs, weights) of block ``i`` for one image."""
    (h, w, c), _ = bd.block_shapes()[i]
    macs = weights = 0
    for st in bd.blocks[i]["stages"]:
        wk = bd.work[st["kind"]]
        macs += wk.macs(st, h, w, c)
        weights += wk.n_weights(st, c)
        h, w, c = wk.out_shape(st, h, w, c)
    return macs, weights


def test_v1_block_hand_count():
    # block 1: 112x112x64 -> DW 3x3 s2 (+bias) -> PW 64->128 (+bias)
    bd = _body(V1)
    assert bd.block_shapes()[1] == ((112, 112, 64), (56, 56, 128))
    dw_macs = 56 * 56 * 64 * 9
    pw_macs = 56 * 56 * 64 * 128
    assert _block_work(bd, 1) == (dw_macs + pw_macs,
                                  (9 * 64 + 64) + (64 * 128 + 128))
    assert not bd.blocks[1]["residual"]


def test_v2_inverted_residual_hand_count():
    # block 1: 112x112x16 -> PW 16->96 -> DW 3x3 s2 -> PW 96->24, no bias
    bd = _body(V2)
    assert bd.block_shapes()[1] == ((112, 112, 16), (56, 56, 24))
    macs = 112 * 112 * 16 * 96 + 56 * 56 * 96 * 9 + 56 * 56 * 96 * 24
    assert _block_work(bd, 1) == (macs, 16 * 96 + 9 * 96 + 96 * 24)
    # block 2 repeats at stride 1 and width 24: the residual is added
    assert bd.block_shapes()[2] == ((56, 56, 24), (56, 56, 24))
    assert bd.blocks[2]["residual"] and not bd.blocks[1]["residual"]


def test_se_block_hand_count():
    # MnasNet-A1's first SE block: 56x56x24 -> PW 24->72 -> DW 5x5 s2 ->
    # SE (72 -> 6 -> 72) -> PW 72->40, no conv bias, both SE FCs biased
    stages = [
        {"kind": "PW", "c_out": 72, "bias": False, "act": "relu"},
        {"kind": "DW", "k": 5, "stride": 2, "bias": False, "act": "relu"},
        {"kind": "SE", "reduce": 6, "hidden_act": "relu", "act": None},
        {"kind": "PW", "c_out": 40, "bias": False, "act": None},
    ]
    h, w, c = 56, 56, 24
    macs = weights = 0
    for st in stages:
        wk = body.load_module(os.path.join(body.HERE, "work",
                                           f"{st['kind']}.py"))
        macs += wk.macs(st, h, w, c)
        weights += wk.n_weights(st, c)
        h, w, c = wk.out_shape(st, h, w, c)
    assert (h, w, c) == (28, 28, 40)
    se_macs = 2 * 72 * 6 + 28 * 28 * 72 + 28 * 28 * 72  # FCs, pool, scale
    assert macs == (56 * 56 * 24 * 72 + 28 * 28 * 72 * 25 + se_macs
                    + 28 * 28 * 72 * 40)
    assert weights == 24 * 72 + 25 * 72 + (2 * 72 * 6 + 6 + 72) + 72 * 40


@pytest.mark.parametrize("name,n_blocks,weights,out", [
    (V1, 13, 3.18e6, (7, 7, 1024)),
    (V2, 17, 1.78e6, (7, 7, 320)),
])
def test_body_totals(name, n_blocks, weights, out):
    bd = _body(name)
    assert len(bd.blocks) == n_blocks
    assert bd.n_weights() == pytest.approx(weights, rel=0.01)
    assert bd.block_shapes()[-1][1] == out


def test_bytes_and_flops_per_call():
    bd1, bd128 = _body(V2, 1), _body(V2, 128)
    acts = sum(a[0] * a[1] * a[2] + o[0] * o[1] * o[2]
               for a, o in bd1.block_shapes())
    # activations scale with the batch; the weights are read once per call
    assert bd1.bytes_per_call() == 2 * (acts + bd1.n_weights())
    assert bd128.bytes_per_call() == 2 * (128 * acts + bd1.n_weights())
    assert bd128.flops_per_call() == 128 * bd1.flops_per_call()
    assert bd1.flops_per_call() == 2 * bd1.macs_per_image()


def test_ideal_time_is_the_larger_bound():
    peak = body.load_peak("TPU v5 lite")
    for name in (V1, V2):
        bd = _body(name, 128)
        t_flops = bd.flops_per_call() / peak["bf16_flops_per_s"]
        t_bytes = bd.bytes_per_call() / peak["hbm_bytes_per_s"]
        assert bd.ideal_s_per_call(peak) == max(t_flops, t_bytes)
        assert t_bytes > t_flops    # these bodies are memory-bound


def test_peaks_table():
    peak = body.load_peak("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in peak["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="TPU v99"):
        body.load_peak("TPU v99")
    with pytest.raises(KeyError):
        body.load_peak("cpu")
