"""The fused3 kernel's expand GEMM over a group of input rows.

Where the kernel reads the unpadded image and its width lies on the fp32
sublane tile, ``separable_fused_pallas`` expands R input rows per MXU GEMM
(``plan_expand_rows``); elsewhere one row per GEMM.  Both orders compute
the same products in the same precision, so the outputs must be bitwise
equal (interpret mode), at the shapes of MobileNetV2's blocks 1-3 at a 224
image.  The lowering counts the two outcomes per ``fused3`` segment
(``fused3.expand_grouped`` / ``fused3.expand_per_row``), pinned here per
build of each published body.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr_audit import param_structs
from repro.core import network
from repro.kernels import blocking, ref
from repro.kernels.policy import DtypePolicy, KernelPolicy
from repro.kernels.separable_fused import (fused_kernel_model,
                                           plan_expand_rows,
                                           separable_fused_pallas)
from repro.runtime import telemetry

RNG = np.random.default_rng(19)


def _arr(shape, scale=1.0):
    return jnp.asarray((RNG.normal(size=shape) * scale).astype(np.float32))


#: (name, size, stride, ci, c, co, residual, R): V2 blocks 1-3 at a 224
#: image (body input 112x112), one image each
BLOCKS = [("v2-b01", 112, 2, 16, 96, 24, False, 28),
          ("v2-b02", 56, 1, 24, 144, 24, True, 56),
          ("v2-b03", 56, 2, 24, 144, 32, False, 14)]
CASES = ([(*blk, dt, "relu6") for blk in BLOCKS
          for dt in ("float32", "bfloat16")]
         + [(*BLOCKS[0], "bfloat16", "relu")])   # MnasNet-A1's block 1


@pytest.mark.parametrize(
    "name,size,stride,ci,c,co,residual,rows,dtype,act", CASES,
    ids=[f"{n}-{d}-{a}" for n, *_, d, a in CASES])
def test_grouped_expand_equals_per_row(name, size, stride, ci, c, co,
                                       residual, rows, dtype, act):
    ho = -(-size // stride)
    pads = blocking.same_pads(size, size, 3, 3, stride)
    assert plan_expand_rows(size, size, ho * ho, pads) == rows
    o = {k: v.astype(dtype) for k, v in dict(
        x=_arr((1, size, size, ci)), expand_w=_arr((ci, c), ci ** -0.5),
        dw_f=_arr((3, 3, c), 1 / 3), pw_w=_arr((c, co), c ** -0.5),
        dw_bias=_arr((c,), 0.1), pw_bias=_arr((co,), 0.1),
        residual=_arr((1, ho, ho, co))).items()}
    res = o["residual"] if residual else None

    def run(expand_rows):
        return np.asarray(separable_fused_pallas(
            o["x"], o["dw_f"], o["pw_w"], o["dw_bias"], o["pw_bias"], res,
            expand_w=o["expand_w"], expand_activation=act, stride=stride,
            dw_activation=act, activation=None, block_c=c, block_co=co,
            slab_h=ho, interpret=True, pads=pads, expand_rows=expand_rows),
            np.float32)

    grouped, per_row = run(None), run(1)
    assert grouped.shape == (1, ho, ho, co)
    np.testing.assert_array_equal(grouped, per_row)
    if dtype == "float32":
        want = ref.separable_fused_ref(
            o["x"], o["dw_f"], o["pw_w"], o["dw_bias"], o["pw_bias"], res,
            expand_w=o["expand_w"], expand_activation=act, stride=stride,
            padding="same", dw_activation=act, activation=None)
        scale = np.abs(np.asarray(want)).max()
        np.testing.assert_allclose(grouped, np.asarray(want), rtol=1e-4,
                                   atol=1e-4 * scale)


def test_plan_expand_rows_takes_only_free_collapses():
    pads = blocking.same_pads(56, 56, 3, 3, 1)
    # the largest divisor of the rows within the DW intermediate's pixels
    assert plan_expand_rows(56, 56, 56 * 56, pads) == 56
    assert plan_expand_rows(56, 56, 28 * 28, pads) == 14
    assert plan_expand_rows(112, 112, 56 * 56, pads) == 28
    assert plan_expand_rows(21, 16, 100, pads) == 3
    # a padded window (no pads), or a width off the fp32 tile: row by row
    assert plan_expand_rows(56, 56, 56 * 56, None) == 1
    for w in (7, 14, 28, 57):
        assert plan_expand_rows(w, w, w * w, pads) == 1


def test_kernel_model_records_the_expand_collapse():
    kw = dict(b=2, ho=28, wo=28, c_in=24, c=144, co=32, hf=3, wf=3,
              stride=2, block_c=144, block_co=32, slab_h=28, itemsize=2,
              out_itemsize=2, has_expand=True, has_dw_bias=True,
              has_pw_bias=True, has_residual=False,
              pads=blocking.same_pads(56, 56, 3, 3, 2))
    dw = ((28, 28, 144), (28 * 28, 144))
    assert fused_kernel_model(**kw).reshapes == (dw, ((14, 56, 24),
                                                      (14 * 56, 24)))
    assert fused_kernel_model(**kw, expand_rows=1).reshapes == (dw,)


#: Per build at a 224 image, bf16 stream: fused3 segments that expand a
#: group of rows per GEMM / one row per GEMM.  Only the 112x112 and 56x56
#: inputs read whole (blocks 1-3 of V2, 1-2 of MnasNet-A1) have a width on
#: the fp32 tile; Lite0 reaches its first fused3 at 28x28.
EXPAND_COUNTS = {
    "mobilenet_v1": (0, 0),
    "mobilenet_v2": (3, 13),
    "mnasnet_a1": (2, 5),
    "efficientnet_lite0": (0, 11),
}
#: the planning of ``test_published_plans.py``: the cells' policy
POLICY = KernelPolicy(impl="pallas", interpret=False, on_failure="raise",
                      autotune=False,
                      dtype_policy=DtypePolicy(stream="bfloat16"))


@pytest.mark.parametrize("batch", [1, 128])
@pytest.mark.parametrize("body", EXPAND_COUNTS)
def test_expand_counters_per_build_at_224(body, batch):
    net = getattr(network, f"{body}_spec")(1.0)
    shape = (batch, 112, 112, net.c_in)
    nplan = network.plan_network(net, shape, dtype=jnp.bfloat16,
                                 policy=POLICY)
    params = [[{k: jax.ShapeDtypeStruct(v.shape, jnp.bfloat16)
                for k, v in p.items()}
               for p in param_structs(spec, bshape[-1], jnp.bfloat16)]
              for spec, bshape in zip(net.blocks, nplan.block_shapes)]
    telemetry.reset_runtime_telemetry()
    jax.eval_shape(network.build_network_fn(net, nplan, POLICY), params,
                   jax.ShapeDtypeStruct(shape, jnp.bfloat16))
    counters = telemetry.runtime_report()["counters"]
    assert (counters.get("fused3.expand_grouped", 0),
            counters.get("fused3.expand_per_row", 0)) == EXPAND_COUNTS[body]
