"""v5e compile guard: AOT-compile the main path's kernels at real widths for
a described (not attached) TPU v5e, so what Mosaic refuses fails here.

Interpret mode — the rest of the suite — cannot see Mosaic's rules: the
stride-2 taps, the lane alignment of element-offset windows and the scoped
VMEM limit were each refused on v5e while every interpret test passed.
Each case compiles one kernel or one planned MobileNet-family block at body
resolution 112 (a 224 image) and checks that the compiled program holds
exactly the Pallas calls the plan lowers to.  The topology is described
inside a fixture, never at import: only one process may load the TPU
library, and pytest-xdist imports this file in every worker.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis.jaxpr_audit import param_structs
from repro.core import network
from repro.kernels import lowering
from repro.kernels.dwconv2d import dwconv2d_pallas
from repro.kernels.policy import DtypePolicy, KernelPolicy
from repro.kernels.pwconv import pwconv_pallas

RES = 112


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip cannot read back a cached executable: keep the
    # persistent cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _tpu_calls(fn, *args) -> int:
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                sharding=sharding)


#: (body, block index, stream dtype): one planned block per distinct
#: (segment kind, stride) the four bodies use, plus a bf16 stream case.
BLOCKS = [
    ("mobilenet_v1_spec", 0, "fp32"),        # fused2 s1, 112x112x32
    ("mobilenet_v1_spec", 1, "fp32"),        # fused2 s2
    ("mobilenet_v2_spec", 2, "fp32"),        # fused3 s1 + residual
    ("mobilenet_v2_spec", 3, "fp32"),        # fused3 s2, 144 lanes
    ("mnasnet_a1_spec", 4, "fp32"),          # pw + dw_se s1 + pw
    ("mnasnet_a1_spec", 3, "fp32"),          # pw + dw_se s2 + pw
    ("efficientnet_lite0_spec", 2, "fp32"),  # fusedmb s1 + residual
    ("efficientnet_lite0_spec", 1, "fp32"),  # fusedmb s2
    ("mobilenet_v2_spec", 3, "bf16"),        # fused3 s2, bf16 stream
]


@pytest.mark.parametrize(
    "body,index,dtype", BLOCKS,
    ids=[f"{b.removesuffix('_spec')}-b{i}-{d}" for b, i, d in BLOCKS])
def test_planned_block_compiles_for_v5e(one_chip, body, index, dtype):
    net = getattr(network, body)(1.0)
    stream = DtypePolicy(stream="bfloat16" if dtype == "bf16" else None)
    pol = KernelPolicy(impl="pallas", on_failure="raise",
                       dtype_policy=stream)
    nplan = network.plan_network(net, (1, RES, RES, net.c_in), policy=pol)
    spec, cp = net.blocks[index], nplan.plans[index]
    shape, in_dtype = nplan.block_shapes[index], nplan.block_dtypes[index]
    pdt = jnp.bfloat16 if dtype == "bf16" else jnp.float32
    params = [{k: _sds(v.shape, pdt, one_chip) for k, v in p.items()}
              for p in param_structs(spec, shape[-1], pdt)]
    block_pol = network.resolve_block_policies(net, pol)[index]
    run = lowering.lower(spec, cp, block_pol)
    n = _tpu_calls(run, params, _sds(shape, in_dtype, one_chip))
    assert n == cp.n_pallas_calls > 0, (
        [s.kind for s in cp.segments], n, cp.n_pallas_calls)


@pytest.mark.parametrize("stride,shape", [(1, (1, 58, 58, 128)),
                                          (2, (1, 113, 113, 64))],
                         ids=["s1", "s2"])
def test_standalone_dw_compiles_for_v5e(one_chip, stride, shape):
    f = _sds((3, 3, shape[-1]), jnp.float32, one_chip)
    fn = lambda x, f: dwconv2d_pallas(x, f, stride=stride)  # noqa: E731
    assert _tpu_calls(fn, _sds(shape, jnp.float32, one_chip), f) == 1


@pytest.mark.parametrize("g,ci,co", [(56 * 56, 128, 128), (28 * 28, 128, 256)],
                         ids=["after-s1", "after-s2"])
def test_standalone_pw_compiles_for_v5e(one_chip, g, ci, co):
    x = _sds((g, ci), jnp.float32, one_chip)
    w = _sds((ci, co), jnp.float32, one_chip)
    assert _tpu_calls(pwconv_pallas, x, w) == 1
