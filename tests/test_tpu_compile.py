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

import dataclasses
import os
import re

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
#: (segment kind, stride) the four bodies use, plus bf16 stream cases.
#: Every fused2 / fused3 block here makes its SAME halo in the kernel.
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
    ("mobilenet_v2_spec", 2, "bf16"),        # fused3 s1 + residual, bf16
    ("mobilenet_v2_spec", 1, "bf16"),        # fused3 s2 at 112, 28-row expand
    ("efficientnet_lite0_spec", 11, "bf16"),  # fused3 5x5 s2, pads (1, 2)
    ("efficientnet_lite0_spec", 12, "fp32"),  # fused3 5x5 s1 at 7x7
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


@pytest.mark.parametrize("body", ["mobilenet_v1_spec", "mobilenet_v2_spec"])
def test_body_has_no_pad_outside_its_kernels_for_v5e(one_chip, body):
    """The V1 and V2 bodies at a 224 image, bf16 stream, batch 1, as one
    program: one ``tpu_custom_call`` per planned Pallas pass and no HLO
    ``pad`` beside them — every fused block makes its SAME halo in VMEM."""
    net = getattr(network, body)(1.0)
    pol = KernelPolicy(impl="pallas", on_failure="raise",
                       dtype_policy=DtypePolicy(stream="bfloat16"))
    shape = (1, RES, RES, net.c_in)
    nplan = network.plan_network(net, shape, dtype=jnp.bfloat16, policy=pol)
    params = [[{k: _sds(v.shape, jnp.bfloat16, one_chip)
                for k, v in p.items()}
               for p in param_structs(spec, bshape[-1], jnp.bfloat16)]
              for spec, bshape in zip(net.blocks, nplan.block_shapes)]
    text = jax.jit(network.build_network_fn(net, nplan, pol)).lower(
        params, _sds(shape, jnp.bfloat16, one_chip)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == (
        nplan.n_pallas_calls)
    assert not re.findall(r"^\s*(?:ROOT\s+)?%\S+ = \S+ pad\(", text,
                          re.MULTILINE)


def test_body_names_its_blocks_and_kernels_for_v5e(one_chip):
    """The first three V2 blocks (fused2, fused3 stride 2, fused3 with a
    residual) through ``build_network_fn``: each op's metadata names its
    block and segment kind, no SAME pad is left outside the kernels, and
    each Pallas kernel is named by its segment kind."""
    full = network.mobilenet_v2_spec(1.0)
    net = dataclasses.replace(full, blocks=full.blocks[:3])
    pol = KernelPolicy(impl="pallas", on_failure="raise",
                       dtype_policy=DtypePolicy(stream="bfloat16"))
    shape = (1, RES, RES, net.c_in)
    nplan = network.plan_network(net, shape, dtype=jnp.bfloat16, policy=pol)
    params = [[{k: _sds(v.shape, jnp.bfloat16, one_chip)
                for k, v in p.items()}
               for p in param_structs(spec, bshape[-1], jnp.bfloat16)]
              for spec, bshape in zip(net.blocks, nplan.block_shapes)]
    text = jax.jit(network.build_network_fn(net, nplan, pol)).lower(
        params, _sds(shape, jnp.bfloat16, one_chip)).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', text)
    for block, kind in (("b00", "fused2"), ("b01", "fused3"),
                        ("b02", "fused3")):
        assert any(f"/{block}/{kind}/" in n for n in op_names), (block, kind)
    assert not any("/same_pad/" in n for n in op_names)
    kernels = re.findall(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = .*'
                         r'custom_call_target="tpu_custom_call".*'
                         r'op_name="([^"]*)"', text, re.MULTILINE)
    assert [(name.split(".")[0], op.split("/")[1:3]) for name, op in
            kernels] == [("fused2", ["b00", "fused2"]),
                         ("fused3", ["b01", "fused3"]),
                         ("fused3", ["b02", "fused3"])]
    assert all(op.endswith("/pallas_call") for _, op in kernels)


def test_mnasnet_body_compiles_for_v5e(one_chip):
    """The MnasNet-A1 body at a 224 image, bf16 stream, batch 1, as one
    program: one ``tpu_custom_call`` per planned Pallas pass (32), and a
    SAME pad in HBM ahead of each of the 8 ``dw_se`` kernels and of no
    other — what ``lowering.halo_padded`` counts."""
    net = network.mnasnet_a1_spec(1.0)
    pol = KernelPolicy(impl="pallas", on_failure="raise",
                       dtype_policy=DtypePolicy(stream="bfloat16"))
    shape = (1, RES, RES, net.c_in)
    nplan = network.plan_network(net, shape, dtype=jnp.bfloat16, policy=pol)
    params = [[{k: _sds(v.shape, jnp.bfloat16, one_chip)
                for k, v in p.items()}
               for p in param_structs(spec, bshape[-1], jnp.bfloat16)]
              for spec, bshape in zip(net.blocks, nplan.block_shapes)]
    text = jax.jit(network.build_network_fn(net, nplan, pol)).lower(
        params, _sds(shape, jnp.bfloat16, one_chip)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == (
        nplan.n_pallas_calls) == 32
    same_pads = sorted({re.search(r"/(b\d\d)/dw_se/same_pad/", n).group(1)
                        for n in re.findall(r'op_name="([^"]*)"', text)
                        if "/same_pad/" in n})
    assert same_pads == ["b03", "b04", "b05", "b10", "b11", "b12", "b13",
                         "b14"]


@pytest.mark.parametrize("body", ["mobilenet_v1_spec", "mobilenet_v2_spec"])
def test_batch_minor_body_input_is_read_in_place_for_v5e(one_chip, body):
    """The first two blocks at batch 128, bf16 stream: the v5e lays the
    body input out batch-minor, and block 0's kernel reads a bitcast of
    it — no HLO ``copy`` or ``transpose`` takes the entry parameter."""
    full = getattr(network, body)(1.0)
    net = dataclasses.replace(full, blocks=full.blocks[:2])
    pol = KernelPolicy(impl="pallas", on_failure="raise",
                       dtype_policy=DtypePolicy(stream="bfloat16"))
    shape = (128, RES, RES, net.c_in)
    nplan = network.plan_network(net, shape, dtype=jnp.bfloat16, policy=pol)
    params = [[{k: _sds(v.shape, jnp.bfloat16, one_chip)
                for k, v in p.items()}
               for p in param_structs(spec, bshape[-1], jnp.bfloat16)]
              for spec, bshape in zip(net.blocks, nplan.block_shapes)]
    fn = network.build_network_fn(net, nplan, pol,
                                  input_layout=lowering.BATCH_MINOR)
    text = jax.jit(fn).lower(params, _sds(shape, jnp.bfloat16,
                                          one_chip)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == (
        nplan.n_pallas_calls) == 2
    (param,) = re.findall(
        r"(%\S+) = bf16\[128,112,112,32\]\{0,3,2,1:\S* parameter\(", text)
    users = [line for line in text.splitlines()
             if re.search(re.escape(param) + r"[,)]", line)
             and " parameter(" not in line]
    assert users and all(re.search(r"= \S+ bitcast\(", u) for u in users)
    views = {re.match(r"\s*(%\S+) =", u).group(1) for u in users}
    (b00,) = [line for line in text.splitlines()
              if 'custom_call_target="tpu_custom_call"' in line
              and "/b00/fused2/" in line]
    assert any(v + "," in b00 or v + ")" in b00 for v in views)
    assert not re.findall(r"= \S+ (?:copy|transpose)\(" + re.escape(param),
                          text)
