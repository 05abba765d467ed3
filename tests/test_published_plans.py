"""The per-block plan of every published body at the benchmark cells' shapes.

Each body is planned as ``chipbench/run.py``'s ``Program`` plans it: a
(B, 112, 112, c_in) body input, bf16 stream, the compiled Pallas kernels,
autotune off and failures raised.  Every block must keep its golden segment
kinds, lint without an error, and make the SAME halo of each ``fused2`` /
``fused3`` segment inside the kernel (the predicate by which the lowering
counts ``lowering.halo_in_kernel``).  A planner change that un-fuses a block
or sends its halo back through an HBM pad fails here, not only as a slower
chip run.
"""
import functools

import jax.numpy as jnp
import pytest

from repro import analysis
from repro.core import network
from repro.kernels import blocking
from repro.kernels.policy import DtypePolicy, KernelPolicy

RES = 112
BATCHES = (1, 128)
POLICY = KernelPolicy(impl="pallas", interpret=False, on_failure="raise",
                      autotune=False,
                      dtype_policy=DtypePolicy(stream="bfloat16"))

SE_BLOCK = "pw+dw_se+pw"
GOLDEN = {
    "mobilenet_v1": ("fused2",) * 13,
    "mobilenet_v2": ("fused2",) + ("fused3",) * 16,
    "mnasnet_a1": (("fused2",) + ("fused3",) * 2 + (SE_BLOCK,) * 3
                   + ("fused3",) * 4 + (SE_BLOCK,) * 5 + ("fused3",)),
    "efficientnet_lite0": ("fused2",) + ("fusedmb",) * 4 + ("fused3",) * 11,
}

CASES = [
    pytest.param(body, batch, i, id=f"{body}-b{batch}-b{i:02d}")
    for body, kinds in GOLDEN.items()
    for batch in BATCHES
    for i in range(len(kinds))
]


@functools.cache
def _planned(body: str, batch: int):
    net = getattr(network, f"{body}_spec")(1.0)
    nplan = network.plan_network(net, (batch, RES, RES, net.c_in),
                                 dtype=jnp.bfloat16, policy=POLICY)
    return net, nplan


@pytest.mark.parametrize("body,batch,i", CASES)
def test_published_block_plan(body, batch, i):
    net, nplan = _planned(body, batch)
    assert len(net.blocks) == len(GOLDEN[body])
    spec, cp, shape = net.blocks[i], nplan.plans[i], nplan.block_shapes[i]

    # (a) the segment kinds the cells run
    assert "+".join(seg.kind for seg in cp.segments) == GOLDEN[body][i]

    # (b) the static verifier passes the block
    report = analysis.analyze_chain(spec, cp, shape, dtype=jnp.bfloat16,
                                    policy=POLICY, label=f"b{i:02d}",
                                    jaxpr=False)
    assert report.ok, "\n".join(d.format() for d in report.errors)

    # (c) each fused DW->PW segment makes its SAME halo in VMEM
    h, w = shape[1:3]
    for seg in cp.segments:
        if seg.kind in ("fused2", "fused3"):
            dw = spec.stages[seg.stages[-2]]
            assert blocking.kernel_pads(dw.same_pads(h, w),
                                        dw.out_dims(h, w)[0],
                                        seg.plan.slab_h) is not None, seg
