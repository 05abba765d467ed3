"""Whole-network chain engine: NetworkSpec -> NetworkPlan -> execute_network.

PRs 1-4 made the separable BLOCK fast (fused single-pass inverted residuals,
dtype-aware VMEM planning, measured autotuning) — but a MobileNet was still
dispatched as a Python loop of independent per-block ``chain.execute`` calls,
re-deriving every plan on every call and streaming everything at one global
dtype.  This module is the network-level step (DESIGN.md §7):

* :class:`NetworkSpec` — an ordered tuple of :class:`~repro.core.chain.
  SeparableSpec` blocks plus the stem width; frozen/hashable, so it is a
  cache key.  :func:`mobilenet_v1_spec` / :func:`mobilenet_v2_spec` build
  the full paper backbones from their config tables (width multiplier
  included).
* :func:`plan_network` -> :class:`NetworkPlan` — every block's ``ChainPlan``
  resolved ONCE by walking the activation shapes/dtypes through the
  network, with the autotune cache consulted under a key derived from the
  WHOLE-network signature (per-block problem signatures concatenated).
* :func:`execute_network` — the entire backbone as ONE jitted call.  The
  (plan, jitted runner) pair is memoized per ``(spec, shape, dtype,
  policy)``, so steady-state calls do zero planning and zero tracing.
* per-segment mixed precision — the policy's :class:`~repro.kernels.policy.
  DtypePolicy` applies to every block, or ``block_dtype_policies`` pins a
  different policy per block (e.g. keep the first block fp32, stream the
  rest bf16).  ``core/intensity.network_traffic`` sums the per-block traffic
  models under whatever the plan was budgeted at, proving the bf16 HBM
  reduction analytically.

    net = mobilenet_v2_spec()
    params = init_network(key, net)
    y = execute_network(net, params, x,
                        policy=KernelPolicy(dtype_policy=BF16_STREAM))
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import json
import time
import warnings
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import chain
from repro.kernels import autotune, lowering
from repro.kernels.blocking import ChainPlan
from repro.kernels.policy import DEFAULT_POLICY, DtypePolicy, KernelPolicy
from repro.runtime import telemetry


# ---------------------------------------------------------------------------
# NetworkSpec: the declarative description of a whole backbone
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class NetworkSpec:
    """An ordered chain of separable blocks.  ``c_in`` is the channel width
    the first block consumes (the stem output — the stem conv itself is a
    dense 3x3 outside the paper's scope, as in ``examples/``)."""
    name: str
    c_in: int
    blocks: Tuple[chain.SeparableSpec, ...]

    def __post_init__(self):
        assert self.blocks, "empty network"
        assert all(isinstance(b, chain.SeparableSpec) for b in self.blocks)

    @property
    def n_blocks(self) -> int:
        return len(self.blocks)

    def out_channels(self) -> int:
        c = self.c_in
        for b in self.blocks:
            c = b.out_channels(c)
        return c

    def stride_product(self) -> int:
        p = 1
        for b in self.blocks:
            p *= b.stride_product()
        return p


def make_divisible(v: float, divisor: int = 8) -> int:
    """Channel rounding used by the MobileNet reference configs: round to
    the nearest multiple of ``divisor``, never dropping below 90% of ``v``."""
    new = max(divisor, int(v + divisor / 2) // divisor * divisor)
    if new < 0.9 * v:
        new += divisor
    return new


#: MobileNetV1 body after the 32-channel stem: (c_out, stride) per block
#: (Howard et al. 2017, Table 1 — the 13 depthwise-separable blocks).
MOBILENET_V1_BODY: Tuple[Tuple[int, int], ...] = (
    (64, 1), (128, 2), (128, 1), (256, 2), (256, 1), (512, 2),
    (512, 1), (512, 1), (512, 1), (512, 1), (512, 1), (1024, 2), (1024, 1),
)

#: MobileNetV2 body after the 32-channel stem: (t, c, n, s) rows
#: (Sandler et al. 2018, Table 2 — expansion, channels, repeats, stride).
MOBILENET_V2_BODY: Tuple[Tuple[int, int, int, int], ...] = (
    (1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
    (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1),
)

#: MnasNet-A1 body after the 32-channel stem: (t, c, n, s, k, se) rows
#: (Tan et al. 2019, Fig. 7(a) — expansion, channels, repeats, stride,
#: DW kernel, squeeze-excite; the same rows as the ``mnasnet_a1`` block
#: strings of the TensorFlow TPU reference, ``mnasnet_models.py``).  The
#: t=1 first row is the SepConv block.  Every activation is ReLU, the SE
#: gate a sigmoid, the projections linear.
MNASNET_A1_BODY: Tuple[Tuple[int, int, int, int, int, bool], ...] = (
    (1, 16, 1, 1, 3, False), (6, 24, 2, 2, 3, False),
    (3, 40, 3, 2, 5, True), (6, 80, 4, 2, 3, False),
    (6, 112, 2, 1, 3, True), (6, 160, 3, 2, 5, True),
    (6, 320, 1, 1, 3, False),
)

#: EfficientNet-Lite0 body after the 32-channel stem: (t, c, n, s, k,
#: fused) rows — the B0 table (Tan & Le 2019) with the Lite deployment
#: edits (no SE, relu6) and the early stages declared as fused-MBConv
#: (full 3x3 conv to the expanded width, the EfficientNet-Lite /
#: EdgeTPU-style mobile idiom this PR's ``FusedMB`` stage models).
EFFICIENTNET_LITE0_BODY: Tuple[Tuple[int, int, int, int, int, bool], ...] = (
    (1, 16, 1, 1, 3, False), (6, 24, 2, 2, 3, True),
    (6, 40, 2, 2, 3, True), (6, 80, 3, 2, 3, False),
    (6, 112, 3, 1, 5, False), (6, 192, 4, 2, 5, False),
    (6, 320, 1, 1, 3, False),
)


def mobilenet_v1_spec(width_mult: float = 1.0) -> NetworkSpec:
    """The 13-block MobileNetV1 body: DW(+bias) -> PW(+bias) per block."""
    blocks = tuple(
        chain.separable_block_spec(make_divisible(c * width_mult), stride=s)
        for c, s in MOBILENET_V1_BODY)
    return NetworkSpec(name=f"mobilenet_v1_{width_mult:g}",
                       c_in=make_divisible(32 * width_mult), blocks=blocks)


def mobilenet_v2_spec(width_mult: float = 1.0) -> NetworkSpec:
    """The 17-block MobileNetV2 body.  The t=1 first row has no expansion
    GEMM, so it declares a (DW, PW) chain — the planner fuses it as a
    single 2-stage pass; every t=6 row is a full inverted residual that
    plans to ONE 3-stage fused pass."""
    c = make_divisible(32 * width_mult)
    c_in = c
    blocks = []
    for t, co, n, s in MOBILENET_V2_BODY:
        co = make_divisible(co * width_mult)
        for i in range(n):
            stride = s if i == 0 else 1
            if t == 1:
                blocks.append(chain.SeparableSpec(stages=(
                    chain.DW(stride=stride, activation="relu6"),
                    chain.PW(co),
                ), residual="auto"))
            else:
                blocks.append(chain.inverted_residual_spec(
                    c, co, expand=t, stride=stride))
            c = co
    return NetworkSpec(name=f"mobilenet_v2_{width_mult:g}",
                       c_in=c_in, blocks=tuple(blocks))


def mnasnet_a1_spec(width_mult: float = 1.0) -> NetworkSpec:
    """The MnasNet-A1 body: SepConv + MBConv blocks, three stages carrying
    squeeze-excite (SE reduced width = 1/4 of the BLOCK INPUT, the MnasNet
    convention).  ReLU throughout, as published: the SepConv DW, every
    expansion and DW, and the SE hidden layer; the projections are
    linear.  The SE rows declare 4-stage (PW, DW, SE, PW) chains — the
    planner's ``dw_se`` window fuses the gate onto the DW pass when the
    full-channel working set fits VMEM (DESIGN.md §10)."""
    c = make_divisible(32 * width_mult)
    c_in = c
    blocks = []
    for t, co, n, s, k, se in MNASNET_A1_BODY:
        co = make_divisible(co * width_mult)
        for i in range(n):
            stride = s if i == 0 else 1
            if t == 1:
                blocks.append(chain.SeparableSpec(stages=(
                    chain.DW(stride=stride, activation="relu"),
                    chain.PW(co),
                ), residual="auto"))
            elif se:
                blocks.append(chain.mbconv_se_spec(
                    c, co, expand=t, stride=stride, hf=k))
            else:
                blocks.append(chain.inverted_residual_spec(
                    c, co, expand=t, stride=stride, hf=k,
                    activation="relu"))
            c = co
    return NetworkSpec(name=f"mnasnet_a1_{width_mult:g}",
                       c_in=c_in, blocks=tuple(blocks))


def efficientnet_lite0_spec(width_mult: float = 1.0) -> NetworkSpec:
    """The EfficientNet-Lite0 body: the B0 stage table with the Lite
    deployment edits (SE removed, relu6) and the early stages declared as
    fused-MBConv — a full 3x3 conv to the expanded width in place of
    PW-expand + DW.  Those rows plan to the single-pass ``fusedmb``
    segment (conv + PW-project in one kernel) when VMEM allows."""
    c = make_divisible(32 * width_mult)
    c_in = c
    blocks = []
    for t, co, n, s, k, fused in EFFICIENTNET_LITE0_BODY:
        co = make_divisible(co * width_mult)
        for i in range(n):
            stride = s if i == 0 else 1
            if t == 1:
                blocks.append(chain.SeparableSpec(stages=(
                    chain.DW(stride=stride, activation="relu6"),
                    chain.PW(co),
                ), residual="auto"))
            elif fused:
                blocks.append(chain.fused_mbconv_spec(
                    c, co, expand=t, stride=stride, hf=k))
            else:
                blocks.append(chain.inverted_residual_spec(
                    c, co, expand=t, stride=stride, hf=k))
            c = co
    return NetworkSpec(name=f"efficientnet_lite0_{width_mult:g}",
                       c_in=c_in, blocks=tuple(blocks))


def init_network(key, net: NetworkSpec, dtype=jnp.float32) -> list:
    """Per-block ``init_chain`` params, aligned with ``net.blocks``."""
    params = []
    c = net.c_in
    for k, spec in zip(jax.random.split(key, net.n_blocks), net.blocks):
        params.append(chain.init_chain(k, spec, c, dtype))
        c = spec.out_channels(c)
    return params


#: fp32 network vs :func:`reference_network`: both sides compute in fp32
#: (the kernels contract fp32 streams at HIGHEST), so only summation order
#: differs; one bf16 rounding of a GEMM operand would show as ~4e-3.
FP32_REL_TOL = 1e-5

#: bf16-streamed network vs the fp32 :func:`reference_network` (DESIGN.md
#: §7): one bf16 rounding per streamed operand per block, compounded over
#: 13-17 blocks, lands ~1e-2.
BF16_REL_TOL = 5e-2


def reference_network(net: NetworkSpec, params, x):
    """The fp32 per-block oracle: every block through the XLA reference
    lowering at ``"highest"`` matmul precision (XLA's TPU default is one
    bf16 pass), with fault injection suppressed — the yardstick itself
    must not degrade.  ``params`` are fp32 ``init_network`` params."""
    from repro.runtime import faultinject  # lazy: runtime sits above core
    oracle = KernelPolicy(impl="xla", on_failure="raise")
    with faultinject.suppressed(), jax.default_matmul_precision("highest"):
        for spec, p in zip(net.blocks, params):
            x = chain.execute(spec, p, x, policy=oracle)
    return x


def cast_network_params(params, dtype) -> list:
    """Cast every parameter leaf once, up front — deployment-style weight
    storage at the stream width, making the lowering's per-call casts
    no-ops (DESIGN.md §7)."""
    return jax.tree_util.tree_map(lambda a: a.astype(dtype), params)


# ---------------------------------------------------------------------------
# NetworkPlan: every block's ChainPlan, resolved once
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class NetworkPlan:
    """Per-block ``ChainPlan``s + the shape/dtype walk they were planned at.
    Frozen/hashable — a complete, reproducible execution recipe for the
    whole backbone (and the unit the network-level autotune cache stores)."""
    plans: Tuple[ChainPlan, ...]
    block_shapes: Tuple[Tuple[int, int, int, int], ...]
    block_dtypes: Tuple[str, ...]
    out_shape: Tuple[int, int, int, int]
    key: str

    @property
    def n_blocks(self) -> int:
        return len(self.plans)

    @property
    def n_kernel_passes(self) -> int:
        return sum(p.n_kernel_passes for p in self.plans)

    @property
    def n_pallas_calls(self) -> int:
        return sum(p.n_pallas_calls for p in self.plans)

    @property
    def fully_fused(self) -> bool:
        """Every block runs as ONE kernel pass."""
        return all(p.fully_fused for p in self.plans)

    def segment_histogram(self) -> dict:
        """{'fused3': n, 'fused2': m, ...} across all blocks."""
        counter = collections.Counter(
            seg.kind for p in self.plans for seg in p.segments)
        return dict(counter)


def resolve_block_policies(
    net: NetworkSpec, policy: KernelPolicy,
    block_dtype_policies: Optional[Sequence[DtypePolicy]] = None,
) -> Tuple[KernelPolicy, ...]:
    """The effective per-block KernelPolicy.

    Broadcasting one policy over the network: intermediate blocks hand off
    at the STREAM width (their ``out`` is cleared — only the final block
    honors the policy's ``out`` pin, otherwise a bf16-streamed network with
    ``out="float32"`` would widen at every block boundary).  With explicit
    ``block_dtype_policies`` each block's policy is taken verbatim — the
    caller states exactly what each block emits.
    """
    n = net.n_blocks
    if block_dtype_policies is None:
        dp = policy.dtype_policy
        inner = dataclasses.replace(dp, out=None)
        return tuple(
            dataclasses.replace(policy,
                                dtype_policy=dp if i == n - 1 else inner)
            for i in range(n))
    assert len(block_dtype_policies) == n, (len(block_dtype_policies), n)
    return tuple(dataclasses.replace(policy, dtype_policy=d)
                 for d in block_dtype_policies)


def _block_problems(net: NetworkSpec, x_shape, dtype,
                    policies: Sequence[KernelPolicy]):
    """Walk (shape, dtype) through the network: the per-block problem
    each ChainPlan answers.  Block i+1's input dtype is block i's OUT
    dtype (= its stream width for broadcast policies), exactly matching
    what the lowering emits at run time."""
    b, h, w, c = (int(v) for v in x_shape)
    assert c == net.c_in, (c, net.c_in)
    problems = []
    d = jnp.dtype(dtype)
    for spec, pol in zip(net.blocks, policies):
        problems.append(((b, h, w, c), d.name))
        for s in spec.stages:
            if isinstance(s, (chain.DW, chain.FusedMB)):
                h, w = s.out_dims(h, w)
        c = spec.out_channels(c)
        d = pol.dtype_policy.out_dtype(d)
    return problems, (b, h, w, c)


def network_signature(net: NetworkSpec, x_shape, dtype,
                      policy: KernelPolicy,
                      block_dtype_policies=None) -> dict:
    """The whole-network identity a tuned NetworkPlan is valid for: the
    concatenated per-block problem signatures (DESIGN.md §6 schema, §7)."""
    policies = resolve_block_policies(net, policy, block_dtype_policies)
    problems, _ = _block_problems(net, x_shape, dtype, policies)
    return {
        "name": net.name,
        "blocks": [
            autotune.problem_signature(spec, shape, dt, pol)
            for spec, (shape, dt), pol in zip(net.blocks, problems, policies)
        ],
    }


def network_key(net: NetworkSpec, x_shape, dtype, policy: KernelPolicy,
                block_dtype_policies=None) -> str:
    blob = json.dumps(
        network_signature(net, x_shape, dtype, policy, block_dtype_policies),
        sort_keys=True, separators=(",", ":"))
    return "net:" + hashlib.sha256(blob.encode("utf-8")).hexdigest()[:20]


def plan_network(net: NetworkSpec, x_shape, *, dtype=jnp.float32,
                 policy: KernelPolicy = DEFAULT_POLICY,
                 block_dtype_policies: Optional[Sequence[DtypePolicy]] = None,
                 ) -> NetworkPlan:
    """Resolve every block's ChainPlan ONCE by walking shapes/dtypes through
    the network.

    With ``policy.autotune`` the network-level tune-cache entry (keyed on
    :func:`network_key`) wins when present; otherwise each block's
    ``chain.plan`` answers (itself consulting the per-block cache), so a
    partially tuned cache still helps.  Measurement never happens here —
    :func:`tune_network` owns that.
    """
    policies = resolve_block_policies(net, policy, block_dtype_policies)
    problems, out_shape = _block_problems(net, x_shape, dtype, policies)
    key = network_key(net, x_shape, dtype, policy, block_dtype_policies)
    if policy.autotune:
        cached = _lookup_network_entry(key, policy)
        if cached is not None and _validate_network_entry(
                net, cached, policy,
                block_dtype_policies=block_dtype_policies):
            return _maybe_verify_network(net, cached, policy,
                                         block_dtype_policies)
    nplan = NetworkPlan(
        plans=tuple(
            chain.plan(spec, shape, dtype=jnp.dtype(dt), policy=pol)
            for spec, (shape, dt), pol in zip(net.blocks, problems,
                                              policies)),
        block_shapes=tuple(shape for shape, _ in problems),
        block_dtypes=tuple(dt for _, dt in problems),
        out_shape=out_shape,
        key=key,
    )
    return _maybe_verify_network(net, nplan, policy, block_dtype_policies)


def _validate_network_entry(net: NetworkSpec, nplan: NetworkPlan,
                            policy: KernelPolicy,
                            block_dtype_policies=None) -> bool:
    """Replayed whole-network cache entries must pass planlint block-wise
    before executing verbatim (DESIGN.md §8) and must not use any
    quarantined rung (DESIGN.md §9); a stale/banned entry is dropped with
    a warning (and the caller re-plans), never executed or crashed on.
    Lazy import: analysis/runtime sit above this module."""
    from repro.analysis import lint_cached_plan
    path = policy.tune_cache or autotune.default_cache_path()
    for i, (spec, cp, shape) in enumerate(zip(net.blocks, nplan.plans,
                                              nplan.block_shapes)):
        rules = lint_cached_plan(spec, cp, shape,
                                 label=f"net-cache/block{i}")
        if rules is not None:
            warnings.warn(
                f"dropping network tune-cache entry {nplan.key} from "
                f"{path}: block {i} failed planlint ({rules}); "
                "re-planning analytically", stacklevel=3)
            return False
    if policy.on_failure == "degrade":
        from repro.runtime import quarantine
        policies = resolve_block_policies(net, policy, block_dtype_policies)
        for i, (spec, cp, shape, dt, pol) in enumerate(zip(
                net.blocks, nplan.plans, nplan.block_shapes,
                nplan.block_dtypes, policies)):
            banned = quarantine.banned_kinds(spec, shape, jnp.dtype(dt), pol)
            if banned and ("unfused" in banned
                           or any(s.kind in banned for s in cp.segments)):
                warnings.warn(
                    f"dropping network tune-cache entry {nplan.key} from "
                    f"{path}: block {i} uses quarantined rungs "
                    f"({sorted(banned)} banned); re-planning analytically",
                    stacklevel=3)
                return False
    return True


def _maybe_verify_network(net: NetworkSpec, nplan: NetworkPlan,
                          policy: KernelPolicy,
                          block_dtype_policies=None) -> NetworkPlan:
    """The ``policy.verify`` knob at network scope: static analyzer over
    every block's resolved plan, raising on error diagnostics."""
    if policy.verify:
        from repro import analysis
        analysis.verify_or_raise(analysis.analyze_network(
            net, nplan, policy=dataclasses.replace(policy, verify=False),
            block_dtype_policies=block_dtype_policies, jaxpr=False))
    return nplan


def _serialize_network_plan(nplan: NetworkPlan) -> dict:
    return {
        "plans": [autotune.serialize_chain_plan(p) for p in nplan.plans],
        "block_shapes": [list(s) for s in nplan.block_shapes],
        "block_dtypes": list(nplan.block_dtypes),
        "out_shape": list(nplan.out_shape),
    }


def _deserialize_network_plan(key: str, d: dict) -> NetworkPlan:
    return NetworkPlan(
        plans=tuple(autotune.deserialize_chain_plan(p) for p in d["plans"]),
        block_shapes=tuple(tuple(int(v) for v in s)
                           for s in d["block_shapes"]),
        block_dtypes=tuple(str(v) for v in d["block_dtypes"]),
        out_shape=tuple(int(v) for v in d["out_shape"]),
        key=key,
    )


def _lookup_network_entry(key: str,
                          policy: KernelPolicy) -> Optional[NetworkPlan]:
    path = policy.tune_cache or autotune.default_cache_path()
    entry = autotune.TuneCache.load(path).get(key)
    if entry is None:
        return None
    try:
        return _deserialize_network_plan(key, entry["network_plan"])
    except (KeyError, TypeError, ValueError):
        return None


# ---------------------------------------------------------------------------
# tune_network: measured per-block plans, persisted under the network key
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class NetworkTuneResult:
    plan: NetworkPlan
    cache_hit: bool
    n_measured: int
    key: str
    cache_path: str


def tune_network(net: NetworkSpec, params, x, *,
                 policy: KernelPolicy,
                 block_dtype_policies: Optional[Sequence[DtypePolicy]] = None,
                 warmup: int = 1, repeats: int = 5) -> NetworkTuneResult:
    """Measured whole-network plan: autotune each block on its REAL
    intermediate activation (produced by executing the preceding tuned
    blocks), then persist the assembled NetworkPlan under the network key.

    A network-entry cache hit replays with ZERO measurements; per-block
    cache hits (e.g. from tuning a different network that shares layers)
    also skip measurement block-wise."""
    path = policy.tune_cache or autotune.default_cache_path()
    key = network_key(net, x.shape, x.dtype, policy, block_dtype_policies)
    cached = _lookup_network_entry(key, policy)
    if cached is not None:
        return NetworkTuneResult(plan=cached, cache_hit=True, n_measured=0,
                                 key=key, cache_path=path)
    policies = resolve_block_policies(net, policy, block_dtype_policies)
    problems, out_shape = _block_problems(net, x.shape, x.dtype, policies)
    plans = []
    n_measured = 0
    y = x
    for spec, p, pol in zip(net.blocks, params, policies):
        base = chain.plan(spec, y.shape, dtype=y.dtype,
                          policy=dataclasses.replace(pol, autotune=False))
        r = autotune.autotune_chain(spec, p, y, policy=pol, base_plan=base,
                                    warmup=warmup, repeats=repeats)
        plans.append(r.plan)
        n_measured += r.n_measured
        y = lowering.lower(spec, r.plan, pol)(p, y)
    nplan = NetworkPlan(
        plans=tuple(plans),
        block_shapes=tuple(shape for shape, _ in problems),
        block_dtypes=tuple(dt for _, dt in problems),
        out_shape=out_shape,
        key=key,
    )
    cache = autotune.TuneCache.load(path)
    cache.put(key, {
        "signature": network_signature(net, x.shape, x.dtype, policy,
                                       block_dtype_policies),
        "network_plan": _serialize_network_plan(nplan),
        "n_measured": n_measured,
    })
    cache.save()
    return NetworkTuneResult(plan=nplan, cache_hit=False,
                             n_measured=n_measured, key=key, cache_path=path)


# ---------------------------------------------------------------------------
# execute_network: the whole backbone as ONE jitted call
# ---------------------------------------------------------------------------

def device_layout(shape, dtype) -> Optional[Tuple[int, ...]]:
    """Major-to-minor order of the default device's layout for an array of
    ``shape`` and ``dtype``: the layout a jitted call's argument has unless
    its caller says otherwise.  On the TPU at a batch of 128 it is
    batch-minor (DESIGN.md §3).  None where the backend does not say."""
    from jax.experimental.layout import Layout
    dev = jax.devices()[0]
    try:
        pj = dev.client.get_default_layout(jnp.dtype(dtype), tuple(shape),
                                           dev)
    except jax.errors.JaxRuntimeError:  # a backend without layouts
        return None
    return tuple(Layout.from_pjrt_layout(pj).major_to_minor)


def array_layout(x) -> Optional[Tuple[int, ...]]:
    """Major-to-minor order of ``x``'s own layout; None for an array (or
    a tracer) that does not expose one."""
    try:
        return tuple(x.format.layout.major_to_minor)
    except AttributeError:
        return None


def build_network_fn(net: NetworkSpec, nplan: NetworkPlan,
                     policy: KernelPolicy = DEFAULT_POLICY,
                     block_dtype_policies=None, input_layout="device"):
    """Compose the per-block lowered runners into one ``run(params, x)``.
    Pure composition — every block executes its planned blocks verbatim
    (the lowering never re-plans), so jitting ``run`` compiles the whole
    backbone as one program.

    ``input_layout`` is the major-to-minor order the body input arrives
    in: ``"device"`` takes :func:`device_layout` for block 0's shape and
    dtype, None means unknown.  Where ``lowering.input_in_place`` admits
    it, block 0 reads the batch-minor input as it lies (DESIGN.md §3);
    decided here, once per build.

    Quarantine honoring (DESIGN.md §9): the planner already degrades
    banned FUSION rungs at plan time, but an ``"unfused"`` ban (the Pallas
    kernels themselves failed for a block's problem) cannot be expressed
    in a ChainPlan — it is honored here by lowering that block on the XLA
    reference backend, keeping the rest of the network on its fast path
    inside the same jitted program."""
    policies = resolve_block_policies(net, policy, block_dtype_policies)
    if policy.on_failure == "degrade":
        from repro.runtime import quarantine  # lazy: runtime sits above
        policies = tuple(
            dataclasses.replace(pol, impl="xla")
            if "unfused" in quarantine.banned_kinds(spec, shape,
                                                    jnp.dtype(dt), pol)
            else pol
            for spec, pol, shape, dt in zip(net.blocks, policies,
                                            nplan.block_shapes,
                                            nplan.block_dtypes))
    if input_layout == "device":
        input_layout = device_layout(nplan.block_shapes[0],
                                     nplan.block_dtypes[0])
    in_place = lowering.input_in_place(
        net.blocks[0], nplan.plans[0], nplan.block_shapes[0], input_layout,
        policies[0].resolved())
    runners = [lowering.lower(spec, cp, pol, batch_minor=in_place and i == 0)
               for i, (spec, cp, pol) in enumerate(zip(
                   net.blocks, nplan.plans, policies))]

    def run(params, x):
        assert len(params) == len(runners), (len(params), len(runners))
        x = lowering.body_input(x, in_place)
        for i, (r, p) in enumerate(zip(runners, params)):
            # compile-time only: names the block in every op's metadata
            with jax.named_scope(f"b{i:02d}"):
                x = r(p, x)
        return x

    return run


#: (net, shape, dtype, policy, block policies, explicit plan) ->
#: (NetworkPlan, jitted runner).  Every component of the key is frozen /
#: hashable, so steady-state execute_network calls do ZERO planning and
#: ZERO tracing.
_NETWORK_CACHE: dict = {}


def clear_network_cache() -> None:
    _NETWORK_CACHE.clear()


def execute_network(net: NetworkSpec, params, x, *,
                    policy: KernelPolicy = DEFAULT_POLICY,
                    network_plan: Optional[NetworkPlan] = None,
                    block_dtype_policies: Optional[Tuple[DtypePolicy, ...]]
                    = None):
    """Run the whole backbone in ONE jitted call.

    First call for a given (net, input shape/dtype, policy): resolve the
    NetworkPlan once — via :func:`tune_network` when ``policy.autotune``
    (cache-replayed when already tuned), else :func:`plan_network` — build
    the composed runner, jit it, and memoize the pair.  Every later call
    is a dictionary hit straight into the compiled program.

    Under the default ``policy.on_failure == "degrade"`` (or with
    ``policy.numeric_guard``) the call routes through the runtime guard
    (``repro.runtime.executor.run_network``, DESIGN.md §9): the
    steady-state path is the same ONE jitted call; a classified failure of
    the composed program recovers per-block, quarantining the failing
    blocks so the next call re-plans and re-jits around them.
    """
    if policy.on_failure == "degrade" or policy.numeric_guard:
        from repro.runtime import executor  # lazy: runtime sits above core
        return executor.run_network(
            net, params, x, policy=policy, network_plan=network_plan,
            block_dtype_policies=block_dtype_policies)
    return _execute_network_raw(
        net, params, x, policy=policy, network_plan=network_plan,
        block_dtype_policies=block_dtype_policies)


def _execute_network_raw(net: NetworkSpec, params, x, *,
                         policy: KernelPolicy = DEFAULT_POLICY,
                         network_plan: Optional[NetworkPlan] = None,
                         block_dtype_policies=None):
    """The unguarded engine behind :func:`execute_network`: plan, jit,
    memoize, run.  The (plan, runner) pair is memoized only AFTER its
    first call succeeds — a plan whose trace/compile fails must not poison
    the memo, or the re-plan after a quarantine write could never happen.

    Host spans (``telemetry.span``, DESIGN.md §9): ``network.memo`` (key +
    lookup), ``network.call`` (a hit's jitted call) and ``network.build``
    (a miss); a successful miss also counts as one ``network.builds``.
    With tracing off each span is a flag test and a shared null context."""
    with telemetry.span("network.memo"):
        cache_key = (net, x.shape, jnp.dtype(x.dtype).name, policy,
                     block_dtype_policies, network_plan)
        hit = _NETWORK_CACHE.get(cache_key)
    if hit is not None:
        with telemetry.span("network.call"):
            return hit[1](params, x)
    t0 = time.perf_counter_ns()
    with telemetry.span("network.build"):
        nplan = network_plan
        if nplan is None:
            if policy.autotune:
                nplan = tune_network(
                    net, params, x, policy=policy,
                    block_dtype_policies=block_dtype_policies).plan
            else:
                nplan = plan_network(
                    net, x.shape, dtype=x.dtype, policy=policy,
                    block_dtype_policies=block_dtype_policies)
        fn = jax.jit(build_network_fn(net, nplan, policy,
                                      block_dtype_policies,
                                      array_layout(x)))
        y = fn(params, x)
    _NETWORK_CACHE[cache_key] = (nplan, fn)
    telemetry.record_build(time.perf_counter_ns() - t0)
    return y
