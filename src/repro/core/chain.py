"""Declarative separable-chain API: spec -> plan -> lower -> execute.

The paper's whole argument is about orchestrating data movement across the
DW/PW pair; this module makes the *block* — not the op — the schedulable
unit (DESIGN.md §5).  A `SeparableSpec` declares an ordered chain of stages
(`PW` expand, `DW`, `PW` project, optional residual); `plan()` budgets the
whole chain against the policy's VMEM budget and answers with a
`ChainPlan` naming which contiguous stages fuse (and at which block
shapes); `kernels/lowering.lower()` maps that onto kernel passes;
`execute()` runs it.  Fusion is a planner decision, not a user boolean:
the planner fuses the longest run that fits and degrades
3-fused -> 2-fused -> unfused on its own.

The capability this unlocks (ROADMAP): a MobileNetV2 inverted residual
lowers to ONE kernel pass — the expansion GEMM is computed on the fly per
row slab inside the fused kernel, so neither the expanded tensor (6x the
input at the usual expansion factor) nor the DW output ever touches HBM.

    spec = inverted_residual_spec(c_in=32, c_out=32, expand=6)
    params = init_chain(key, spec, c_in=32)
    cp = plan(spec, x.shape)           # ChainPlan: [fused3] at MobileNet shapes
    y = execute(spec, params, x)       # or lower(spec, cp)(params, x)

`separable_block` / `inverted_residual` in ``core/separable.py`` are thin
shims over this API.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import intensity as it
from repro.kernels import autotune, blocking, lowering
from repro.kernels.blocking import ChainPlan, ChainSegment
from repro.kernels.epilogue import ACTIVATIONS
from repro.kernels.policy import DEFAULT_POLICY, KernelPolicy


# ---------------------------------------------------------------------------
# Spec: the declarative description of a separable block
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PW:
    """Pointwise stage: 1x1 conv / GEMM to ``features`` output channels.

    ``bias=False`` on an *expansion* PW is what makes it eligible for
    3-stage fusion (a biased expansion cannot commute with the zero SAME
    padding the fused kernel applies to the raw input —
    kernels/separable_fused.py).
    """
    features: int
    activation: Optional[str] = None
    bias: bool = False

    def __post_init__(self):
        assert self.activation is None or self.activation in ACTIVATIONS


@dataclasses.dataclass(frozen=True)
class DW:
    """Depthwise stage: ``hf x wf`` spatial conv at the incoming width."""
    stride: int = 1
    activation: Optional[str] = "relu6"
    hf: int = 3
    wf: int = 3
    padding: str = "same"
    bias: bool = False

    def __post_init__(self):
        assert self.activation is None or self.activation in ACTIVATIONS
        assert self.padding.lower() in ("same", "valid"), self.padding

    def out_dims(self, h: int, w: int) -> Tuple[int, int]:
        if self.padding.lower() == "same":
            return -(-h // self.stride), -(-w // self.stride)
        return ((h - self.hf) // self.stride + 1,
                (w - self.wf) // self.stride + 1)

    def same_pads(self, h: int, w: int) -> Optional[blocking.Pads]:
        """The SAME padding of an ``h x w`` input (None when VALID)."""
        if self.padding.lower() != "same":
            return None
        return blocking.same_pads(h, w, self.hf, self.wf, self.stride)


@dataclasses.dataclass(frozen=True)
class SE:
    """Squeeze-excite stage: global-avg-pool -> FC-reduce (``reduce``
    hidden units, ``activation``) -> FC-expand back to the incoming width
    -> sigmoid -> channelwise scale of the stage input.

    ``reduce`` is the explicit reduced width (builders compute it, e.g.
    ``max(1, c_block_input // 4)`` for MnasNet's se_ratio=0.25 counted on
    the *block* input, not the expanded width).  SE stages are always
    biased — both FCs carry a bias vector, per the reference networks.

    Note the sigmoid gate does NOT map 0 -> 0, so SE can never join the
    shared fused-kernel epilogue set (``kernels/epilogue.ACTIVATIONS`` is
    the zero-padding-commuting family); it gets its own lowering paths:
    fused as the ``dw_se`` segment epilogue (padded channels carry zero DW
    output, and 0 * sigmoid(gate) == 0 regardless of the gate), or the
    standalone two-GEMM ``se`` segment.
    """
    reduce: int
    activation: str = "relu"

    def __post_init__(self):
        assert self.reduce >= 1, self.reduce
        assert self.activation in ACTIVATIONS


@dataclasses.dataclass(frozen=True)
class FusedMB:
    """Fused-MBConv stage: a full ``hf x wf`` dense conv straight to
    ``features`` output channels — the EfficientNet-Lite edge block that
    replaces PW-expand + DW with one MXU-shaped convolution.  When followed
    by a PW projection the planner fuses the pair into ONE kernel pass
    (segment kind ``fusedmb``): conv-on-the-fly per row slab, projection
    GEMM accumulating in VMEM, the expanded tensor never touching HBM.
    """
    features: int
    stride: int = 1
    hf: int = 3
    wf: int = 3
    activation: Optional[str] = "relu6"
    padding: str = "same"
    bias: bool = False

    def __post_init__(self):
        assert self.activation is None or self.activation in ACTIVATIONS
        assert self.padding.lower() in ("same", "valid"), self.padding

    def out_dims(self, h: int, w: int) -> Tuple[int, int]:
        if self.padding.lower() == "same":
            return -(-h // self.stride), -(-w // self.stride)
        return ((h - self.hf) // self.stride + 1,
                (w - self.wf) // self.stride + 1)


Stage = Union[PW, DW, SE, FusedMB]


@dataclasses.dataclass(frozen=True)
class SeparableSpec:
    """An ordered chain of PW/DW stages + residual declaration.

    ``residual``: ``False`` (none), ``True`` (always add the chain input to
    the chain output), or ``"auto"`` (add it exactly when shapes allow —
    total stride 1 and c_out == c_in; the MobileNetV2 rule).
    """
    stages: Tuple[Stage, ...]
    residual: Union[bool, str] = False

    def __post_init__(self):
        assert self.stages, "empty chain"
        assert self.residual in (True, False, "auto"), self.residual
        assert all(isinstance(s, (PW, DW, SE, FusedMB))
                   for s in self.stages)

    def out_channels(self, c_in: int) -> int:
        c = c_in
        for s in self.stages:
            if isinstance(s, (PW, FusedMB)):
                c = s.features
        return c

    def stride_product(self) -> int:
        p = 1
        for s in self.stages:
            if isinstance(s, (DW, FusedMB)):
                p *= s.stride
        return p

    def residual_active(self, c_in: int) -> bool:
        if self.residual == "auto":
            return (self.stride_product() == 1
                    and self.out_channels(c_in) == c_in)
        return bool(self.residual)


def separable_block_spec(c_out: int, *, stride: int = 1,
                         activation: str = "relu6",
                         hf: int = 3) -> SeparableSpec:
    """MobileNetV1 separable block: DW(+bias) -> PW(+bias), both activated."""
    return SeparableSpec(stages=(
        DW(stride=stride, activation=activation, hf=hf, wf=hf, bias=True),
        PW(c_out, activation=activation, bias=True),
    ))


def inverted_residual_spec(c_in: int, c_out: int, *, expand: int = 6,
                           stride: int = 1, hf: int = 3,
                           activation: str = "relu6") -> SeparableSpec:
    """MobileNetV2 inverted residual: bias-free PW-expand -> DW, both with
    ``activation`` (MobileNetV2's relu6 by default) -> linear PW-project,
    residual when shapes allow."""
    return SeparableSpec(stages=(
        PW(c_in * expand, activation=activation),
        DW(stride=stride, activation=activation, hf=hf, wf=hf),
        PW(c_out),
    ), residual="auto")


def mbconv_se_spec(c_in: int, c_out: int, *, expand: int = 6,
                   stride: int = 1, hf: int = 3, se_ratio: float = 0.25,
                   activation: str = "relu") -> SeparableSpec:
    """MnasNet-A1 MBConv block with squeeze-excite: bias-free PW-expand ->
    DW -> SE -> linear PW-project, residual when shapes allow.  The SE
    reduced width is ``se_ratio`` of the *block input* width (the MnasNet /
    EfficientNet convention — NOT of the expanded width)."""
    return SeparableSpec(stages=(
        PW(c_in * expand, activation=activation),
        DW(stride=stride, activation=activation, hf=hf, wf=hf),
        SE(max(1, int(c_in * se_ratio))),
        PW(c_out),
    ), residual="auto")


def fused_mbconv_spec(c_in: int, c_out: int, *, expand: int = 6,
                      stride: int = 1, hf: int = 3,
                      activation: str = "relu6") -> SeparableSpec:
    """EfficientNet-Lite fused-MBConv block: a full ``hf x wf`` conv to the
    expanded width -> linear PW-project, residual when shapes allow."""
    return SeparableSpec(stages=(
        FusedMB(c_in * expand, stride=stride, hf=hf, wf=hf,
                activation=activation),
        PW(c_out),
    ), residual="auto")


def init_chain(key, spec: SeparableSpec, c_in: int,
               dtype=jnp.float32) -> list:
    """He-style init for a chain; one params dict per stage, aligned with
    ``spec.stages`` (see kernels/lowering.PARAM_KEYS)."""
    params = []
    c = c_in
    keys = jax.random.split(key, len(spec.stages))
    for k, s in zip(keys, spec.stages):
        if isinstance(s, PW):
            p = {"w": (jax.random.normal(k, (c, s.features), dtype)
                       / jnp.sqrt(c).astype(dtype))}
            if s.bias:
                p["b"] = jnp.zeros((s.features,), dtype)
            c = s.features
        elif isinstance(s, SE):
            k1, k2 = jax.random.split(k)
            p = {"w1": (jax.random.normal(k1, (c, s.reduce), dtype)
                        / jnp.sqrt(c).astype(dtype)),
                 "b1": jnp.zeros((s.reduce,), dtype),
                 "w2": (jax.random.normal(k2, (s.reduce, c), dtype)
                        / jnp.sqrt(s.reduce).astype(dtype)),
                 "b2": jnp.zeros((c,), dtype)}
        elif isinstance(s, FusedMB):
            p = {"f": (jax.random.normal(k, (s.hf, s.wf, c, s.features),
                                         dtype)
                       / jnp.sqrt(s.hf * s.wf * c).astype(dtype))}
            if s.bias:
                p["b"] = jnp.zeros((s.features,), dtype)
            c = s.features
        else:
            p = {"f": (jax.random.normal(k, (s.hf, s.wf, c), dtype)
                       / jnp.sqrt(s.hf * s.wf).astype(dtype))}
            if s.bias:
                p["b"] = jnp.zeros((c,), dtype)
        params.append(p)
    return params


# ---------------------------------------------------------------------------
# plan: budget the whole chain, decide what fuses (DESIGN.md §5)
# ---------------------------------------------------------------------------

def _fusable3(stages: Tuple[Stage, ...], i: int) -> bool:
    """stages[i:i+3] is a (bias-free PW-expand, DW, PW) run."""
    return (i + 2 < len(stages)
            and isinstance(stages[i], PW) and not stages[i].bias
            and isinstance(stages[i + 1], DW)
            and isinstance(stages[i + 2], PW))


def _fusable2(stages: Tuple[Stage, ...], i: int) -> bool:
    """stages[i:i+2] is a (DW, PW) run."""
    return (i + 1 < len(stages)
            and isinstance(stages[i], DW)
            and isinstance(stages[i + 1], PW))


def _fusable_mb(stages: Tuple[Stage, ...], i: int) -> bool:
    """stages[i:i+2] is a (FusedMB, PW) run — the fused-MBConv window."""
    return (i + 1 < len(stages)
            and isinstance(stages[i], FusedMB)
            and isinstance(stages[i + 1], PW))


def _fusable_dw_se(stages: Tuple[Stage, ...], i: int) -> bool:
    """stages[i:i+2] is a (DW, SE) run — the SE-as-epilogue window."""
    return (i + 1 < len(stages)
            and isinstance(stages[i], DW)
            and isinstance(stages[i + 1], SE))


def plan(spec: SeparableSpec, x_shape: Sequence[int], *,
         dtype=jnp.float32,
         policy: KernelPolicy = DEFAULT_POLICY) -> ChainPlan:
    """Budget the whole chain at ``x_shape`` and decide which contiguous
    stages fuse.

    Greedy longest-run-first with per-run VMEM feasibility, degrading
    3-fused -> 2-fused -> unfused: at each position try the 3-stage window
    (bias-free PW-expand -> DW -> PW, ``plan_separable3``), then the
    2-stage window (DW -> PW, ``plan_separable``), else lower a standalone
    stage and move on.  The residual is folded into the final segment's
    kernel when that segment is fused (the kernels' residual operand);
    otherwise it lowers to a separate add.  Deterministic, shape-only
    arithmetic — the returned ChainPlan is a cacheable, comparable unit.

    With ``policy.autotune`` the persistent tune cache
    (``kernels/autotune.py``) is consulted first and a measured winner for
    this exact problem signature wins over the analytic walk; on a cache
    miss this function still answers analytically (measurement needs data
    and happens in :func:`execute`).

    Mixed precision (DESIGN.md §7): all VMEM budgeting happens at the
    policy's STREAM dtype, not the input's native dtype — a bf16-streaming
    policy halves the streamed working set, so the same budget affords
    larger blocks (fewer panels, less input re-fetch).  The returned
    ``ChainPlan.dtype_bytes`` is likewise the stream width, which makes
    :func:`chain_traffic` model the streamed bytes automatically.

    Runtime hardening (DESIGN.md §9): under the default
    ``policy.on_failure == "degrade"`` the persistent plan quarantine is
    consulted (keyed like the tune cache, on the NATIVE input dtype) and
    fusion rungs a previous run failed at on this backend are excluded from
    the walk — the plan degrades at plan time, with zero retries.
    """
    banned: frozenset = frozenset()
    if policy.on_failure == "degrade":
        from repro.runtime import quarantine  # lazy: runtime sits above core
        banned = quarantine.banned_kinds(spec, x_shape, dtype, policy)
    if policy.autotune:
        cached = autotune.lookup_cached_plan(spec, x_shape, dtype, policy)
        if cached is not None:
            return _maybe_verify(spec, cached, x_shape, policy)
    b, h, w, c = x_shape
    dtype = policy.dtype_policy.stream_dtype(dtype)
    stages = spec.stages
    n = len(stages)
    # The residual also needs the spatial dims preserved (a valid-padded DW
    # shrinks them even at stride 1, which the channel/stride rule alone
    # would miss).
    ho_f, wo_f = h, w
    for s in stages:
        if isinstance(s, (DW, FusedMB)):
            ho_f, wo_f = s.out_dims(ho_f, wo_f)
    spatial_ok = (ho_f, wo_f) == (h, w)
    if spec.residual is True and not spatial_ok:
        raise ValueError(
            f"residual=True but the chain maps {h}x{w} -> {ho_f}x{wo_f}")
    res_active = spec.residual_active(c) and spatial_ok
    allowed = policy.fusion_allowed
    budget = policy.vmem_budget
    nb = blocking.dtype_bytes(dtype)

    segments: list = []
    i = 0
    while i < n:
        s = stages[i]
        if allowed and "fused3" not in banned and _fusable3(stages, i):
            d, proj = stages[i + 1], stages[i + 2]
            ho, wo = d.out_dims(h, w)
            with_res = res_active and i + 3 == n
            p3 = blocking.plan_separable3(
                ho, wo, c, stages[i].features, proj.features,
                stride=d.stride, hf=d.hf, wf=d.wf, dtype=dtype,
                vmem_budget=budget, residual=with_res,
                pads=d.same_pads(h, w))
            if p3 is not None:
                segments.append(ChainSegment("fused3", (i, i + 1, i + 2), p3))
                h, w, c = ho, wo, proj.features
                i += 3
                continue
        if allowed and "fusedmb" not in banned and _fusable_mb(stages, i):
            mb, proj = stages[i], stages[i + 1]
            ho, wo = mb.out_dims(h, w)
            with_res = res_active and i + 2 == n
            pmb = blocking.plan_fused_mb(
                ho, wo, c, mb.features, proj.features, stride=mb.stride,
                hf=mb.hf, wf=mb.wf, dtype=dtype, vmem_budget=budget,
                residual=with_res)
            if pmb is not None:
                segments.append(ChainSegment("fusedmb", (i, i + 1), pmb))
                h, w, c = ho, wo, proj.features
                i += 2
                continue
        if allowed and "fused2" not in banned and _fusable2(stages, i):
            d, proj = stages[i], stages[i + 1]
            ho, wo = d.out_dims(h, w)
            with_res = res_active and i + 2 == n
            p2 = blocking.plan_separable(
                ho, wo, c, proj.features, stride=d.stride, hf=d.hf,
                wf=d.wf, dtype=dtype, vmem_budget=budget,
                residual=with_res, pads=d.same_pads(h, w))
            if p2 is not None:
                segments.append(ChainSegment("fused2", (i, i + 1), p2))
                h, w, c = ho, wo, proj.features
                i += 2
                continue
        if allowed and "dw_se" not in banned and _fusable_dw_se(stages, i):
            d, se = stages[i], stages[i + 1]
            ho, wo = d.out_dims(h, w)
            hi_v = (ho - 1) * d.stride + d.hf
            wi_v = (wo - 1) * d.stride + d.wf
            pse = blocking.plan_dw_se(
                hi_v, wi_v, ho, wo, c, se.reduce, d.hf, d.wf,
                dtype=dtype, vmem_budget=budget)
            if pse is not None:
                segments.append(ChainSegment("dw_se", (i, i + 1), pse))
                h, w = ho, wo
                i += 2
                continue
        if isinstance(s, PW):
            pp = blocking.plan_pwconv(b * h * w, c, s.features, dtype=dtype,
                                      vmem_budget=budget)
            segments.append(ChainSegment("pw", (i,), pp))
            c = s.features
        elif isinstance(s, SE):
            segments.append(ChainSegment("se", (i,), blocking.plan_se(
                b, c, s.reduce, dtype=dtype, vmem_budget=budget)))
        elif isinstance(s, FusedMB):
            ho, wo = s.out_dims(h, w)
            segments.append(ChainSegment("mb", (i,), blocking.plan_mb(
                ho, wo, c, s.features, s.hf, s.wf, stride=s.stride,
                dtype=dtype, vmem_budget=budget)))
            h, w, c = ho, wo, s.features
        else:
            ho, wo = s.out_dims(h, w)
            hi_v = (ho - 1) * s.stride + s.hf
            wi_v = (wo - 1) * s.stride + s.wf
            dp = blocking.plan_dwconv2d(hi_v, wi_v, ho, wo, c, s.hf, s.wf,
                                        dtype=dtype, vmem_budget=budget)
            segments.append(ChainSegment("dw", (i,), dp))
            h, w = ho, wo
        i += 1

    residual_fused = bool(
        res_active and segments
        and segments[-1].kind in blocking.FUSED_KINDS)
    cp = ChainPlan(
        segments=tuple(segments),
        residual=res_active,
        residual_fused=residual_fused,
        dtype_bytes=nb,
        vmem_budget=budget,
    )
    return _maybe_verify(spec, cp, x_shape, policy)


def _maybe_verify(spec: SeparableSpec, cp: ChainPlan, x_shape,
                  policy: KernelPolicy) -> ChainPlan:
    """The ``policy.verify`` debug knob (DESIGN.md §8): run the static
    analyzer (planlint + mosaic rules — the cheap, trace-free passes) on
    the resolved plan and raise on any error diagnostic.  Lazy import:
    the analysis layer imports this module's consumers."""
    if policy.verify:
        from repro import analysis
        analysis.verify_or_raise(analysis.analyze_chain(
            spec, cp, x_shape, policy=policy, jaxpr=False))
    return cp


# ---------------------------------------------------------------------------
# lower / execute
# ---------------------------------------------------------------------------

#: Re-export: lowering lives at the kernel layer (kernels/lowering.py).
lower = lowering.lower


def resolve_plan(spec: SeparableSpec, params: Sequence[dict], x: jax.Array,
                 *, policy: KernelPolicy = DEFAULT_POLICY,
                 chain_plan: Optional[ChainPlan] = None) -> ChainPlan:
    """The plan :func:`execute` runs: the explicitly supplied plan
    (verified), the measured autotune winner (tune-on-first-execute on a
    miss), or the analytic :func:`plan` — exactly the resolution order of
    the raw execute path, factored out so the runtime executor
    (``repro.runtime.executor``) shares it verbatim."""
    if chain_plan is None:
        if policy.autotune:
            base = plan(spec, x.shape, dtype=x.dtype,
                        policy=dataclasses.replace(policy, autotune=False))
            return _maybe_verify(
                spec, autotune.autotune_chain(
                    spec, params, x, policy=policy, base_plan=base).plan,
                x.shape, policy)
        return plan(spec, x.shape, dtype=x.dtype, policy=policy)
    # an explicitly supplied plan bypasses plan() — verify it here so
    # the debug knob also gates hand-built / deserialized plans
    _maybe_verify(spec, chain_plan, x.shape, policy)
    return chain_plan


def execute(spec: SeparableSpec, params: Sequence[dict], x: jax.Array, *,
            policy: KernelPolicy = DEFAULT_POLICY,
            chain_plan: Optional[ChainPlan] = None) -> jax.Array:
    """Run the chain: plan (unless given), lower, execute.

    With ``policy.autotune`` the plan is the MEASURED winner from
    ``kernels/autotune.py``: the first call for a given problem signature
    times the candidate ladder and persists the winner; every later call
    (including in other processes) replays the cached plan with zero
    re-measurement.  Cache miss with tuning disabled — or tuning disabled
    outright — falls back to the analytic planner.

    Under the default ``policy.on_failure == "degrade"`` (or with
    ``policy.numeric_guard``) execution routes through the runtime
    degradation ladder (``repro.runtime.executor``, DESIGN.md §9): the
    steady-state path is identical — same plan resolution, same lowering,
    bitwise-identical outputs — plus a try/except; a classified backend
    failure quarantines the failing rung and retries one rung down.
    """
    if policy.on_failure == "degrade" or policy.numeric_guard:
        from repro.runtime import executor  # lazy: runtime sits above core
        return executor.execute_chain(spec, params, x, policy=policy,
                                      chain_plan=chain_plan)
    cp = resolve_plan(spec, params, x, policy=policy, chain_plan=chain_plan)
    return lower(spec, cp, policy)(params, x)


# ---------------------------------------------------------------------------
# ChainPlan traffic model (core/intensity.py per-segment terms)
# ---------------------------------------------------------------------------

def chain_traffic(spec: SeparableSpec, chain_plan: ChainPlan,
                  x_shape: Sequence[int], *,
                  dtype_bytes: Optional[int] = None) -> "it.Traffic":
    """Modeled HBM traffic + FLOPs of the planned chain: the sum of each
    segment's kernel-level model (``core/intensity.py``), plus the separate
    residual add when it is not folded into a fused pass, plus the
    standalone-DW bias/activation epilogue (``apply_epilogue`` in
    ``kernels/lowering.py`` is a separate elementwise op that reads and
    re-writes the whole ``(B,Ho,Wo,C)`` tensor — fused segments apply it
    inside the kernel for free).  This is the table the benchmark gate
    prints per block (3-stage fused vs 2-stage fused vs unfused)."""
    nb = dtype_bytes or chain_plan.dtype_bytes
    b, h, w, c = x_shape
    stages = spec.stages
    flops = 0.0
    bytes_ = 0.0
    for seg in chain_plan.segments:
        if seg.kind == "fused3":
            d, proj = stages[seg.stages[1]], stages[seg.stages[2]]
            ho, wo = d.out_dims(h, w)
            hi_v = (ho - 1) * d.stride + d.hf
            wi_v = (wo - 1) * d.stride + d.wf
            t = it.separable_traffic_fused3(
                b, hi_v, wi_v, c, stages[seg.stages[0]].features,
                proj.features, d.hf, d.wf, d.stride,
                block_co=seg.plan.block_co, slab_h=seg.plan.slab_h,
                dtype_bytes=nb)
            h, w, c = ho, wo, proj.features
        elif seg.kind == "fused2":
            d, proj = stages[seg.stages[0]], stages[seg.stages[1]]
            ho, wo = d.out_dims(h, w)
            hi_v = (ho - 1) * d.stride + d.hf
            wi_v = (wo - 1) * d.stride + d.wf
            t = it.separable_traffic_fused(
                b, hi_v, wi_v, c, proj.features, d.hf, d.wf, d.stride,
                block_co=seg.plan.block_co, slab_h=seg.plan.slab_h,
                dtype_bytes=nb)
            h, w, c = ho, wo, proj.features
        elif seg.kind == "fusedmb":
            mb, proj = stages[seg.stages[0]], stages[seg.stages[1]]
            ho, wo = mb.out_dims(h, w)
            hi_v = (ho - 1) * mb.stride + mb.hf
            wi_v = (wo - 1) * mb.stride + mb.wf
            t = it.fused_mb_traffic(
                b, hi_v, wi_v, c, mb.features, proj.features, mb.hf,
                mb.wf, mb.stride, block_co=seg.plan.block_co,
                slab_h=seg.plan.slab_h, dtype_bytes=nb)
            h, w, c = ho, wo, proj.features
        elif seg.kind == "dw_se":
            d, se = stages[seg.stages[0]], stages[seg.stages[1]]
            ho, wo = d.out_dims(h, w)
            hi_v = (ho - 1) * d.stride + d.hf
            wi_v = (wo - 1) * d.stride + d.wf
            t = it.dw_se_traffic(b, hi_v, wi_v, c, se.reduce, d.hf, d.wf,
                                 d.stride, dtype_bytes=nb)
            h, w = ho, wo
        elif seg.kind == "se":
            se = stages[seg.stages[0]]
            t = it.se_traffic(b, h, w, c, se.reduce, dtype_bytes=nb)
        elif seg.kind == "mb":
            mb = stages[seg.stages[0]]
            ho, wo = mb.out_dims(h, w)
            t = it.mb_traffic(b, h, w, c, mb.features, mb.hf, mb.wf,
                              mb.stride, dtype_bytes=nb)
            h, w, c = ho, wo, mb.features
        elif seg.kind == "pw":
            st = stages[seg.stages[0]]
            t = it.pwconv_traffic_rtrd(
                b * h * w, c, st.features, seg.plan.block_g,
                seg.plan.block_c, seg.plan.block_co, dtype_bytes=nb)
            c = st.features
        else:
            st = stages[seg.stages[0]]
            ho, wo = st.out_dims(h, w)
            hi_v = (ho - 1) * st.stride + st.hf
            wi_v = (wo - 1) * st.stride + st.wf
            t = it.dwconv2d_traffic(b, hi_v, wi_v, c, st.hf, st.wf,
                                    st.stride, dtype_bytes=nb)
            if st.bias or st.activation is not None:
                # standalone-DW epilogue: a separate elementwise op in the
                # lowering that re-reads and re-writes the whole output
                # tensor (+ the bias vector); XLA elides it when there is
                # neither bias nor activation, so only count it then
                epi = nb * (2 * b * ho * wo * c + (c if st.bias else 0))
                t = it.Traffic(t.flops + b * ho * wo * c,
                               t.bytes_hbm + epi)
            h, w = ho, wo
        flops += t.flops
        bytes_ += t.bytes_hbm
    if chain_plan.residual:
        if chain_plan.residual_fused:
            # the kernel streams the residual operand once; the accumulate
            # and store are already inside the fused pass
            bytes_ += nb * b * h * w * c
        else:
            # separate elementwise add: read both operands, write the sum
            bytes_ += nb * 3 * b * h * w * c
        flops += b * h * w * c
    return it.Traffic(flops, bytes_)
