"""Execution policy for the paper's ops — the single backend-resolution rule.

``resolve_impl`` is the ONE place the "auto -> pallas on TPU, else xla" rule
lives.  It used to be implemented twice (``kernels/ops._resolve`` and
``core.pwconv.KernelPolicy.resolved``), which is exactly the kind of
duplicated decision the declarative chain API removes; both now call here.

``KernelPolicy`` is policy-only: *how* to execute (backend, interpret mode,
VMEM budget, explicit GEMM grid overrides) — never *what* to fuse.  Fusion
is a planner decision (``core/chain.plan`` -> ``ChainPlan``, DESIGN.md §5):
the planner fuses the longest stage run whose working set fits the policy's
``vmem_budget`` and degrades 3-fused -> 2-fused -> unfused on its own.  The
legacy ``fused`` boolean survives one release as a deprecated tri-state
override for the old call sites.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from repro.kernels.blocking import DEFAULT_VMEM_BUDGET


#: Dtype names a DtypePolicy may stream/store at (narrow enough to matter,
#: wide enough that fp32 accumulation recovers the precision).
STREAMABLE_DTYPES = ("float32", "bfloat16", "float16")


@dataclasses.dataclass(frozen=True)
class DtypePolicy:
    """Per-segment mixed-precision STREAMING policy (DESIGN.md §7).

    The paper's ops are memory-bound, so the width at which operands move
    HBM<->VMEM is a first-order performance knob.  This policy names it
    explicitly, per chain segment:

    * ``stream`` — dtype name every segment's *streamed* operands move at:
      the activation tensors entering/leaving each kernel pass and the
      weight/filter/bias tiles.  ``None`` keeps the native dtype of the
      input (the legacy behavior — an fp32 model streams fp32).  With
      ``"bfloat16"`` every streamed term of the traffic model halves while
      **accumulators stay fp32** (the kernels already upcast per tile and
      accumulate in fp32 VMEM scratch — ``blocking.ACC_BYTES`` — so only
      the HBM traffic narrows, not the arithmetic).
    * ``out`` — dtype name of the final chain/network output; ``None``
      stores at the stream width (the next block consumes it as-is).
      Pinning ``out="float32"`` makes the LAST kernel pass widen on store,
      inside its epilogue — no extra elementwise cast pass over the output.

    Frozen + hashable: it rides on :class:`KernelPolicy`, participates in
    the autotune cache key (``kernels/autotune.problem_signature`` — a
    bf16-streamed measured plan must never replay onto a native run), and
    the chain planner budgets VMEM at the stream width
    (``core/chain.plan``), so bf16 streaming also affords larger blocks.
    """
    stream: Optional[str] = None
    out: Optional[str] = None

    def __post_init__(self):
        for name in (self.stream, self.out):
            assert name is None or name in STREAMABLE_DTYPES, name

    def stream_dtype(self, native):
        """Dtype streamed operands move at, given the input's dtype."""
        import jax.numpy as jnp
        return jnp.dtype(self.stream) if self.stream else jnp.dtype(native)

    def out_dtype(self, native):
        """Dtype the final output is stored at, given the input's dtype."""
        import jax.numpy as jnp
        return (jnp.dtype(self.out) if self.out
                else self.stream_dtype(native))

    def signature(self) -> dict:
        """Serialized identity for the autotune cache key (DESIGN.md §6)."""
        return {"stream": self.stream, "out": self.out}


#: Stream at the input's native dtype (the legacy behavior).
NATIVE = DtypePolicy()

#: The DESIGN.md §7 default for mixed-precision serving: stream activations
#: and weights as bf16, accumulate fp32, store the network output as bf16.
BF16_STREAM = DtypePolicy(stream="bfloat16")


def contract_precision(stream_dtype):
    """MXU precision of the in-kernel GEMMs for operands streamed at
    ``stream_dtype``.  An fp32 stream asks for fp32 contraction
    (``HIGHEST``) rather than rely on Mosaic's default, so an fp32 network
    is fp32 end to end; a narrower stream contracts in one bf16 pass, as
    its weights already are bf16."""
    import jax.numpy as jnp
    if jnp.dtype(stream_dtype) == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return jax.lax.Precision.DEFAULT


def resolve_impl(impl: str) -> str:
    """'auto' -> 'pallas' on TPU backends, 'xla' elsewhere; else pass-through.

    Single source of truth for backend resolution (used by ``kernels/ops``,
    ``kernels/lowering`` and ``KernelPolicy.resolved``).
    """
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown impl {impl!r} (want auto|pallas|xla)")
    return impl


@dataclasses.dataclass(frozen=True)
class KernelPolicy:
    """Global execution policy for the paper's ops.

    impl: "auto" | "xla" | "pallas". interpret=True only for CPU validation.
    vmem_budget: HBM->VMEM working-set budget the chain planner and the
    per-kernel planners size blocks against (DESIGN.md §4/§5).
    block_g/co/ci: explicit GEMM grid overrides; None (default) defers to
    the dtype-aware planner (kernels/blocking.plan_pwconv).

    fused: DEPRECATED. Fusion is a planner decision now — ``None`` (the
    default) lets ``core/chain.plan`` fuse whatever fits the budget;
    ``False`` forces the unfused composition (the old default behavior);
    ``True`` is accepted for old call sites and means the same as ``None``.

    autotune: measured plan selection (kernels/autotune.py). ``True`` makes
    ``core/chain.plan``/``execute`` consult the persistent tune cache and,
    on a miss, measure the candidate ladder on the first ``execute`` call
    (the winner is persisted, so later runs replay it without measuring).
    ``False`` (default) keeps today's analytic planner.

    verify: the static-verification debug knob (repro.analysis, DESIGN.md
    §8).  ``True`` makes ``core/chain.plan`` / ``execute`` /
    ``core/network.plan_network`` run the static analyzer (planlint +
    mosaic rules) on every resolved plan and raise
    ``analysis.PlanVerificationError`` on any error-severity diagnostic —
    an infeasible or corrupted plan then fails at plan time with rule ids,
    not on hardware as a Mosaic lowering error.  ``False`` (default) keeps
    verification to the CI sweep (``python -m repro.analysis``).
    tune_cache: path of the on-disk JSON tune cache; ``None`` uses
    ``kernels/autotune.default_cache_path()`` ($REPRO_TUNE_CACHE or
    ~/.cache/repro/autotune.json).

    dtype_policy: per-segment mixed-precision streaming (:class:`DtypePolicy`,
    DESIGN.md §7).  The default :data:`NATIVE` streams at the input's dtype
    — every cast the lowering inserts is then a no-op, so fp32 behavior is
    bitwise-identical to the pre-policy code path.

    on_failure: the runtime hardening knob (repro.runtime, DESIGN.md §9).
    ``"degrade"`` (default) wraps execution in the runtime degradation
    ladder: a classified backend failure (Mosaic/Pallas lowering rejection,
    XLA compile/OOM, numeric-guard trip) quarantines the failing rung
    persistently and retries one rung down (fused3 -> fused2 -> unfused ->
    XLA reference), with bounded attempts and fallback telemetry; the
    steady-state success path is unchanged (bitwise-identical outputs, zero
    fallback events).  ``"raise"`` propagates the taxonomy error
    (``runtime.failures.KernelFailure`` subclass, tagged with the failing
    ChainPlan segment) to the caller instead — for tests, debugging, and
    callers that own their own retry policy.

    numeric_guard: ``True`` checks every chain/network output for
    non-finite values after execution (host-side sync) and treats a trip
    as a ``NumericalFailure`` — degraded or raised per ``on_failure``.
    Off by default: the guard costs a device sync per call.
    """
    impl: str = "auto"
    interpret: bool = False
    vmem_budget: int = DEFAULT_VMEM_BUDGET
    fused: Optional[bool] = None
    autotune: bool = False
    verify: bool = False
    tune_cache: Optional[str] = None
    block_g: Optional[int] = None
    block_co: Optional[int] = None
    block_ci: Optional[int] = None
    dtype_policy: DtypePolicy = NATIVE
    on_failure: str = "degrade"
    numeric_guard: bool = False

    def __post_init__(self):
        assert self.on_failure in ("degrade", "raise"), self.on_failure

    def resolved(self) -> str:
        return resolve_impl(self.impl)

    @property
    def fusion_allowed(self) -> bool:
        """Planner gate from the deprecated knob: only ``fused=False``
        (the explicit legacy opt-out) disables fusion."""
        return self.fused is not False


DEFAULT_POLICY = KernelPolicy()
