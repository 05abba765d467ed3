"""jaxpr_audit — trace planned segments and audit the fusion/cast claims
(DESIGN.md §8).

``ChainPlan.fully_fused`` and the per-segment ``single_pass`` claims are
the whole point of the fused lowering (neither intermediate in HBM); the
dtype policy's contract is that EVERY cast is owned by the lowering
boundary and accumulation stays fp32.  Parity tests check values, not
these structural claims — this pass checks them on the traced jaxpr:

* JX301 (error) — pass-count mismatch: the traced chain contains a
  different number of ``pallas_call``s than the plan's segment count (a
  fused plan that silently lowered to multiple passes, or re-planning
  inside the lowering).
* JX302 (error) — HBM intermediate: a ``fully_fused`` chain whose traced
  program runs compute primitives OUTSIDE the kernel — any such op
  materializes an intermediate the fusion claim says does not exist.
  (Data movement/layout prep — pad, slice, reshape, transpose, casts — is
  allowed: it feeds the one kernel.)
* JX310 (error) — rogue cast: a ``convert_element_type`` outside kernels
  to a dtype the :class:`~repro.kernels.policy.DtypePolicy` does not own
  (allowed: the stream dtype, the out dtype, and float32 — the
  accumulation width).
* JX311 (error) — accumulation not fp32: an in-kernel ``dot_general``
  whose ``preferred_element_type`` is not float32.

Tracing uses ``jax.make_jaxpr`` over the lowered runner with
``ShapeDtypeStruct`` params — no data, no compilation, works in interpret
mode.  The audit functions are granular (each takes a jaxpr) so the
seeded-violation tests can corrupt a callable and audit the trace.
"""
from __future__ import annotations

from typing import Iterable, List, Sequence, Set, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.diagnostics import ERROR, Diagnostic
from repro.kernels import lowering
from repro.kernels.blocking import ChainPlan
from repro.kernels.policy import KernelPolicy

#: Primitives a fully-fused chain may run OUTSIDE the kernel: data prep for
#: the one kernel pass (padding, layout, casts) — never compute.
ALLOWED_OUTSIDE = frozenset({
    "pallas_call", "jit", "closed_call", "custom_jvp_call",
    "custom_vjp_call", "convert_element_type", "pad", "slice",
    "dynamic_slice", "reshape", "broadcast_in_dim", "transpose", "squeeze",
    "concatenate", "iota", "copy",
})


def param_structs(spec, c_in: int, dtype) -> list:
    """``ShapeDtypeStruct`` params mirroring ``core/chain.init_chain``
    (duck-typed on the stage objects, like the lowering)."""
    d = jnp.dtype(dtype)
    params = []
    c = c_in
    for s in spec.stages:
        if hasattr(s, "reduce"):            # SE
            p = {"w1": jax.ShapeDtypeStruct((c, s.reduce), d),
                 "b1": jax.ShapeDtypeStruct((s.reduce,), d),
                 "w2": jax.ShapeDtypeStruct((s.reduce, c), d),
                 "b2": jax.ShapeDtypeStruct((c,), d)}
        elif hasattr(s, "features") and hasattr(s, "stride"):  # FusedMB
            p = {"f": jax.ShapeDtypeStruct((s.hf, s.wf, c, s.features), d)}
            if s.bias:
                p["b"] = jax.ShapeDtypeStruct((s.features,), d)
            c = s.features
        elif hasattr(s, "features"):        # PW
            p = {"w": jax.ShapeDtypeStruct((c, s.features), d)}
            if s.bias:
                p["b"] = jax.ShapeDtypeStruct((s.features,), d)
            c = s.features
        else:                               # DW
            p = {"f": jax.ShapeDtypeStruct((s.hf, s.wf, c), d)}
            if s.bias:
                p["b"] = jax.ShapeDtypeStruct((c,), d)
        params.append(p)
    return params


def trace_chain(spec, chain_plan: ChainPlan, x_shape: Sequence[int],
                dtype, policy: KernelPolicy):
    """The closed jaxpr of the lowered chain at these shapes (trace only —
    no data, no compile)."""
    run = lowering.lower(spec, chain_plan, policy)
    params = param_structs(spec, int(x_shape[-1]), dtype)
    x = jax.ShapeDtypeStruct(tuple(int(v) for v in x_shape),
                             jnp.dtype(dtype))
    return jax.make_jaxpr(run)(params, x)


def iter_eqns(jaxpr, in_kernel: bool = False) -> Iterable[Tuple[object,
                                                                bool]]:
    """Yield (eqn, in_kernel) over a jaxpr and every sub-jaxpr in its
    params; ``in_kernel`` is True inside a ``pallas_call`` body."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)   # ClosedJaxpr -> Jaxpr
    for eqn in inner.eqns:
        yield eqn, in_kernel
        child_in_kernel = in_kernel or eqn.primitive.name == "pallas_call"
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub, child_in_kernel)


def _sub_jaxprs(v):
    if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
        yield v
    elif isinstance(v, (tuple, list)):
        for item in v:
            yield from _sub_jaxprs(item)


# ---------------------------------------------------------------------------
# Granular audits (each over one traced jaxpr)
# ---------------------------------------------------------------------------

def audit_passes(jaxpr, n_expected: int, fully_fused: bool,
                 segment: str = "") -> List[Diagnostic]:
    """JX301 (pass count) and JX302 (HBM intermediates of a fused chain)."""
    diags: List[Diagnostic] = []
    n_calls = 0
    outside_compute = []
    for eqn, in_kernel in iter_eqns(jaxpr):
        if in_kernel:
            continue
        name = eqn.primitive.name
        if name == "pallas_call":
            n_calls += 1
        elif name not in ALLOWED_OUTSIDE:
            outside_compute.append(name)
    if n_calls != n_expected:
        diags.append(Diagnostic(
            "JX301", ERROR,
            f"traced chain runs {n_calls} kernel pass(es) but the plan "
            f"has {n_expected} segment(s)", segment,
            hint="the lowering re-planned or a fused segment silently "
                 "split"))
    if fully_fused and outside_compute:
        names = sorted(set(outside_compute))
        diags.append(Diagnostic(
            "JX302", ERROR,
            f"fully_fused chain runs compute outside the kernel: "
            f"{', '.join(names)} — an intermediate reaches HBM", segment,
            hint="every stage of a fused segment must execute inside the "
                 "single pallas_call"))
    return diags


def audit_casts(jaxpr, allowed_dtypes: Set[str],
                segment: str = "") -> List[Diagnostic]:
    """JX310: every outside-kernel ``convert_element_type`` must target a
    dtype the policy owns (stream, out, or the fp32 accumulation width)."""
    diags: List[Diagnostic] = []
    flagged = set()
    for eqn, in_kernel in iter_eqns(jaxpr):
        if in_kernel or eqn.primitive.name != "convert_element_type":
            continue
        new = jnp.dtype(eqn.params["new_dtype"]).name
        if new not in allowed_dtypes and new not in flagged:
            flagged.add(new)
            diags.append(Diagnostic(
                "JX310", ERROR,
                f"cast to {new} outside any kernel, not attributable to "
                f"the dtype policy (owns: {sorted(allowed_dtypes)})",
                segment,
                hint="all casts belong to the lowering boundary "
                     "(kernels/lowering.py, DESIGN.md §7)"))
    return diags


def audit_accumulation(jaxpr, segment: str = "") -> List[Diagnostic]:
    """JX311: in-kernel matmuls must accumulate fp32
    (``preferred_element_type=float32`` — what the MXU widens to)."""
    diags: List[Diagnostic] = []
    for eqn, in_kernel in iter_eqns(jaxpr):
        if not in_kernel or eqn.primitive.name != "dot_general":
            continue
        pref = eqn.params.get("preferred_element_type")
        if pref is None or jnp.dtype(pref) != jnp.float32:
            diags.append(Diagnostic(
                "JX311", ERROR,
                f"in-kernel dot_general accumulates at "
                f"{jnp.dtype(pref).name if pref is not None else 'input'} "
                "width, not float32", segment,
                hint="pass preferred_element_type=jnp.float32 "
                     "(blocking.ACC_BYTES is the fp32 contract)"))
            break
    return diags


# ---------------------------------------------------------------------------
# The whole pass over one planned chain
# ---------------------------------------------------------------------------

def lint_chain_jaxpr(spec, chain_plan: ChainPlan, x_shape: Sequence[int],
                     *, dtype, policy: KernelPolicy,
                     label: str = "chain") -> List[Diagnostic]:
    """Trace the lowered chain and run every jaxpr audit.  Pass-structure
    rules (JX301/JX302) only apply on the Pallas backend — the XLA
    reference path has no kernel passes to count."""
    jaxpr = trace_chain(spec, chain_plan, x_shape, dtype, policy)
    dp = policy.dtype_policy
    allowed = {dp.stream_dtype(dtype).name, dp.out_dtype(dtype).name,
               "float32"}
    diags = audit_casts(jaxpr, allowed, label)
    diags.extend(audit_accumulation(jaxpr, label))
    if policy.resolved() == "pallas":
        diags.extend(audit_passes(jaxpr, chain_plan.n_pallas_calls,
                                  chain_plan.fully_fused, label))
    return diags
