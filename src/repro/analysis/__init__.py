"""repro.analysis — static plan/kernel verifier (DESIGN.md §8).

Proves the repo's resource claims BEFORE anything runs: fused blocks fit
VMEM (at the actual BlockSpecs the lowering emits, not the planner's
model), slabs + halos tile the output exactly once with in-bounds input
windows, blocks respect the TPU lane/sublane layout, and every cast in the
traced program is owned by the dtype policy.  Three passes:

* ``planlint``     — plan-field + derived-VMEM + grid-enumeration proofs
  (PL1xx rules) over the shared :class:`~repro.kernels.gridspec.
  KernelModel` each kernel builds its ``pl.BlockSpec``s from.
* ``mosaic_check`` — TPU tiling lint (MC2xx) over the same models.
* ``jaxpr_audit``  — fusion/cast audits (JX3xx) over the traced lowering.

Entry points: :func:`analyze_chain` / :func:`analyze_network` return a
:class:`~repro.analysis.diagnostics.Report`; :func:`verify_or_raise` turns
error diagnostics into :class:`PlanVerificationError` (the
``KernelPolicy(verify=True)`` debug knob); ``python -m repro.analysis``
runs the CI sweep over every benchmarked geometry and the full
MobileNetV1/V2 network plans.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp

from repro.analysis import jaxpr_audit, mosaic_check, planlint
from repro.analysis.diagnostics import (ERROR, INFO, WARNING, Diagnostic,
                                        Report)
from repro.kernels.blocking import ChainPlan
from repro.kernels.policy import DEFAULT_POLICY, KernelPolicy

__all__ = [
    "Diagnostic", "Report", "PlanVerificationError",
    "analyze_chain", "analyze_network", "verify_or_raise",
    "ERROR", "WARNING", "INFO",
]


class PlanVerificationError(AssertionError):
    """A plan failed static verification; ``.report`` holds the findings."""

    def __init__(self, report: Report):
        self.report = report
        rules = ", ".join(report.rules(ERROR))
        super().__init__(
            f"plan verification failed ({rules}):\n"
            + "\n".join(d.format() for d in report.errors))


def analyze_chain(spec, chain_plan: ChainPlan, x_shape: Sequence[int], *,
                  dtype=jnp.float32,
                  policy: KernelPolicy = DEFAULT_POLICY,
                  label: str = "chain", jaxpr: bool = True) -> Report:
    """All passes over one planned chain.  ``jaxpr=False`` skips the trace
    audit (used at plan time, where tracing has not happened yet and the
    static passes are the cheap invariant gate)."""
    report = Report()
    report.extend(planlint.lint_chain(spec, chain_plan, x_shape,
                                      label=label))
    for seg_label, _geom, model in planlint.chain_models(spec, chain_plan,
                                                         x_shape):
        if model is not None:
            report.extend(mosaic_check.lint_model(model,
                                                  f"{label}/{seg_label}"))
    if jaxpr:
        report.extend(jaxpr_audit.lint_chain_jaxpr(
            spec, chain_plan, x_shape, dtype=dtype, policy=policy,
            label=label))
    return report


def analyze_network(net, nplan, *,
                    policy: KernelPolicy = DEFAULT_POLICY,
                    block_dtype_policies=None, jaxpr: bool = True,
                    ) -> Report:
    """All passes over a resolved NetworkPlan: each block analyzed at the
    shape/dtype the plan walk recorded, under its effective policy, and
    the batch-minor kernel block 0 takes where the body input may arrive
    so (``planlint.body_input_model``)."""
    from repro.core.network import resolve_block_policies
    policies = resolve_block_policies(net, policy, block_dtype_policies)
    report = Report()
    for i, (spec, cp, shape, dt, pol) in enumerate(zip(
            net.blocks, nplan.plans, nplan.block_shapes,
            nplan.block_dtypes, policies)):
        report.extend(analyze_chain(
            spec, cp, shape, dtype=jnp.dtype(dt), policy=pol,
            label=f"block{i}", jaxpr=jaxpr).diagnostics)
    model = planlint.body_input_model(net.blocks[0], nplan.plans[0],
                                      nplan.block_shapes[0])
    if model is not None:
        segment = "block0/seg0/fused2.batch_minor"
        report.extend(planlint.lint_body_input(
            model, nplan.plans[0].vmem_budget, segment=segment))
        report.extend(mosaic_check.lint_model(model, segment))
    return report


def verify_or_raise(report: Report) -> Report:
    """Raise :class:`PlanVerificationError` on any error diagnostic."""
    if not report.ok:
        raise PlanVerificationError(report)
    return report


def lint_cached_plan(spec, chain_plan: ChainPlan, x_shape: Sequence[int],
                     *, label: str = "cache") -> Optional[str]:
    """Static-only validation for replayed tune-cache entries: the error
    rule ids as one string, or None when the plan is clean.  Kept tiny and
    import-light — ``kernels/autotune.py`` calls this lazily on every
    cache hit."""
    diags = planlint.lint_chain(spec, chain_plan, x_shape, label=label)
    rules = sorted({d.rule for d in diags if d.severity == ERROR})
    return ", ".join(rules) if rules else None
