"""Measured ChainPlan autotuner with a persistent on-disk cache (DESIGN §6).

The analytic planner (``core/chain.plan`` -> ``kernels/blocking.py``) picks
block shapes by VMEM arithmetic alone.  That is the right *feasibility*
filter, but on real hardware the fastest feasible blocking is not always
the first one the preference ladder hits — TVM (the paper's baseline) and
the ARMv8 DWConv follow-up both close that gap with a measurement loop over
a pruned candidate set.  This module is that loop for declared separable
chains:

* **candidate ladder** — per chain segment, enumerate a handful of feasible
  ``BlockPlan``s from the SAME ladders the analytic planner walks
  (``co_candidates`` x ``slab_candidates`` probed via
  ``plan_separable_at``/``plan_separable3_at``, the ``PW_G_CANDIDATES``
  GEMM panel ladder, ``snap_channels`` channel blocks), capped at
  :data:`MAX_SEGMENT_CANDIDATES` per segment;
* **timing harness** — each candidate ``ChainPlan`` is lowered
  (``kernels/lowering.lower`` — which executes plans verbatim, never
  re-plans) and timed jitted with ``block_until_ready``: warmup runs to
  absorb compilation, then median-of-k repeats.  Works on the Pallas
  interpret path in a CPU container and on compiled Pallas on real TPU;
* **persistent cache** — winners are stored in a JSON file keyed on the
  serialized problem signature (spec stages + input shape/dtype + VMEM
  budget + backend fingerprint), so repeated runs — and repeated identical
  layers within a run — replay cache hits with zero re-measurement.  A
  corrupted cache file is treated as empty (recoverable), never a crash.

A candidate only dethrones the incumbent when it wins by more than
:data:`REL_IMPROVEMENT` — on backends where block shapes cannot change the
wall time (the XLA reference path) the analytic plan therefore stays the
winner, and measured noise cannot flip plans between runs.

Entry points: ``core/chain.execute(policy=KernelPolicy(autotune=True))``
measures on the first call and replays the cache afterwards;
``core/chain.plan`` consults :func:`lookup_cached_plan`;
``benchmarks/run.py --autotune`` prints the analytic-vs-measured table.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import statistics
import time
import warnings
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.kernels import blocking, lowering
from repro.kernels.blocking import BlockPlan, ChainPlan, ChainSegment
from repro.kernels.diskstore import VersionedJsonStore
from repro.kernels.policy import KernelPolicy

#: Cache-file schema version; bump on incompatible layout changes (old
#: files then read as empty and re-tune, they are never mis-parsed).
#: v2: problem signatures gained the per-segment dtype policy (DESIGN §7) —
#: v1 keys hashed only the input dtype, so a bf16-streamed winner could
#: replay onto a native fp32 run of the same problem.
#: v3: the stage algebra grew SE and FusedMB stages (DESIGN §10); v2 stage
#: signatures could collide a FusedMB with a PW of the same features.
CACHE_VERSION = 3

#: Feasible candidates measured per chain segment (incl. the analytic plan).
MAX_SEGMENT_CANDIDATES = 8

#: A candidate must beat the incumbent by this relative margin to win —
#: keeps plan churn at measurement-noise level (and keeps the analytic plan
#: the winner on backends where blocks cannot change the wall time).
REL_IMPROVEMENT = 0.02


def default_cache_path() -> str:
    """$REPRO_TUNE_CACHE, else ~/.cache/repro/autotune.json."""
    env = os.environ.get("REPRO_TUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "autotune.json")


# ---------------------------------------------------------------------------
# Problem signature: the cache key schema (DESIGN.md §6)
# ---------------------------------------------------------------------------

def _stage_signature(s) -> dict:
    """Duck-typed stage descriptor, mirroring kernels/lowering.py's
    duck-typing so this module needs no import of core/chain.  Order
    matters: SE is the only stage with ``reduce``; FusedMB has BOTH
    ``features`` and ``stride`` (a PW has only ``features``)."""
    if hasattr(s, "reduce"):
        return {"kind": "se", "reduce": int(s.reduce),
                "activation": s.activation}
    if hasattr(s, "features") and hasattr(s, "stride"):
        return {"kind": "mb", "features": int(s.features),
                "stride": int(s.stride), "hf": int(s.hf), "wf": int(s.wf),
                "padding": s.padding.lower(), "activation": s.activation,
                "bias": bool(s.bias)}
    if hasattr(s, "features"):
        return {"kind": "pw", "features": int(s.features),
                "activation": s.activation, "bias": bool(s.bias)}
    return {"kind": "dw", "stride": int(s.stride), "hf": int(s.hf),
            "wf": int(s.wf), "padding": s.padding.lower(),
            "activation": s.activation, "bias": bool(s.bias)}


def backend_fingerprint(policy: KernelPolicy) -> dict:
    """What makes a measurement transferable: same resolved impl, interpret
    mode, jax backend and device kind (a v5e winner must not replay on a
    v4, nor an interpret-mode winner on compiled Pallas)."""
    dev = jax.devices()[0]
    return {
        "impl": policy.resolved(),
        "interpret": bool(policy.interpret),
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", "unknown"),
        "jax": jax.__version__,
    }


def problem_signature(spec, x_shape: Sequence[int], dtype,
                      policy: KernelPolicy) -> dict:
    """The full serialized problem identity a measurement is valid for."""
    residual = spec.residual
    return {
        "stages": [_stage_signature(s) for s in spec.stages],
        "residual": residual if isinstance(residual, bool) else str(residual),
        "x_shape": [int(v) for v in x_shape],
        "dtype": jnp.dtype(dtype).name,
        # ``dtype`` alone is NOT the precision identity: the dtype policy
        # changes both what was measured (streamed bytes) and what the plan
        # was budgeted at (stream-width VMEM), so a bf16-streamed winner
        # must never replay onto a native run of the same input dtype.
        "dtype_policy": policy.dtype_policy.signature(),
        "vmem_budget": int(policy.vmem_budget),
        "backend": backend_fingerprint(policy),
    }


def problem_key(spec, x_shape: Sequence[int], dtype,
                policy: KernelPolicy) -> str:
    """Stable digest of :func:`problem_signature` — the cache key."""
    blob = json.dumps(problem_signature(spec, x_shape, dtype, policy),
                      sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:20]


# ---------------------------------------------------------------------------
# ChainPlan (de)serialization
# ---------------------------------------------------------------------------

def serialize_chain_plan(cp: ChainPlan) -> dict:
    return {
        "segments": [
            {"kind": s.kind, "stages": list(s.stages),
             "plan": dataclasses.asdict(s.plan)}
            for s in cp.segments],
        "residual": bool(cp.residual),
        "residual_fused": bool(cp.residual_fused),
        "dtype_bytes": int(cp.dtype_bytes),
        "vmem_budget": int(cp.vmem_budget),
    }


def deserialize_chain_plan(d: dict) -> ChainPlan:
    segments = tuple(
        ChainSegment(kind=s["kind"], stages=tuple(int(i) for i in s["stages"]),
                     plan=BlockPlan(**{k: int(v)
                                       for k, v in s["plan"].items()}))
        for s in d["segments"])
    return ChainPlan(
        segments=segments,
        residual=bool(d["residual"]),
        residual_fused=bool(d["residual_fused"]),
        dtype_bytes=int(d["dtype_bytes"]),
        vmem_budget=int(d["vmem_budget"]),
    )


# ---------------------------------------------------------------------------
# Persistent cache
# ---------------------------------------------------------------------------

class TuneCache(VersionedJsonStore):
    """JSON-file-backed map ``key -> {signature, plan, measured_us, ...}``.

    All the durability mechanics live in the shared
    :class:`~repro.kernels.diskstore.VersionedJsonStore` (also the base of
    the runtime plan quarantine): load tolerates a missing file silently and
    WARNS on a corrupted/unreadable one before recovering as empty (the
    cache is a performance artifact, never a correctness dependency), and
    save is merge-on-write + atomic ``os.replace`` — two processes tuning
    disjoint problems into one file both keep their entries."""

    version = CACHE_VERSION


def validate_cached_plan(spec, cp: ChainPlan, x_shape: Sequence[int],
                         key: str, path: str) -> Optional[ChainPlan]:
    """Replayed cache entries must pass planlint before executing verbatim
    (DESIGN.md §8): an entry that became infeasible after a planner/kernel
    change — or was hand-edited — is dropped with a warning naming the
    cache path and the rule ids, and the caller falls back to the analytic
    planner / re-tunes.  A stale cache is a performance artifact, never a
    crash.  Lazy import: analysis sits above this module."""
    from repro.analysis import lint_cached_plan
    rules = lint_cached_plan(spec, cp, x_shape, label=f"tune-cache[{key}]")
    if rules is None:
        return cp
    warnings.warn(
        f"dropping tune-cache entry {key} from {path}: failed planlint "
        f"({rules}); falling back to the analytic plan (the entry is "
        "stale — delete the cache or re-tune)",
        stacklevel=3)
    return None


def lookup_cached_plan(spec, x_shape: Sequence[int], dtype,
                       policy: KernelPolicy) -> Optional[ChainPlan]:
    """Pure cache consult (no measurement): the tuned ChainPlan for this
    problem signature, or None on a miss / undecodable / planlint-rejected
    entry."""
    path = policy.tune_cache or default_cache_path()
    key = problem_key(spec, x_shape, dtype, policy)
    entry = TuneCache.load(path).get(key)
    if entry is None:
        return None
    try:
        cp = deserialize_chain_plan(entry["plan"])
    except (KeyError, TypeError, ValueError):
        return None
    cp = validate_cached_plan(spec, cp, x_shape, key, path)
    if cp is None:
        return None
    if getattr(policy, "on_failure", "raise") == "degrade":
        # a tuned winner that uses a quarantined rung must not replay
        # (DESIGN.md §9) — drop it and let the planner degrade
        from repro.runtime import quarantine  # lazy: runtime sits above
        banned = quarantine.load(quarantine.quarantine_path(policy)) \
            .banned(key)
        if banned and ("unfused" in banned
                       or any(s.kind in banned for s in cp.segments)):
            warnings.warn(
                f"dropping tune-cache entry {key} from {path}: its plan "
                f"uses quarantined rungs ({sorted(banned)} banned); the "
                "analytic planner will degrade around them", stacklevel=3)
            return None
    return cp


# ---------------------------------------------------------------------------
# Candidate enumeration (the pruned ladder the tuner measures)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _SegGeom:
    """Shapes a segment's kernel sees — what candidate feasibility needs."""
    kind: str
    ho: int
    wo: int
    ci: int        # segment input channels (raw input for fused3/fusedmb)
    c: int         # DW / expanded width (fused segments)
    co: int        # output channels
    stride: int
    hf: int
    wf: int
    g: int         # GEMM rows (pw); SE reduced width (dw_se / se)
    residual: bool  # the folded residual rides this segment's kernel
    pads: Optional[blocking.Pads] = None  # SAME pads (fused2 / fused3)


def _segment_geoms(stages, cp: ChainPlan,
                   x_shape: Sequence[int]) -> list[_SegGeom]:
    """Walk the chain shapes segment by segment (same walk as
    ``core/chain.chain_traffic``, duck-typed on the stage objects)."""
    b, h, w, c = (int(v) for v in x_shape)
    geoms = []
    for si, seg in enumerate(cp.segments):
        with_res = bool(cp.residual_fused and si == len(cp.segments) - 1)
        if seg.kind == "fused3":
            ex, d, proj = (stages[i] for i in seg.stages)
            ho, wo = d.out_dims(h, w)
            geoms.append(_SegGeom("fused3", ho, wo, c, ex.features,
                                  proj.features, d.stride, d.hf, d.wf, 0,
                                  with_res, d.same_pads(h, w)))
            h, w, c = ho, wo, proj.features
        elif seg.kind == "fused2":
            d, proj = (stages[i] for i in seg.stages)
            ho, wo = d.out_dims(h, w)
            geoms.append(_SegGeom("fused2", ho, wo, c, c, proj.features,
                                  d.stride, d.hf, d.wf, 0, with_res,
                                  d.same_pads(h, w)))
            h, w, c = ho, wo, proj.features
        elif seg.kind == "fusedmb":
            mb, proj = (stages[i] for i in seg.stages)
            ho, wo = mb.out_dims(h, w)
            geoms.append(_SegGeom("fusedmb", ho, wo, c, mb.features,
                                  proj.features, mb.stride, mb.hf, mb.wf,
                                  0, with_res))
            h, w, c = ho, wo, proj.features
        elif seg.kind == "dw_se":
            d, se = (stages[i] for i in seg.stages)
            ho, wo = d.out_dims(h, w)
            geoms.append(_SegGeom("dw_se", ho, wo, c, c, c, d.stride, d.hf,
                                  d.wf, se.reduce, False))
            h, w = ho, wo
        elif seg.kind == "se":
            se = stages[seg.stages[0]]
            geoms.append(_SegGeom("se", h, w, c, c, c, 1, 0, 0, se.reduce,
                                  False))
        elif seg.kind == "mb":
            mb = stages[seg.stages[0]]
            ho, wo = mb.out_dims(h, w)
            geoms.append(_SegGeom("mb", ho, wo, c, mb.features, mb.features,
                                  mb.stride, mb.hf, mb.wf, 0, False))
            h, w, c = ho, wo, mb.features
        elif seg.kind == "pw":
            st = stages[seg.stages[0]]
            geoms.append(_SegGeom("pw", h, w, c, 0, st.features, 1, 0, 0,
                                  b * h * w, False))
            c = st.features
        else:  # "dw"
            st = stages[seg.stages[0]]
            ho, wo = st.out_dims(h, w)
            geoms.append(_SegGeom("dw", ho, wo, c, c, c, st.stride, st.hf,
                                  st.wf, 0, False))
            h, w = ho, wo
    return geoms


def segment_candidates(geom: _SegGeom, base: BlockPlan, dtype,
                       vmem_budget: int,
                       max_candidates: int = MAX_SEGMENT_CANDIDATES,
                       ) -> list[BlockPlan]:
    """Up to ``max_candidates`` feasible BlockPlans for one segment, the
    analytic plan first.  Fused segments sweep the (Co panel x row slab)
    grid the analytic ladder prefers the corner of; pw sweeps the GEMM
    G-panel ladder; dw sweeps snapped channel blocks."""
    nb = blocking.dtype_bytes(dtype)
    cands = [base]
    if geom.kind in ("fused2", "fused3"):
        probe = (blocking.plan_separable3_at if geom.kind == "fused3"
                 else blocking.plan_separable_at)
        for cob in blocking.co_candidates(geom.co):
            if len(cands) >= max_candidates:
                break
            for slab_h in blocking.slab_candidates(geom.ho):
                if len(cands) >= max_candidates:
                    break
                if geom.kind == "fused3":
                    p = probe(geom.ho, geom.wo, geom.ci, geom.c, geom.co,
                              block_co=cob, slab_h=slab_h,
                              stride=geom.stride, hf=geom.hf, wf=geom.wf,
                              dtype=dtype, vmem_budget=vmem_budget,
                              residual=geom.residual, pads=geom.pads)
                else:
                    p = probe(geom.ho, geom.wo, geom.c, geom.co,
                              block_co=cob, slab_h=slab_h,
                              stride=geom.stride, hf=geom.hf, wf=geom.wf,
                              dtype=dtype, vmem_budget=vmem_budget,
                              residual=geom.residual, pads=geom.pads)
                if p is not None and p not in cands:
                    cands.append(p)
    elif geom.kind == "fusedmb":
        for cob in blocking.co_candidates(geom.co):
            if len(cands) >= max_candidates:
                break
            for slab_h in blocking.slab_candidates(geom.ho):
                if len(cands) >= max_candidates:
                    break
                p = blocking.plan_fused_mb_at(
                    geom.ho, geom.wo, geom.ci, geom.c, geom.co,
                    block_co=cob, slab_h=slab_h, stride=geom.stride,
                    hf=geom.hf, wf=geom.wf, dtype=dtype,
                    vmem_budget=vmem_budget, residual=geom.residual)
                if p is not None and p not in cands:
                    cands.append(p)
    elif geom.kind in ("dw_se", "se", "mb"):
        # no block ladder: dw_se is feasible only at full-channel
        # single-slab residency (anything else is WRONG, not slower), the
        # standalone SE GEMMs are tiny, and the standalone conv is
        # XLA-lowered — the analytic plan is the only candidate
        pass
    elif geom.kind == "pw":
        for bg in blocking.PW_G_CANDIDATES:
            if len(cands) >= max_candidates:
                break
            vb = blocking.pwconv_vmem_bytes(bg, base.block_c, base.block_co,
                                            nb)
            if vb > vmem_budget:
                continue
            p = dataclasses.replace(base, block_g=bg, vmem_bytes=vb)
            if p not in cands:
                cands.append(p)
    else:  # "dw"
        hi = (geom.ho - 1) * geom.stride + geom.hf
        wi = (geom.wo - 1) * geom.stride + geom.wf
        for target in (geom.c, 1024, 512, 256, 128, 64, 32, 16, 8):
            if len(cands) >= max_candidates:
                break
            cb = blocking.snap_channels(min(target, geom.c), geom.c)
            vb = blocking.dwconv2d_vmem_bytes(hi, wi, geom.ho, geom.wo, cb,
                                              geom.hf, geom.wf, nb)
            if vb > vmem_budget:
                continue
            p = BlockPlan(block_c=cb, block_co=0, slab_h=geom.ho, n_slabs=1,
                          halo_rows=0, vmem_bytes=vb, dtype_bytes=nb)
            if p not in cands:
                cands.append(p)
    return cands[:max_candidates]


def _with_segment_plan(cp: ChainPlan, si: int, plan: BlockPlan) -> ChainPlan:
    segments = tuple(
        dataclasses.replace(seg, plan=plan) if i == si else seg
        for i, seg in enumerate(cp.segments))
    return dataclasses.replace(cp, segments=segments)


# ---------------------------------------------------------------------------
# Timing harness
# ---------------------------------------------------------------------------

#: Transient-failure retries per measurement (RESOURCE_EXHAUSTED while a
#: sibling benchmark holds the device, a flaky interpret-mode trace):
#: retried this many times before the failure propagates to the tuner.
MEASURE_RETRIES = 2


def measure_run(run, params, x, *, warmup: int = 1, repeats: int = 5,
                retries: int = MEASURE_RETRIES) -> float:
    """Median wall seconds of ``run(params, x)`` jitted: ``warmup`` calls
    absorb compilation (and interpret-mode tracing), then median-of-k timed
    calls, each synchronized with ``block_until_ready``.

    Robustness (DESIGN.md §9): a classified backend failure
    (``runtime.failures.classify``) during warmup/timing is retried up to
    ``retries`` times — transient device contention must not abort a whole
    tune — then propagates to the caller (``autotune_chain`` folds it into
    the candidate's record).  Unrecognized exceptions propagate immediately.
    A first timed sample more than 10x the median of the rest is discarded
    as a straggler (late compilation, page-in): warmup should absorb it, but
    a deadline-scheduled first call occasionally slips through.
    """
    from repro.runtime import failures as _failures  # runtime sits above

    fn = jax.jit(run)
    for attempt in range(max(retries, 0) + 1):
        try:
            for _ in range(max(warmup, 1)):
                jax.block_until_ready(fn(params, x))
            ts = []
            for _ in range(max(repeats, 1)):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(params, x))
                ts.append(time.perf_counter() - t0)
            break
        except Exception as e:
            if _failures.classify(e) is None or attempt >= max(retries, 0):
                raise
            warnings.warn(
                f"measure_run: transient {type(e).__name__} during "
                f"measurement (attempt {attempt + 1}/{max(retries, 0) + 1}):"
                f" {e}; retrying", stacklevel=2)
    if len(ts) > 2 and ts[0] > 10.0 * statistics.median(ts[1:]):
        ts = ts[1:]  # discard the straggler first sample
    return float(statistics.median(ts))


# ---------------------------------------------------------------------------
# The tuner
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AutotuneResult:
    """What one autotune consult answered: the plan to execute, whether it
    replayed the cache (``n_measured == 0`` then), and the timings behind
    the decision (microseconds; on a hit, as recorded at tune time)."""
    plan: ChainPlan
    cache_hit: bool
    measured_us: float
    analytic_us: float
    n_measured: int
    key: str
    cache_path: str


def autotune_chain(spec, params, x, *, policy: KernelPolicy,
                   base_plan: ChainPlan,
                   warmup: int = 1, repeats: int = 5,
                   max_candidates: int = MAX_SEGMENT_CANDIDATES,
                   cache: Optional[TuneCache] = None) -> AutotuneResult:
    """Measured plan selection for one declared chain at one input.

    Cache hit: decode and return the stored winner — ZERO measurements.
    Miss: time the analytic ``base_plan``, then coordinate-descend over the
    per-segment candidate ladder (vary one segment, keep the others at the
    incumbent) timing the WHOLE chain per candidate, persist the winner.
    The analytic plan is always among the candidates, so the tuner can
    never do worse than the planner it replaces (up to measurement noise,
    bounded by :data:`REL_IMPROVEMENT`).
    """
    path = policy.tune_cache or default_cache_path()
    if cache is None:
        cache = TuneCache.load(path)
    key = problem_key(spec, x.shape, x.dtype, policy)
    entry = cache.get(key)
    if entry is not None:
        try:
            plan = deserialize_chain_plan(entry["plan"])
        except (KeyError, TypeError, ValueError):
            plan = None  # undecodable entry -> re-tune and overwrite
        if plan is not None:
            plan = validate_cached_plan(spec, plan, x.shape, key, path)
        if plan is not None:
            return AutotuneResult(
                plan=plan, cache_hit=True,
                measured_us=float(entry.get("measured_us", 0.0)),
                analytic_us=float(entry.get("analytic_us", 0.0)),
                n_measured=0, key=key, cache_path=path)

    from repro.runtime import failures as _failures  # runtime sits above

    failed: list = []

    def timed(cp: ChainPlan, label: str) -> float:
        run = lowering.lower(spec, cp, policy)
        try:
            return measure_run(run, params, x, warmup=warmup,
                               repeats=repeats)
        except Exception as e:
            # a candidate that cannot even run must lose, not abort the
            # tune — fold the classified failure into the entry's record
            # (unrecognized exceptions still propagate: those are bugs)
            if _failures.classify(e) is None:
                raise
            failed.append({"candidate": label,
                           "error": f"{type(e).__name__}: {e}"[:200]})
            return float("inf")

    t_base = timed(base_plan, "analytic")
    best, t_best = base_plan, t_base
    n_measured = 1
    geoms = _segment_geoms(spec.stages, base_plan, x.shape)
    for si, geom in enumerate(geoms):
        for cand in segment_candidates(geom, best.segments[si].plan,
                                       x.dtype, policy.vmem_budget,
                                       max_candidates):
            if cand == best.segments[si].plan:
                continue
            cp = _with_segment_plan(best, si, cand)
            t = timed(cp, f"seg{si}:{cand.block_c}/{cand.block_co}"
                          f"/{cand.slab_h}")
            n_measured += 1
            if t < t_best * (1.0 - REL_IMPROVEMENT):
                best, t_best = cp, t
    if t_best == float("inf"):
        # every candidate (incl. the analytic plan) failed to measure:
        # nothing to persist — return the analytic plan unpersisted and let
        # execution-time handling (the runtime ladder) deal with it
        warnings.warn(
            f"autotune: every candidate failed to measure for {key} "
            f"({len(failed)} failures, first: "
            f"{failed[0]['error'] if failed else '?'}); returning the "
            "analytic plan unpersisted", stacklevel=2)
        return AutotuneResult(plan=base_plan, cache_hit=False,
                              measured_us=float("inf"),
                              analytic_us=float("inf"),
                              n_measured=n_measured, key=key,
                              cache_path=path)
    entry = {
        "signature": problem_signature(spec, x.shape, x.dtype, policy),
        "plan": serialize_chain_plan(best),
        "measured_us": t_best * 1e6,
        "analytic_us": t_base * 1e6,
        "n_measured": n_measured,
    }
    if failed:
        entry["failed_candidates"] = failed
    cache.put(key, entry)
    cache.save()
    return AutotuneResult(plan=best, cache_hit=False,
                          measured_us=t_best * 1e6,
                          analytic_us=t_base * 1e6,
                          n_measured=n_measured, key=key, cache_path=path)
