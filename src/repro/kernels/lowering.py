"""Lower a ChainPlan onto kernels: the execute half of spec -> plan -> run.

``core/chain.plan`` decides WHICH contiguous stages of a declared separable
chain fuse (DESIGN.md §5); this module maps that decision onto the actual
executables:

* ``fused3`` segments -> ``separable_fused_pallas(expand_w=...)`` — the
  whole PW-expand -> DW -> PW-project inverted residual as ONE kernel pass
  (expand-on-the-fly, neither intermediate in HBM);
* ``fused2`` segments -> ``separable_fused_pallas`` (the PR-2 DW -> PW
  kernel);
* SAME padding of a fused segment: a one-slab plan hands the kernel the
  unpadded input and its ``pads`` (the kernel makes the halo in VMEM); a
  slabbed plan pads the input in HBM first (``ops.pad_same``), as the
  ``fusedmb`` and ``dw_se`` segments always do.  The always-on telemetry
  counters ``lowering.halo_in_kernel`` / ``lowering.halo_padded`` count
  the two outcomes at trace time, the second for every SAME halo padded
  in HBM ahead of a fused kernel, whatever the kernel;
* the body input (``build_network_fn``'s block 0): when it arrives
  batch-minor — XLA's default TPU layout at a batch of 128, see
  :func:`input_in_place` — a one-slab stride-1 ``fused2`` first segment
  reads it as it lies, through ``separable_fused_batch_minor``, with no
  relayout ``copy`` ahead of the kernel; otherwise the first kernel takes
  the channel-minor array and XLA relays out whatever arrives.
  ``lowering.input_in_place`` / ``lowering.input_relayout`` count the two
  at trace time, once per build;
* a ``fused3`` kernel expands a group of input rows per GEMM where they
  collapse for free, else one row per GEMM (``plan_expand_rows``);
  ``fused3.expand_grouped`` / ``fused3.expand_per_row`` count the two at
  trace time, one per ``fused3`` segment;
* a chain's residual rides in its last kernel pass or is added after it
  as a separate op; ``lowering.residual_in_kernel`` /
  ``lowering.residual_separate`` count the two at trace time, one per
  chain with a residual;
* ``pw`` / ``dw`` segments -> the standalone ``ops.pwconv`` /
  ``ops.dwconv2d`` kernels;
* on the XLA backend every fused segment runs ``ref.separable_fused_ref``
  (same fusion numerics — fp32 intermediates — without Pallas).

The lowering never re-plans: each segment executes at exactly the block
shapes its ``ChainSegment.plan`` carries, so a ``ChainPlan`` is a complete,
reproducible execution recipe (and therefore a cacheable autotuning unit).

Stage objects are duck-typed (``features``/``activation``/``bias`` for PW,
``stride``/``hf``/``wf``/``padding``/``same_pads``/``activation``/``bias``
for DW) so this module depends only on the kernel layer; the spec
dataclasses live in ``core/chain.py``.

The dtype policy (``KernelPolicy.dtype_policy``, DESIGN.md §7) is applied
HERE, once per chain: the input and every parameter leaf are cast to the
stream dtype at segment boundaries (no-ops when the caller pre-cast them,
e.g. ``core/network.cast_network_params``), and the LAST kernel pass stores
at the policy's ``out`` dtype via the kernels' ``out_dtype`` epilogue —
accumulators stay fp32 inside every kernel regardless.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.kernels import blocking, ops, ref
from repro.kernels.blocking import ChainPlan
from repro.kernels.epilogue import apply_epilogue
from repro.kernels.fused_mbconv import fused_mbconv_pallas
from repro.kernels.policy import DEFAULT_POLICY, KernelPolicy
from repro.kernels.se_epilogue import dw_se_pallas
from repro.kernels.separable_fused import (BATCH_LANES,
                                           batch_minor_kernel_model,
                                           plan_expand_rows,
                                           separable_fused_batch_minor,
                                           separable_fused_pallas)
from repro.runtime import failures, faultinject, telemetry

#: Per-stage parameter leaves the lowering consumes: PW stages take
#: ``{"w": (Ci, Co)[, "b": (Co,)]}``, DW stages ``{"f": (Hf, Wf, C)[,
#: "b": (C,)]}``, SE stages ``{"w1": (C, Cse), "b1": (Cse,), "w2":
#: (Cse, C), "b2": (C,)}``, FusedMB stages ``{"f": (Hf, Wf, Ci, C)[,
#: "b": (C,)]}``; params are a sequence aligned with ``spec.stages``.
PARAM_KEYS = {"pw": ("w", "b"), "dw": ("f", "b"),
              "se": ("w1", "b1", "w2", "b2"), "mb": ("f", "b")}

#: Fault-injection point per segment kind (repro.runtime.faultinject,
#: DESIGN.md §9), checked before each dispatch; fused2 and fused3 share one
#: point because they share the kernel, as do fusedmb/mb and dw_se/se.
_INJECT = {"fused3": "lowering:separable_fused",
           "fused2": "lowering:separable_fused",
           "fusedmb": "lowering:fused_mbconv",
           "mb": "lowering:fused_mbconv",
           "dw_se": "lowering:se_epilogue",
           "se": "lowering:se_epilogue",
           "pw": "lowering:pwconv",
           "dw": "lowering:dwconv2d"}


#: ``x.format.layout.major_to_minor`` of a (B, H, W, C) array laid out
#: batch-minor: H major, then W, then C, the batch in the lanes.
BATCH_MINOR = (1, 2, 3, 0)


def _cast(a, dtype):
    return None if a is None else a.astype(dtype)


def input_in_place(spec, chain_plan: ChainPlan, x_shape, layout,
                   impl: str) -> bool:
    """Whether the chain's first kernel reads a body input of ``x_shape``
    laid out ``layout`` (major to minor; None when unknown) as it lies:
    the layout is :data:`BATCH_MINOR`, the batch a multiple of 128, the
    backend Pallas, and the first segment a ``fused2`` of one row slab
    whose stride-1 SAME halo the kernel makes, with no residual (which
    would need the channel-minor input).  The width must fill whole
    sublane tiles, for the kernel's image-major store, and the kernel's
    working set the plan's VMEM budget."""
    if layout is None or tuple(layout) != BATCH_MINOR or impl != "pallas":
        return False
    b, h, w, _ = x_shape
    seg = chain_plan.segments[0]
    if (b % BATCH_LANES or w % 8 or seg.kind != "fused2"
            or chain_plan.residual):
        return False
    d = spec.stages[seg.stages[0]]
    pads = d.same_pads(h, w)
    if (d.stride != 1 or pads is None or h < d.hf or blocking.kernel_pads(
            pads, d.out_dims(h, w)[0], seg.plan.slab_h) is None):
        return False
    return batch_minor_model(spec, chain_plan, x_shape).vmem_bytes() <= (
        chain_plan.vmem_budget)


def batch_minor_model(spec, chain_plan: ChainPlan, x_shape):
    """The kernel model of the batch-minor first segment (biases counted
    whether or not the spec has them, as the planner does)."""
    seg = chain_plan.segments[0]
    d, proj = (spec.stages[i] for i in seg.stages)
    b, h, w, c = (int(v) for v in x_shape)
    nb = seg.plan.dtype_bytes
    return batch_minor_kernel_model(
        b=b, h=h, w=w, c=c, co=proj.features, hf=d.hf, wf=d.wf,
        pads=d.same_pads(h, w), itemsize=nb, out_itemsize=nb,
        has_dw_bias=True, has_pw_bias=True)


def body_input(x, in_place: bool):
    """The body input as block 0 takes it, counted once per build: the
    (H, W, C, B) view of a batch-minor array — a bitcast on the TPU — or
    the array itself."""
    if in_place:
        telemetry.count("lowering.input_in_place")
        return jnp.transpose(x, BATCH_MINOR)
    telemetry.count("lowering.input_relayout")
    return x


def _run_fused(seg, stages, params, y, res, *, impl, interpret,
               stream_dtype, out_dtype):
    """One fused segment (2- or 3-stage) as a single kernel pass."""
    if seg.kind == "fused3":
        i_ex, i_dw, i_pw = seg.stages
        expand_w = params[i_ex]["w"].astype(stream_dtype)
        expand_act = stages[i_ex].activation
    else:
        i_dw, i_pw = seg.stages
        expand_w, expand_act = None, None
    d = stages[i_dw]
    proj = stages[i_pw]
    dw_f = params[i_dw]["f"].astype(stream_dtype)
    dw_b = _cast(params[i_dw].get("b"), stream_dtype)
    pw_w = params[i_pw]["w"].astype(stream_dtype)
    pw_b = _cast(params[i_pw].get("b"), stream_dtype)
    if impl == "xla":
        out = ref.separable_fused_ref(
            y, dw_f, pw_w, dw_b, pw_b, res,
            expand_w=expand_w, expand_activation=expand_act,
            stride=d.stride, padding=d.padding,
            dw_activation=d.activation, activation=proj.activation,
        )
        return out.astype(out_dtype)
    if d.padding.lower() not in ("same", "valid"):
        raise ValueError(d.padding)
    _, h, w, _ = y.shape
    pads = d.same_pads(h, w)
    halo = blocking.kernel_pads(pads, d.out_dims(h, w)[0], seg.plan.slab_h)
    if halo is not None:
        telemetry.count("lowering.halo_in_kernel")
    elif pads is not None:
        telemetry.count("lowering.halo_padded")
        y = ops.pad_same(y, d.hf, d.wf, d.stride)
    if expand_w is not None:
        ho, wo = d.out_dims(h, w)
        telemetry.count("fused3.expand_grouped"
                        if plan_expand_rows(h, w, ho * wo, halo) > 1
                        else "fused3.expand_per_row")
    return separable_fused_pallas(
        y, dw_f, pw_w, dw_b, pw_b, res,
        expand_w=expand_w, expand_activation=expand_act,
        stride=d.stride, dw_activation=d.activation,
        activation=proj.activation,
        block_c=seg.plan.block_c, block_co=seg.plan.block_co,
        slab_h=seg.plan.slab_h, interpret=interpret,
        out_dtype=jnp.dtype(out_dtype).name, pads=halo,
    )


def _run_fused_batch_minor(seg, stages, params, y, *, interpret,
                           stream_dtype, out_dtype):
    """A ``fused2`` first segment over the (H, W, C, B) body input."""
    i_dw, i_pw = seg.stages
    d = stages[i_dw]
    h, w = y.shape[:2]
    telemetry.count("lowering.halo_in_kernel")
    return separable_fused_batch_minor(
        y, params[i_dw]["f"].astype(stream_dtype),
        params[i_pw]["w"].astype(stream_dtype),
        _cast(params[i_dw].get("b"), stream_dtype),
        _cast(params[i_pw].get("b"), stream_dtype),
        pads=d.same_pads(h, w), dw_activation=d.activation,
        activation=stages[i_pw].activation, interpret=interpret,
        out_dtype=jnp.dtype(out_dtype).name)


def _run_fused_mb(seg, stages, params, y, res, *, impl, interpret,
                  stream_dtype, out_dtype):
    """One fused-MBConv segment (full conv + PW-project) as one pass."""
    i_mb, i_pw = seg.stages
    mb = stages[i_mb]
    proj = stages[i_pw]
    mb_f = params[i_mb]["f"].astype(stream_dtype)
    mb_b = _cast(params[i_mb].get("b"), stream_dtype)
    pw_w = params[i_pw]["w"].astype(stream_dtype)
    pw_b = _cast(params[i_pw].get("b"), stream_dtype)
    if impl == "xla":
        out = ref.fused_mbconv_ref(
            y, mb_f, pw_w, mb_b, pw_b, res,
            stride=mb.stride, padding=mb.padding,
            mb_activation=mb.activation, activation=proj.activation,
        )
        return out.astype(out_dtype)
    if mb.padding.lower() == "same":
        telemetry.count("lowering.halo_padded")
        y = ops.pad_same(y, mb.hf, mb.wf, mb.stride)
    elif mb.padding.lower() != "valid":
        raise ValueError(mb.padding)
    return fused_mbconv_pallas(
        y, mb_f, pw_w, mb_b, pw_b, res,
        stride=mb.stride, mb_activation=mb.activation,
        activation=proj.activation,
        block_c=seg.plan.block_c, block_co=seg.plan.block_co,
        slab_h=seg.plan.slab_h, interpret=interpret,
        out_dtype=jnp.dtype(out_dtype).name,
    )


def _run_dw_se(seg, stages, params, y, *, impl, interpret, stream_dtype,
               out_dtype):
    """One fused DW + SE-epilogue segment as one pass."""
    i_dw, i_se = seg.stages
    d = stages[i_dw]
    se = stages[i_se]
    dw_f = params[i_dw]["f"].astype(stream_dtype)
    dw_b = _cast(params[i_dw].get("b"), stream_dtype)
    sp = params[i_se]
    w1, b1 = sp["w1"].astype(stream_dtype), sp["b1"].astype(stream_dtype)
    w2, b2 = sp["w2"].astype(stream_dtype), sp["b2"].astype(stream_dtype)
    if impl == "xla":
        out = ref.dw_se_ref(
            y, dw_f, w1, b1, w2, b2, dw_b,
            stride=d.stride, padding=d.padding,
            dw_activation=d.activation, se_activation=se.activation,
        )
        return out.astype(out_dtype)
    if d.padding.lower() == "same":
        telemetry.count("lowering.halo_padded")
        y = ops.pad_same(y, d.hf, d.wf, d.stride)
    elif d.padding.lower() != "valid":
        raise ValueError(d.padding)
    return dw_se_pallas(
        y, dw_f, w1, b1, w2, b2, dw_b,
        stride=d.stride, dw_activation=d.activation,
        se_activation=se.activation, interpret=interpret,
        out_dtype=jnp.dtype(out_dtype).name,
    )


def _run_se(seg, stages, params, y, policy, *, impl, interpret,
            stream_dtype, out_dtype):
    """One standalone SE segment: pool + two pwconv GEMM passes + the
    sigmoid scale.  On the Pallas path the two (tiny) FCs run through the
    pwconv kernel — the SE gate itself is elementwise XLA work; the
    lowering owns the gate's cast back to the stream width (JX310)."""
    se = stages[seg.stages[0]]
    sp = params[seg.stages[0]]
    w1, b1 = sp["w1"].astype(stream_dtype), sp["b1"].astype(stream_dtype)
    w2, b2 = sp["w2"].astype(stream_dtype), sp["b2"].astype(stream_dtype)
    pooled = jnp.mean(y.astype(jnp.float32), axis=(1, 2)).astype(
        stream_dtype)
    hid = ops.pwconv(pooled, w1, b1, activation=se.activation,
                     impl=impl, interpret=interpret,
                     vmem_budget=policy.vmem_budget)
    pre = ops.pwconv(hid, w2, b2, activation=None,
                     impl=impl, interpret=interpret,
                     vmem_budget=policy.vmem_budget)
    gate = jax.nn.sigmoid(pre.astype(jnp.float32)).astype(stream_dtype)
    return (y * gate[:, None, None, :]).astype(out_dtype)


def lower(spec, chain_plan: ChainPlan,
          policy: KernelPolicy = DEFAULT_POLICY, *,
          batch_minor: bool = False,
          ) -> Callable[[Sequence[dict], jax.Array], jax.Array]:
    """Map a planned chain onto kernels; returns ``run(params, x)``.

    ``params`` is a sequence of per-stage dicts aligned with
    ``spec.stages`` (see :data:`PARAM_KEYS`).  The residual source is the
    chain input ``x``; it rides inside the final fused kernel pass when
    ``chain_plan.residual_fused``, else it is added as a separate op.
    With ``batch_minor`` (a chain that :func:`input_in_place` admits)
    ``x`` is the (H, W, C, B) view :func:`body_input` gives, and the first
    segment reads it through the batch-minor kernel.
    """
    impl = policy.resolved()
    interpret = policy.interpret
    stages = spec.stages
    segments = chain_plan.segments
    dp = policy.dtype_policy

    def run(params: Sequence[dict], x: jax.Array) -> jax.Array:
        assert len(params) == len(stages), (len(params), len(stages))
        sdt = dp.stream_dtype(x.dtype)
        odt = dp.out_dtype(x.dtype)
        y = x.astype(sdt)
        res = y if chain_plan.residual else None
        # the residual add after an unfused tail is a separate op, so the
        # LAST kernel must still store at the stream width in that case
        sep_res = chain_plan.residual and not chain_plan.residual_fused
        if chain_plan.residual:
            telemetry.count("lowering.residual_separate" if sep_res
                            else "lowering.residual_in_kernel")
        for si, seg in enumerate(segments):
            last = si == len(segments) - 1
            k_out = odt if (last and not sep_res) else sdt
            seg_res = res if (chain_plan.residual_fused and last) else None
            try:
                faultinject.check(_INJECT[seg.kind])
                # compile-time only: names the segment in op metadata
                with jax.named_scope(seg.kind):
                    if batch_minor and si == 0:
                        y = _run_fused_batch_minor(
                            seg, stages, params, y, interpret=interpret,
                            stream_dtype=sdt, out_dtype=k_out)
                    elif seg.kind in ("fused3", "fused2"):
                        y = _run_fused(seg, stages, params, y, seg_res,
                                       impl=impl, interpret=interpret,
                                       stream_dtype=sdt, out_dtype=k_out)
                    elif seg.kind == "fusedmb":
                        y = _run_fused_mb(seg, stages, params, y, seg_res,
                                          impl=impl, interpret=interpret,
                                          stream_dtype=sdt, out_dtype=k_out)
                    elif seg.kind == "dw_se":
                        y = _run_dw_se(seg, stages, params, y,
                                       impl=impl, interpret=interpret,
                                       stream_dtype=sdt, out_dtype=k_out)
                    elif seg.kind == "se":
                        y = _run_se(seg, stages, params, y, policy,
                                    impl=impl, interpret=interpret,
                                    stream_dtype=sdt, out_dtype=k_out)
                    elif seg.kind == "mb":
                        # standalone dense conv: XLA-lowered on every impl —
                        # the dense conv is MXU-shaped as-is, the Pallas win is
                        # the fused projection (segment kind "fusedmb")
                        st = stages[seg.stages[0]]
                        p = params[seg.stages[0]]
                        y = ref.conv2d_ref(
                            y, p["f"].astype(sdt), _cast(p.get("b"), sdt),
                            stride=st.stride, padding=st.padding,
                            activation=st.activation,
                        ).astype(k_out)
                    elif seg.kind == "pw":
                        st = stages[seg.stages[0]]
                        p = params[seg.stages[0]]
                        y = ops.pwconv(
                            y, p["w"].astype(sdt), _cast(p.get("b"), sdt),
                            activation=st.activation,
                            impl=impl, interpret=interpret,
                            block_g=policy.block_g or seg.plan.block_g,
                            block_co=policy.block_co or seg.plan.block_co,
                            block_ci=policy.block_ci or seg.plan.block_c,
                            vmem_budget=policy.vmem_budget,
                            out_dtype=jnp.dtype(k_out).name,
                        )
                    else:  # "dw"
                        st = stages[seg.stages[0]]
                        p = params[seg.stages[0]]
                        # execute the planned channel block verbatim —
                        # re-planning here would silently ignore
                        # policy.vmem_budget (and defeat measured autotuning,
                        # which keys on the plan it timed)
                        y = ops.dwconv2d(
                            y, p["f"].astype(sdt), stride=st.stride,
                            padding=st.padding,
                            impl=impl, interpret=interpret,
                            block_c=seg.plan.block_c,
                            vmem_budget=policy.vmem_budget,
                        )
                        y = apply_epilogue(y, _cast(p.get("b"), sdt),
                                           st.activation)
                        if last:
                            y = y.astype(k_out)
            except Exception as e:
                # tag recognized backend failures with the segment that
                # produced them (the runtime ladder keys its quarantine
                # decision on this); anything else propagates unwrapped
                f = failures.classify(e, segment_kind=seg.kind,
                                      segment_index=si,
                                      stage_indices=seg.stages)
                if f is None or f is e:
                    raise
                raise f from e
        if sep_res:
            y = (y + res).astype(odt)
        return y

    return run
