"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Of it this module keeps three kinds of interval, in nanoseconds on the
trace's one clock:

* device operations: the ``XLA Ops`` line of the first TPU plane, each
  named by its instruction in the optimized HLO (the event's name holds
  the whole instruction; the part before `` = `` is kept);
* device program runs: the ``XLA Modules`` line of that plane;
* the harness's host spans (``window``, ``dispatch``, ``sync``,
  ``next_input``) from the host plane.

The traced window runs from the start of the first program run that
begins inside the harness's ``window`` span to the end of the last one
that ends inside it, so it holds whole calls only.  Busy time is the
union of the device operations in it; every stretch between them is an
idle gap, shared out among the host spans that overlap it.
"""
import collections
import glob
import os
import shutil

HOST_SPANS = ("window", "dispatch", "sync", "next_input")


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return paths[-1] if paths else None


def short_name(text):
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def load(path):
    """{"ops", "modules", "host"}: lists of (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"ops": [], "modules": [], "host": []}
    device = None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and device is None:
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                device = plane
                for key, line in (("ops", "XLA Ops"),
                                  ("modules", "XLA Modules")):
                    out[key] = [(short_name(e.name), e.start_ns, e.end_ns)
                                for e in lines.get(line, _Empty()).events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [(e.name, e.start_ns, e.end_ns)
                                for e in line.events
                                if e.name in HOST_SPANS]
    for v in out.values():
        v.sort(key=lambda t: t[1])
    return out


class _Empty:
    events = ()


def union(intervals):
    """Merged, sorted (start, end) pairs."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def _overlap(a0, a1, b0, b1):
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce(events, kernel_ops, top=10):
    """The traced window's numbers, or None when it holds no whole call.

    ``kernel_ops`` names the device operations that are Pallas kernels
    (the compiled program's ``tpu_custom_call`` instructions)."""
    windows = [(s, e) for n, s, e in events["host"] if n == "window"]
    if not windows:
        return None
    ws, we = windows[0]
    calls = [(s, e) for _, s, e in events["modules"] if s >= ws and e <= we]
    if not calls:
        return None
    w0, w1 = calls[0][0], max(e for _, e in calls)
    ops = [(n, max(s, w0), min(e, w1)) for n, s, e in events["ops"]
           if e > w0 and s < w1]
    busy = union((s, e) for _, s, e in ops)
    busy_ns = sum(e - s for s, e in busy)
    kernels = {short_name(k) for k in kernel_ops}
    per_op = collections.Counter()
    kernel_ns = 0.0
    for n, s, e in ops:
        per_op[n] += e - s
        if n in kernels:
            kernel_ns += e - s
    op_ns = sum(per_op.values())

    gaps = []
    prev = w0
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if w1 > prev:
        gaps.append((prev, w1))
    spans = [(n, s, e) for n, s, e in events["host"] if n != "window"]
    idle_by = collections.Counter()
    j = 0
    for g0, g1 in gaps:
        covered = 0.0
        while j < len(spans) and spans[j][2] <= g0:
            j += 1
        k = j
        while k < len(spans) and spans[k][1] < g1:
            n, s, e = spans[k]
            ov = _overlap(g0, g1, s, e)
            idle_by[n] += ov
            covered += ov
            k += 1
        idle_by["other"] += max(0.0, (g1 - g0) - covered)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "calls": len(calls),
        "op_s": op_ns * 1e-9,
        "kernel_s": kernel_ns * 1e-9,
        "n_ops": len(ops),
        "top_ops": [[n, v * 1e-9] for n, v in per_op.most_common(top)],
        "idle_by_host": [[n, v * 1e-9] for n, v in idle_by.most_common(top)
                         if v > 0],
    }


def reduce_dir(trace_dir, kernel_ops):
    """Reduce the newest trace under ``trace_dir``, then delete the
    directory: what the metrics need is in the result."""
    path = find_xplane(trace_dir)
    try:
        return None if path is None else reduce(load(path), kernel_ops)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
