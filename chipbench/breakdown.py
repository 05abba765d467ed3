"""Where one cell's time goes, by block and by program span, on the chip.

    python3 chipbench/breakdown.py --workload mnv2-b1-bf16 --seed 7

A diagnostic beside ``run.py``: it prints no result line and checks no
answer.  After ``run.setup`` it runs the cell's loop in four windows of
``SECONDS``, each as a ``--trace 1`` run does (its last second under the
profiler), with the program's spans (``repro.runtime.telemetry
.tracing()``) off, on, off, on.  Each window prints one JSON line:

* ``metrics``: the cell's per-layer metrics from BENCHMARK.json, read
  from the window as ``run.py`` reads them;
* ``network_memo_us``, ``network_call_us``: median µs of the program's
  spans ``network.memo`` and ``network.call`` over the window's calls;
* ``same_pad_share``: device op time in instructions scoped ``same_pad``
  over all device op time in the traced second, %;
* ``device_blocks``: device op seconds per ``bNN.<segment kind>``, and
  ``unscoped`` for ops outside every block (top 10);
* ``block_ideal_share``: for each block among them, its ideal time (the
  larger of its FLOPs over peak FLOP/s and its bytes over peak HBM
  bandwidth, counted as ``body.Body`` counts a call, from ``work/``)
  times the traced calls, over its device seconds, %;
* ``idle_by_program``: idle time charged to the innermost open program
  span, else to the harness spans as ``reduce_trace.reduce`` shares it;
* ``builds``: ``network.builds`` counted in the window (0 in steady
  state).

The process's ``network_build_s`` is printed once, before the windows.
An instruction's block and scopes come from the compiled program's text
(``scopes.py``).  All lines also go to
``chiprun_out/chipbench/breakdown/<workload>.json``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import body as body_mod  # noqa: E402
import reduce_trace  # noqa: E402
import run  # noqa: E402
import scopes  # noqa: E402

PROGRAM_SPANS = ("network.memo", "network.call", "network.build")
#: Metrics of the whole process, read once before the windows.
PROCESS_METRICS = ("network_build_s",)
#: Program tracing in each window, and each window's seconds.
WINDOWS = (False, True, False, True)
SECONDS = 6.0


def load_program_spans(path):
    """The program's spans in a trace: (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns) for e in line.events
                        if e.name in PROGRAM_SPANS]
    return sorted(out, key=lambda t: t[1])


def device_blocks(op_s, smap, top=10):
    """Device seconds per ``bNN.<kind>``; ops the compiled text does not
    name read ``unmapped``."""
    by = collections.Counter()
    for n, v in op_s.items():
        by[scopes.block_key(smap[n]) if n in smap else "unmapped"] += v
    return [[k, v] for k, v in by.most_common(top)]


def same_pad_share(op_s, smap):
    """% of device op time in ops scoped ``same_pad``; None when the
    program names no block (a program without scopes) or ran nothing."""
    total = sum(op_s.values())
    if total <= 0 or all(b == scopes.UNSCOPED for b, _ in smap.values()):
        return None
    pad = sum(v for n, v in op_s.items()
              if "same_pad" in smap.get(n, (None, ()))[1])
    return 100.0 * pad / total


def idle_by_program(events, program, kernel_ops, top=10):
    """Idle seconds of the traced window per innermost open span: a
    program span, else the harness spans and ``other`` as
    ``reduce_trace.reduce`` shares them.  The program's spans all run
    inside the harness's ``dispatch``, which keeps what they leave."""
    idle = collections.Counter(dict(
        reduce_trace.reduce(events, kernel_ops, top=None)["idle_by_host"]))
    host = sorted(events["host"] + program, key=lambda t: t[1])
    inner = dict(reduce_trace.reduce(dict(events, host=host), kernel_ops,
                                     top=None)["idle_by_host"])
    for n in PROGRAM_SPANS:
        if n in inner:
            idle[n] = inner[n]
            idle["dispatch"] -= inner[n]
    return [[n, v] for n, v in idle.most_common(top) if v > 0]


def block_ideal_s(bd, peak):
    """Each block's ideal time per call, counted as
    ``body.Body.ideal_s_per_call`` counts the whole body: 2 x MACs, one
    read of the block's input, one write of its output, its weights
    once, at the stream width."""
    out = []
    for b, (i, o) in zip(bd.blocks, bd.block_shapes()):
        macs = n_w = 0
        h, w, c = i
        for st in b["stages"]:
            work = bd.work[st["kind"]]
            macs += work.macs(st, h, w, c)
            n_w += work.n_weights(st, c)
            h, w, c = work.out_shape(st, h, w, c)
        acts = i[0] * i[1] * i[2] + o[0] * o[1] * o[2]
        nbytes = (bd.batch * acts + n_w) * bd.stream.itemsize
        out.append(max(2 * bd.batch * macs / peak["bf16_flops_per_s"],
                       nbytes / peak["hbm_bytes_per_s"]))
    return out


def compiled_text(prog, x):
    """The compiled text of the program ``prog`` runs on inputs like
    ``x`` (the same compile the window's calls hit;
    ``run.Program.compiled_counts`` keeps only the kernels' names)."""
    import jax
    net = prog.network
    nplan = net.plan_network(prog.net, x.shape, dtype=x.dtype,
                             policy=prog.policy)
    fn = jax.jit(net.build_network_fn(prog.net, nplan, prog.policy))
    return fn.lower(prog.params, x).compile().as_text()


def measure(cell, st, seconds, program_tracing, smap, kernel_ops, chip):
    """One window; returns its line."""
    from repro.runtime import telemetry
    telemetry.reset_runtime_telemetry()
    with telemetry.tracing() if program_tracing else contextlib.nullcontext():
        out, traced, compiles = run.window(cell, st, seconds, True)
    rep = telemetry.runtime_report()
    path = reduce_trace.find_xplane(st["trace_dir"])
    try:
        events = reduce_trace.load(path) if path else None
        program = load_program_spans(path) if path else []
    finally:
        shutil.rmtree(st["trace_dir"], ignore_errors=True)
    red = reduce_trace.reduce(events, kernel_ops, top=None) if events \
        else None
    ctx = {"host": out, "trace": red,
           "ideal_s_per_call": st["bd"].ideal_s_per_call(chip)}
    line = {"program_tracing": program_tracing,
            "requests": out["requests"] + (traced or {}).get("requests", 0),
            "compiles": compiles,
            "builds": rep["counters"].get("network.builds", 0),
            "metrics": {m: cell["readers"][m].read(ctx)
                        for m in cell["per_layer"]
                        if m not in PROCESS_METRICS}}
    spans = rep["spans"]
    for name in ("network.memo", "network.call"):
        key = "network_" + name.split(".")[1] + "_us"
        line[key] = spans[name]["median_us"] if name in spans else None
    line["spans"] = spans
    if red:
        op_s = dict(red["top_ops"])
        line["same_pad_share"] = same_pad_share(op_s, smap)
        line["device_blocks"] = device_blocks(op_s, smap)
        line["calls"] = red["calls"]
        ideal = block_ideal_s(st["bd"], chip)
        line["block_ideal_share"] = {
            k: 100.0 * ideal[int(k[1:].split(".")[0])] * red["calls"] / v
            for k, v in line["device_blocks"] if k.startswith("b")}
        line["idle_by_program"] = idle_by_program(events, program,
                                                  kernel_ops)
        line["op_s"] = red["op_s"]
        line["unmapped_s"] = sum(v for n, v in op_s.items()
                                 if n not in smap)
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    try:
        st = run.setup(cell, args.seed)
    except run.NoChip as e:
        print(f"breakdown: {e}", file=sys.stderr)
        return 3
    setup_s = time.perf_counter() - T_START
    x = st["pool"][0]
    text = compiled_text(st["prog"], x)
    smap = scopes.scope_map(text)
    kernel_ops = scopes.kernels(text)
    chip = body_mod.load_peak(st["devs"][0].device_kind)
    ctx = {"host": {}, "trace": None, "ideal_s_per_call": 0.0}
    head = {"workload": cell["name"], "seed": args.seed,
            "setup_s": setup_s, "tpu_custom_call": len(kernel_ops),
            "kernels_outside_blocks": [
                k for k in kernel_ops
                if smap.get(k, (scopes.UNSCOPED,))[0] == scopes.UNSCOPED],
            "metrics": {m: cell["readers"][m].read(ctx)
                        for m in cell["per_layer"]
                        if m in PROCESS_METRICS}}
    lines = [head]
    print(json.dumps(head), flush=True)
    for on in WINDOWS:
        lines.append(measure(cell, st, SECONDS, on, smap, kernel_ops, chip))
        print(json.dumps(lines[-1]), flush=True)
    st["prog"].free()
    out_dir = os.path.join(run.STATE_DIR, "breakdown")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{cell['name']}.json"), "w") as f:
        json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
