"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload mnv2-b128-bf16 --seed 7 \
        --seconds 10 --trace 0

The cell (its entry in BENCHMARK.json, and ``workloads/<name>.json`` for
its correctness limit) names a configuration (``configs/<config>.json``)
and a traffic mix (``traffic/<traffic>.json``): a loop (``latency``: one
client, one request at a time; ``throughput``: ``in_flight`` calls kept
queued), a batch and a pool of inputs.  Weights and inputs are made on
the device from ``--seed``; the window drives the program's
``execute_network`` on the compiled Pallas kernels.  After the window, a
sample of the requests it served, drawn from the seed, is compared with
the plain fp32 reference (``body.py``).  The last line of standard
output is one JSON object: the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics (one reader each,
``metrics/<name>.py``) with ``--trace 1``.

It needs a TPU: on any other platform it exits non-zero and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
STATE_DIR = os.path.join(ROOT, "chiprun_out", "chipbench")
sys.path.insert(0, HERE)

import body as body_mod  # noqa: E402

#: Seconds of the window that a ``--trace 1`` run traces, at its end.
TRACE_SECONDS = 1.0


class NoChip(RuntimeError):
    pass


def load_cell(name, bench=None):
    """The cell's BENCHMARK.json entry, its own file (``limit``), its
    configuration and traffic mix, and the metrics it reports, all found
    by name."""
    if bench is None:
        bench = body_mod.load_json(os.pardir, "BENCHMARK.json")
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = entries[0]
    cell = body_mod.load_json("workloads", f"{name}.json")
    for key in ("config", "traffic"):
        if cell[key] != entry[key]:
            raise ValueError(f"{name}: BENCHMARK.json names {key} "
                             f"{entry[key]!r}, the workload file "
                             f"{cell[key]!r}")

    def applies(m):
        return name in m.get("workloads", [name])

    per_layer = [m["name"] for m in bench["per_layer"] if applies(m)]
    return {
        "name": name, "entry": entry, "limit": cell["limit"],
        "config": body_mod.load_config(entry["config"]),
        "traffic": body_mod.load_json("traffic", f"{entry['traffic']}.json"),
        "end_to_end": [m["name"] for m in bench["end_to_end"]
                       if applies(m)],
        "per_layer": per_layer,
        "readers": {m: body_mod.load_module(os.path.join(
            HERE, "metrics", f"{m}.py")) for m in per_layer},
    }


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

def check_program_spec(net, bd):
    """The program's NetworkSpec must be the body this benchmark counts
    and computes: same stages, widths, strides, biases and residuals.
    A program stage of class ``K`` reads as ``reference/K.py``'s
    ``describe(stage)``."""
    if net.c_in != bd.in_shape[2] or net.n_blocks != len(bd.blocks):
        raise ValueError(f"program spec {net.name}: c_in {net.c_in}, "
                         f"{net.n_blocks} blocks; the config has "
                         f"{bd.in_shape[2]}, {len(bd.blocks)}")
    refs = dict(bd.ref)

    def describe(stage):
        kind = type(stage).__name__
        if kind not in refs:
            path = os.path.join(HERE, "reference", f"{kind}.py")
            if not os.path.isfile(path):
                raise FileNotFoundError(
                    f"program stage kind {kind} has no reference file: "
                    f"add chipbench/reference/{kind}.py with describe(), "
                    "params() and apply()")
            refs[kind] = body_mod.load_module(path)
        return refs[kind].describe(stage)

    c = net.c_in
    for i, (spec, blk) in enumerate(zip(net.blocks, bd.blocks)):
        got = [describe(s) for s in spec.stages]
        if got != blk["stages"] or spec.residual_active(c) != blk["residual"]:
            raise ValueError(f"program block {i} is {got} "
                             f"(residual {spec.residual_active(c)}); the "
                             f"config has {blk}")
        c = spec.out_channels(c)


class Program:
    """``execute_network`` on the compiled Pallas kernels, bf16 stream,
    autotune off, failures raised (never a silent XLA fallback)."""

    def __init__(self, cfg, bd, params):
        from repro.core import network
        from repro.kernels.policy import DtypePolicy, KernelPolicy
        self.network = network
        self.net = getattr(network, cfg["program_spec"])(
            cfg["width_multiplier"])
        check_program_spec(self.net, bd)
        self.policy = KernelPolicy(
            impl="pallas", interpret=False, on_failure="raise",
            autotune=False,
            dtype_policy=DtypePolicy(stream=cfg["stream_dtype"]))
        self.params = params

    def __call__(self, x):
        return self.network.execute_network(self.net, self.params, x,
                                            policy=self.policy)

    def compiled_counts(self, x):
        """(tpu_custom_calls in the compiled program, the plan's Pallas
        calls, the names of the custom-call instructions)."""
        import jax
        nplan = self.network.plan_network(self.net, x.shape, dtype=x.dtype,
                                          policy=self.policy)
        fn = jax.jit(self.network.build_network_fn(self.net, nplan,
                                                   self.policy))
        text = fn.lower(self.params, x).compile().as_text()
        names = [m.group(1) for m in re.finditer(
            r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*custom_call_target='
            r'"tpu_custom_call"', text, re.MULTILINE)]
        return len(names), nplan.n_pallas_calls, names

    def free(self):
        self.network.clear_network_cache()
        self.params = None


# ---------------------------------------------------------------------------
# Loops
# ---------------------------------------------------------------------------

class Sample:
    """A reservoir of the served requests, drawn from the seed: every
    request of the window is equally likely to be compared."""

    def __init__(self, size, seed):
        import numpy as np
        self.size = size
        self.rng = np.random.default_rng(seed)
        self.kept = []   # (request index, pool index, output)
        self.seen = 0

    def offer(self, i, pool_i, y):
        if len(self.kept) < self.size:
            self.kept.append((i, pool_i, y))
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.size:
                self.kept[j] = (i, pool_i, y)
        self.seen += 1


def latency_loop(call, pool, seconds, sample, span=contextlib.nullcontext,
                 start=0):
    """One client, one request at a time: each request's time runs from
    the call to ``block_until_ready`` returning."""
    lat, host = [], []
    clock = time.perf_counter
    t0 = clock()
    t_end = t0 + seconds
    i = start
    while True:
        with span("next_input"):
            pi = i % len(pool)
            x = pool[pi]
        ta = clock()
        with span("dispatch"):
            y = call(x)
        tb = clock()
        with span("sync"):
            y.block_until_ready()
        tc = clock()
        lat.append(tc - ta)
        host.append(tb - ta)
        with span("next_input"):
            sample.offer(i, pi, y)
        i += 1
        if tc >= t_end:
            break
    return {"requests": i - start, "window_s": tc - t0, "latency_s": lat,
            "host_call_s": host}


def throughput_loop(call, pool, seconds, sample, in_flight,
                    span=contextlib.nullcontext, start=0):
    """Offline scoring: ``in_flight`` calls kept queued until the window
    ends, then drained.  The window runs to the last completion."""
    clock = time.perf_counter
    queue = collections.deque()
    host = []
    t0 = clock()
    t_end = t0 + seconds
    i = start
    done = 0
    while True:
        while len(queue) < in_flight and clock() < t_end:
            with span("next_input"):
                pi = i % len(pool)
                x = pool[pi]
            ta = clock()
            with span("dispatch"):
                queue.append((i, pi, call(x)))
            host.append(clock() - ta)
            i += 1
        if not queue:
            break
        j, pj, y = queue.popleft()
        with span("sync"):
            y.block_until_ready()
        done += 1
        with span("next_input"):
            sample.offer(j, pj, y)
    return {"requests": done, "window_s": clock() - t0, "host_call_s": host}


def run_loop(wl, call, pool, seconds, sample, span=contextlib.nullcontext,
             start=0):
    if wl["loop"] == "latency":
        return latency_loop(call, pool, seconds, sample, span, start)
    if wl["loop"] == "throughput":
        return throughput_loop(call, pool, seconds, sample,
                               wl["in_flight"], span, start)
    raise ValueError(f"unknown loop {wl['loop']!r}")


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------

def compare(bd, params32, pool, kept, limit):
    """(worst per-image relative gap max|y - ref| / max|ref| over the
    sampled requests, how many requests read over ``limit``, how many
    inputs the reference ran on); a wrong shape or a non-finite value
    reads inf."""
    import numpy as np
    refs = {}
    worst = 0.0
    bad = 0
    for _, pi, y in kept:
        if pi not in refs:
            refs[pi] = np.asarray(bd.reference_fn(params32, pool[pi]),
                                  np.float32)
        r = refs[pi]
        got = np.asarray(y, np.float32)
        if got.shape != r.shape or not np.isfinite(got).all():
            err = float("inf")
        else:
            axes = tuple(range(1, r.ndim))
            err = float((np.abs(got - r).max(axis=axes)
                         / np.abs(r).max(axis=axes)).max())
        worst = max(worst, err)
        bad += err > limit
    return worst, bad, len(refs)


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def _device_check(chips):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform!r} device(s)")
    return devs


def _pin_state(workload):
    """The program's stores and the TPU runtime's logs live in the
    checkout, the stores fresh for each run."""
    os.makedirs(STATE_DIR, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(STATE_DIR, "tpu_logs"))
    quarantine = os.path.join(STATE_DIR, "quarantine.json")
    if os.path.exists(quarantine):
        os.remove(quarantine)
    os.environ["REPRO_QUARANTINE"] = quarantine
    os.environ["REPRO_TUNE_CACHE"] = os.path.join(STATE_DIR,
                                                  "autotune.json")
    return os.path.join(STATE_DIR, "trace", workload)


def _count_compiles():
    """Counts jaxpr traces and backend compiles from now on."""
    from jax import monitoring
    counts = collections.Counter()

    def listen(event, duration, **kw):
        if event.startswith("/jax/core/compile/"):
            counts[event.rsplit("/", 1)[-1]] += 1

    monitoring.register_event_duration_secs_listener(listen)
    return counts


def setup(cell, seed, *, device_check=True, make_program=Program):
    """Everything before the window: device, stores, weights, inputs,
    plan, compile or cache load, warm-up.  Returns the state the window
    and the check use."""
    import jax
    trace_dir = _pin_state(cell["name"])
    devs = _device_check(cell["entry"]["chips"]) if device_check else \
        jax.devices()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    cache_dir = None
    if devs[0].platform == "tpu":
        from repro.runtime.compile_cache import enable_compile_cache
        cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    wl, cfg = cell["traffic"], cell["config"]
    bd = body_mod.Body(cfg, wl["batch"])
    key = jax.random.PRNGKey(seed)
    kw, kx = jax.random.split(key)

    def make_weights(k):
        p32 = bd.init_params(k)
        return p32, jax.tree_util.tree_map(lambda a: a.astype(bd.stream),
                                           p32)

    params32, params = jax.jit(make_weights)(kw)
    pool = jax.jit(bd.make_inputs, static_argnums=1)(kx, wl["pool"])
    pool = [pool[i] for i in range(wl["pool"])]
    prog = make_program(cfg, bd, params)
    t_first = time.perf_counter()
    sample = Sample(wl["sample"], seed)
    warm = run_loop_fixed(wl, prog, pool, wl["warmup_calls"])
    jax.block_until_ready(warm)
    return {"devs": devs, "bd": bd, "params32": params32, "pool": pool,
            "prog": prog, "sample": sample, "trace_dir": trace_dir,
            "cache_dir": cache_dir,
            "first_call_s": time.perf_counter() - t_first}


def run_loop_fixed(wl, call, pool, n):
    """Warm-up: ``n`` calls in the cell's own pattern and shape."""
    ys = []
    for i in range(n):
        ys.append(call(pool[i % len(pool)]))
        if len(ys) >= wl["in_flight"]:
            ys.pop(0).block_until_ready()
    return ys


def window(cell, st, seconds, trace):
    """The measured window; with ``trace`` its last TRACE_SECONDS run
    under the profiler, and the host numbers come from the rest."""
    import jax
    wl = cell["traffic"]
    compiles = _count_compiles()
    if not trace:
        out = run_loop(wl, st["prog"], st["pool"], seconds, st["sample"])
        return out, None, dict(compiles)
    t_trace = min(TRACE_SECONDS, seconds / 2)
    out = run_loop(wl, st["prog"], st["pool"], seconds - t_trace,
                   st["sample"])
    shutil.rmtree(st["trace_dir"], ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(st["trace_dir"], profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("window"):
            traced = run_loop(wl, st["prog"], st["pool"], t_trace,
                              st["sample"], jax.profiler.TraceAnnotation,
                              start=out["requests"])
    finally:
        jax.profiler.stop_trace()
    return out, traced, dict(compiles)


def end_to_end(cell, out, setup_s):
    wl = cell["traffic"]
    values = {"setup_s": (setup_s, "s")}
    if wl["loop"] == "throughput":
        values["images_per_s"] = (out["requests"] * wl["batch"]
                                  / out["window_s"], "images/s")
    else:
        values["latency_ms_mean"] = (out["window_s"] / out["requests"] * 1e3,
                                     "ms")
    missing = [m for m in cell["end_to_end"] if m not in values]
    if missing:
        raise KeyError(f"{cell['name']}: no end-to-end value for {missing}")
    return {m: {"value": values[m][0], "unit": values[m][1]}
            for m in cell["end_to_end"]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    return run(load_cell(args.workload), args.seed, args.seconds,
               bool(args.trace))


def run(cell, seed, seconds, trace, *, device_check=True,
        make_program=Program, t_start=T_START):
    """One run of one cell; prints the result line; returns the exit code."""
    import jax
    import reduce_trace
    try:
        st = setup(cell, seed, device_check=device_check,
                   make_program=make_program)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    setup_s = time.perf_counter() - t_start
    bd, wl = st["bd"], cell["traffic"]
    out, traced, compiles = window(cell, st, seconds, trace)
    attempted = out["requests"] + (traced["requests"] if traced else 0)
    mem = [d.memory_stats() or {} for d in st["devs"]]
    peak = max(m.get("peak_bytes_in_use", 0) for m in mem)
    dev = st["devs"][0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(st["devs"]), "memory_peak_bytes": peak}
    print(f"chipbench: {cell['name']} seed={seed} setup_s={setup_s:.3f} "
          f"first_calls_s={st['first_call_s']:.3f} window_s="
          f"{out['window_s']:.3f} requests={attempted} "
          f"peak_bytes_in_use={peak} compiles_in_window={compiles} "
          f"compile_cache={st['cache_dir']}", file=sys.stderr, flush=True)

    result = {"correct": None, "attempted": attempted, "failed": None}
    if trace:
        chip = body_mod.load_peak(dev.device_kind)
        n_custom, n_planned, kernel_ops = st["prog"].compiled_counts(
            st["pool"][0])
        print(f"chipbench: tpu_custom_call={n_custom} "
              f"n_pallas_calls={n_planned}", file=sys.stderr, flush=True)
        red = reduce_trace.reduce_dir(st["trace_dir"], kernel_ops)
        if red:
            print("chipbench: trace " + " ".join(
                f"{k}={red[k]}" for k in ("window_s", "busy_s", "calls",
                                          "op_s", "kernel_s", "n_ops")),
                file=sys.stderr, flush=True)
        device["busy_s"] = red["busy_s"] if red else 0.0
        device["window_s"] = red["window_s"] if red else 0.0
        ctx = {"host": out, "trace": red,
               "ideal_s_per_call": bd.ideal_s_per_call(chip)}
        metrics = {}
        for name in cell["per_layer"]:
            r = cell["readers"][name]
            v = r.read(ctx)
            if v is not None:
                metrics[name] = {"value": v, "unit": r.UNIT}
        if red:
            result["breakdown"] = {"device_ops": red["top_ops"],
                                   "idle_gaps": red["idle_by_host"]}
    else:
        metrics = end_to_end(cell, out, setup_s)
    result["metrics"] = metrics
    result["device"] = device

    kept = st["sample"].kept
    st["prog"].free()
    limit = cell["limit"]["max_rel_err"]
    worst, bad, n_refs = compare(bd, st["params32"], st["pool"], kept, limit)
    checks = {"max_rel_err": {"value": worst, "limit": limit}}
    result["correct"] = bool(worst <= limit and len(kept) > 0
                             and len(kept) >= min(wl["sample"], attempted))
    result["failed"] = bad
    result["checks"] = checks
    print(f"chipbench: compared {len(kept)} requests over {n_refs} "
          f"inputs", file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
