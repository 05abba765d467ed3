"""Prove the main path runs on one TPU: every supported MobileNet body at
its full published width, on the compiled Pallas kernels, in one process.

  python chip_smoke.py               # on a TPU host; exits 0 only if all
                                     # runs pass
  python chip_smoke.py --interpret   # CPU rehearsal of the same control
                                     # flow at a tiny size; always exits 1

Each run goes spec -> ``plan_network`` -> ``execute_network`` with
``KernelPolicy(impl="pallas", interpret=False, on_failure="raise")``:
MobileNetV1, MobileNetV2, MnasNet-A1 and EfficientNet-Lite0 at width 1.0,
body resolution 112 (a 224x224 image after the stem), batch 1, in fp32
and in bf16 stream, plus MobileNetV2 fp32 at batch 8.  Weights and inputs
are random, made from fixed seeds.  A run passes when

* the compiled program holds exactly one ``tpu_custom_call`` per Pallas
  pass the plan lowers to (``NetworkPlan.n_pallas_calls``), and
* its output is within tolerance of the fp32 per-block XLA oracle at
  "highest" matmul precision (``network.reference_network``).

After all runs the runtime telemetry must show no fallback and no
quarantine hit.  Only then is the last line of stdout
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Per-run results also go to ``chiprun_out/chip_smoke/runs.json``; the
quarantine store is a fresh file beside it.
"""
import argparse
import json
import os
import sys
import time

import jax

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

#: (label, spec builder name, stream dtype, batch) per run.
RUNS = (
    ("mobilenet_v1", "mobilenet_v1_spec", "fp32", 1),
    ("mobilenet_v1", "mobilenet_v1_spec", "bf16", 1),
    ("mobilenet_v2", "mobilenet_v2_spec", "fp32", 1),
    ("mobilenet_v2", "mobilenet_v2_spec", "bf16", 1),
    ("mnasnet_a1", "mnasnet_a1_spec", "fp32", 1),
    ("mnasnet_a1", "mnasnet_a1_spec", "bf16", 1),
    ("efficientnet_lite0", "efficientnet_lite0_spec", "fp32", 1),
    ("efficientnet_lite0", "efficientnet_lite0_spec", "bf16", 1),
    ("mobilenet_v2", "mobilenet_v2_spec", "fp32", 8),
)

RES = 112               # body input: a 224x224 image after the stride-2 stem
INTERPRET_RES = 16      # the CPU rehearsal's tiny size
INTERPRET_BATCH = 2     # stands in for the batch-8 run in the rehearsal
WARM_REPS = 5


def one_run(label, builder, dtype, batch, *, res, interpret):
    import jax.numpy as jnp
    import numpy as np

    from repro.core import network
    from repro.kernels.policy import DtypePolicy, KernelPolicy

    net = getattr(network, builder)(1.0)
    dp = DtypePolicy(stream="bfloat16") if dtype == "bf16" else DtypePolicy()
    pol = KernelPolicy(impl="pallas", interpret=interpret,
                       on_failure="raise", dtype_policy=dp)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (batch, res, res, net.c_in), jnp.float32)
    params32 = network.init_network(jax.random.PRNGKey(0), net)
    params = (network.cast_network_params(params32, jnp.bfloat16)
              if dtype == "bf16" else params32)

    nplan = network.plan_network(net, x.shape, dtype=x.dtype, policy=pol)
    t0 = time.perf_counter()
    compiled = jax.jit(network.build_network_fn(net, nplan, pol)).lower(
        params, x).compile()
    compile_s = time.perf_counter() - t0
    n_calls = compiled.as_text().count('custom_call_target="tpu_custom_call"')

    y = network.execute_network(net, params, x, policy=pol,
                                network_plan=nplan)
    jax.block_until_ready(y)
    t0 = time.perf_counter()
    for _ in range(WARM_REPS):
        y = network.execute_network(net, params, x, policy=pol,
                                    network_plan=nplan)
    jax.block_until_ready(y)
    ms_per_image = (time.perf_counter() - t0) / WARM_REPS / batch * 1e3

    ref = np.asarray(network.reference_network(net, params32, x), np.float32)
    got = np.asarray(y, np.float32)
    rel = float(np.abs(got - ref).max() / (np.abs(ref).max() + 1e-30))
    tol = network.BF16_REL_TOL if dtype == "bf16" else network.FP32_REL_TOL
    calls_ok = interpret or n_calls == nplan.n_pallas_calls
    ok = (calls_ok and rel < tol and got.shape == nplan.out_shape
          and bool(np.isfinite(got).all()))
    return {
        "body": label, "dtype": dtype, "batch": batch, "res": res,
        "segments": dict(sorted(nplan.segment_histogram().items())),
        "kernel_passes": nplan.n_kernel_passes,
        "pallas_calls_planned": nplan.n_pallas_calls,
        "tpu_custom_calls": None if interpret else n_calls,
        "compile_s": compile_s, "max_rel_err": rel, "tol": tol,
        "warm_ms_per_image_not_a_benchmark": ms_per_image, "ok": ok,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--interpret", action="store_true",
                    help="CPU rehearsal: Pallas interpret mode at res "
                         f"{INTERPRET_RES}; never reports ok")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if not args.interpret and dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.runtime import telemetry
    from repro.runtime.compile_cache import enable_compile_cache

    os.makedirs(OUT_DIR, exist_ok=True)
    quarantine = os.path.join(OUT_DIR, "quarantine.json")
    if os.path.exists(quarantine):
        os.remove(quarantine)
    os.environ["REPRO_QUARANTINE"] = quarantine
    if not args.interpret:
        print(f"compile cache: {enable_compile_cache()}", flush=True)

    results = []
    for label, builder, dtype, batch in RUNS:
        if args.interpret:
            batch = min(batch, INTERPRET_BATCH)
        r = one_run(label, builder, dtype, batch, interpret=args.interpret,
                    res=INTERPRET_RES if args.interpret else RES)
        results.append(r)
        segs = ",".join(f"{k}:{v}" for k, v in r["segments"].items())
        print(f"{r['body']} {r['dtype']} b{r['batch']} res{r['res']} "
              f"[{segs}] passes={r['kernel_passes']} "
              f"tpu_custom_call={r['tpu_custom_calls']}/"
              f"{r['pallas_calls_planned']} "
              f"compile_s={r['compile_s']:.2f} "
              f"max_rel_err={r['max_rel_err']:.3e} (tol {r['tol']:g}) "
              f"warm_ms/image={r['warm_ms_per_image_not_a_benchmark']:.3f} "
              f"(not a benchmark) {'PASS' if r['ok'] else 'FAIL'}",
              flush=True)

    rep = telemetry.runtime_report()
    counters = {k: rep[k] for k in ("fallbacks", "injected_fallbacks",
                                    "numeric_trips", "recoveries",
                                    "quarantine_hits")}
    print(f"runtime telemetry: {counters}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    with open(os.path.join(OUT_DIR, "runs.json"), "w") as f:
        json.dump({"device": device, "runs": results,
                   "telemetry": counters}, f, indent=1)

    failed = [f"{r['body']}/{r['dtype']}/b{r['batch']}"
              for r in results if not r["ok"]]
    if failed or any(counters.values()):
        print(f"chip_smoke: FAILED {failed} telemetry={counters}",
              file=sys.stderr)
        return 1
    if args.interpret:
        print("chip_smoke: interpret rehearsal passed; it is not a chip run",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
