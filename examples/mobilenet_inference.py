"""The paper's own workload: full MobileNet V1/V2 bodies through the
whole-network chain engine (NetworkSpec -> NetworkPlan -> ONE jitted
execute_network call, DESIGN.md §7) with per-segment mixed-precision
streaming, on the compiled Pallas kernels.

  PYTHONPATH=src python examples/mobilenet_inference.py \
      [--interpret] [--res N] [--dtype fp32|bf16] [--arch v1|v2|both] \
      [--verify] [--fault-inject POINTS]

Without --interpret the kernels compile for the TPU and the run fails on
any other backend.  --interpret runs the same kernels in Pallas interpret
mode on the CPU (slow; a rehearsal, not a measurement).

--dtype bf16 streams activations and weights as bf16 while every kernel
accumulates in fp32 (the DtypePolicy of DESIGN.md §7) — the modeled HBM
traffic halves, which is the whole game for these memory-bound ops.
--res N runs at an NxN body input instead of 112x112 (a 224 image after
the stem).  CI smokes --interpret at --res 16 (fp32) and --res 32 (bf16).
--fused is accepted for compatibility; fusion is a planner decision now
and always on (KernelPolicy(fused=False) remains the opt-out).

Every run is checked against the fp32 per-block XLA oracle at "highest"
matmul precision.  A kernel the compiler refuses raises (on_failure=
"raise"), and the run fails if the runtime telemetry records any fallback
or quarantine hit.  --fault-inject POINTS instead arms the runtime
fault-injection harness (DESIGN.md §9) at the named points (comma-separated
``point[:times]``) under the degradation ladder: the oracle parity still
holds and exactly the injected fallbacks are recorded.  Either way the
quarantine store is a fresh artifacts/runtime/quarantine.json.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import KernelPolicy, network
from repro.core import intensity as it
from repro.kernels.policy import DtypePolicy
from repro.runtime import faultinject, telemetry
from repro.runtime.compile_cache import enable_compile_cache

QUARANTINE = os.path.join("artifacts", "runtime", "quarantine.json")


def _policy(args, dtype_policy):
    return KernelPolicy(
        impl="pallas", interpret=args.interpret, dtype_policy=dtype_policy,
        on_failure="degrade" if args.fault_inject else "raise")


def run_network(name, net, args):
    dp = (DtypePolicy(stream="bfloat16") if args.dtype == "bf16"
          else DtypePolicy())
    pol = _policy(args, dp)
    res = args.res
    x = jax.random.normal(jax.random.PRNGKey(1), (1, res, res, net.c_in))
    params = network.init_network(jax.random.PRNGKey(0), net)
    if args.dtype == "bf16":
        # deployment-style: store the weights once at the stream width
        params = network.cast_network_params(params, jnp.bfloat16)

    nplan = network.plan_network(net, x.shape, policy=pol)
    if args.verify:
        from repro import analysis
        report = analysis.analyze_network(net, nplan, policy=pol,
                                          jaxpr=False)
        print(f"  planlint: {report.summary()}"
              + ("" if report.ok else
                 " -> " + ",".join(report.rules(analysis.ERROR))))
        analysis.verify_or_raise(report)
    histo = ",".join(f"{k}:{v}"
                     for k, v in sorted(nplan.segment_histogram().items()))
    print(f"\n{name} body @{res}x{res} ({args.dtype}, pallas"
          f"{' interpret' if pol.interpret else ''}):")
    print(f"  plan: {net.n_blocks} blocks -> {nplan.n_kernel_passes} kernel "
          f"passes ({histo}), fully fused: {nplan.fully_fused}")

    t = it.network_traffic(net, nplan)
    n32 = network.plan_network(net, x.shape, policy=_policy(args,
                                                            DtypePolicy()))
    t32 = it.network_traffic(net, n32)
    nunf = network.plan_network(
        net, x.shape, policy=KernelPolicy(impl="pallas", fused=False))
    tunf = it.network_traffic(net, nunf)
    print(f"  modeled HBM: {t.bytes_hbm/1e6:.2f} MB "
          f"(fp32 fused {t32.bytes_hbm/1e6:.2f} MB, per-block unfused "
          f"{tunf.bytes_hbm/1e6:.2f} MB); AI {t.intensity:.1f} FLOPs/B")

    # ONE jitted call for the whole backbone; plan resolved once above.
    # Under --fault-inject the plan is left to the engine, and a second
    # call runs what it re-planned after a quarantine write.
    nplan_arg = None if args.fault_inject else nplan
    for _ in range(2 if args.fault_inject else 1):
        y = network.execute_network(net, params, x, policy=pol,
                                    network_plan=nplan_arg)
    jax.block_until_ready(y)
    print(f"  features {y.shape} {y.dtype}")

    ref = network.reference_network(
        net, network.init_network(jax.random.PRNGKey(0), net), x)
    ref = np.asarray(ref, np.float32)
    got = np.asarray(y, np.float32)
    rel = float(np.abs(got - ref).max() / (np.abs(ref).max() + 1e-30))
    tol = (network.BF16_REL_TOL if args.dtype == "bf16"
           else network.FP32_REL_TOL)
    print(f"  vs fp32 per-block oracle: max rel err {rel:.2e} "
          f"(tol {tol:g})")
    assert rel < tol, f"{name}: {rel} >= {tol}"


def main():
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--interpret", action="store_true",
                    help="run the Pallas kernels in interpret mode on the "
                         "CPU (slow; a rehearsal of the TPU run)")
    ap.add_argument("--fused", action="store_true",
                    help="(compat no-op) fusion is a planner decision and "
                         "always on; KernelPolicy(fused=False) opts out")
    ap.add_argument("--res", type=int, default=112, metavar="N",
                    help="body input resolution NxN (a 224 image after the "
                         "stem is 112; CI smokes 16 and 32)")
    ap.add_argument("--dtype", choices=("fp32", "bf16"), default="fp32",
                    help="streaming dtype policy: bf16 halves the streamed "
                         "HBM bytes, accumulation stays fp32 (DESIGN.md §7)")
    ap.add_argument("--arch", choices=("v1", "v2", "both"), default="both")
    ap.add_argument("--verify", action="store_true",
                    help="run the static plan verifier (repro.analysis, "
                         "DESIGN.md §8) on the resolved NetworkPlan before "
                         "executing; raises on any error diagnostic")
    ap.add_argument("--fault-inject", default=None, metavar="POINTS",
                    help="arm runtime fault-injection points "
                         "(comma-separated point[:times], DESIGN.md §9) "
                         "under the degradation ladder")
    args = ap.parse_args()

    backend = jax.default_backend()
    if not args.interpret and backend != "tpu":
        sys.exit(f"compiled Pallas kernels need a TPU, found {backend!r}; "
                 "pass --interpret to rehearse on the CPU")
    if not args.interpret:
        enable_compile_cache()
    if os.path.exists(QUARANTINE):
        os.remove(QUARANTINE)
    os.environ["REPRO_QUARANTINE"] = QUARANTINE

    if args.fault_inject:
        points = faultinject.arm_from_spec(args.fault_inject)
        print(f"fault injection armed: {', '.join(points)}")

    nets = []
    if args.arch in ("v1", "both"):
        nets.append(("MobileNetV1", network.mobilenet_v1_spec()))
    if args.arch in ("v2", "both"):
        nets.append(("MobileNetV2", network.mobilenet_v2_spec()))
    for name, net in nets:
        run_network(name, net, args)

    rep = telemetry.runtime_report()
    print(f"\nruntime telemetry: {rep['fallbacks']} fallbacks "
          f"({rep['injected_fallbacks']} injected), "
          f"{rep['recoveries']} recoveries, "
          f"{rep['quarantine_hits']} quarantine hits"
          + (f"; fired: {faultinject.fired_counts()}"
             if args.fault_inject else ""))
    if args.fault_inject:
        assert rep["fallbacks"] == rep["injected_fallbacks"], rep
        faultinject.disarm_all()
    else:
        assert rep["fallbacks"] == 0 and rep["quarantine_hits"] == 0, rep

    print("\nper-layer AI bounds (paper's analysis, DESIGN.md §2): "
          f"DW ours {it.t_ours_dw_asymptotic(3, 3):.3f} vs TF-Lite "
          f"{it.t_tf_dw(4):.3f}; PW RTRD {it.t_rtrd_pw(ci=1024):.3f} vs "
          f"RTRA {it.t_rtra_pw(co=1024):.3f}")


if __name__ == "__main__":
    main()
