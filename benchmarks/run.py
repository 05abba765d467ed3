"""Benchmark orchestrator. One section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
* figs 4-6 — per-layer DW/PW benchmarks (measured CPU wall time of the XLA
  path + the paper's analytical AI model and modeled TPU-roofline speedup).
* fig 7 — modeled core-scalability curves (channel- vs row-parallel).
* fig 1 anchor — Algorithm-1 naive loops vs compiled (the paper's
  "Unoptimized" point).

Flags:
* ``--full``     — benchmark every layer (default: first 3 per suite).
* ``--dry-run``  — model-only mode: skip compilation and wall-clock timing
  (all ``us`` columns are 0.0) but emit every analytical row — planner
  blocks, traffic, AI, roofline bounds. CI runs this as the traffic-model
  regression gate.
* ``--out PATH`` — additionally dump the raw results dict as JSON to PATH
  (e.g. ``artifacts/bench_results.json``). Without it nothing is written.
* ``--autotune`` — append the analytic-vs-measured ChainPlan table
  (``autotune/mobilenet_v2/...`` rows, benchmarks/autotune_table.py): each
  V2 inverted residual is tuned with the measured autotuner and the row
  reports cache=miss|hit, both blockings and both timings. Unlike the
  other sections this MEASURES even under ``--dry-run`` (measurement is
  the feature under test); quick mode uses tiny stand-in geometries so the
  interpret-mode ladder stays in CI seconds, ``--full`` tunes the real V2
  shapes.
* ``--tune-cache PATH`` — persistent tune-cache JSON for ``--autotune``
  (default: $REPRO_TUNE_CACHE or ~/.cache/repro/autotune.json). Re-running
  with the same PATH must print every row as cache=hit with n_cand=0 —
  CI's replay gate.
* ``--fault-inject POINTS`` — run the three-phase runtime-hardening matrix
  (``benchmarks/runtime_faults.py``, DESIGN.md §9): arm the comma-separated
  injection points (``point[:times]``, persistent by default) against the
  full V1/V2 bodies, assert oracle parity + exact injected-fallback
  telemetry, then prove the quarantined replay and a clean run report zero
  fallbacks. Like ``--autotune`` this EXECUTES even under ``--dry-run``
  (fault recovery is the feature under test); quick mode runs @16x16,
  ``--full`` at the paper's 112x112.
* ``--runtime-report PATH`` — write the three phase telemetry snapshots as
  JSON (requires ``--fault-inject``).
* ``--baseline`` — (re)write the committed benchmark-trajectory baseline
  (``BENCH_baseline.json``: geometry-keyed traffic + per-block plan rows,
  sorted keys) and exit. ``--check-baseline`` re-collects and diffs
  against the committed baseline, exiting 1 on any traffic regression or
  plan downgrade — the CI ``bench-gate`` job (benchmarks/trajectory.py).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="benchmark every layer, and time the hires suite")
    ap.add_argument("--dry-run", action="store_true",
                    help="model-only: no compilation or timing")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write raw results JSON to PATH")
    ap.add_argument("--autotune", action="store_true",
                    help="append the analytic-vs-measured ChainPlan table")
    ap.add_argument("--tune-cache", default=None, metavar="PATH",
                    help="tune-cache JSON for --autotune")
    ap.add_argument("--fault-inject", default=None, metavar="POINTS",
                    help="comma-separated injection points (point[:times]) "
                         "for the runtime-hardening matrix (DESIGN.md §9)")
    ap.add_argument("--runtime-report", default=None, metavar="PATH",
                    help="write the fault-injection telemetry report here")
    ap.add_argument("--baseline", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="write the trajectory baseline JSON (default: "
                         "BENCH_baseline.json at the repo root) and exit")
    ap.add_argument("--check-baseline", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="diff the trajectory against the committed "
                         "baseline and exit 1 on regression (bench-gate)")
    args = ap.parse_args()

    if args.baseline is not None:
        from benchmarks import trajectory
        path = trajectory.write_baseline(
            args.baseline or trajectory.DEFAULT_BASELINE)
        print(f"trajectory baseline written to {os.path.normpath(path)}")
        return
    if args.check_baseline is not None:
        from benchmarks import trajectory
        sys.exit(trajectory.check_baseline(
            args.check_baseline or trajectory.DEFAULT_BASELINE))

    from benchmarks.paper_figs import run_all

    results = run_all(quick=not args.full, dry_run=args.dry_run)
    rows = []
    for suite in ("mobilenet_v1", "mobilenet_v2", "mnasnet_a1"):
        for r in results[suite]["dw"]:
            rows.append(
                f"dwconv/{suite}/{r['name']},{r['us_xla_cpu']:.1f},"
                f"AI_ours={r['ai_ours']:.3f};AI_tflite={r['ai_tflite']:.3f};"
                f"modeled_tpu_speedup={r['modeled_speedup']:.2f}x")
        for r in results[suite]["pw"]:
            rows.append(
                f"pwconv/{suite}/{r['name']},{r['us_xla_cpu']:.1f},"
                f"AI_rtrd={r['ai_rtrd']:.3f};AI_rtra={r['ai_rtra']:.3f};"
                f"modeled_tpu_speedup={r['modeled_speedup']:.2f}x")
    for suite in ("mobilenet_v1", "mobilenet_v2", "hires"):
        for r in results[suite].get("sep", []):
            if not r["fusible"]:
                # no fused block plan fits VMEM: the op takes the unfused
                # fallback, so a fused-traffic claim would be fiction
                rows.append(
                    f"sepfused/{suite}/{r['name']},"
                    f"{r['us_fused_xla_cpu']:.1f},fusible=False;"
                    f"MB_unfused={r['bytes_unfused']/1e6:.2f}")
                continue
            rows.append(
                f"sepfused/{suite}/{r['name']},{r['us_fused_xla_cpu']:.1f},"
                f"us_unfused={r['us_unfused_xla_cpu']:.1f};"
                f"slabs={r['n_slabs']}x{r['slab_h']};"
                f"MB_unfused={r['bytes_unfused']/1e6:.2f};"
                f"MB_fused={r['bytes_fused']/1e6:.2f};"
                f"MB_saved={r['bytes_saved']/1e6:.2f};"
                f"modeled_tpu_speedup={r['modeled_speedup']:.2f}x")
    # per-block ChainPlan traffic table: what the declarative chain planner
    # lowers a WHOLE V2 inverted residual to (3-stage fused), vs the PR-2
    # 2-stage lowering, vs fully unfused (DESIGN.md §5)
    from benchmarks.roofline_table import chain_fusion_rows
    for r in chain_fusion_rows():
        rows.append(
            f"chain/mobilenet_v2/{r['name']},0.0,"
            f"plan={r['plan']};single_pass={r['single_pass']};"
            f"residual={r['residual']};blocks={r['blocks']};"
            f"MB_3stage={r['mb_3stage']:.2f};"
            f"MB_2stage={r['mb_2stage']:.2f};"
            f"MB_unfused={r['mb_unfused']:.2f};"
            f"MB_saved_vs_2stage={r['saved_vs_2stage_mb']:.2f}")

    # whole-network table (DESIGN.md §7): the network engine's plan for the
    # full V1/V2 bodies and the bf16-streaming traffic reduction — CI gates
    # traffic_ok (bf16 < fp32 fused < per-block unfused, strict) per row
    from benchmarks.network_table import csv_network_rows, network_rows
    net_rows = network_rows()
    rows.extend(csv_network_rows(net_rows))
    results["network"] = net_rows

    a = results["fig1_anchor"]
    rows.append(f"fig1/{a['name']},{a['us_xla_cpu']:.1f},"
                f"naive_loops_us={a['us_naive_loops']:.0f};"
                f"speedup_vs_naive={a['speedup']:.0f}x")
    for r in results["fig7"]:
        rows.append(f"fig7/scaling/p{r['threads']},0.0,"
                    f"speedup_ours={r['speedup_ours']:.2f};"
                    f"speedup_rowpar={r['speedup_rowpar']:.2f}")

    from benchmarks.kernel_vmem import csv_rows as vmem_rows
    rows.extend(vmem_rows())

    if args.autotune:
        from benchmarks.autotune_table import autotune_rows
        tune_rows, tune_recs = autotune_rows(args.tune_cache,
                                             full=args.full)
        rows.extend(tune_rows)
        results["autotune"] = tune_recs

    if args.fault_inject:
        from benchmarks.runtime_faults import runtime_rows
        rt_rows, rt_recs = runtime_rows(args.fault_inject, full=args.full,
                                        report_path=args.runtime_report)
        rows.extend(rt_rows)
        results["runtime"] = rt_recs

    print("name,us_per_call,derived")
    for row in rows:
        print(row)

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            # sorted keys + trailing newline: byte-stable across runs with
            # identical results, so CI artifacts diff cleanly
            json.dump(results, f, indent=2, default=str, sort_keys=True)
            f.write("\n")


if __name__ == "__main__":
    main()
