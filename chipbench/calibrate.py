"""Readings that a cell's correctness limit is set from, at the cell's own
size, in one process:

    python3 chipbench/calibrate.py --workload mnv2-b128-bf16 \
        --seeds 11 12 13 --control-seeds 11 12 13

For each seed it makes the run's weights and input pool and compares, by
the run's own measure (``run.compare``), three things with the plain fp32
reference:

* ``program``: the program's timed path on every input of the pool (the
  lower reading);
* ``control``: the reference itself computed with every streamed operand
  rounded through float8 e4m3, the precision below the configuration's
  bfloat16 (the upper reading);
* ``faults``: the timed path with half of each batch left out (zeros),
  and with one answer altered where it is produced.

One JSON line per seed goes to standard output.  It needs a TPU, as a run
does; the benchmark's own runs never call it.
"""
import argparse
import json
import sys
import time

import run

FAULTS = {
    "half_batch_left_out": lambda y: y.at[y.shape[0] // 2:].set(0),
    "answer_altered": lambda y: y.at[0, 0, 0, 0].add(1),
}


def readings(cell, seed, control, faults, **kw):
    """{reading name: worst per-image relative gap} for one seed."""
    st = run.setup(cell, seed, **kw)
    bd, pool, prog = st["bd"], st["pool"], st["prog"]
    ys = [prog(x) for x in pool]
    outputs = {"program": ys}
    if faults:
        for name, fault in FAULTS.items():
            if name != "half_batch_left_out" or bd.batch > 1:
                outputs[name] = [fault(y) for y in ys]
    prog.free()
    if control:
        outputs["control"] = [bd.control_fn(st["params32"], x) for x in pool]
    out = {"seed": seed}
    for name, ys in outputs.items():
        out[name] = run.compare(bd, st["params32"], pool,
                                [(i, i, y) for i, y in enumerate(ys)],
                                cell["limit"]["max_rel_err"])[0]
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=())
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=())
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    for seed in args.seeds:
        t = time.perf_counter()
        r = readings(cell, seed, seed in args.control_seeds,
                     seed in args.fault_seeds)
        r["workload"] = args.workload
        r["seconds"] = time.perf_counter() - t
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
