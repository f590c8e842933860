"""Readings that set the limits of the comparison deciding ``correct``.

    python3 bench/control.py --workload ddr5_8ch2r.sweep24 --seeds 12 \
        --control-seeds 3 --first-seed 1000

For one cell, at its own size and on its own chips, in one process: one
set-up, then for each of ``--seeds`` seeds one call of the timed path and
the comparison of the points a run would sample with the plain
reference (the lower reading: sound runs of the program), and for each
of ``--control-seeds`` seeds the same points computed by the control (the
configuration's reference with one published guarantee broken, its
``simulate(control=True)``; ``bench/reference.py``: tRCD one cycle short)
in the program's place (the upper reading).  The benchmark's own runs never
run this.  Prints one JSON line per seed and a summary line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import harness
    bench = harness.load_benchmark(ROOT)
    cell, config, traffic = harness.resolve_cell(bench, args.workload, ROOT)
    import jax

    import devices as D
    from repro.core import engine as E
    os.makedirs(E.enable_compile_cache(), exist_ok=True)
    dev = D.device_info(int(cell["chips"]))
    devs = jax.devices()[:int(cell["chips"])]
    runner = harness.load_plugin("kinds", traffic["kind"], ROOT).Runner(
        config, traffic, devs)
    runner.warm()
    lower, upper = [], []
    for k in range(max(args.seeds, args.control_seeds)):
        seed = args.first_seed + k
        s = harness.call_seed(seed, 0)
        t = time.perf_counter()
        call = harness.Call(seed=s, t0=t, t1=t, points=runner.call(s))
        run = harness.Run(cell=cell, config=config, traffic=traffic,
                          chips=len(devs), points=runner.points,
                          calls=[call], setup={})
        picks = harness.choose_checks(run, harness.sample_rng(seed))
        row = {"seed": seed, "points": [run.points[p] for _, p in picks]}
        if k < args.seeds:
            row["program"] = sum(harness.compare(
                call.points[p], harness.reference_leaves(
                    run, run.points[p], s))[0] for _, p in picks)
            lower.append(row["program"])
        if k < args.control_seeds:
            row["control"] = sum(harness.compare(
                harness.reference_leaves(run, run.points[p], s,
                                         control=True),
                harness.reference_leaves(run, run.points[p], s))[0]
                for _, p in picks)
            upper.append(row["control"])
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload, "device": dev,
                      "reference": os.path.relpath(config["reference"],
                                                   ROOT),
                      "lower_reading": max(lower) if lower else None,
                      "upper_reading": min(upper) if upper else None,
                      "program": lower, "controls": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
