"""Benchmark orchestrator: one module per paper table/figure.

Prints ``name,value,derived`` CSV lines.  ``--quick`` shrinks cycle counts
for CI-speed runs; the full run reproduces the paper artifacts.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced cycle counts (CI)")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    args = ap.parse_args()

    from benchmarks import (bench_engine, bench_features,
                            bench_latency_throughput, bench_loc, roofline)
    benches = {
        "loc": lambda rep: bench_loc.run(rep),                 # Table 1
        "latency_throughput": lambda rep: bench_latency_throughput.run(
            rep, n_cycles=6_000 if args.quick else 20_000),    # Fig. 1
        "features": lambda rep: bench_features.run(
            rep, n_cycles=6_000 if args.quick else 12_000),    # §2
        "engine": lambda rep: bench_engine.run(
            rep, n_cycles=6_000 if args.quick else 20_000),    # DSE perf
        "roofline": lambda rep: roofline.run(rep),             # §Roofline
    }
    only = set(args.only.split(",")) if args.only else None

    print("name,value,derived")

    def report(name, value, derived=""):
        print(f"{name},{value},{derived}", flush=True)

    for name, fn in benches.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            fn(report)
            report(f"bench_{name}_wall_s", round(time.time() - t0, 1), "ok")
        except Exception as e:   # noqa: BLE001
            report(f"bench_{name}_FAILED", 0, repr(e))
            raise


if __name__ == "__main__":
    from repro.core.engine import enable_compile_cache
    enable_compile_cache()
    main()
