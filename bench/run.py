"""Chip benchmark of the simulator: one cell, one run.

    python3 bench/run.py --workload ddr5_8ch2r.sweep24 --seed 7 \
        --seconds 30 --trace 0

Runs from the root of a checkout on a machine with the TPU chips the
cell asks for (``BENCHMARK.json``).  Exits non-zero, with no result, when
JAX finds no TPU or fewer chips than the cell needs.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace
1`` its per-layer metrics), ``device``, ``breakdown`` (traced runs) and
``checks``, each number compared with the reference beside its limit,
which are also the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def parse(argv=None):
    ap = argparse.ArgumentParser(prog="bench/run.py",
                                 description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import harness
    bench = harness.load_benchmark(ROOT)
    cell, config, traffic = harness.resolve_cell(bench, args.workload, ROOT)
    import jax

    import devices as D
    from repro.core import engine as E
    # JAX_COMPILATION_CACHE_DIR where set, else the checkout's .jax_cache
    os.makedirs(E.enable_compile_cache(), exist_ok=True)
    t = time.perf_counter()
    dev = D.device_info(int(cell["chips"]))
    devs = jax.devices()[:int(cell["chips"])]
    print(f"device: {json.dumps(dev)}; tpu init "
          f"{time.perf_counter() - t:.3f} s; compile cache "
          f"{jax.config.jax_compilation_cache_dir}", flush=True)
    res = harness.run_cell(bench, cell, config, traffic, args.seed,
                           args.seconds, bool(args.trace), devs, T_START,
                           log=lambda s: print(s, flush=True))
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": len(devs),
              "memory_peak_bytes": res.pop("memory_peak_bytes")}
    trace = res.pop("trace", None)
    checks = res.pop("checks")
    out = dict(res, device=device)
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        out["breakdown"] = {"device_ops": trace["device_ops"],
                            "idle_gaps": trace["idle_gaps"]}
    out["checks"] = checks
    for name, v in checks.items():
        print(f"{name} {v['value']} limit {v['limit']}", file=sys.stderr,
              flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
