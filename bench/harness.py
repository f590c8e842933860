"""The chip benchmark's harness: everything between the command line and
the traffic kinds, metric readers and reference.

A cell is found by its name in ``BENCHMARK.json``; its configuration file
and its traffic file (``bench/traffic/<traffic>.json``) are data.  The
traffic file's ``kind`` names the runner in ``bench/kinds/<kind>.py``;
each per-layer metric is read by ``bench/metrics/<metric>.py``.  A later
cell, traffic mix or per-layer metric is therefore added by adding files.

One run: set-up (load the program's modules, name the devices, build the
cell's runner, compile or load its one program with a light-load call),
then the window (calls with seeds derived from ``--seed`` until
``--seconds`` have passed), then the comparison of a sample of the
window's results with the plain reference (``bench/reference.py``).

A traced run (``--trace 1``) makes one call of the timed program, for the
exact counts and the check, then one call under the JAX profiler of the
same program at the traffic file's ``trace_cycles`` (a full-length loop
emits more device events than one trace holds), and reports the per-layer
metrics instead of the end-to-end ones.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class BenchError(RuntimeError):
    """The benchmark's own files are missing or inconsistent."""


@dataclasses.dataclass
class Call:
    seed: int
    t0: float
    t1: float
    points: list            # one {leaf: int64 array} per design point


@dataclasses.dataclass
class Run:
    """What the metric readers see."""
    cell: dict
    config: dict
    traffic: dict
    chips: int
    points: list            # the runner's load points, in output order
    calls: list             # window calls
    setup: dict             # set-up split, seconds
    trace: dict | None = None
    traced_calls: list = dataclasses.field(default_factory=list)


# -- files -------------------------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchError(f"no BENCHMARK.json at {root}")
    return load_json(path)


def load_plugin(kind: str, name: str, root: str = ROOT):
    """Import ``bench/<kind>/<name>.py`` under ``root`` by path (names may
    hold dots)."""
    path = os.path.join(root, "bench", kind, f"{name}.py")
    if not os.path.exists(path):
        raise BenchError(f"no {kind} reader {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve_cell(bench: dict, workload: str, root: str = ROOT) -> tuple:
    """``(cell, config, traffic)`` of the named cell."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, confs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     f"{cell['traffic']}.json"))
    return cell, config, traffic


def cell_metrics(bench: dict, cell_name: str, section: str) -> list:
    """The metric entries of ``section`` that the cell reports."""
    return [m for m in bench[section]
            if "workloads" not in m or cell_name in m["workloads"]]


# -- seeds ------------------------------------------------------------------

def call_seed(seed: int, i: int) -> int:
    """The 32-bit seed of the window's ``i``-th call: a splitmix64 mix of
    (``seed``, ``i``), so calls differ in every bit (the program seeds its
    generator with ``seed | 1``, so ``seed`` and ``seed + 1`` would alias)."""
    x = (int(seed) * 0x9E3779B97F4A7C15 + int(i) + 1) % (1 << 64)
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) % (1 << 64)
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) % (1 << 64)
    return int((x ^ (x >> 31)) & 0xFFFFFFFF)


def sample_rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed) % (1 << 63))


# -- metrics ----------------------------------------------------------------

def sim_cycles_per_s(calls: list) -> float:
    """Simulated cycles of every call of the window, summed over each
    call's design points, over the wall time from the first call's start
    to the last call's end."""
    cycles = sum(int(p["cycles"]) for c in calls for p in c.points)
    return cycles / (calls[-1].t1 - calls[0].t0)


def counter_faults(calls: list) -> int:
    """Points whose driver counters break ``scan_steps + skipped_cycles ==
    cycles`` with ``1 <= scan_steps <= cycles``.  The two counters are the
    engine's own work, not simulated results (a tighter fast-forward
    horizon changes them and nothing else), so they are held to this and
    not to the reference."""
    bad = 0
    for c in calls:
        for p in c.points:
            steps, skip, cyc = (int(p["scan_steps"]),
                                int(p["skipped_cycles"]), int(p["cycles"]))
            bad += not (steps + skip == cyc and 1 <= steps <= cyc)
    return bad


def read_per_layer(bench: dict, run: Run, root: str = ROOT) -> dict:
    """Each per-layer metric of the cell, read by its own reader; a
    reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in cell_metrics(bench, run.cell["name"], "per_layer"):
        v = load_plugin("metrics", m["name"], root).read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# -- correctness -------------------------------------------------------------

def choose_checks(run: Run, rng: np.random.Generator) -> list:
    """``(call index, point index)`` pairs to compare with the reference,
    drawn from the seed: one point at the traffic's heaviest load (the
    smallest interval, whose call does the most work), plus
    ``check_points - 1`` others of other loads where the mix has them."""
    n = int(run.traffic.get("check_points", 1))
    iv = np.asarray([p["interval"] for p in run.points])
    heavy = np.flatnonzero(iv == iv.min())
    other = np.flatnonzero(iv != iv.min())
    picks = [int(rng.choice(heavy))]
    if len(other) and n > 1:
        picks += [int(x) for x in rng.choice(other, size=min(n - 1,
                                                              len(other)),
                                             replace=False)]
    return [(int(rng.integers(len(run.calls))), p) for p in picks]


def compare(program: dict, reference: dict) -> tuple:
    """``(differing elements, names of differing leaves)`` over every leaf
    the reference gives.  A leaf the program does not give counts as
    differing in all of its elements."""
    n, names = 0, []
    for k, ref in reference.items():
        got = program.get(k)
        if got is None or np.shape(got) != np.shape(ref):
            n += int(np.size(ref))
            names.append(k)
            continue
        d = int(np.sum(np.asarray(got) != np.asarray(ref)))
        if d:
            n += d
            names.append(k)
    return n, names


def reference_leaves(run: Run, point: dict, seed: int,
                     control: bool = False) -> dict:
    """The plain reference's result for one design point, keyed like the
    program's leaves for this traffic kind."""
    import reference
    ref = reference.simulate(run.config, interval=point["interval"],
                             read_ratio=point["read_ratio"], seed=seed,
                             n_cycles=int(run.config["n_cycles"]),
                             control=control)
    keep = load_plugin("kinds", run.traffic["kind"]).CHECKED
    return ref if keep is None else {k: ref[k] for k in keep}


def check(run: Run, seed: int, config_bad: list) -> tuple:
    """``(checks, failed points, lines)``: each number compared with its
    limit, and a line per compared point for the log."""
    picks = choose_checks(run, sample_rng(seed))
    differing, failed, lines = 0, 0, []
    for ci, pi in picks:
        call = run.calls[ci]
        t0 = time.perf_counter()
        ref = reference_leaves(run, run.points[pi], call.seed)
        n, names = compare(call.points[pi], ref)
        differing += n
        failed += bool(n)
        lines.append(f"checked call {ci} point {pi} {run.points[pi]} "
                     f"seed {call.seed}: {n} differing elements "
                     f"{names[:8]} (reference {time.perf_counter() - t0:.3f} s)")
    bad_counters = counter_faults(run.calls)
    checks = {"stats_differing": {"value": differing, "limit": 0},
              "config_differing": {"value": len(config_bad), "limit": 0},
              "counters_inconsistent": {"value": bad_counters, "limit": 0}}
    return checks, failed + bad_counters, lines


# -- one run -----------------------------------------------------------------

def compile_counts() -> tuple:
    from repro.core import engine as E
    return E.TRACE_COUNT, E.RUN_CACHE.stats()["misses"]


def run_cell(bench: dict, cell: dict, config: dict, traffic: dict,
             seed: int, seconds: float, trace: bool, devices: list,
             t_start: float, log=print) -> dict:
    """Set-up, window and check of one cell on ``devices``; returns the
    result object (without ``device``)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)      # the kinds import devices, programs
    setup = {}
    t = time.perf_counter()
    kind = load_plugin("kinds", traffic["kind"])
    runner = kind.Runner(config, traffic, devices)
    import programs
    config_bad = programs.config_mismatches(config)
    setup["spec_s"] = time.perf_counter() - t
    t = time.perf_counter()
    runner.warm()
    setup["program_s"] = time.perf_counter() - t
    tracer = runner
    n_trace = int(traffic.get("trace_cycles", config["n_cycles"]))
    if trace and n_trace != int(config["n_cycles"]):
        # a loop of the full length emits more device events than one
        # trace holds: the traced call runs the same program at a shorter
        # length, compiled in traced set-up only
        t = time.perf_counter()
        tracer = kind.Runner(dict(config, n_cycles=n_trace), traffic,
                             devices)
        tracer.warm()
        setup["trace_program_s"] = time.perf_counter() - t
    setup["setup_s"] = time.perf_counter() - t_start
    log(f"setup split: {json.dumps(setup)}")

    c0 = compile_counts()
    calls, traced = [], []
    win0 = time.perf_counter()

    def one(r, i):
        s = call_seed(seed, i)
        t0 = time.perf_counter()
        pts = r.call(s)
        return Call(seed=s, t0=t0, t1=time.perf_counter(), points=pts)

    if trace:
        # one call of the timed program, for the exact counts and the
        # check, then one traced call
        import jax
        from tracing import capture
        calls.append(one(runner, 0))
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        with capture(tdir):
            with jax.profiler.TraceAnnotation("bench.call"):
                traced.append(one(tracer, 1))
    else:
        while not calls or calls[-1].t1 - win0 < seconds:
            calls.append(one(runner, len(calls)))
    c1 = compile_counts()
    window_s = calls[-1].t1 - calls[0].t0
    log(f"window: {len(calls)} calls in {window_s:.6f} s; call seconds "
        f"{[round(c.t1 - c.t0, 6) for c in calls]}")
    log(f"compiles in window: traces {c1[0] - c0[0]}, run-cache misses "
        f"{c1[1] - c0[1]}")
    import devices as D
    mem = D.memory_peak_bytes(devices)

    run = Run(cell=cell, config=config, traffic=traffic, chips=len(devices),
              points=runner.points, calls=calls, setup=setup,
              traced_calls=traced)
    del runner, tracer
    if trace:
        from tracing import extract, reduce
        t = time.perf_counter()
        ev = extract(tdir)
        # the traced window: from the first traced call's start to the
        # last one's end, on the trace's own clock (its host span)
        spans = [(s, s + d) for n, s, d in ev["host"] if n == "bench.call"]
        lo = min(s for s, _ in spans) if spans else 0.0
        hi = max(e for _, e in spans) if spans else 0.0
        run.trace = reduce(ev, (lo, hi))
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace: {sum(len(v) for v in ev['devices'].values())} device "
            f"events on {len(ev['devices'])} planes, "
            f"{len(ev['host'])} host events, reduced in "
            f"{time.perf_counter() - t:.3f} s")
        if run.trace["truncated"]:
            log(f"trace cut short, no device metrics: "
                f"{run.trace['truncated']}")

    checks, failed, lines = check(run, seed, config_bad)
    for line in lines:
        log(line)
    attempted = sum(len(c.points) for c in calls)
    result = {"correct": all(v["value"] <= v["limit"]
                             for v in checks.values()),
              "attempted": attempted, "failed": failed}
    if trace:
        result["metrics"] = read_per_layer(bench, run)
    else:
        e2e = {"sim_cycles_per_s": sim_cycles_per_s(calls),
               "setup_s": setup["setup_s"]}
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell_metrics(bench, cell["name"], "end_to_end")}
        served = sum(int(p["reads_done"]) + int(p["writes_done"])
                     for c in calls for p in c.points)
        log(f"served requests per s: {served / window_s}")
    result["memory_peak_bytes"] = mem
    if trace:
        result["trace"] = run.trace
    result["checks"] = checks
    return result
