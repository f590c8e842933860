"""The comparison that decides the benchmark's ``correct``, on the CPU at
sizes a test run can hold.

- The plain reference (``bench/reference.py``) gives every ``Stats`` leaf
  of the program exactly, on both configurations at several loads.
- The control (the reference with tRCD one cycle short) fails the
  comparison.
- A whole run of each cell, with the look for a chip skipped, comes out
  correct; with the timed path broken underneath it comes out not
  correct, once for each fault the cell can have: a controller step that
  returns its state unchanged, half of the sweep batch left out (its
  points replaced by copies of the other half), an answer altered where
  it is produced.  The sweep also runs with its batch sharded over four
  forced host devices, in a child process.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.normpath(os.path.join(HERE, "..", ".."))
sys.path[:0] = [os.path.join(ROOT, "bench")]

import harness  # noqa: E402
import reference  # noqa: E402
from devices import stats_leaves  # noqa: E402

#: simulated cycles per call in these tests (the cells run 50,000)
N = 1500
BENCH = harness.load_benchmark(ROOT)


def _cell(name, n=N):
    cell, config, traffic = harness.resolve_cell(BENCH, name, ROOT)
    return cell, dict(config, n_cycles=n), traffic


def _program(config, interval, read_ratio, seed, n):
    import programs
    from repro.core import Simulator
    sim = Simulator(config["standard"], config["org_preset"],
                    config["timing_preset"], channels=config["channels"],
                    timing_overrides=programs.timing_overrides(config),
                    controller=programs.controller_config(config),
                    frontend=programs.frontend_config(config),
                    channel_shard=False)
    return stats_leaves(sim.run(n, interval=interval, read_ratio=read_ratio,
                                seed=seed))


@pytest.mark.parametrize("config_name,interval,read_ratio,seed", [
    ("ddr5_8ch2r", 1.0, 0.5, 4_000_000_007),
    ("ddr5_8ch2r", 16.0, 0.667, 11),
    ("hbm3_16ch", 1.0, 0.667, 2_147_483_659),
    ("hbm3_16ch", 4.0, 1.0, 5),
])
def test_reference_gives_every_stats_leaf_of_the_program(
        config_name, interval, read_ratio, seed):
    config = harness.load_json(os.path.join(
        ROOT, "bench", "configs", f"{config_name}.json"))
    # long enough for refresh (DDR5 nREFI 9360, staggered over channels)
    n = 3000
    got = _program(config, interval, read_ratio, seed, n)
    ref = reference.simulate(config, interval, read_ratio, seed, n)
    assert harness.compare(got, ref) == (0, [])
    assert set(got) - set(ref) == {"scan_steps", "skipped_cycles"}
    assert int(ref["cmd_counts"][reference.NAMES.index("REFab")]) > 0


@pytest.mark.parametrize("config_name", ["ddr5_8ch2r", "hbm3_16ch"])
def test_control_fails_the_comparison(config_name):
    config = harness.load_json(os.path.join(
        ROOT, "bench", "configs", f"{config_name}.json"))
    ref = reference.simulate(config, 1.0, 0.667, 99, N)
    ctl = reference.simulate(config, 1.0, 0.667, 99, N, control=True)
    assert harness.compare(ctl, ref)[0] > 0


@pytest.fixture
def fresh_programs():
    """Every run in these tests traces its programs anew, so that a fault
    planted in the program is compiled in, and none outlives the test."""
    from repro.core import engine as E
    E.RUN_CACHE.clear()
    yield
    E.RUN_CACHE.clear()


def _plant(monkeypatch, fault):
    from repro.core import controller as C
    from repro.core import engine as E
    from repro.dse import executor as X
    if fault == "state_unchanged":
        step = C.controller_step

        def frozen(cspec, dp, cfg, cs, clk, link_latency=0):
            return cs, step(cspec, dp, cfg, cs, clk, link_latency)[1]
        monkeypatch.setattr(C, "controller_step", frozen)
    elif fault == "half_batch":
        front = X._front_params

        def half(pts, fcfg):
            keep = pts[:(len(pts) + 1) // 2]
            return front(keep + keep[:len(pts) - len(keep)], fcfg)
        monkeypatch.setattr(X, "_front_params", half)
    elif fault == "answer_altered":
        agg = E._aggregate_stats

        def altered(*a, **k):
            s = agg(*a, **k)
            return s._replace(writes_done=s.writes_done + 1)
        monkeypatch.setattr(E, "_aggregate_stats", altered)
    else:
        assert fault is None


def _run_cell(name, devices, seed=3_000_000_019):
    cell, config, traffic = _cell(name)
    return harness.run_cell(BENCH, cell, config, traffic, seed, 0.01,
                            False, devices, time.perf_counter(),
                            log=lambda s: None)


CASES = [
    ("ddr5_8ch2r.sweep24", None),
    ("ddr5_8ch2r.sweep24", "state_unchanged"),
    ("ddr5_8ch2r.sweep24", "half_batch"),
    ("ddr5_8ch2r.sweep24", "answer_altered"),
    ("hbm3_16ch.stream_i1", None),
    ("hbm3_16ch.stream_i1", "state_unchanged"),
    ("hbm3_16ch.stream_i1", "answer_altered"),
    ("ddr5_8ch2r.light_i16", None),
    ("ddr5_8ch2r.light_i16", "state_unchanged"),
    ("ddr5_8ch2r.light_i16", "answer_altered"),
]


@pytest.mark.parametrize("name,fault", CASES)
def test_run_is_correct_only_when_the_timed_path_is_sound(
        name, fault, fresh_programs, monkeypatch):
    import jax
    _plant(monkeypatch, fault)
    res = _run_cell(name, jax.devices()[:1])
    assert res["correct"] is (fault is None), res["checks"]
    assert res["attempted"] >= 1
    assert list(res["checks"]) == ["stats_differing", "config_differing",
                                   "counters_inconsistent"]
    assert res["metrics"]["sim_cycles_per_s"]["value"] > 0


FOUR_CHIP = r"""
import json, sys
sys.path[:0] = [{bench!r}, {src!r}, {tests!r}]
import jax
from repro.core import engine as E
from test_bench_correctness import _plant, _run_cell


class Patch:
    def __init__(self):
        self.undo = []

    def setattr(self, obj, name, value):
        self.undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)


out = {{}}
for fault in (None, "half_batch", "answer_altered"):
    E.RUN_CACHE.clear()
    patch = Patch()
    _plant(patch, fault)
    out[str(fault)] = _run_cell("ddr5_8ch2r.sweep24",
                                jax.devices()[:4])["correct"]
    for obj, name, value in reversed(patch.undo):
        setattr(obj, name, value)
print(json.dumps(out))
"""


def test_sweep_sharded_over_four_host_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR_CHIP.format(bench=os.path.join(ROOT, "bench"),
                            src=os.path.join(ROOT, "src"), tests=HERE)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got == {"None": True, "half_batch": False,
                   "answer_altered": False}


def test_sweep_recovers_probe_latency_sums_exactly():
    import jax
    from repro.core import engine as E
    cell, config, traffic = _cell("ddr5_8ch2r.sweep24", n=2000)
    kind = harness.load_plugin("kinds", "sweep")
    runner = kind.Runner(config, dict(traffic, intervals=[2.0],
                                      read_ratios=[0.5]), jax.devices()[:1])
    seed = harness.call_seed(12345, 0)
    (pt,) = runner.call(seed)
    ref = reference.simulate(config, 2.0, 0.5, seed, 2000)
    assert int(pt["probe_cnt"]) > 5
    assert int(pt["probe_lat_sum"]) == int(ref["probe_lat_sum"])
    assert np.array_equal(pt["cmd_counts"], ref["cmd_counts"])
    E.RUN_CACHE.clear()
