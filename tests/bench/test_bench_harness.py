"""The chip benchmark's harness on the CPU: the command refuses to run
without a TPU, cells, traffic, metrics and references are found by name
(a cell, or a deployment with its own reference, added as files alone is
picked up), and the metric arithmetic holds on hand-made results."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.normpath(os.path.join(HERE, "..", ".."))
sys.path.insert(0, os.path.join(ROOT, "bench"))

import harness  # noqa: E402


def _pt(cycles, steps, reads=0, writes=0):
    return {"cycles": np.int64(cycles), "scan_steps": np.int64(steps),
            "skipped_cycles": np.int64(cycles - steps),
            "reads_done": np.int64(reads), "writes_done": np.int64(writes)}


def _run(calls, points, trace=None, traced=()):
    return harness.Run(cell={"name": "x"}, config={}, traffic={},
                       chips=1, points=points, calls=calls,
                       setup={"program_s": 1.5}, trace=trace,
                       traced_calls=list(traced))


def _metric(name, run):
    return harness.load_plugin("metrics", name).read(run)


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark(ROOT)


def test_command_exits_nonzero_without_a_tpu(bench):
    cell = bench["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", cell, "--seed", "3000000001", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "no TPU" in p.stderr


def test_every_cell_resolves_to_its_files(bench):
    names = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        cell, config, traffic = harness.resolve_cell(bench, w["name"], ROOT)
        assert config["name"] == w["config"] in names
        kind = harness.load_plugin("kinds", traffic["kind"], ROOT)
        assert hasattr(kind, "Runner") and hasattr(kind, "CHECKED")
    for name in names:
        config = harness.load_config(bench, name, ROOT)
        ref = harness.load_reference(config["reference"])
        assert callable(ref.simulate) and len(ref.NAMES) > 0
    for m in bench["per_layer"]:
        assert hasattr(harness.load_plugin("metrics", m["name"], ROOT),
                       "read")


def test_a_cell_added_as_files_alone_is_picked_up(bench, tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "traffic" / "light_i64.json").write_text(json.dumps(
        {"kind": "scalar", "why": "light", "interval": 64.0,
         "read_ratio": 1.0}))
    (root / "bench" / "metrics" / "driver.calls.py").write_text(
        "def read(run):\n    return len(run.calls)\n")
    new = dict(bench)
    new["workloads"] = bench["workloads"] + [
        {"name": "ddr5_8ch2r.light_i64", "config": "ddr5_8ch2r",
         "traffic": "light_i64", "chips": 1, "why": "added by files"}]
    new["per_layer"] = bench["per_layer"] + [
        {"name": "driver.calls", "unit": "calls", "better": "higher",
         "source": "program_counter", "layer": "cycle driver",
         "moves": "sim_cycles_per_s", "workloads": ["ddr5_8ch2r.light_i64"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    loaded = harness.load_benchmark(str(root))
    cell, config, traffic = harness.resolve_cell(
        loaded, "ddr5_8ch2r.light_i64", str(root))
    assert traffic["interval"] == 64.0 and config["channels"] == 8
    names = [m["name"] for m in harness.cell_metrics(
        loaded, cell["name"], "per_layer")]
    assert "driver.calls" in names
    assert "setup.program_s" in names
    run = _run([harness.Call(1, 0.0, 1.0, [_pt(50, 10)])],
               [{"interval": 64.0, "read_ratio": 1.0}])
    run.cell = cell
    got = harness.read_per_layer(loaded, run, str(root))
    assert got["driver.calls"] == {"value": 1.0, "unit": "calls"}
    assert got["driver.skip_share"]["value"] == pytest.approx(0.8)


#: a reference that wraps bench/reference.py and adds 1 to one leaf
ECHO = '''"""bench/reference.py with one more write served on every run.
``control=True`` breaks what bench/reference.py's does (tRCD)."""
import reference

NAMES = reference.NAMES


def simulate(config, interval, read_ratio, seed, n_cycles, control=False):
    out = reference.simulate(config, interval, read_ratio, seed, n_cycles,
                             control)
    out["writes_done"] = out["writes_done"] + 1
    return out
'''


def _deployment_added_as_files(bench, tmp_path, reference):
    """A checkout with a configuration ``ddr5_echo`` (the DDR5 socket's
    file, with ``reference`` set where not None) and a scalar cell on it,
    added as files; returns ``(root, loaded BENCHMARK.json)``."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "references").mkdir()
    (root / "bench" / "references" / "echo.py").write_text(ECHO)
    config = harness.load_json(os.path.join(ROOT, "bench", "configs",
                                            "ddr5_8ch2r.json"))
    config = dict(config, name="ddr5_echo", n_cycles=400)
    if reference is not None:
        config["reference"] = reference
    (root / "bench" / "configs" / "ddr5_echo.json").write_text(
        json.dumps(config))
    new = dict(bench)
    new["configs"] = bench["configs"] + [
        dict(bench["configs"][0], name="ddr5_echo",
             file="bench/configs/ddr5_echo.json")]
    new["workloads"] = bench["workloads"] + [
        {"name": "ddr5_echo.stream_i1", "config": "ddr5_echo",
         "traffic": "stream_i1", "chips": 1, "why": "added by files"}]
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    return root, harness.load_benchmark(str(root))


@pytest.mark.parametrize("reference,differing", [
    ("bench/references/echo.py", 1), (None, 0)])
def test_a_deployment_added_as_files_is_checked_by_its_own_reference(
        bench, tmp_path, reference, differing):
    import reference as plain
    root, loaded = _deployment_added_as_files(bench, tmp_path, reference)
    cell, config, traffic = harness.resolve_cell(
        loaded, "ddr5_echo.stream_i1", str(root))
    # the program's result stands in as the plain reference's own
    seed, n = harness.call_seed(77, 0), int(config["n_cycles"])
    pt = plain.simulate(config, traffic["interval"], traffic["read_ratio"],
                        seed, n)
    pt.update(scan_steps=np.int64(n), skipped_cycles=np.int64(0))
    run = harness.Run(cell=cell, config=config, traffic=traffic, chips=1,
                      points=[{"interval": traffic["interval"],
                               "read_ratio": traffic["read_ratio"]}],
                      calls=[harness.Call(seed, 0.0, 1.0, [pt])], setup={})
    checks, failed, _ = harness.check(run, 77, [])
    assert checks["stats_differing"]["value"] == differing
    assert failed == bool(differing)


def test_a_reference_named_but_missing_is_an_error(bench, tmp_path):
    root, loaded = _deployment_added_as_files(
        bench, tmp_path, "bench/references/missing.py")
    with pytest.raises(harness.BenchError, match="missing.py"):
        harness.resolve_cell(loaded, "ddr5_echo.stream_i1", str(root))


def test_the_plain_reference_refuses_a_standard_it_does_not_model():
    import reference
    config = harness.load_json(os.path.join(ROOT, "bench", "configs",
                                            "ddr5_8ch2r.json"))
    with pytest.raises(ValueError, match="models DDR5 and HBM3, not LPDDR5"):
        reference.simulate(dict(config, standard="LPDDR5"), 1.0, 1.0, 1, 10)


def test_sim_cycles_per_s_is_all_work_over_all_time():
    calls = [harness.Call(1, 10.0, 12.0, [_pt(50_000, 50_000)] * 3),
             harness.Call(2, 12.5, 15.0, [_pt(50_000, 10_000)] * 3)]
    # 6 points x 50k cycles over 10.0 -> 15.0 s, gaps included
    assert harness.sim_cycles_per_s(calls) == pytest.approx(300_000 / 5.0)


def test_skip_share_on_hand_made_columns():
    pts = [{"interval": i, "read_ratio": 1.0} for i in (64.0, 16.0, 1.0)]
    calls = [harness.Call(1, 0.0, 1.0, [_pt(100, 10), _pt(100, 40),
                                         _pt(100, 100)]),
             harness.Call(2, 1.0, 2.0, [_pt(100, 20), _pt(100, 30),
                                         _pt(100, 50)])]
    run = _run(calls, pts)
    assert _metric("driver.skip_share", run) == pytest.approx(
        (90 + 60 + 0 + 80 + 70 + 50) / 600)
    one = _run([harness.Call(1, 0.0, 1.0, [_pt(100, 10)])], pts[:1])
    assert _metric("driver.skip_share", one) == pytest.approx(0.9)


def test_trace_metrics_read_nothing_without_a_trace():
    pts = [{"interval": 1.0, "read_ratio": 1.0}]
    calls = [harness.Call(1, 0.0, 1.0, [_pt(100, 100)])]
    run = _run(calls, pts)
    for name in ("body.us_per_iter", "device.idle_share"):
        assert _metric(name, run) is None
    trace = {"busy_s": 0.5, "idle_share": 0.25}
    traced = _run(calls, pts, trace=trace, traced=calls)
    # 0.5 s of device time over the traced call's 100 iterations
    assert _metric("body.us_per_iter", traced) == pytest.approx(5000.0)
    assert _metric("device.idle_share", traced) == 0.25
    assert _metric("setup.program_s", run) == 1.5


def test_call_seeds_are_32_bit_distinct_and_repeatable():
    seeds = [harness.call_seed(2**31 + 17, i) for i in range(64)]
    assert len(set(seeds)) == 64
    assert all(0 <= s < 2**32 for s in seeds)
    # the program seeds its generator with seed | 1: no two calls alias
    assert len({s | 1 for s in seeds}) == 64
    assert seeds == [harness.call_seed(2**31 + 17, i) for i in range(64)]


def test_counter_faults_and_compare():
    ok = harness.Call(1, 0.0, 1.0, [_pt(100, 40)])
    bad = harness.Call(2, 0.0, 1.0, [dict(_pt(100, 40),
                                          skipped_cycles=np.int64(59))])
    assert harness.counter_faults([ok]) == 0
    assert harness.counter_faults([ok, bad]) == 1
    ref = {"a": np.arange(4), "b": np.int64(3)}
    assert harness.compare({"a": np.arange(4), "b": np.int64(3)}, ref) \
        == (0, [])
    assert harness.compare({"a": np.array([0, 1, 9, 9]), "b": 3}, ref) \
        == (2, ["a"])
    assert harness.compare({"a": np.arange(4)}, ref) == (1, ["b"])
