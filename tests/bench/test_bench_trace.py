"""The benchmark's reduction from a profiler trace to numbers
(``bench/tracing.py``), on hand-made events and on a small trace recorded
on the CPU backend and committed as a fixture."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "..", "bench"))

import tracing  # noqa: E402

FIXTURE = os.path.join(HERE, "fixtures", "cpu_trace")


def _events():
    # chip 0: a while op [0, 100) holding two fusions; an all-reduce
    # [120, 130); idle [100, 120) and [130, 200).  chip 1: busy [0, 50)
    # and [120, 170).
    return {
        "devices": {
            "/device:TPU:0": [("while.1", 0.0, 100.0),
                              ("fusion.1", 10.0, 30.0),
                              ("fusion.2", 50.0, 20.0),
                              ("all-reduce.3", 120.0, 10.0)],
            "/device:TPU:1": [("while.1", 0.0, 50.0),
                              ("while.2", 120.0, 50.0)],
        },
        "host": [("bench.call", 0.0, 200.0),
                 ("collect", 95.0, 40.0)],
    }


def test_reduce_unions_busy_time_per_chip_and_averages():
    r = tracing.reduce(_events(), (0.0, 200.0))
    # chip 0 busy 110 ns, chip 1 busy 100 ns
    assert r["busy_s"] == pytest.approx(105e-9)
    assert r["window_s"] == pytest.approx(200e-9)
    assert r["idle_share"] == pytest.approx(1 - 105 / 200)
    # collectives: chip 0 10 of 110 ns, chip 1 none of 100 ns
    assert r["collective_share"] == pytest.approx((10 / 110 + 0) / 2)
    assert r["truncated"] is None


def test_reduce_reports_self_time_and_named_gaps():
    r = tracing.reduce(_events(), (0.0, 200.0))
    ops = dict(r["device_ops"])
    # the while op's own time excludes the 50 ns of its nested fusions,
    # summed over both chips: (100 - 50) + 50
    assert ops["while.1"] == pytest.approx(100e-9)
    assert ops["while.2"] == pytest.approx(50e-9)
    assert ops["fusion.1"] == pytest.approx(30e-9)
    assert ops["all-reduce.3"] == pytest.approx(10e-9)
    gaps = r["idle_gaps"]
    assert gaps[0][1] == pytest.approx(70e-9)       # [130, 200)
    assert gaps[0][0].startswith("bench.call")
    assert gaps[1][1] == pytest.approx(20e-9)       # [100, 120)
    assert gaps[1][0].startswith("collect")         # shortest covering span


def test_reduce_clips_to_the_window_and_finds_nothing_outside():
    r = tracing.reduce(_events(), (60.0, 100.0))
    # chip 0 busy [60, 100), chip 1 idle throughout: still averaged in
    assert r["busy_s"] == pytest.approx((40 + 0) / 2 * 1e-9)
    assert r["truncated"] is None
    empty = tracing.reduce(_events(), (300.0, 400.0))
    assert empty["busy_s"] is None and empty["idle_share"] is None


def test_recorded_cpu_trace_reduces_to_the_pinned_numbers():
    ev = tracing.extract(FIXTURE)
    assert list(ev["devices"]) == ["/host:CPU"]
    spans = [(s, s + d) for n, s, d in ev["host"] if n == "bench.call"]
    assert len(spans) == 2
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    r = tracing.reduce(ev, (lo, hi))
    assert r["window_s"] == pytest.approx(0.003975888, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.001571455, rel=1e-9)
    assert r["idle_share"] == pytest.approx(1 - 0.001571455 / 0.003975888)
    assert r["collective_share"] == 0.0
    assert r["device_ops"][0][0] == "cosine_add_fusion"
    assert 0 < len(r["idle_gaps"]) <= tracing.TOP
    assert r["idle_gaps"][0][1] == pytest.approx(0.002196874, rel=1e-6)
    assert r["truncated"] is None


def test_a_trace_cut_short_reduces_to_nothing(monkeypatch):
    # the profiler stopped recording: a chip's last operation ends more
    # than half the window before the window's end
    r = tracing.reduce(_events(), (0.0, 400.0))
    assert r["busy_s"] is None and r["idle_share"] is None
    assert r["device_ops"] == [] and "TPU:0" in r["truncated"]
    # or the device events reached the profiler's limit
    assert tracing.EVENT_LIMIT > 5_000_000
    monkeypatch.setattr(tracing, "EVENT_LIMIT", 7)
    assert tracing.reduce(_events(), (0.0, 200.0))["truncated"] is None
    monkeypatch.setattr(tracing, "EVENT_LIMIT", 6)
    r = tracing.reduce(_events(), (0.0, 200.0))
    assert r["busy_s"] is None and "limit" in r["truncated"]


def test_recorded_cpu_trace_cut_short_reduces_to_nothing():
    ev = tracing.extract(FIXTURE)
    spans = [(s, s + d) for n, s, d in ev["host"] if n == "bench.call"]
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    assert tracing.truncation(ev, (lo, hi)) is None
    # keep only the device events of the first 40% of the window, as a
    # profiler that stopped recording there would
    stop = lo + 0.4 * (hi - lo)
    cut = {"devices": {p: [e for e in v if e[1] + e[2] <= stop]
                       for p, v in ev["devices"].items()},
           "host": ev["host"]}
    r = tracing.reduce(cut, (lo, hi))
    assert r["busy_s"] is None and r["idle_share"] is None
    assert r["truncated"].startswith("/host:CPU")
