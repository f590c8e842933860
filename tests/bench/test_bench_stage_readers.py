"""The per-layer readers of the compile stages (``setup.trace_s``,
``setup.compile_s``) and of the four-chip cell's collectives
(``mesh.collective_share``), on hand-made runs and on the program's own
first-call records on the CPU."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.normpath(os.path.join(HERE, "..", ".."))
sys.path.insert(0, os.path.join(ROOT, "bench"))

import harness  # noqa: E402
from repro.core import Simulator  # noqa: E402
from repro.core import engine as E  # noqa: E402


def _run(n_cycles=50_000, trace=None):
    return harness.Run(cell={"name": "x"}, config={"n_cycles": n_cycles},
                       traffic={}, chips=1, points=[], calls=[],
                       setup={}, trace=trace)


def _metric(name, run):
    return harness.load_plugin("metrics", name).read(run)


class _Records:
    """A stand-in cache that holds the given first-call records."""

    def __init__(self, records):
        self._records = records

    def first_calls(self):
        return [dict(r) for r in self._records]


def _record(n_cycles, trace_s, lower_s, compile_s):
    return {"n_cycles": n_cycles, "first_call_s": 9.0, "trace_s": trace_s,
            "lower_s": lower_s, "compile_s": compile_s,
            "cache_load_s": 0.0, "persistent_hits": 0,
            "persistent_misses": 0}


def test_stage_readers_take_the_timed_length_alone(monkeypatch):
    # the timed program (50k cycles) and the traced one (5k)
    monkeypatch.setattr(E, "RUN_CACHE", _Records(
        [_record(50_000, 1.25, 0.5, 0.25), _record(5_000, 7.0, 7.0, 7.0)]))
    run = _run()
    assert _metric("setup.trace_s", run) == pytest.approx(1.75)
    assert _metric("setup.compile_s", run) == pytest.approx(0.25)
    # no program of the cell's length, or no length at all: nothing
    for other in (_run(n_cycles=1_000), _run(n_cycles=None)):
        assert _metric("setup.trace_s", other) is None
        assert _metric("setup.compile_s", other) is None


@pytest.mark.parametrize("name", ["setup.trace_s", "setup.compile_s"])
def test_stage_readers_read_nothing_without_first_call_records(
        monkeypatch, name):
    # a program whose cache keeps no first-call records
    monkeypatch.setattr(E, "RUN_CACHE", object())
    assert _metric(name, _run()) is None


def test_stage_readers_on_the_programs_own_records(monkeypatch):
    import jax.numpy as jnp
    cache = E.RunCache()
    monkeypatch.setattr(E, "RUN_CACHE", cache)
    sim = Simulator("DDR4", "DDR4_8Gb_x8", "DDR4_2400R")
    # cycle counts no other test compiles, so that every stage runs
    args = (sim._dyn_params(), sim.frontend.params(), jnp.uint32(5))
    for n in (431, 173):
        cache.get(sim.cspec, sim.controller, sim.frontend, n)(*args)
    timed, traced = cache.first_calls()
    assert (timed["n_cycles"], traced["n_cycles"]) == (431, 173)
    run = _run(n_cycles=431)
    assert _metric("setup.trace_s", run) == pytest.approx(
        timed["trace_s"] + timed["lower_s"])
    assert _metric("setup.compile_s", run) == pytest.approx(
        timed["compile_s"])
    assert _metric("setup.trace_s", run) > 0
    assert _metric("setup.compile_s", run) > 0


def test_collective_share_reads_the_trace():
    assert _metric("mesh.collective_share", _run()) is None
    assert _metric("mesh.collective_share",
                   _run(trace={"collective_share": None})) is None
    assert _metric("mesh.collective_share",
                   _run(trace={"collective_share": 0.021})) == 0.021
