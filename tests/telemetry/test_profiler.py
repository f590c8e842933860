"""Host-side run profiler: RunCache public accounting, span recording,
and the one-shot cold/warm characterization."""
import numpy as np
import pytest

from repro import telemetry as T
from repro.core import Simulator
from repro.core import engine as E


def test_runcache_stats_public_api():
    s = E.RUN_CACHE.stats()
    assert set(s) == {"entries", "hits", "misses", "first_call_s",
                      "trace_s", "lower_s", "compile_s", "cache_load_s",
                      "persistent_hits", "persistent_misses",
                      "devices", "shard_topologies"}
    assert s["entries"] >= 0 and s["first_call_s"] >= 0.0
    assert s["devices"] >= 1
    assert all(t == "vmap" or t.startswith("channels:")
               for t in s["shard_topologies"])
    sim = Simulator("DDR4", "DDR4_8Gb_x8", "DDR4_2400R")
    sim.run(500)
    s2 = E.RUN_CACHE.stats()
    # the run either compiled a new program (miss) or reused one (hit)
    assert s2["hits"] + s2["misses"] > s["hits"] + s["misses"]


def test_profiler_spans_and_cache_delta():
    prof = T.Profiler()
    sim = Simulator("DDR4", "DDR4_8Gb_x8", "DDR4_2400R")
    with prof.span("first"):
        sim.run(600, interval=3.0)
    with prof.span("warm"):
        sim.run(600, interval=3.0)
    with prof.span("warm"):
        sim.run(600, interval=3.0)
    r = prof.report()
    assert r["spans"]["first"]["calls"] == 1
    assert r["spans"]["warm"]["calls"] == 2
    assert r["wall_s"] >= r["spans"]["first"]["s"]
    # cache view is a delta: exactly one compile, then hits
    assert r["cache"]["misses"] == 1
    assert r["cache"]["hits"] == 2
    assert "programs" in prof.summary()


def test_profile_run_cold_warm():
    sim = Simulator("DDR5", "DDR5_16Gb_x8", "DDR5_4800B")
    p = T.profile_run(sim, 800, repeats=2, interval=2.0)
    assert set(p) >= {"first_call_s", "warm_s", "compile_s",
                      "cycles_per_sec", "cache"}
    assert p["first_call_s"] >= p["warm_s"] > 0
    assert p["compile_s"] >= 0
    assert p["cycles_per_sec"] > 0
    # forwarding run_kw: telemetry-on profiling also works and the
    # windowed run produces the same aggregate throughput
    p_tel = T.profile_run(sim, 800, repeats=1, interval=2.0, telemetry=128)
    assert p_tel["cycles_per_sec"] > 0


def test_sweep_reports_cache_accounting():
    from repro.dse import SweepSpec, execute
    res = execute(SweepSpec(systems=("DDR4",), intervals=(4.0,),
                            read_ratios=(1.0,), n_cycles=500))
    c = res.meta["cache"]
    assert set(c) >= {"entries", "hits", "misses", "first_call_s"}


def test_runcache_counts_the_compile_stages_of_first_calls():
    import jax.numpy as jnp
    cache = E.RunCache()
    prof = T.Profiler(cache)
    sim = Simulator("DDR4", "DDR4_8Gb_x8", "DDR4_2400R")
    # a cycle count no other test compiles, so that the program is new
    # to this process and every stage runs
    args = (sim._dyn_params(), sim.frontend.params(), jnp.uint32(3))
    cache.get(sim.cspec, sim.controller, sim.frontend, 417)(*args)
    first = cache.stats()
    for stage in ("trace_s", "lower_s", "compile_s"):
        assert first[stage] > 0, stage
    assert first["compile_s"] <= first["first_call_s"] + 1e-3
    assert first["persistent_hits"] == first["persistent_misses"] == 0
    # the same, per program
    (record,) = cache.first_calls()
    assert record["n_cycles"] == 417
    for stage in ("trace_s", "lower_s", "compile_s"):
        assert record[stage] == pytest.approx(first[stage], abs=1e-6)
    # a second identical call (and lookup) compiles nothing
    cache.get(sim.cspec, sim.controller, sim.frontend, 417)(*args)
    again = cache.stats()
    assert {k: v for k, v in again.items() if k != "hits"} == \
        {k: v for k, v in first.items() if k != "hits"}
    # the profiler's view is the delta since it was made
    delta = prof.cache_stats()
    for stage in ("trace_s", "lower_s", "compile_s", "cache_load_s"):
        assert delta[stage] == pytest.approx(again[stage], abs=1e-6)
    assert len(cache.first_calls()) == 1
    cache.clear()
    assert cache.stats()["trace_s"] == 0.0
    assert cache.first_calls() == []


def test_entry_points_emit_their_spans_inside_the_callers_span(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro.dse import SweepSpec, execute
    sim = Simulator("DDR4", "DDR4_8Gb_x8", "DDR4_2400R")
    spec = SweepSpec(systems=("DDR4",), intervals=(4.0, 2.0),
                     read_ratios=(1.0,), n_cycles=300)
    sim.run(300)
    execute(spec)                   # both programs compiled beforehand
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.call"):
            sim.run(300)
        with jax.profiler.TraceAnnotation("bench.call"):
            execute(spec)
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for plane in ProfileData.from_file(path[0]).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(("bench.", "sim.", "dse."))]
    calls = sorted((s, e) for n, s, e in spans if n == "bench.call")
    assert len(calls) == 2

    def inside(call):
        lo, hi = calls[call]
        return [n for n, s, e in sorted(spans, key=lambda x: x[1])
                if lo <= s and e <= hi and n != "bench.call"]
    assert inside(0) == ["sim.lookup", "sim.launch", "sim.fetch"]
    # one compile group: plan the sweep, then plan, look up, dispatch and
    # collect the group
    assert inside(1) == ["dse.plan", "dse.plan", "dse.lookup",
                         "dse.dispatch", "dse.collect"]
