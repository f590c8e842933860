"""Simulator-engine performance: cycles/second of the jitted lax.scan
engine vs the scalar python oracle, vmap DSE scaling, and channel-scaling
of the vmapped multi-channel memory system (the TPU-native payoff claimed
in DESIGN.md §2).

Emits ``BENCH_engine.json`` (scalar, batched, and channel-scaling
cycles/sec) so the performance trajectory is recorded run over run.
"""
from __future__ import annotations

import json
import time


def run(report, n_cycles: int = 20_000, json_path: str = "BENCH_engine.json"):
    import jax
    from repro.core import DeviceUnderTest, Simulator, compile_spec
    from repro.core import device as D
    from repro.core.frontend import FrontendConfig

    results: dict = {"n_cycles": n_cycles}
    sim = Simulator("DDR4", "DDR4_8Gb_x8", "DDR4_2400R")

    # scan-carry footprint of the timing state: the split (dense last-issue
    # table + windowed ring) vs the old dense per-(node, cmd) ring baseline.
    # This is the cache-pressure number behind the channel-scaling curve.
    results["carry_bytes"] = {}
    for std, org, tim in (("DDR4", "DDR4_8Gb_x8", "DDR4_2400R"),
                          ("DDR5", "DDR5_16Gb_x8", "DDR5_4800B"),
                          ("HBM3", "HBM3_16Gb", "HBM3_5200")):
        cs = compile_spec(std, org, tim)
        slim, dense = D.carry_nbytes(cs), D.dense_ring_nbytes(cs)
        results["carry_bytes"][std] = {
            "table_ring": slim, "dense_ring_baseline": dense,
            "reduction": round(dense / slim, 2)}
        report(f"carry_bytes_{std}", slim,
               f"per channel; dense-ring baseline {dense} "
               f"({dense / slim:.1f}x reduction)")

    # jitted engine, steady-state rate (exclude compile: the run cache
    # keys on n_cycles, so warm with the exact timed program)
    sim.run(n_cycles)
    t0 = time.perf_counter()
    sim.run(n_cycles)
    dt = time.perf_counter() - t0
    rate = n_cycles / dt
    report("engine_cycles_per_sec", int(rate), f"{n_cycles} cycles in {dt:.2f}s")
    results["scalar_cycles_per_sec"] = int(rate)

    # scalar oracle rate (issue/probe loop)
    dut = DeviceUnderTest("DDR4", "DDR4_8Gb_x8", "DDR4_2400R")
    addr = dut.addr_vec(Rank=0, BankGroup=0, Bank=0, Row=1, Column=0)
    n_oracle = 2_000
    t0 = time.perf_counter()
    clk = 0
    for i in range(n_oracle):
        r = dut.probe("RD", addr, clk=clk)
        if r.ready:
            dut.issue("RD", addr, clk=clk)
        elif dut.probe(r.preq, addr, clk=clk).timing_OK:
            dut.issue(r.preq, addr, clk=clk)
        clk += 2
    dt_o = time.perf_counter() - t0
    report("oracle_cycles_per_sec", int(2 * n_oracle / dt_o),
           "scalar numpy reference")

    # trace-capture overhead: the "high-performance" claim of the trace
    # subsystem, measured — trace=True cycles/sec vs the plain engine,
    # plus the dense->columnar compaction cost (repro.trace.capture)
    from repro.trace.capture import capture
    # warm the exact timed program: the run cache keys on n_cycles, so a
    # short warm-up run would leave compile time inside the measurement
    sim.run(n_cycles, trace=True)
    t0 = time.perf_counter()
    _, dense = sim.run(n_cycles, trace=True)
    dt_t = time.perf_counter() - t0
    report("engine_trace_cycles_per_sec", int(n_cycles / dt_t),
           f"trace=True; {100 * (dt_t - dt) / dt:+.0f}% vs trace=False")
    results["trace_cycles_per_sec"] = int(n_cycles / dt_t)
    t0 = time.perf_counter()
    tr = capture(sim.cspec, dense)
    dt_c = time.perf_counter() - t0
    report("trace_capture_ms", round(1e3 * dt_c, 2),
           f"{len(tr)} commands compacted from {n_cycles}x2 dense cells")

    # vmap DSE scaling: N configs in one compiled program.  The first call
    # per batch shape is compile-dominated (recorded as wall_s /
    # config_cycles_per_sec, the historical trajectory fields); the warm
    # re-run isolates steady-state execution throughput.
    results["batched"] = {}
    for n_pts in (1, 8, 32):
        intervals = [1.0 + 0.5 * i for i in range(n_pts)]
        t0 = time.perf_counter()
        sim.run_batch(4_000, intervals, [1.0])
        dt = time.perf_counter() - t0
        t0 = time.perf_counter()
        sim.run_batch(4_000, intervals, [1.0])
        dt_warm = time.perf_counter() - t0
        report(f"dse_batch_{n_pts}_configs_s", round(dt, 2),
               f"{n_pts * 4_000} simulated cycles total "
               f"({n_pts * 4_000 / dt:,.0f} config-cycles/s incl compile; "
               f"{n_pts * 4_000 / dt_warm:,.0f} warm)")
        results["batched"][str(n_pts)] = {
            "wall_s": round(dt, 3),
            "config_cycles_per_sec": int(n_pts * 4_000 / dt),
            "warm_config_cycles_per_sec": int(n_pts * 4_000 / dt_warm)}

    # channel scaling: C vmapped per-channel controllers inside one scan,
    # batched over 8 load points — aggregate simulated channel-cycles/sec
    # as the channel axis widens.  This is the new multi-channel benchmark
    # scenario.  Measurement is interleaved best-of-N: per-run wall times
    # on small shared CPUs swing 2x run-to-run, so each channel count's
    # best of several alternating timed runs is recorded.
    bcycles = max(n_cycles // 5, 2_000)
    b_intervals = [1.0 + 0.5 * i for i in range(8)]
    chans = (1, 2, 4)
    sims = {}
    for c in chans:
        sims[c] = Simulator("DDR4", "DDR4_8Gb_x8", "DDR4_2400R", channels=c,
                            frontend=FrontendConfig(probes=False))
        sims[c].run_batch(bcycles, b_intervals, [1.0])    # warm the program
    best = {c: float("inf") for c in chans}
    for _ in range(3):
        for c in chans:
            t0 = time.perf_counter()
            sims[c].run_batch(bcycles, b_intervals, [1.0])
            best[c] = min(best[c], time.perf_counter() - t0)
    results["channel_scaling"] = {}
    for c in chans:
        agg = len(b_intervals) * bcycles * c / best[c]
        report(f"channel_scaling_{c}ch_cycles_per_sec", int(agg),
               f"{len(b_intervals)} load points x {bcycles} cycles x "
               f"{c} channels in {best[c]:.2f}s (batched, best of 3)")
        results["channel_scaling"][str(c)] = {
            "wall_s": round(best[c], 3),
            "aggregate_channel_cycles_per_sec": int(agg),
            "carry_bytes_per_channel": D.carry_nbytes(sims[c].cspec)}
    # explicit per-entry speedup vs the 1-channel run of the SAME box/run
    # (reviewers previously re-derived this by hand from the raw rates)
    agg1 = results["channel_scaling"]["1"]["aggregate_channel_cycles_per_sec"]
    for c in chans:
        entry = results["channel_scaling"][str(c)]
        entry["aggregate_speedup"] = round(
            entry["aggregate_channel_cycles_per_sec"] / max(agg1, 1), 3)

    # windowed-telemetry overhead: the tentpole's "low-overhead" claim,
    # measured — scalar 4-channel engine with telemetry window=256 vs
    # telemetry off, end to end (in-scan accumulators + snapshot emission
    # + host-side window diffing), on warm programs.  Shared boxes have
    # multi-second load phases that swing single runs +-20%, so mean- or
    # median-based estimators are unreliable; the floor (min over many
    # interleaved runs) of each side IS stable, so the reported overhead
    # is the ratio of interleaved minima.  The cycle count is fixed (not
    # scaled by --quick): short runs make the per-call fixed cost (extra
    # dispatch + host window diffing, ~10ms) masquerade as per-cycle
    # overhead, and long runs are what windowed telemetry is for.
    # The committed ceiling is what tools/check_bench_regression.py gates.
    tw, tn = 256, 60_000
    tsim = Simulator("DDR4", "DDR4_8Gb_x8", "DDR4_2400R", channels=4)
    tsim.run(tn)                                   # warm telemetry-off
    tsim.run(tn, telemetry=tw)                     # warm telemetry-on
    rounds = 8
    t_min = {0: float("inf"), tw: float("inf")}
    for _ in range(rounds):
        for tel in (0, tw):
            t0 = time.perf_counter()
            tsim.run(tn, telemetry=tel)
            t_min[tel] = min(t_min[tel], time.perf_counter() - t0)
    overhead = t_min[tw] / t_min[0] - 1.0
    report("telemetry_overhead_pct", round(100 * overhead, 2),
           f"4ch DDR4, window={tw}, {tn} cycles: floor {t_min[tw]:.3f}s on"
           f" vs {t_min[0]:.3f}s off (interleaved min of {rounds})")
    results["telemetry"] = {
        "window": tw, "channels": 4, "cycles": tn, "rounds": rounds,
        "off_wall_s": round(t_min[0], 4), "on_wall_s": round(t_min[tw], 4),
        "overhead": round(overhead, 4)}
    #: the CI gate: windowed capture may cost at most 5% engine slowdown
    results["telemetry_overhead_ceiling"] = 0.05
    # heterogeneous composition: DDR5x2 + CXL-attached DDR4x2 (link 80)
    # behind one mapper — the 2-spec-group scenario of the hetero-smoke CI
    # job, measured the same interleaved best-of-N way and recorded so
    # future PRs gate on it (tools/check_bench_regression.py).
    from repro.core import compile_system
    hsys = compile_system([
        dict(standard="DDR5", org_preset="DDR5_16Gb_x8",
             timing_preset="DDR5_4800B", channels=2),
        dict(standard="DDR4", org_preset="DDR4_8Gb_x8",
             timing_preset="DDR4_2400R", channels=2, link_latency=80),
    ])
    hsim = Simulator(system=hsys, frontend=FrontendConfig(probes=False))
    hsim.run_batch(bcycles, b_intervals, [1.0])          # warm the program
    best_h = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        hsim.run_batch(bcycles, b_intervals, [1.0])
        best_h = min(best_h, time.perf_counter() - t0)
    h_agg = len(b_intervals) * bcycles * hsys.n_channels / best_h
    homo4 = results["channel_scaling"]["4"][
        "aggregate_channel_cycles_per_sec"]
    h_ratio = h_agg / max(homo4, 1)
    report("hetero_2grp_cycles_per_sec", int(h_agg),
           f"{hsys.label}: {len(b_intervals)} load points x {bcycles} "
           f"cycles x {hsys.n_channels} channels in {best_h:.2f}s "
           f"({100 * h_ratio:.0f}% of the homogeneous 4ch rate)")
    results["hetero"] = {
        "label": hsys.label,
        "wall_s": round(best_h, 3),
        "aggregate_channel_cycles_per_sec": int(h_agg),
        "vs_4ch_homogeneous": round(h_ratio, 3),
    }
    # noise-padded floor for the gate: the 2-group engine may never fall
    # below half its merge-time rate relative to the homogeneous 4ch run
    results["hetero_floor_vs_4ch"] = round(0.5 * h_ratio, 3)

    # event-horizon fast-forward: wall-clock ratio of the same low-rate
    # workload with fast-forward on vs off.  interval=64 sits well below
    # 20% of DDR4-2400 saturation, the regime every latency-throughput
    # sweep spends half its points in — mostly idle cycles the horizon
    # stepper skips in closed form.  Both sides are warm programs on the
    # same box measured as interleaved minima (the only stable estimator
    # on shared runners, same rationale as the telemetry ratio above),
    # and the ratio is what tools/check_bench_regression.py gates.
    ff_n, ff_interval, ff_rounds = 60_000, 64.0, 6
    fsim = {
        True: Simulator("DDR4", "DDR4_8Gb_x8", "DDR4_2400R",
                        fast_forward=True),
        False: Simulator("DDR4", "DDR4_8Gb_x8", "DDR4_2400R",
                         fast_forward=False),
    }
    ff_stats = {}
    for ff, s in fsim.items():
        ff_stats[ff] = s.run(ff_n, interval=ff_interval)       # warm
    ff_min = {True: float("inf"), False: float("inf")}
    for _ in range(ff_rounds):
        for ff, s in fsim.items():
            t0 = time.perf_counter()
            jax.block_until_ready(s.run(ff_n, interval=ff_interval))
            ff_min[ff] = min(ff_min[ff], time.perf_counter() - t0)
    ff_speedup = ff_min[False] / max(ff_min[True], 1e-9)
    skipped = int(ff_stats[True].skipped_cycles)
    report("fast_forward_speedup", round(ff_speedup, 2),
           f"interval={ff_interval}, {ff_n} cycles: "
           f"{ff_min[False]:.3f}s off vs {ff_min[True]:.3f}s on "
           f"({100 * skipped / ff_n:.0f}% cycles skipped, "
           f"{int(ff_stats[True].scan_steps)} scan steps)")
    results["fast_forward"] = {
        "interval": ff_interval, "cycles": ff_n, "rounds": ff_rounds,
        "off_wall_s": round(ff_min[False], 4),
        "on_wall_s": round(ff_min[True], 4),
        "skipped_cycles": skipped,
        "scan_steps": int(ff_stats[True].scan_steps),
        "idle_fraction": round(skipped / ff_n, 4),
        "speedup": round(ff_speedup, 3)}
    # noise-padded merge-time floor for the CI gate (same pattern as the
    # hetero floor: half this box's measured ratio)
    results["fast_forward_speedup_floor"] = round(0.5 * ff_speedup, 3)

    # scale-out: the channel-sharded engine (shard_map over the channel
    # mesh) and the device-sharded sweep, each against its one-device
    # twin in this same process (a chip belongs to one process, so no
    # child may take the devices).  The "4" side uses every visible
    # device; run with XLA_FLAGS=--xla_force_host_platform_device_count=4
    # on a CPU box to give it four.
    probe = _scale_probe(n_cycles, 64)
    ch1, ch4 = probe["channel"]["1"], probe["channel"]["4"]
    sw1, sw4 = probe["sweep"]["1"], probe["sweep"]["4"]
    ch_speedup = (ch4["aggregate_channel_cycles_per_sec"]
                  / max(ch1["aggregate_channel_cycles_per_sec"], 1))
    sw_speedup = sw1["wall_s"] / max(sw4["wall_s"], 1e-9)
    results["channel_scaling_sharded"] = {
        "1": ch1, "4": ch4, "speedup_1_to_4": round(ch_speedup, 3)}
    results["sweep_scaling"] = {
        "points": sw1["points"], "1": sw1, "4": sw4,
        "speedup_1_to_4": round(sw_speedup, 3)}
    report("channel_scaling_sharded_speedup_1_to_4", round(ch_speedup, 2),
           f"4ch scalar engine, shard_map d={ch4['shard']} vs "
           f"single-device vmap ({ch4['wall_s']}s vs {ch1['wall_s']}s)")
    report("sweep_scaling_speedup_1_to_4", round(sw_speedup, 2),
           f"{sw1['points']}-point sweep, {sw4['devices']} devices vs 1 "
           f"({sw4['wall_s']}s vs {sw1['wall_s']}s)")
    # merge-time floors for the CI gate: forced host devices on a small
    # runner time-slice one physical core rather than parallelize, so the
    # floor is a noise-padded capture of THIS box's measured ratio (the
    # same pattern as speedup_floor_1_to_4 below) — on real multi-core
    # boxes the recorded speedups, and hence the floors, rise with the
    # hardware that measured them
    results["sharded_speedup_floor_1_to_4"] = round(0.75 * ch_speedup, 3)
    results["sweep_speedup_floor_1_to_4"] = round(0.75 * sw_speedup, 3)

    cs = results["channel_scaling"]
    for hi in (2, 4):
        speedup = (cs[str(hi)]["aggregate_channel_cycles_per_sec"]
                   / max(cs["1"]["aggregate_channel_cycles_per_sec"], 1))
        report(f"channel_scaling_speedup_1_to_{hi}", round(speedup, 2),
               f"aggregate simulated-cycles/sec, {hi}ch vs 1ch")
        results[f"channel_scaling_speedup_1_to_{hi}"] = round(speedup, 3)
    # the regression floor the bench-smoke CI job enforces on future runs:
    # the 1->4 speedup may never drop below a noise-padded floor of the
    # speedups recorded at merge time (capped by the 1->2 speedup — the
    # cliff PR 3 measured was 4ch falling far below the 2ch trend)
    s12 = results["channel_scaling_speedup_1_to_2"]
    s14 = results["channel_scaling_speedup_1_to_4"]
    results["speedup_floor_1_to_4"] = round(0.75 * min(s12, s14), 3)

    with open(json_path, "w") as f:
        json.dump(results, f, indent=1)
    report("bench_engine_json", json_path, "perf trajectory artifact")


def _scale_probe(n_cycles: int, n_points: int) -> dict:
    """Measure the 4-channel scalar engine with ``channel_shard=False``
    (side "1") against auto channel sharding over the visible devices
    (side "4"), and a ``n_points``-point sweep on the first device against
    the sweep sharded over every device.  Each side is warmed first and
    timed as the best of a few runs."""
    import jax
    from repro.core import Simulator
    from repro.core import engine as E
    from repro.core.frontend import FrontendConfig
    from repro.dse import SweepSpec, execute

    out = {"channel": {}, "sweep": {}}
    for side, cs in (("1", False), ("4", None)):
        sim = Simulator("DDR4", "DDR4_8Gb_x8", "DDR4_2400R", channels=4,
                        frontend=FrontendConfig(probes=False),
                        channel_shard=cs)
        shard = sim._resolved_shard()
        jax.block_until_ready(sim.run(n_cycles))            # compile + warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(sim.run(n_cycles))
            best = min(best, time.perf_counter() - t0)
        out["channel"][side] = {
            "channels": 4, "shard": int(shard) if shard else 0,
            "wall_s": round(best, 4),
            "aggregate_channel_cycles_per_sec": int(4 * n_cycles / best)}

    # sweep axis: one compile group, ``n_points`` load points sharded
    # across the device mesh with donated carries + streamed collection
    spec = SweepSpec(
        systems=("DDR4",),
        intervals=tuple(1.0 + 0.5 * i for i in range(n_points // 4)),
        read_ratios=(1.0, 0.9, 0.8, 0.7),
        n_cycles=max(n_cycles // 5, 2_000))
    for side, devices in (("1", jax.devices()[:1]), ("4", jax.devices())):
        cache = E.RunCache()
        execute(spec, cache=cache, devices=devices)         # compile + warm
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            execute(spec, cache=cache, devices=devices)
            best = min(best, time.perf_counter() - t0)
        out["sweep"][side] = {"points": spec.n_points,
                              "devices": len(devices),
                              "wall_s": round(best, 4)}
    return out


if __name__ == "__main__":
    run(lambda name, value, derived="":
        print(f"{name},{value},{derived}", flush=True))
