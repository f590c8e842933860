"""Batched sweep executor: group -> compile(cached) -> vmap -> stats,
with streamed cross-group collection.

The executor turns an expanded `SweepSpec` into as few compiled programs
as possible:

  1. group the `RunPoint`s by *compile group* — everything that changes
     the traced program: (standard, org, timing, overrides, controller
     config, frontend config, n_cycles).  Load knobs (interval,
     read ratio) are traced `FrontParams`, so the whole load grid of a
     group is one program;
  2. fetch the jitted batched run callable from the engine's process-wide
     `RUN_CACHE` — identical specs across sweeps (or repeated `execute`
     calls) re-trace exactly zero times.  The program's `FrontParams`
     argument is DONATED (`donate_argnums`): the executor rebuilds the
     stacked load points per group, so the device reuses their buffers
     for the scan carry instead of holding both live;
  3. vmap over the group's load points, sharding the batch across devices
     when more than one is available (padding by repeating the last point
     when the batch does not divide — padded entries are dropped from the
     results and accounted in ``meta["padded_points"]``);
  4. STREAM the groups: each group's program call is dispatched
     asynchronously (jax dispatch returns before the device finishes) and
     its results are harvested — synchronized, unpadded, folded into the
     `SweepResult` columns — only once `max_in_flight` later dispatches
     are in the pipeline or the sweep ends.  Host-side harvesting of one
     group overlaps device execution of the next, and at most
     `max_in_flight` groups' device buffers are ever live, so
     thousands-of-point sweeps never materialize all outputs at once.
     A `repro.telemetry.Profiler` attributes the wall clock to the
     phases ``dse.plan`` (expand, group, compile the spec, stack and
     place the load points), ``dse.lookup`` (`RunCache.get`),
     ``dse.dispatch`` (the async call; a program's first call compiles)
     and ``dse.collect`` (device sync + host fold), reported in
     ``meta["profile"]`` and, under a profiler session, as spans on the
     device trace's clock.

With `SweepSpec(capture_traces=...)` each group runs its *trace-emitting*
program instead — still exactly one compiled program per group (the trace
variant replaces the stats-only variant rather than adding to it, so
`engine.TRACE_COUNT` advances identically to a no-capture sweep) — and the
batched trace arrays are compacted per point into
`repro.trace.CommandTrace` objects, optionally persisted as one `.npz`
artifact per point.  `SweepSpec(telemetry=W)` works the same way for the
windowed-metrics program: every point gains a
`repro.telemetry.Telemetry` time series on `SweepResult.telemetry`, and
`meta["cache"]` reports the public `RunCache.stats()` accounting.
"""
from __future__ import annotations

import os
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import device as D
from repro.core import engine as E
from repro.core import frontend as F
from repro.core.compile import as_system, compile_spec, compile_system
from repro.dse import results as R
from repro.dse.spec import Composition, SweepSpec


def lint_sweep_systems(points) -> None:
    """Pre-compile spec-lint gate for a sweep: run the spec linter over
    every distinct override-carrying system (plain or inside a
    composition) and raise :class:`repro.analysis.SpecLintError` with the
    merged report if any has error-severity findings.  Systems without
    overrides are skipped — the registered standards are lint-clean by
    construction (CI gates that separately)."""
    from repro.analysis.report import merge
    from repro.analysis.speclint import SpecLintError, lint_spec
    seen: set = set()
    bad = []
    for pt in points:
        if isinstance(pt.system, Composition):
            members = [(g.system, g.channels) for g in pt.system.groups]
        else:
            members = [(pt.system, pt.n_channels)]
        for sy, ch in members:
            if not sy.timing_overrides or (sy, ch) in seen:
                continue
            seen.add((sy, ch))
            rep = lint_spec(sy.standard, sy.org_preset, sy.timing_preset,
                            sy.overrides_dict, channels=ch)
            if not rep.ok():
                bad.append(rep)
    if bad:
        raise SpecLintError(merge(bad, target="sweep-pre-lint"))


def _compile_point_system(pt):
    """Compile a RunPoint's memory system: a plain `System` becomes the
    (1-group) CompiledSpec the historical cache key expects; a
    `Composition` becomes a MemorySystemSpec with one compiled spec per
    group."""
    if isinstance(pt.system, Composition):
        return compile_system([
            dict(standard=g.system.standard, org_preset=g.system.org_preset,
                 timing_preset=g.system.timing_preset,
                 timing_overrides=g.system.overrides_dict,
                 channels=g.channels, link_latency=g.link_latency)
            for g in pt.system.groups])
    return compile_spec(pt.system.standard, pt.system.org_preset,
                        pt.system.timing_preset, pt.system.overrides_dict,
                        channels=pt.n_channels)


def compile_group_key(pt) -> tuple:
    """Hashable key identifying the compiled program a point runs under.
    The channel count and the mapper order (inside the frontend freeze)
    both change the traced program, so they split compile groups."""
    return (pt.system, E._freeze(pt.controller), E._freeze(pt.frontend),
            pt.n_cycles, pt.n_channels)


def group_points(points) -> dict:
    """Group (index, point) pairs by compile group, preserving order."""
    groups: dict = {}
    for i, pt in enumerate(points):
        groups.setdefault(compile_group_key(pt), []).append((i, pt))
    return groups


def _front_params(pts, fcfg) -> F.FrontParams:
    """Stack the group's load points into vmappable `FrontParams`."""
    return F.stack_params([(pt.interval, pt.read_ratio) for pt in pts],
                          fcfg.probe_gap)


def _shard_batch(fp: F.FrontParams, devices):
    """Shard the batch axis across `devices`; pad by repeating the last
    point so the batch divides evenly.  Returns (fp, n_padding)."""
    ndev = len(devices)
    if ndev == 0:
        raise ValueError(
            "devices=[] — no devices to place the sweep batch on; pass "
            "devices=None to use jax.devices(), or a non-empty device "
            "list")
    n = fp.interval_fp.shape[0]
    if ndev == 1:
        # still honor an explicit single-device pin (e.g. devices=[gpu1])
        return jax.tree.map(lambda a: jax.device_put(a, devices[0]), fp), 0
    pad = (-n) % ndev
    if pad:
        fp = jax.tree.map(
            lambda a: jnp.concatenate([a, jnp.repeat(a[-1:], pad, axis=0)]),
            fp)
    mesh = jax.sharding.Mesh(np.asarray(devices), ("b",))
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("b"))
    return jax.tree.map(lambda a: jax.device_put(a, sh), fp), pad


def execute(spec: SweepSpec, cache: E.RunCache | None = None,
            devices=None, max_in_flight: int = 2,
            profiler=None) -> R.SweepResult:
    """Run every point of `spec`, one compiled program per compile group,
    dispatching groups asynchronously and harvesting results as they
    complete (see the module docstring for the streaming pipeline).

    `cache` defaults to the engine's process-wide `RUN_CACHE`; pass a
    fresh `RunCache()` to isolate compilations (tests do).  `devices`
    defaults to `jax.devices()`.  `max_in_flight` bounds how many groups'
    device buffers may be live at once (>= 1); `profiler` is an optional
    `repro.telemetry.Profiler` to record the phase spans into (one is
    created per call otherwise, reported in ``meta["profile"]``).
    """
    from repro import telemetry as T    # lazy: keeps import order flexible
    cache = E.RUN_CACHE if cache is None else cache
    devices = jax.devices() if devices is None else devices
    if len(devices) == 0:
        raise ValueError("devices=[] — pass devices=None for jax.devices()"
                         " or a non-empty device list")
    prof = profiler if profiler is not None else T.Profiler(cache)
    with prof.span("dse.plan"):
        points = spec.expand()
        if spec.lint_specs:
            lint_sweep_systems(points)      # fail fast with a LintReport
        groups = group_points(points)

    n = len(points)
    cols = {k: np.zeros((n,), np.float64)
            for k in ("throughput_gbps", "latency_ns", "peak_gbps")}
    ints = {k: np.zeros((n,), np.int64)
            for k in ("reads_done", "writes_done", "probe_cnt", "deferred",
                      "cycles", "scan_steps", "skipped_cycles")}
    cmd_counts: list = [None] * n
    cmd_names: list = [None] * n
    capture = spec.capture_traces
    traces: list | None = [None] * n if capture else None
    trace_dir = capture if isinstance(capture, str) else None
    trace_paths: dict = {}
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
    telemetry: list | None = [None] * n if spec.telemetry else None
    telem_paths: dict = {}
    if spec.telemetry_dir:
        os.makedirs(spec.telemetry_dir, exist_ok=True)

    t0 = time.perf_counter()
    misses0, hits0, trace0 = cache.misses, cache.hits, E.TRACE_COUNT
    group_meta = []
    padded_total = 0
    inflight: deque = deque()

    def _harvest():
        """Synchronize the OLDEST in-flight group and fold its results."""
        with prof.span("dse.collect"):
            _fold(inflight.popleft())

    def _fold(g):
        """Wait for one dispatched group and fold it into the columns."""
        out = jax.block_until_ready(g["out"])
        members, idx = g["members"], g["idx"]
        msys, cspec = g["msys"], g["cspec"]
        ccfg, fcfg, pad = g["ccfg"], g["fcfg"], g["pad"]
        snaps = None
        if spec.telemetry:
            *out, snaps = out
            out = out[0] if len(out) == 1 else tuple(out)
        stats, dense = out if capture else (out, None)
        stats = jax.tree.map(np.asarray, stats)
        if pad:
            stats = jax.tree.map(lambda a: a[:-pad], stats)
        if snaps is not None:
            snaps = jax.tree.map(np.asarray, snaps)
            for j, (i, pt) in enumerate(members):
                telemetry[i] = T.build(
                    msys, jax.tree.map(lambda a: a[j], snaps),
                    window=spec.telemetry, n_cycles=pt.n_cycles)
                telemetry[i].meta["point"] = pt.label
                if spec.telemetry_dir:
                    telem_paths[i] = T.save(
                        telemetry[i], os.path.join(
                            spec.telemetry_dir, f"point_{i:04d}.npz"))
        if capture:
            from repro.trace.capture import capture as capture_trace
            from repro.trace.format import save as save_trace
            dense = jax.tree.map(np.asarray, dense)
            for j, (i, pt) in enumerate(members):
                tr = capture_trace(
                    cspec, dense, point=j, controller=ccfg, frontend=fcfg,
                    interval=pt.interval, read_ratio=pt.read_ratio,
                    seed=spec.seed, point_index=i, label=pt.label)
                traces[i] = tr
                if trace_dir:
                    trace_paths[i] = save_trace(
                        tr, os.path.join(trace_dir, f"point_{i:04d}.npz"))

        cols["throughput_gbps"][idx] = R.throughput_gbps_array(msys, stats)
        cols["latency_ns"][idx] = R.avg_probe_latency_ns_array(msys, stats)
        cols["peak_gbps"][idx] = E.peak_gbps(msys)
        for k in ints:
            ints[k][idx] = np.asarray(getattr(stats, k))
        for j, i in enumerate(idx):
            cmd_counts[i] = np.asarray(stats.cmd_counts[j])
            cmd_names[i] = list(msys.cmd_names)

    for key, members in groups.items():
        with prof.span("dse.plan"):
            idx = [i for i, _ in members]
            pts = [pt for _, pt in members]
            sy, ccfg, fcfg = (pts[0].system, pts[0].controller,
                              pts[0].frontend)
            cspec = _compile_point_system(pts[0])
            msys = as_system(cspec)
            dp = tuple(D.dyn_params(g.cspec) for g in msys.groups)
            fp = _front_params(pts, fcfg)
            fp, pad = _shard_batch(fp, devices)
            padded_total += pad
        with prof.span("dse.lookup"):
            fn = cache.get(cspec, ccfg, fcfg, pts[0].n_cycles,
                           trace=bool(capture), batched=True,
                           telemetry=spec.telemetry, donate=True)
        with prof.span("dse.dispatch"):
            # async dispatch: jax returns un-synchronized arrays; the
            # device churns through this group while the host dispatches
            # the next (and harvests the oldest).  A program's FIRST call
            # still blocks inside the cache's compile timer.
            out = fn(dp, fp, jnp.uint32(spec.seed))
        group_meta.append({"system": sy.label, "n_points": len(pts),
                           "n_channels": pts[0].n_channels,
                           "n_spec_groups": msys.n_groups,
                           "mapper": fcfg.mapper, "padded": pad})
        inflight.append({"out": out, "members": members, "idx": idx,
                         "msys": msys, "cspec": cspec, "ccfg": ccfg,
                         "fcfg": fcfg, "pad": pad})
        while len(inflight) > max(1, int(max_in_flight)):
            _harvest()
    while inflight:
        _harvest()

    meta = {
        "n_points": n,
        "n_groups": len(groups),
        "n_devices": len(devices),
        "compile_cache_misses": cache.misses - misses0,
        "compile_cache_hits": cache.hits - hits0,
        "traces": E.TRACE_COUNT - trace0,
        "wall_s": round(time.perf_counter() - t0, 3),
        "groups": group_meta,
        "seed": spec.seed,
        # batch-padding audit: device-count-aligned repeats of each
        # group's last point (simulated, then dropped from the results)
        "padded_points": padded_total,
        "max_in_flight": max(1, int(max_in_flight)),
        # plan/lookup/dispatch/collect wall attribution for the streamed
        # pipeline, plus what event-horizon fast-forward bought across
        # the sweep
        "profile": {
            **prof.report(),
            "fast_forward": {
                "scan_steps": int(ints["scan_steps"].sum()),
                "skipped_cycles": int(ints["skipped_cycles"].sum()),
                "idle_fraction": round(
                    float(ints["skipped_cycles"].sum())
                    / max(float(ints["cycles"].sum()), 1.0), 4),
            },
        },
        # public RunCache accounting (RunCache.stats()) — cumulative over
        # the cache's lifetime, alongside the per-sweep deltas above
        "cache": cache.stats(),
    }
    if trace_paths:
        meta["trace_artifacts"] = [trace_paths.get(i) for i in range(n)]
    if telem_paths:
        meta["telemetry_artifacts"] = [telem_paths.get(i) for i in range(n)]
    return R.SweepResult(points=points, cmd_counts=cmd_counts,
                         cmd_names=cmd_names, meta=meta, traces=traces,
                         telemetry=telemetry, **cols, **ints)
