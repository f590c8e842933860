"""Cycle-level memory-system engine: `lax.scan` over cycles, `vmap` over
channels *and* configs.

The engine composes (frontend -> address mapper -> per-channel controllers
-> devices) into one pure cycle function and runs it under `jax.lax.scan`.
Controller and device state carry a leading channel axis; `controller_step`
runs across the system's C channels via an inner `jax.vmap`, so a 1-channel
and an 8-channel system are the *same* compiled program shape family — one
trace, one XLA compile, regardless of channel count.  Because every load
knob and every timing latency is a traced array (`FrontParams`,
`DynParams`), a *batched* engine falls out of an outer `jax.vmap` —
hundreds of design-space points (timing presets x scheduler loads x read
ratios x channel counts x mapper orders) simulate in few compiled
programs.  This is the TPU-native analogue of Ramulator's DSE workflows
(DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
import os
import time
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import controller as C
from repro.core import device as D
from repro.core import frontend as F
from repro.core.compile import (CompiledSpec, MemorySystemSpec, SpecGroup,
                                as_system, compile_spec, compile_system)


class ChannelStats(NamedTuple):
    """Per-channel breakdowns; every leaf has a leading ``(C,)`` axis
    (``(B, C)`` for batched runs)."""
    reads_done: jnp.ndarray
    writes_done: jnp.ndarray
    probe_lat_sum: jnp.ndarray
    probe_cnt: jnp.ndarray
    data_bus_busy: jnp.ndarray      # cycles the channel's data bus was busy
    cmd_counts: jnp.ndarray         # (C, n_cmds)
    deferred: jnp.ndarray


class Stats(NamedTuple):
    """Aggregate run statistics plus the per-channel breakdown.

    The scalar fields sum across channels — and, for a heterogeneous
    system, across all spec groups (identical to the historical
    single-channel semantics when there is one group of one channel).
    ``per_channel`` holds the same counters split by *system* channel
    (group-major order); its ``cmd_counts`` are expressed in the system's
    merged command namespace (``MemorySystemSpec.cmd_names``), which for a
    homogeneous system IS the spec's own namespace.  ``per_group`` holds
    each spec group's native-namespace :class:`ChannelStats` — the
    group-correct view heterogeneous metrics (``throughput_gbps``,
    ``channel_breakdown``) are derived from.
    """
    cycles: jnp.ndarray
    reads_done: jnp.ndarray
    writes_done: jnp.ndarray
    probe_lat_sum: jnp.ndarray
    probe_cnt: jnp.ndarray
    data_bus_busy: jnp.ndarray      # cycles any data bus carried data
    cmd_counts: jnp.ndarray         # (n_cmds,) merged namespace
    deferred: jnp.ndarray           # predicate-masked candidate count
    per_channel: ChannelStats
    per_group: tuple                # per-group native ChannelStats
    #: scan-body executions this run — with fast-forward, the number of
    #: cycles actually stepped; ``cycles`` otherwise
    scan_steps: jnp.ndarray = 0
    #: cycles the fast-forward horizon skipped (``cycles - scan_steps``);
    #: 0 on the classic per-cycle path
    skipped_cycles: jnp.ndarray = 0

    # -- human-readable views ---------------------------------------------
    def to_dict(self) -> dict:
        """Plain-Python counter dict of one scalar run (ints throughout;
        per-channel counters as lists).  Raises on batched (B,)-shaped
        stats — index one point out first."""
        d = {k: int(getattr(self, k))
             for k in ("cycles", "reads_done", "writes_done",
                       "probe_lat_sum", "probe_cnt", "data_bus_busy",
                       "deferred", "scan_steps", "skipped_cycles")}
        d["cmd_counts"] = [int(c) for c in np.asarray(self.cmd_counts)]
        ch = self.per_channel
        d["per_channel"] = {
            k: [int(v) for v in np.asarray(getattr(ch, k))]
            for k in ("reads_done", "writes_done", "probe_cnt",
                      "data_bus_busy", "deferred")}
        return d

    def summary(self, spec=None) -> str:
        """Human-readable run summary; pass the run's spec/system for the
        group-aware view with physical units (GB/s, ns, %).  Replaces the
        ad-hoc prints of the examples and the trace CLI."""
        return format_stats(self, spec)


def _zero_channel_stats(cspec: CompiledSpec, telemetry: bool = False,
                        n_channels: int | None = None) -> ChannelStats:
    """Zeroed per-channel counters; with ``telemetry``, ``cmd_counts``
    is widened by the ``1 + n_edges`` telemetry gauge columns of
    :func:`_accum_channel_stats`.  ``n_channels`` overrides the spec's
    channel count (the channel-sharded path carries one device's slice)."""
    nch = cspec.n_channels if n_channels is None else n_channels
    width = cspec.n_cmds + (1 + len(cspec.lat_bucket_edges)
                            if telemetry else 0)
    z = lambda *sh: jnp.zeros(sh, jnp.int32)
    return ChannelStats(z(nch), z(nch), z(nch), z(nch), z(nch),
                        z(nch, width), z(nch))


class GroupWindowSnap(NamedTuple):
    """One window-boundary telemetry snapshot of ONE spec group: the
    cumulative :class:`ChannelStats` the scan already carries (gauge
    columns split off), plus the packed cumulative telemetry gauges
    (see :func:`_accum_channel_stats`).  Emitted as scan ``ys`` once
    per window — O(n_windows) output, never O(n_cycles)."""
    ch: ChannelStats
    tm: jnp.ndarray         # (C, 1 + n_edges) packed gauges


def _snap_telemetry(cspec: CompiledSpec, gs: GroupState,
                    clk) -> "GroupWindowSnap":
    """The window-boundary view of one group's counters: the carried
    :class:`ChannelStats` with its telemetry extension columns (see
    :func:`_accum_channel_stats`) split back out into the packed gauge
    array, plus the residual queue residency of requests still queued
    at ``clk`` (computed once per window, never per cycle).  The gauge
    array's column 0 is then the exact cycle-sum of queue occupancy
    over ``[0, clk)``."""
    nc = cspec.n_cmds
    q = gs.cs.queue
    resid = jnp.sum(jnp.where(q.valid, clk - q.arrive, 0), axis=1)
    return GroupWindowSnap(
        ch=gs.ch._replace(cmd_counts=gs.ch.cmd_counts[:, :nc]),
        tm=gs.ch.cmd_counts[:, nc:].at[:, 0].add(resid))


class GroupState(NamedTuple):
    """Scan-carried state of ONE spec group: controller+device state and
    running stats, every leaf with a leading group-channel axis.  When a
    telemetry window is requested the ``ch.cmd_counts`` leaf is widened
    by the gauge columns (no extra carry leaf; the telemetry-off traced
    program is unchanged)."""
    cs: C.CtrlState
    ch: ChannelStats


class SimState(NamedTuple):
    """Group-indexed scan carry: ``gs`` is a static-length tuple with one
    :class:`GroupState` per spec group (the homogeneous path is the
    1-tuple special case)."""
    gs: tuple
    fs: F.FrontState
    clk: jnp.ndarray


class TraceArrays(NamedTuple):
    """Dense per-cycle trace emitted by ``run(..., trace=True)``.

    Single-channel systems emit ``[T, 2]`` fields ([cycles, bus slots];
    slot 0 is the column C/A bus, slot 1 the row bus — single-bus
    standards only use slot 0).  Multi-channel systems emit ``[T, C, 2]``
    with the *system* channel axis in the middle (heterogeneous systems
    concatenate their groups' channels in group-major order; ``cmd`` ids
    are then GROUP-LOCAL — ``repro.trace.capture`` resolves them into the
    system's merged command namespace using the channel→group map).
    ``cmd`` is -1 on idle slots.  ``repro.trace.capture`` compacts these
    dense arrays into a columnar :class:`repro.trace.CommandTrace` (with
    ``chan`` and ``group`` columns).
    """
    cmd: jnp.ndarray         # issued command id, -1 == idle
    bank: jnp.ndarray        # flat bank id (refresh: representative bank)
    row: jnp.ndarray         # target row, -1 when n/a
    arrive: jnp.ndarray      # served request's arrival clk, -1 for refresh
    hit_ready: jnp.ndarray   # bool — a post-predicate row hit was available


# --------------------------------------------------------------------------
# Compile cache
# --------------------------------------------------------------------------
#
# `make_run` returns a fresh closure every call, so a bare `jax.jit(run_fn)`
# can never share traces between two `Simulator` instances of the same
# (standard, org, timing) triple — every instance would pay the full trace +
# XLA-compile cost again.  `RunCache` memoizes the *jitted callable* keyed on
# everything that changes the traced program: the compiled-spec identity
# (including timing overrides and post-hoc `rows`/`columns` edits), the
# controller and frontend configs, the cycle count, and the trace/batched
# flags.  Load knobs (interval / read ratio / seed) are traced arguments and
# therefore never part of the key.

#: Incremented once per actual trace of a run closure; tests use it to
#: assert that identical sweep specs are compiled exactly once.
TRACE_COUNT = 0

#: JAX's compile-stage events and the ``RunCache.stats()`` field each one
#: feeds: durations in seconds, the persistent-cache events as counts.
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
    "/jax/compilation_cache/cache_hits": "persistent_hits",
    "/jax/compilation_cache/cache_misses": "persistent_misses",
}


def _zero_stages() -> dict:
    return {f: 0 if f.startswith("persistent_") else 0.0
            for f in COMPILE_EVENTS.values()}


#: process totals of :data:`COMPILE_EVENTS` since import; a
#: :class:`RunCache` adds the part that falls inside its programs' first
#: calls
_COMPILE_TOTALS = _zero_stages()


def _on_compile_duration(event: str, duration: float, **_) -> None:
    field = COMPILE_EVENTS.get(event)
    if field is not None:
        _COMPILE_TOTALS[field] += duration


def _on_compile_event(event: str, **_) -> None:
    field = COMPILE_EVENTS.get(event)
    if field is not None:
        _COMPILE_TOTALS[field] += 1


jax.monitoring.register_event_duration_secs_listener(_on_compile_duration)
jax.monitoring.register_event_listener(_on_compile_event)


def _freeze(obj):
    """Recursively convert configs/dicts into hashable cache-key tuples.

    Callables (user filtering predicates in ``extra_predicates``) freeze
    to their qualified name plus frozen closure constants — two equal
    configs built from *separate but identical* factory calls therefore
    share one cache entry, instead of silently never hitting because the
    lambdas hash by identity.
    """
    if obj is None or isinstance(obj, (int, float, str, bool, bytes)):
        return obj
    if isinstance(obj, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in obj.items()))
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return (type(obj).__name__,) + tuple(
            (f.name, _freeze(getattr(obj, f.name)))
            for f in dataclasses.fields(obj))
    if isinstance(obj, (list, tuple)):
        return tuple(_freeze(x) for x in obj)
    if callable(obj):
        # Key on everything that can bind a value into the function:
        # closure cells, default args (the `def pred(..., t=t)` binding
        # idiom), and bytecode+consts (distinguishes different lambdas
        # sharing the '<lambda>' qualname).  Factory-rebuilt equal copies
        # still collide into one cache entry.  Known limitation: a
        # predicate reading a *module-level global* that mutates between
        # runs is not re-keyed — bind state via closures/defaults instead.
        cells = getattr(obj, "__closure__", None) or ()
        closure = tuple(_freeze(c.cell_contents) for c in cells)
        defaults = (_freeze(getattr(obj, "__defaults__", None)),
                    _freeze(getattr(obj, "__kwdefaults__", None)))
        code = getattr(obj, "__code__", None)
        code_key = ((code.co_code, _freeze(code.co_consts))
                    if code is not None else id(obj))
        return ("callable", getattr(obj, "__module__", ""),
                getattr(obj, "__qualname__", repr(obj)), code_key, closure,
                defaults)
    try:
        hash(obj)
        return obj
    except TypeError:
        return repr(obj)


def spec_fingerprint(cspec: CompiledSpec):
    """Hashable identity of a compiled spec *as the engine traces it*.

    Keyed on provenance (standard/org/timing preset names) plus the resolved
    timing table, the geometry fields benchmarks are allowed to mutate
    in place (`rows`, `columns`), and the memory-system channel count — so
    an edited spec never aliases a cached program built from the pristine
    one, and an N-channel system never aliases a 1-channel program.  The
    channel count is appended only when >1: every pre-multi-channel trace
    artifact was captured single-channel, and this keeps their stored
    fingerprints verifiable.
    """
    base = (cspec.standard, cspec.org_preset, cspec.timing_preset,
            _freeze(cspec.timings), cspec.rows, cspec.columns)
    return base if cspec.n_channels == 1 else base + (cspec.n_channels,)


def system_fingerprint(spec):
    """Hashable identity of a memory system *as the engine traces it*.

    A bare :class:`CompiledSpec` — and the 1-group, zero-link system it is
    equivalent to — keeps the historical :func:`spec_fingerprint` value,
    so every stored trace artifact and cached program stays verifiable
    (and ``Simulator(system=[one group])`` aliases the very same compiled
    program as ``Simulator(..., channels=N)``).  A genuine composition
    keys on the ordered tuple of (group fingerprint, channels,
    link_latency)."""
    if isinstance(spec, CompiledSpec):
        return spec_fingerprint(spec)
    msys = as_system(spec)
    if msys.homogeneous:
        return spec_fingerprint(msys.groups[0].cspec)
    return tuple((spec_fingerprint(g.cspec), g.channels, g.link_latency)
                 for g in msys.groups)


#: mesh axis name of the channel-sharded engine path
CHANNEL_AXIS = "channels"


def auto_channel_shard(spec, n_devices: int | None = None) -> int | None:
    """Largest channel-mesh size ``d > 1`` the visible device count
    supports and that divides EVERY spec group's channel count — the
    fan-out ``make_run(..., shard=d)`` places one contiguous channel
    slice per device.  None when no such ``d`` exists (single device,
    single channel, or indivisible counts): callers then stay on the
    vmapped single-device path."""
    msys = as_system(spec)
    ndev = jax.device_count() if n_devices is None else int(n_devices)
    counts = [g.channels for g in msys.groups]
    for d in range(min(ndev, min(counts)), 1, -1):
        if all(c % d == 0 for c in counts):
            return d
    return None


def _vary(tree, axis_name):
    """Mark every leaf of ``tree`` as varying over the ``shard_map`` axis
    ``axis_name`` (leaves that already vary are kept as they are)."""
    return jax.tree.map(
        lambda a: a if axis_name in jax.typeof(a).vma
        else jax.lax.pcast(a, axis_name, to="varying"), tree)


def _shard_desc(shard):
    """Hashable mesh identity of a channel-sharded program: axis name,
    mesh size, and the participating devices' (platform, id) pairs — a
    cache warmed under one device topology never aliases another's
    programs."""
    if not shard or int(shard) <= 1:
        return None
    return (CHANNEL_AXIS, int(shard),
            tuple((d.platform, d.id) for d in jax.devices()[:int(shard)]))


def run_key(spec, ccfg: C.ControllerConfig,
            fcfg: F.FrontendConfig, n_cycles: int, trace: bool,
            batched: bool, replay: F.ReplayStream | None = None,
            telemetry: int = 0, shard: int | None = None,
            donate: bool = False, fast_forward: bool = True):
    # interval/read_ratio reach the traced program only through FrontParams
    # (a traced argument) in both scalar and batched mode; the fcfg copies
    # are dead at trace time, so drop them from the key — sweeping the load
    # knobs through `Simulator.run` never recompiles.  The mapper order
    # stays in the key (it changes the traced decode), as does the replay
    # stream's content fingerprint and the telemetry window (windowed runs
    # restructure the scan, so every window size is its own program).
    # The device count + channel-mesh descriptor + donation flag key the
    # topology: a program compiled for one mesh (or with donated inputs)
    # is never silently reused for another.
    fkey = tuple(kv for kv in _freeze(fcfg)
                 if not (isinstance(kv, tuple)
                         and kv[0] in ("interval", "read_ratio")))
    # fast_forward restructures the scan into event-horizon macro-steps
    # (a different traced program), so it keys the cache too
    return (system_fingerprint(spec), _freeze(ccfg), fkey,
            int(n_cycles), bool(trace), bool(batched),
            None if replay is None else replay.fingerprint,
            int(telemetry), int(jax.device_count()), _shard_desc(shard),
            bool(donate), bool(fast_forward))


class _TimedRun:
    """Callable wrapper around one cached jitted run: its FIRST call —
    trace + XLA compile + the run itself, synchronized — is timed, with
    the compile stages JAX reports inside it (:data:`COMPILE_EVENTS`),
    into one record of the owning cache's :meth:`RunCache.first_calls`.
    Warm calls pass straight through.  This is the observable the run
    profiler reports as compile cost (the pure-execute share is
    separately measurable from a warm re-run)."""

    __slots__ = ("fn", "_cache", "_timed", "_n_cycles")

    def __init__(self, fn, cache: "RunCache", n_cycles: int):
        self.fn = fn
        self._cache = cache
        self._timed = False
        self._n_cycles = int(n_cycles)

    def __call__(self, *args):
        if self._timed:
            return self.fn(*args)
        before = dict(_COMPILE_TOTALS)
        t0 = time.perf_counter()
        out = jax.block_until_ready(self.fn(*args))
        record = {"n_cycles": self._n_cycles,
                  "first_call_s": time.perf_counter() - t0}
        record.update({k: v - before[k] for k, v in _COMPILE_TOTALS.items()})
        self._cache._first_calls.append(record)
        self._timed = True
        return out


class RunCache:
    """Memoizes jitted engine run callables.

    ``get`` returns a jitted ``(dp, fp, seed) -> Stats`` callable (vmapped
    over ``fp`` when ``batched=True``).  ``hits``/``misses`` count lookups;
    re-tracing is observable via the module-level ``TRACE_COUNT``, and
    ``stats()`` publishes the full accounting (entries, hit/miss counts,
    cumulative first-call wall time and its compile stages) for the run
    profiler and the DSE sweep reports; ``first_calls()`` gives the same
    per program.
    """

    def __init__(self):
        self._runs: dict = {}
        self.hits = 0
        self.misses = 0
        #: one record per cached program's FIRST call (trace + XLA
        #: compile + one synchronized run), in the order they ended
        self._first_calls: list = []
        #: distinct program topologies compiled ("vmap" single-device,
        #: "channels:<d>" for channel-sharded meshes)
        self._topologies: set = set()

    def __len__(self):
        return len(self._runs)

    def clear(self):
        self._runs.clear()
        self.hits = self.misses = 0
        self._first_calls.clear()
        self._topologies.clear()

    def first_calls(self) -> list:
        """One dict per cached program whose first call has ended, in that
        order: its ``n_cycles``, the call's wall seconds ``first_call_s``
        and the compile stages JAX reported inside it (the
        :data:`COMPILE_EVENTS` fields of :meth:`stats`)."""
        return [dict(r) for r in self._first_calls]

    def stats(self) -> dict:
        """Public cache accounting: ``entries`` (live programs), ``hits``
        / ``misses`` (lookup counts since construction/clear),
        ``first_call_s`` (cumulative wall time of each program's first
        call — the trace + compile cost plus one run) and, inside those
        first calls, the compile stages JAX reports: ``trace_s`` (jaxpr
        tracing), ``lower_s`` (lowering to an MLIR module), ``compile_s``
        (XLA compile, or the load from JAX's persistent compilation
        cache), ``cache_load_s`` (that load alone), ``persistent_hits``
        and ``persistent_misses`` (persistent-cache lookups that hit, and
        misses whose program was written to it); plus the device
        topology view: ``devices`` (visible device count) and
        ``shard_topologies`` (distinct program topologies compiled —
        ``"vmap"`` for single-device programs, ``"channels:<d>"`` for
        channel-sharded meshes)."""
        totals = _zero_stages()
        first_call_s = 0.0
        for r in self._first_calls:
            first_call_s += r["first_call_s"]
            for k in totals:
                totals[k] += r[k]
        stages = {k: v if isinstance(v, int) else round(v, 6)
                  for k, v in totals.items()}
        return {"entries": len(self._runs), "hits": self.hits,
                "misses": self.misses,
                "first_call_s": round(first_call_s, 3),
                **stages,
                "devices": int(jax.device_count()),
                "shard_topologies": tuple(sorted(self._topologies))}

    def get(self, spec, ccfg: C.ControllerConfig,
            fcfg: F.FrontendConfig, n_cycles: int, trace: bool = False,
            batched: bool = False, replay: F.ReplayStream | None = None,
            telemetry: int = 0, shard: int | None = None,
            donate: bool = False, fast_forward: bool = True):
        """``spec`` may be a :class:`CompiledSpec` (homogeneous system) or
        a :class:`MemorySystemSpec` (heterogeneous composition).
        ``telemetry`` is the windowed-telemetry window in cycles (0 =
        off); windowed programs emit cumulative snapshots every window.
        ``shard`` runs the scan channel-sharded over a ``shard``-device
        mesh (see :func:`make_run`); ``donate`` donates the ``fp``
        argument's buffers to the computation (``donate_argnums``) — safe
        whenever the caller rebuilds FrontParams per call, as the DSE
        executor does."""
        if shard and batched:
            raise ValueError(
                "channel sharding (shard=) composes with scalar runs only "
                "— batched DSE points shard across devices in repro.dse "
                "instead")
        key = run_key(spec, ccfg, fcfg, n_cycles, trace, batched, replay,
                      telemetry, shard, donate, fast_forward)
        fn = self._runs.get(key)
        if fn is not None:
            self.hits += 1
            return fn
        self.misses += 1
        # Close over a snapshot, not the caller's object: jit may re-trace
        # this closure much later (new batch shape), and by then the caller
        # may have mutated its cspec(s) in place — the snapshot keeps every
        # retrace consistent with the fingerprint taken above.
        if isinstance(spec, CompiledSpec):
            spec = dataclasses.replace(spec)
        else:
            spec = MemorySystemSpec(tuple(
                SpecGroup(dataclasses.replace(g.cspec), g.channels,
                          g.link_latency) for g in as_system(spec).groups))
        fn = make_run(spec, ccfg, fcfg, n_cycles, trace, replay,
                      telemetry_window=telemetry, shard=shard,
                      fast_forward=fast_forward)
        if batched:
            fn = jax.vmap(fn, in_axes=(None, 0, None))
        fn = _TimedRun(
            jax.jit(fn, donate_argnums=(1,) if donate else ()), self,
            n_cycles)
        self._topologies.add(f"{CHANNEL_AXIS}:{int(shard)}" if shard
                             else "vmap")
        self._runs[key] = fn
        return fn


#: Process-wide default cache used by `Simulator` and `repro.dse`.
RUN_CACHE = RunCache()

#: JAX's persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR``
#: is unset: a fixed directory of the checkout, since the cache only hits
#: when the path is the same from one process to the next.
COMPILE_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for a program entry
    point and return its directory.  ``JAX_COMPILATION_CACHE_DIR``, when
    set, is JAX's own setting and is left alone; otherwise the cache is
    kept in :data:`COMPILE_CACHE_DIR`.  Entry points call this as they
    start; library code and tests never do."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


@dataclasses.dataclass
class Simulator:
    """User-facing memory-system handle: one (standard, org, timing)
    triple with a channel count and mapper order, OR an explicit
    heterogeneous composition via ``system=``.

    >>> sim = Simulator("DDR4", "DDR4_8Gb_x8", "DDR4_2400R")
    >>> stats = sim.run(100_000, interval=4.0, read_ratio=1.0)
    >>> quad = Simulator("HBM3", "HBM3_16Gb", "HBM3_5200", channels=4)
    >>> stats = quad.run(50_000)      # stats.per_channel: (4,) breakdowns
    >>> cxl = Simulator(system=[
    ...     dict(standard="DDR5", org_preset="DDR5_16Gb_x8",
    ...          timing_preset="DDR5_4800B", channels=2),
    ...     dict(standard="DDR4", org_preset="DDR4_8Gb_x8",
    ...          timing_preset="DDR4_2400R", channels=2, link_latency=80),
    ... ])
    >>> stats = cxl.run(50_000)       # 4 system channels, 2 spec groups

    Every system — homogeneous or not — compiles exactly once: the whole
    composition is one ``lax.scan`` program keyed in the process-wide
    :class:`RunCache` on the system tuple.
    """
    standard: str | None = None
    org_preset: str | None = None
    timing_preset: str | None = None
    controller: C.ControllerConfig = dataclasses.field(
        default_factory=C.ControllerConfig)
    frontend: F.FrontendConfig = dataclasses.field(
        default_factory=F.FrontendConfig)
    timing_overrides: dict | None = None
    #: memory-system channel fan-out (vmapped controllers inside the scan)
    channels: int = 1
    #: convenience override for ``frontend.mapper`` (None keeps it)
    mapper: str | None = None
    #: replay source for ``FrontendConfig(pattern="trace")``
    replay: F.ReplayStream | None = None
    #: heterogeneous composition: a :class:`MemorySystemSpec` or a list of
    #: group descriptors (see :func:`repro.core.compile.compile_system`);
    #: mutually exclusive with the (standard, org, timing) triple
    system: object = None
    #: channel-axis device sharding for scalar runs: ``None`` = auto
    #: (shard across the largest channel mesh the visible devices
    #: support; single-device boxes stay on the vmapped path), ``False``
    #: = never, ``True`` = require (raise when no mesh fits), int ``d``
    #: = exact mesh size.  Sharded and vmapped runs are bit-exact twins
    #: (pinned by the golden command-stream hashes).
    channel_shard: object = None
    #: event-horizon fast-forward: skip provably idle cycle runs in one
    #: variable-stride step (docs/architecture.md "Performance model").
    #: Bit-exact by construction — stats, command streams, and telemetry
    #: are identical with it on or off (pinned by the golden hashes) —
    #: so it defaults on; False forces the classic per-cycle scan.
    fast_forward: bool = True

    def __post_init__(self):
        if self.system is not None:
            if self.standard is not None:
                raise ValueError("pass either a (standard, org_preset, "
                                 "timing_preset) triple or system=..., "
                                 "not both")
            if self.channels != 1 or self.timing_overrides is not None:
                raise ValueError(
                    "channels=/timing_overrides= apply to the (standard, "
                    "org, timing) path only — a system=... composition "
                    "carries its own per-group channel counts and timing "
                    "overrides (see compile_system)")
            self.msys = as_system(self.system)
            # the 1-group zero-link composition IS the classic path: hand
            # the cache the bare CompiledSpec so both spellings alias one
            # compiled program (and one fingerprint)
            self.cspec = self.msys.groups[0].cspec \
                if self.msys.n_groups == 1 else None
        else:
            if self.standard is None:
                raise ValueError("Simulator needs a (standard, org_preset, "
                                 "timing_preset) triple or system=...")
            self.cspec = compile_spec(self.standard, self.org_preset,
                                      self.timing_preset,
                                      self.timing_overrides,
                                      channels=self.channels)
            self.msys = as_system(self.cspec)
        if self.mapper is not None:
            self.frontend = dataclasses.replace(self.frontend,
                                                mapper=self.mapper)

    @property
    def _cache_spec(self):
        """What the run cache is keyed/traced on: the bare CompiledSpec
        for homogeneous systems (historical key), the MemorySystemSpec
        otherwise."""
        return self.cspec if self.msys.homogeneous else self.msys

    def _dyn_params(self):
        return tuple(D.dyn_params(g.cspec) for g in self.msys.groups)

    def _resolved_shard(self) -> int | None:
        """The channel-mesh size scalar runs use, per ``channel_shard``."""
        cs = self.channel_shard
        if cs is None or cs is True:
            d = auto_channel_shard(self.msys)
            if d is None and cs is True:
                raise ValueError(
                    "channel_shard=True but no usable channel mesh: "
                    f"{jax.device_count()} device(s) for per-group "
                    f"channel counts "
                    f"{[g.channels for g in self.msys.groups]} (pin host "
                    "devices with XLA_FLAGS=--xla_force_host_platform_"
                    "device_count=N)")
            return d
        if not cs or int(cs) <= 1:
            return None
        return int(cs)

    # -- single-config run ------------------------------------------------
    def run(self, n_cycles: int, interval: float | None = None,
            read_ratio: float | None = None, trace: bool = False,
            seed: int = 0x1234, telemetry: int = 0,
            fast_forward: bool | None = None):
        """Run ``n_cycles``.  Returns ``stats`` — plus the raw trace
        arrays when ``trace=True``, plus a :class:`repro.telemetry.
        Telemetry` time series when ``telemetry=W > 0`` (windowed
        counters, one sample every W cycles; see docs/observability.md).
        Both extras: ``(stats, ys, telem)``."""
        fcfg = self.frontend
        if interval is not None or read_ratio is not None:
            fcfg = dataclasses.replace(
                fcfg,
                interval=interval if interval is not None else fcfg.interval,
                read_ratio=(read_ratio if read_ratio is not None
                            else fcfg.read_ratio))
        # host phases as profiler spans, on the device trace's clock
        ff = self.fast_forward if fast_forward is None else fast_forward
        with jax.profiler.TraceAnnotation("sim.lookup"):
            run_fn = RUN_CACHE.get(self._cache_spec, self.controller, fcfg,
                                   n_cycles, trace=trace, replay=self.replay,
                                   telemetry=telemetry,
                                   shard=self._resolved_shard(),
                                   fast_forward=ff)
        with jax.profiler.TraceAnnotation("sim.launch"):
            out = run_fn(self._dyn_params(), fcfg.params(),
                         jnp.uint32(seed))
        with jax.profiler.TraceAnnotation("sim.fetch"):
            out = jax.tree.map(np.asarray, out)
        if telemetry:
            from repro import telemetry as T   # lazy: keeps core dep-free
            *rest, snaps = out
            telem = T.build(self.msys, snaps, window=telemetry,
                            n_cycles=n_cycles)
            return tuple(rest) + (telem,) if len(rest) > 1 \
                else (rest[0], telem)
        return out

    # -- batched DSE run ---------------------------------------------------
    def run_batch(self, n_cycles: int, intervals, read_ratios,
                  seed: int = 0x1234):
        """Simulate the outer product of load points in one vmapped program."""
        pts = [(i, r) for i in intervals for r in read_ratios]
        fp = F.stack_params(pts, self.frontend.probe_gap)
        batched = RUN_CACHE.get(self._cache_spec, self.controller,
                                self.frontend, n_cycles, batched=True,
                                replay=self.replay,
                                fast_forward=self.fast_forward)
        out = batched(self._dyn_params(), fp, jnp.uint32(seed))
        return pts, jax.tree.map(np.asarray, out)


def _accum_channel_stats(cspec: CompiledSpec, ch: ChannelStats,
                         ev: C.StepEvents, clk=None,
                         telemetry: bool = False) -> ChannelStats:
    """Fold one cycle's channel-stacked events into the running stats of
    ONE spec group (counts in the group's native command namespace).

    With ``telemetry``, the SAME per-cycle ``cmd_counts`` add also folds
    the windowed-telemetry gauges into ``1 + n_edges`` extension columns
    (split back off by :func:`_snap_telemetry` before stats ever leave
    the engine) — no extra scan carry, no extra per-cycle kernel:

    - column ``n_cmds``: the queue-residency integral of SERVED
      requests (``clk - arrive`` at each service event; requests
      release their queue slot on the column bus — FINAL_RD/FINAL_WR
      are column commands — so the served arrival clock is
      ``ev.arrive[:, 0]``).  The cycle-sum of queue occupancy over
      ``[0, t)`` is this plus the residual ``t - arrive`` of requests
      still queued at ``t``, added once per window boundary — so no
      per-cycle occupancy reduction is ever needed;
    - column ``n_cmds + 1 + k``: served probes with latency <= edge
      ``k``, a CUMULATIVE histogram (the host diffs along the bucket
      axis and closes the open top bucket with ``probe_cnt``).

    Separate accumulators for the same gauges — a packed carry-add, a
    per-cycle (C, 2) ys emission with per-window folds, searchsorted +
    one-hot — all measured noticeably more engine overhead than riding
    the adds that the stats fold performs anyway."""
    nBL = jnp.int32(cspec.timings["nBL"])
    rd = ev.served_read.astype(jnp.int32)          # (C,)
    wr = ev.served_write.astype(jnp.int32)
    counts = ch.cmd_counts                          # (C, n_cmds [+ 1 + E])
    cmd_ids = jnp.arange(cspec.n_cmds, dtype=jnp.int32)
    if telemetry:
        served = ev.served_read | ev.served_write                  # (C,)
        res = jnp.where(served, clk - ev.arrive[:, 0], 0)          # (C,)
        edges = jnp.asarray(cspec.lat_bucket_edges, jnp.int32)
        lat = jnp.where(ev.served_probe, ev.probe_latency,
                        jnp.int32(1 << 30))
        cum = (lat[:, None] <= edges[None, :]).astype(jnp.int32)   # (C, E)
        oh = ((cmd_ids[None, :] == ev.cmd[:, 0:1]).astype(jnp.int32)
              + (cmd_ids[None, :] == ev.cmd[:, 1:2]).astype(jnp.int32))
        counts = counts + jnp.concatenate([oh, res[:, None], cum], axis=1)
    else:
        for i in range(2):
            # dense one-hot add (idle slots are -1: no match, no count)
            counts = counts + (cmd_ids[None, :]
                               == ev.cmd[:, i:i + 1]).astype(jnp.int32)
    return ChannelStats(
        reads_done=ch.reads_done + rd,
        writes_done=ch.writes_done + wr,
        probe_lat_sum=ch.probe_lat_sum + ev.probe_latency,
        probe_cnt=ch.probe_cnt + ev.served_probe.astype(jnp.int32),
        data_bus_busy=ch.data_bus_busy + nBL * (rd + wr),
        cmd_counts=counts,
        deferred=ch.deferred + ev.deferred,
    )


def _aggregate_stats(msys: MemorySystemSpec, chs: list, clk,
                     scan_steps=None) -> Stats:
    """Fold the per-group running stats into the uniform :class:`Stats`.

    The 1-group path is bit-identical to the historical aggregation; for
    a composition the per-channel view concatenates the groups' channels
    (group-major) and lifts each group's command counts into the merged
    namespace via its local→global id map."""
    if msys.n_groups == 1:
        ch = chs[0]
        per_channel = ch
        cmd_counts = jnp.sum(ch.cmd_counts, axis=0)
    else:
        n_global = msys.n_cmds
        lifted = []
        for g, ch in enumerate(chs):
            gmap = jnp.asarray(msys.group_cmd_maps[g], jnp.int32)
            c_g = ch.cmd_counts.shape[0]
            lift = jnp.zeros((c_g, n_global), jnp.int32)
            lifted.append(lift.at[:, gmap].set(ch.cmd_counts))
        cat = lambda f: jnp.concatenate([getattr(ch, f) for ch in chs])
        per_channel = ChannelStats(
            reads_done=cat("reads_done"), writes_done=cat("writes_done"),
            probe_lat_sum=cat("probe_lat_sum"), probe_cnt=cat("probe_cnt"),
            data_bus_busy=cat("data_bus_busy"),
            cmd_counts=jnp.concatenate(lifted, axis=0),
            deferred=cat("deferred"))
        cmd_counts = jnp.sum(per_channel.cmd_counts, axis=0)
    return Stats(
        cycles=clk,
        reads_done=jnp.sum(per_channel.reads_done),
        writes_done=jnp.sum(per_channel.writes_done),
        probe_lat_sum=jnp.sum(per_channel.probe_lat_sum),
        probe_cnt=jnp.sum(per_channel.probe_cnt),
        data_bus_busy=jnp.sum(per_channel.data_bus_busy),
        cmd_counts=cmd_counts,
        deferred=jnp.sum(per_channel.deferred),
        per_channel=per_channel,
        per_group=tuple(chs),
        # classic per-cycle path: every cycle is one scan step
        scan_steps=clk if scan_steps is None else scan_steps,
        skipped_cycles=(jnp.zeros_like(clk) if scan_steps is None
                        else clk - scan_steps),
    )


#: ``jax.named_scope`` names of the cycle loop's parts.  They reach the
#: compiled program's op metadata (``op_name="…/while/body/<scope>/…"``),
#: so a profiler trace can attribute each device operation to one part:
#: ``frontend`` (insert, commit, finish), ``controller`` (each group's
#: vmapped ``controller_step``), ``fold`` (stats fold, fused reduction
#: and its ``psum``), ``horizon`` (fast-forward horizon, ``pmin`` and
#: idle jump), ``trace_write`` (trace-buffer update) and
#: ``telemetry_snap`` (window snapshot).  Two nested parts name a
#: standard's own mechanisms inside ``controller`` and ``horizon``, and
#: only in the programs of standards that have them: ``split_act``
#: (LPDDR5/6 ACT-1/ACT-2: the ACT-2 predicates, the Activating branch of
#: the prerequisite decode and its ``act1_row`` read) and ``clock_sync``
#: (WCK/RCK: the ``clock_until`` read and sync choice of the decode, the
#: clock update at issue, the horizon's clock-expiry term).
SCOPES = ("frontend", "controller", "fold", "horizon", "trace_write",
          "telemetry_snap", "split_act", "clock_sync")


def make_run(spec, ccfg: C.ControllerConfig,
             fcfg: F.FrontendConfig, n_cycles: int, trace: bool,
             replay: F.ReplayStream | None = None,
             telemetry_window: int = 0, shard: int | None = None,
             fast_forward: bool = True):
    """Build the pure run function (dps, fp, seed) -> Stats [, trace]
    [, telemetry snapshots].

    ``shard = d > 1`` runs the SAME cycle function channel-sharded over a
    ``d``-device mesh (one contiguous slice of every group's channel axis
    per device, ``d`` dividing every group's channel count): the whole
    scan sits inside one ``jax.shard_map``, the frontend decode runs
    replicated on every shard, each shard inserts into / steps its local
    channels only, and the sole cross-shard traffic is one fused 5-wide
    int32 ``psum`` per cycle (insert accepts + completion events).  The
    sharded and vmapped programs are bit-exact twins — same stats, same
    command streams, same telemetry.

    ``spec`` is a :class:`CompiledSpec` or a :class:`MemorySystemSpec`;
    ``dps`` is the per-group tuple of :class:`repro.core.device.DynParams`
    (a bare ``DynParams`` is accepted for the 1-group case).  One compiled
    program per (system, configs, n_cycles, trace, replay, telemetry)
    regardless of group or channel count: the frontend routes decoded
    requests to per-(group, channel) queues, ``controller_step`` runs
    across each group's channels via an inner ``jax.vmap``, and the groups
    advance as parallel branches of the single ``lax.scan`` body, their
    states living in the group-indexed :class:`SimState` carry.
    CXL-attached groups (``link_latency > 0``) see requests
    ``link_latency`` cycles after arrival and return read data
    ``link_latency`` cycles late.

    ``fast_forward`` (default on) replaces the fixed-stride cycle scan
    with event-horizon macro-stepping: a ``lax.while_loop`` executes one
    full cycle, then computes a safe skip distance — the minimum of the
    frontend's next arrival/probe attempt, every channel's next
    timing-ready/refresh/clock-expiry event, the BlockHammer decay
    boundary, and the current segment end — and advances the state
    across the provably idle run in closed form (clamped accumulator
    refill + LCG jump; all other state is frozen on idle cycles).  The
    result is O(events) instead of O(cycles) on idle-heavy workloads and
    bit-exact by construction: stats, command streams, and telemetry
    snapshots are identical with it on or off (pinned by the golden-hash
    suite).  With ``trace=True`` the dense per-cycle ys become an
    idle-initialized ``(T, C, 2)`` buffer written at the TRUE cycle
    index of each executed cycle, so skipped cycles hold exactly the
    idle values the per-cycle scan would have emitted.

    ``telemetry_window = W > 0`` restructures the cycle scan into windows
    of W cycles (an outer scan over full windows around an inner W-cycle
    scan of the SAME cycle function, plus a ragged final segment for the
    ``n_cycles % W`` remainder) and emits one cumulative
    :class:`GroupWindowSnap` tuple per window boundary — O(n_windows)
    output, so long runs pay neither per-cycle trace memory nor
    end-of-run-only blindness.  The per-cycle math is identical to the
    flat scan, so stats — and command streams under ``trace=True`` — are
    bit-equal with telemetry on or off."""
    msys = as_system(spec)
    groups = msys.groups
    n_groups = msys.n_groups
    n_chan_total = msys.n_channels
    sys_layout = F.make_system_layout(msys, fcfg.mapper)
    if fcfg.stream and fcfg.pattern == "trace" and replay is None:
        raise ValueError('FrontendConfig(pattern="trace") needs a '
                         "ReplayStream (Simulator(..., replay=...))")
    if replay is not None:
        if len(replay) == 0:
            raise ValueError("replay stream is empty — nothing to replay")
        if replay.arrive is not None \
                and np.any(np.diff(np.asarray(replay.arrive)) < 0):
            raise ValueError(
                "replay arrive column must be non-decreasing (injection "
                "is index-ordered) — sort the stream into arrival order "
                "as trace.to_replay does")
        top = int(np.max(replay.chan))
        if top >= n_chan_total or int(np.min(replay.chan)) < 0:
            raise ValueError(
                f"replay stream targets channel {top} but the memory "
                f"system has {n_chan_total} channel(s) — re-encode the "
                "stream through this system's mapper (ReplayStream."
                "from_addresses) instead of reusing captured channels")
        max_sub = max(len(g.cspec.levels) - 1 for g in groups)
        if replay.sub.shape[1] != max_sub:
            raise ValueError(
                f"replay sub columns are {replay.sub.shape[1]} wide but "
                f"this system needs {max_sub} sub-level indices — rebuild "
                "the stream against this system (ReplayStream."
                "from_addresses / trace.to_replay)")
    rp = None if replay is None else F.ReplayStream(
        chan=jnp.asarray(replay.chan), sub=jnp.asarray(replay.sub),
        row=jnp.asarray(replay.row), col=jnp.asarray(replay.col),
        is_write=jnp.asarray(replay.is_write),
        # arrive stays host-side numpy: the frontend derives static pacing
        # scalars (base / span / wrap gap) from it at trace time
        arrive=replay.arrive,
        fingerprint=replay.fingerprint,
        dep=None if replay.dep is None else jnp.asarray(replay.dep))

    static_bases = []
    _b = 0
    for grp in groups:
        static_bases.append(_b)
        _b += grp.channels

    def cycle(sim: SimState, _, dps, fp, axis_name=None, bases=None):
        # insert → step → ONE fused reduction → commit/finish.  On the
        # sharded path ``axis_name``/``bases`` are set: the frontend
        # decode runs replicated, inserts hit the local channel slice
        # only, and the 5-wide int32 vector below is the cycle's entire
        # cross-shard traffic (a single psum).  The fast-forward path
        # widens it to 6 with the cycle's issued command count and
        # returns it, so the macro-stepper can gate its horizon
        # computation on a busy/idle verdict that is uniform across
        # shards by construction (it rides the psum); it also returns
        # the groups' events, whose lookups the idle horizon reads.
        # each part runs in its named scope (SCOPES)
        with jax.named_scope("frontend"):
            queues, draft = F.system_frontend_insert(
                msys, fcfg, fp, sim.fs, tuple(g.cs.queue for g in sim.gs),
                sim.clk, sys_layout, rp, bases)
        new_gs, evs = [], []
        for gi, (grp, dp) in enumerate(zip(groups, dps)):
            cs = sim.gs[gi].cs._replace(queue=queues[gi])
            with jax.named_scope("controller"):
                cs, ev = jax.vmap(
                    lambda s: C.controller_step(grp.cspec, dp, ccfg, s,
                                                sim.clk, grp.link_latency))(cs)
            with jax.named_scope("fold"):
                # with telemetry, the gauge columns ride this same stats
                # fold (the telemetry-off traced program is unchanged)
                ch = _accum_channel_stats(grp.cspec, sim.gs[gi].ch, ev,
                                          sim.clk, bool(telemetry_window))
            new_gs.append(GroupState(cs=cs, ch=ch))
            evs.append(ev)
        with jax.named_scope("fold"):
            absorb = F.absorb_locals(evs[0])
            for ev in evs[1:]:
                absorb = absorb + F.absorb_locals(ev)
            # [probe-accept, stream-accept, probes-done, served, completion]
            loc = jnp.concatenate([jnp.stack([draft.okp, draft.ok]), absorb])
            if fast_forward:
                issued = sum(jnp.sum((ev.cmd >= 0).astype(jnp.int32))
                             for ev in evs)
                loc = jnp.concatenate([loc, issued[None]])
            if axis_name is not None:
                loc = jax.lax.psum(loc, axis_name)
        with jax.named_scope("frontend"):
            fs = F.frontend_commit(fcfg, fp, sim.fs, draft, loc[0], loc[1],
                                   F.paced_by_arrive(fcfg, rp))
            fs = F.frontend_finish(fs, fp, loc[2], loc[3], loc[4])
        out = SimState(gs=tuple(new_gs), fs=fs, clk=sim.clk + 1)
        # trace ys stay a per-group tuple ((C_g, 2) leaves) until the
        # post-scan finalize — on the sharded path the gather happens on
        # the group tuples, so the concat order is shard-independent
        ys = tuple(TraceArrays(e.cmd, e.bank, e.row, e.arrive,
                               e.hit_ready) for e in evs) if trace else None
        if fast_forward:
            return out, ys, loc, tuple(evs)
        return out, ys

    def _finalize_trace(ys_groups):
        """Per-group ``(T, C_g, 2)`` trace fields → the public
        :class:`TraceArrays` layout: single-channel systems keep the
        historical ``(T, 2)`` slot shape; multi-channel systems
        concatenate the groups' channel axes group-major."""
        if n_chan_total == 1:
            return jax.tree.map(lambda a: a[:, 0], ys_groups[0])
        if n_groups == 1:
            return ys_groups[0]
        return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1),
                            *ys_groups)

    def _init_state(seed, shard_index=None):
        gs = []
        for grp in groups:
            cspec, nch = grp.cspec, grp.channels
            loc = nch // shard if shard else nch
            cs1 = C.init_ctrl_state(cspec, ccfg.queue_depth)
            css = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (loc,) + a.shape), cs1)
            if ccfg.refresh_stagger and nch > 1:
                # phase-shift each channel's refresh epoch by c*nREFI/C
                # (real controllers stagger REF so the channels' refresh
                # windows — and their bandwidth dips — never align);
                # channel 0 keeps the historical phase, so single-channel
                # groups are bit-identical.  Staggering is group-local:
                # each group phases its own nREFI.  On the sharded path
                # the offsets come from the GLOBAL channel ids of this
                # shard's slice, so every channel keeps the phase it has
                # on the vmapped path.
                nrefi = int(cspec.timings["nREFI"])
                if shard_index is None:
                    offs = jnp.asarray(
                        [-(c * nrefi // nch) for c in range(nch)],
                        jnp.int32)
                else:
                    gidx = (shard_index * jnp.int32(loc)
                            + jnp.arange(loc, dtype=jnp.int32))
                    offs = -((gidx * jnp.int32(nrefi)) // jnp.int32(nch))
                css = css._replace(dev=css.dev._replace(
                    last_ref=css.dev.last_ref + offs[:, None]))
            gs.append(GroupState(
                cs=css,
                ch=_zero_channel_stats(cspec, bool(telemetry_window),
                                       n_channels=loc)))
        gs = tuple(gs)
        if shard_index is not None:
            # each shard owns its channel slice: the group state varies
            # over the mesh axis from the first loop iteration on
            gs = _vary(gs, CHANNEL_AXIS)
        init = SimState(gs=gs, fs=F.init_front(), clk=jnp.int32(0))
        return init._replace(
            fs=init.fs._replace(rng=seed | jnp.uint32(1)))

    def snapshot(sim):
        """Every group's window-boundary telemetry view at ``sim.clk``."""
        with jax.named_scope("telemetry_snap"):
            return tuple(_snap_telemetry(grp.cspec, g, sim.clk)
                         for grp, g in zip(groups, sim.gs))

    def _scan_cycles(init, body):
        """Drive ``body`` over ``n_cycles`` honoring the telemetry
        windowing; returns ``(final SimState, per-group trace ys | None,
        per-group window snaps | None)``.  Shared verbatim by the
        vmapped and sharded paths (the body closure is the only
        difference), so the windowed restructure cannot diverge between
        them."""
        if not telemetry_window:
            final, ys = jax.lax.scan(body, init, None, length=n_cycles)
            return final, ys, None

        # Windowed telemetry: same cycle function, scanned in W-cycle
        # segments.  Each boundary emits the CUMULATIVE counters (the
        # host diffs consecutive snapshots), so the final snapshot equals
        # the end-of-run aggregates bit-exactly by construction.
        W = telemetry_window
        n_full, rem = divmod(n_cycles, W)
        sim = init
        snap_parts, ys_parts = [], []

        def window(sim, _):
            sim, ys = jax.lax.scan(body, sim, None, length=W)
            return sim, (snapshot(sim), ys)

        if n_full:
            sim, (snaps, ys) = jax.lax.scan(window, sim, None,
                                            length=n_full)
            snap_parts.append(snaps)
            if trace:
                # [n_full, W, ...] -> [n_full*W, ...]: cycle-major order
                # is unchanged, so command streams hash identically
                ys_parts.append(jax.tree.map(
                    lambda a: a.reshape((n_full * W,) + a.shape[2:]), ys))
        if rem:
            sim, ys = jax.lax.scan(body, sim, None, length=rem)
            snap_parts.append(jax.tree.map(lambda a: a[None],
                                           snapshot(sim)))
            if trace:
                ys_parts.append(ys)
        if not snap_parts:          # n_cycles == 0: one (all-zero) window
            snap_parts.append(jax.tree.map(lambda a: a[None],
                                           snapshot(sim)))
        cat = (lambda *xs: xs[0] if len(xs) == 1 else jnp.concatenate(xs))
        snaps = jax.tree.map(lambda *xs: cat(*xs), *snap_parts)
        ys = jax.tree.map(lambda *xs: cat(*xs), *ys_parts) if trace \
            else None
        return sim, ys, snaps

    # -- event-horizon fast-forward machinery --------------------------
    # Static per-cycle rng advance: the frontend draws a FIXED number of
    # LCG values per cycle (independent of accepts), so a run of skipped
    # cycles is one affine jump.  Computed host-side at build time.
    _k_draws = F.rng_draws_per_cycle(fcfg, sys_layout)
    _a_cyc, _c_cyc = F.lcg_affine(_k_draws)
    _paced = F.paced_by_arrive(fcfg, rp)

    def _horizon(sim, dps, fp, evs):
        """min over all event sources of the next cycle >= sim.clk at
        which anything could happen: frontend arrival/probe attempts
        plus every channel's controller horizon.  Conservative — an
        early horizon just executes an idle cycle (see
        ``C.channel_horizon``).  ``sim`` follows a cycle that accepted
        and issued nothing, so each channel's state is the one its
        controller pass just read: a group whose lookup outlives that
        cycle reduces the pass's own (``evs``, ``C.idle_horizon``); a
        data-clock-sync group looks up anew at ``sim.clk``."""
        h = F.arrival_horizon(fcfg, fp, sim.fs, sim.clk, rp, _paced)
        for gi, (grp, dp) in enumerate(zip(groups, dps)):
            if C.lookup_outlives_idle_cycle(grp.cspec):
                ev = evs[gi]
                hc = jax.vmap(
                    lambda s, t, r: C.idle_horizon(
                        grp.cspec, dp, ccfg, s, sim.clk, t, r,
                        grp.link_latency)
                )(sim.gs[gi].cs, ev.slot_ready_at, ev.ref_ready_at)
            else:
                hc = jax.vmap(
                    lambda s: C.channel_horizon(grp.cspec, dp, ccfg, s,
                                                sim.clk, grp.link_latency)
                )(sim.gs[gi].cs)
            h = jnp.minimum(h, jnp.min(hc))
        return h

    def _idle_jump(sim, target):
        """Advance the state across the idle run [sim.clk, target) in
        one step: only the frontend accumulator and rng move on idle
        cycles (closed forms); everything else is provably frozen."""
        fs = F.idle_advance(fcfg, sim.fs, target - sim.clk,
                            _a_cyc, _c_cyc, _k_draws)
        return sim._replace(fs=fs, clk=target)

    def _init_trace_bufs(local_counts):
        """Idle-initialized dense per-cycle trace buffers, one per spec
        group: the fast-forward path writes each EXECUTED cycle's events
        at its true cycle index, and skipped cycles keep these fill
        values — exactly what the per-cycle scan emits on an idle cycle
        (no candidate is ready, so cmd/bank/row/arrive are -1 and no
        post-predicate row hit exists), making the dense trace — and its
        golden sha256 — bit-identical to the fixed-stride path's."""
        bufs = []
        for nch in local_counts:
            i32 = lambda: jnp.full((n_cycles, nch, 2), -1, jnp.int32)
            bufs.append(TraceArrays(
                cmd=i32(), bank=i32(), row=i32(), arrive=i32(),
                hit_ready=jnp.zeros((n_cycles, nch, 2), bool)))
        return tuple(bufs)

    def _ff_cycles(init, body, dps, fp, local_counts, axis_name=None):
        """Fast-forward twin of ``_scan_cycles``: ONE ``lax.while_loop``
        over the whole run, each iteration executing ONE real cycle and
        then jumping to ``min(horizon, next window boundary)``.  Returns
        ``(final SimState, per-group trace buffers | None, window snaps
        | None, scan-step count)``.  The horizon computation is gated on
        the cycle's busy verdict (any accept or issue => next cycle runs
        anyway), which rides the fused reduction — on the sharded path
        the verdict is therefore uniform across shards and the
        cross-device ``pmin`` of the per-shard horizons sits OUTSIDE the
        gate, so every shard takes the same trip count.

        Windowed telemetry rides the SAME loop: jump targets are capped
        at the next ``W``-boundary, so the clock lands on every boundary
        exactly once (it advances by >= 1 per iteration and never jumps
        across a cap), and that iteration writes one snapshot row into a
        dense ``(n_full, ...)`` buffer carried through the loop.  An
        earlier revision nested the while loop inside a ``lax.scan``
        over windows instead; XLA:CPU would not keep the loop carry
        in-place across the scan->while boundary and the resulting
        per-iteration state copies cost ~20% wall clock regardless of
        window count."""
        bufs0 = _init_trace_bufs(local_counts) if trace else None
        W = telemetry_window
        n_full = n_cycles // W if W else 0
        snaps0 = jax.tree.map(
            lambda s: jnp.zeros((n_full,) + s.shape, s.dtype),
            jax.eval_shape(snapshot, init)) if W else None
        if axis_name is not None:
            # per-shard buffers: their loop carry varies over the mesh
            bufs0, snaps0 = _vary((bufs0, snaps0), axis_name)

        def cond(c):
            return c[0].clk < jnp.int32(n_cycles)

        def step(c):
            sim, steps, bufs, snaps = c
            t0 = sim.clk
            out, ys, loc, evs = body(sim)
            if trace:
                z = jnp.int32(0)
                with jax.named_scope("trace_write"):
                    bufs = tuple(
                        jax.tree.map(
                            lambda b, y: jax.lax.dynamic_update_slice(
                                b, y[None].astype(b.dtype), (t0, z, z)),
                            bufs[g], ys[g])
                        for g in range(n_groups))
            with jax.named_scope("horizon"):
                busy = (loc[0] + loc[1] + loc[5]) > 0
                nxt_clk = out.clk
                if axis_name is not None:
                    # the horizon reads this shard's channels, so it
                    # varies over the mesh axis: both cond branches must
                    # carry the same varying axes, and the pmin makes h
                    # uniform again
                    nxt_clk = _vary(nxt_clk, axis_name)
                h = jax.lax.cond(busy, lambda _: nxt_clk,
                                 lambda _: _horizon(out, dps, fp, evs),
                                 None)
                if axis_name is not None:
                    h = jax.lax.pmin(h, axis_name)
                cap = jnp.int32(n_cycles)
                if W:
                    cap = jnp.minimum(cap, (t0 // W + 1) * W)
                target = jnp.minimum(jnp.maximum(h, out.clk), cap)
                nxt = _idle_jump(out, target)
            if W and n_full:        # n_cycles < W: tail snapshot only
                with jax.named_scope("telemetry_snap"):
                    snaps = jax.lax.cond(
                        target % W == 0,
                        lambda s: jax.tree.map(
                            lambda b, v: jax.lax.dynamic_update_index_in_dim(
                                b, v.astype(b.dtype), target // W - 1, 0),
                            s, snapshot(nxt)),
                        lambda s: s, snaps)
            return nxt, steps + jnp.int32(1), bufs, snaps

        sim, steps, bufs, snaps = jax.lax.while_loop(
            cond, step, (init, jnp.int32(0), bufs0, snaps0))
        if not W:
            return sim, bufs, None, steps
        snap_parts = [snaps] if n_full else []
        if n_cycles % W or not n_full:   # ragged tail / n_cycles < W
            snap_parts.append(jax.tree.map(lambda a: a[None],
                                           snapshot(sim)))
        cat = (lambda *xs: xs[0] if len(xs) == 1 else jnp.concatenate(xs))
        snaps = jax.tree.map(lambda *xs: cat(*xs), *snap_parts)
        return sim, bufs, snaps, steps

    def _final_chs(final_gs):
        """The groups' end-of-run ChannelStats, telemetry gauge columns
        stripped before the uniform aggregation."""
        if not telemetry_window:
            return [g.ch for g in final_gs]
        return [g.ch._replace(cmd_counts=g.ch.cmd_counts[:, :grp.cspec
                              .n_cmds])
                for grp, g in zip(groups, final_gs)]

    def _check_dps(dps):
        if isinstance(dps, D.DynParams):
            dps = (dps,)            # 1-group back-compat
        if len(dps) != n_groups:
            raise ValueError(f"expected {n_groups} DynParams (one per spec "
                             f"group), got {len(dps)}")
        return dps

    def run(dps, fp, seed):
        global TRACE_COUNT
        TRACE_COUNT += 1            # runs once per jax trace, not per call
        dps = _check_dps(dps)
        if fast_forward:
            body = lambda sim: cycle(sim, None, dps=dps, fp=fp)
            final, ys, snaps, steps = _ff_cycles(
                _init_state(seed), body, dps, fp,
                tuple(g.channels for g in groups))
        else:
            body = partial(cycle, dps=dps, fp=fp)
            final, ys, snaps = _scan_cycles(_init_state(seed), body)
            steps = None
        stats = _aggregate_stats(msys, _final_chs(final.gs), final.clk,
                                 steps)
        out = (stats,)
        if trace:
            out += (_finalize_trace(ys),)
        if telemetry_window:
            out += (snaps,)
        return out if len(out) > 1 else stats

    if not shard:
        return run

    # -- channel-sharded variant --------------------------------------
    # The ENTIRE scan sits inside one shard_map, so the per-cycle psum
    # compiles into the same single program as the scan (no per-cycle
    # host round trips).  Each device owns a contiguous slice of every
    # group's channel axis; out_specs gather the per-channel outputs
    # back onto the global channel axis, and the replicated aggregation
    # below is shared verbatim with the vmapped path.
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    shard = int(shard)
    bad = [g.channels for g in groups if g.channels % shard]
    if shard < 2 or bad:
        raise ValueError(
            f"channel shard {shard} must be >= 2 and divide every "
            f"group's channel count {[g.channels for g in groups]}")
    devs = jax.devices()
    if len(devs) < shard:
        raise ValueError(
            f"channel shard {shard} needs {shard} devices, have "
            f"{len(devs)} — pin host devices with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={shard}")
    mesh = Mesh(np.asarray(devs[:shard]), (CHANNEL_AXIS,))

    def run_sharded(dps, fp, seed):
        global TRACE_COUNT
        TRACE_COUNT += 1
        dps = _check_dps(dps)

        def shard_body(dps, fp, seed):
            si = jax.lax.axis_index(CHANNEL_AXIS)
            bases = tuple(
                jnp.int32(b) + si * jnp.int32(grp.channels // shard)
                for b, grp in zip(static_bases, groups))
            if fast_forward:
                body = lambda sim: cycle(sim, None, dps=dps, fp=fp,
                                         axis_name=CHANNEL_AXIS,
                                         bases=bases)
                final, ys, snaps, steps = _ff_cycles(
                    _init_state(seed, si), body, dps, fp,
                    tuple(g.channels // shard for g in groups),
                    axis_name=CHANNEL_AXIS)
            else:
                body = partial(cycle, dps=dps, fp=fp,
                               axis_name=CHANNEL_AXIS, bases=bases)
                final, ys, snaps = _scan_cycles(_init_state(seed, si),
                                                body)
                steps = jnp.int32(n_cycles)
            # steps is uniform across shards (the busy verdict rides the
            # psum and the horizon is pmin-reduced) — emit a (1,) slice
            # per shard and read any one back after the gather
            return tuple(_final_chs(final.gs)), ys, snaps, steps[None]

        chs, ys, snaps, steps = jax.shard_map(
            shard_body, mesh=mesh, in_specs=(P(), P(), P()),
            out_specs=(P(CHANNEL_AXIS), P(None, CHANNEL_AXIS),
                       P(None, CHANNEL_AXIS),
                       P(CHANNEL_AXIS)))(dps, fp, seed)
        stats = _aggregate_stats(msys, list(chs), jnp.int32(n_cycles),
                                 steps[0] if fast_forward else None)
        out = (stats,)
        if trace:
            out += (_finalize_trace(ys),)
        if telemetry_window:
            out += (snaps,)
        return out if len(out) > 1 else stats

    return run_sharded


# --------------------------------------------------------------------------
# Derived metrics
# --------------------------------------------------------------------------
#
# These helpers take the Stats of ONE run: the `float()` casts require
# 0-d (scalar) stat fields and raise on the stacked (B,)-shaped Stats that
# `run_batch` / `repro.dse` produce.  For batched stats either index one
# point out first (`jax.tree.map(lambda a: a[i], stats)`) or use the
# vectorized equivalents in `repro.dse.results`.
#
# Every helper accepts either a CompiledSpec (homogeneous system) or a
# MemorySystemSpec.  For heterogeneous systems the math is GROUP-CORRECT:
# each group's bytes/cycle-time come from its own spec — never one spec's
# bandwidth multiplied by the total channel count — and a spec/stats
# mismatch raises instead of silently aggregating wrong numbers.


def _check_system_stats(msys: MemorySystemSpec, stats):
    got = len(getattr(stats, "per_group", ()) or ())
    if got != msys.n_groups:
        raise ValueError(
            f"stats carry {got} spec group(s) but the system has "
            f"{msys.n_groups} — these stats were produced by a different "
            "memory system (pass the matching spec/system)")


def throughput_gbps(spec, stats) -> float:
    """Achieved data throughput in GB/s (1e9 bytes per second).

    Homogeneous: bytes moved = (reads + writes) * access_bytes; wall time
    = cycles * tCK_ps.  Heterogeneous: each group's bytes and clock come
    from its own spec (``sum_g bytes_g / (cycles * tCK_g)``).  Scalar
    stats only — see the batched-stats caveat above.
    """
    msys = as_system(spec)
    _check_system_stats(msys, stats)
    total = 0.0
    for grp, ch in zip(msys.groups, stats.per_group):
        moved = float(np.sum(np.asarray(ch.reads_done))
                      + np.sum(np.asarray(ch.writes_done))) \
            * grp.cspec.access_bytes
        seconds = float(stats.cycles) * grp.cspec.tCK_ps * 1e-12
        total += moved / seconds / 1e9 if seconds else 0.0
    return total


def peak_gbps(spec) -> float:
    """Theoretical peak throughput of the memory *system* in GB/s:
    each group sustains access_bytes / nBL per cycle on every cycle of
    every one of its channels' data buses, on its own clock — summed
    across groups (the homogeneous case degenerates to the historical
    ``n_channels * per_channel_peak``)."""
    msys = as_system(spec)
    total = 0.0
    for grp in msys.groups:
        per_chan = grp.cspec.peak_bytes_per_cycle \
            / (grp.cspec.tCK_ps * 1e-12) / 1e9
        total += grp.channels * per_chan
    return total


def channel_breakdown(spec, stats) -> dict:
    """Per-system-channel summary of one scalar run's ``stats``:
    ``{channel: {group, standard, reads_done, writes_done,
    throughput_gbps, bus_util}}`` — each channel's conversion uses its own
    group's access_bytes and tCK."""
    msys = as_system(spec)
    _check_system_stats(msys, stats)
    out = {}
    c_sys = 0
    for g, (grp, ch) in enumerate(zip(msys.groups, stats.per_group)):
        seconds = float(stats.cycles) * grp.cspec.tCK_ps * 1e-12
        for c in range(grp.channels):
            moved = (int(ch.reads_done[c]) + int(ch.writes_done[c])) \
                * grp.cspec.access_bytes
            out[c_sys] = {
                "group": g,
                "standard": grp.cspec.standard or grp.cspec.name,
                "reads_done": int(ch.reads_done[c]),
                "writes_done": int(ch.writes_done[c]),
                "throughput_gbps": moved / seconds / 1e9 if seconds else 0.0,
                "bus_util": (float(ch.data_bus_busy[c]) / float(stats.cycles)
                             if int(stats.cycles) else 0.0),
            }
            c_sys += 1
    return out


def avg_probe_latency_ns(spec, stats) -> float:
    """Mean random-probe read latency in nanoseconds (arrival to data
    completion — CXL-attached groups include the round-trip link time),
    NaN when no probe finished.  Probe latencies are counted on the
    system's shared cycle index and converted with the reference clock
    (group 0's tCK).  Scalar stats only — see the batched-stats caveat
    above."""
    if int(stats.probe_cnt) == 0:
        return float("nan")
    cycles = float(stats.probe_lat_sum) / float(stats.probe_cnt)
    return cycles * as_system(spec).tCK_ps * 1e-3


def format_stats(stats, spec=None) -> str:
    """Human-readable summary of one scalar run's ``stats``.

    Without a spec: raw counters only.  With the run's spec/system:
    group-aware physical units — per-group GB/s vs peak, bus utilization,
    row-hit rate (1 - ACT/(RD+WR)), mean probe latency in ns — and a
    per-channel table labeled by each channel's owning standard.  This is
    the formatter behind :meth:`Stats.summary`, shared by the examples
    and the trace/telemetry CLIs."""
    cyc = int(stats.cycles)
    lines = [f"cycles            {cyc:>14,}",
             f"reads done        {int(stats.reads_done):>14,}",
             f"writes done       {int(stats.writes_done):>14,}",
             f"deferred          {int(stats.deferred):>14,}"]
    skipped = int(stats.skipped_cycles)
    if cyc:
        # what fast-forward bought on this workload: the fraction of
        # cycles the engine never had to execute
        lines.append(f"idle fast-forward {skipped / cyc:>14.1%}  "
                     f"({int(stats.scan_steps):,} scan steps)")
    if spec is None:
        if cyc:
            lines.append(f"bus busy          "
                         f"{int(stats.data_bus_busy) / cyc:>14.1%}")
        return "\n".join(lines)
    msys = as_system(spec)
    _check_system_stats(msys, stats)
    ach = throughput_gbps(msys, stats)
    lines += [f"throughput (GB/s) {ach:>14.2f}  "
              f"(peak {peak_gbps(msys):.2f})",
              f"probe latency(ns) {avg_probe_latency_ns(msys, stats):>14.1f}"]
    hit = row_hit_rate(msys, stats)
    if hit == hit:                          # NaN-safe
        lines.append(f"row-hit rate      {hit:>14.1%}")
    bd = channel_breakdown(msys, stats)
    if len(bd) > 1 or msys.n_groups > 1:
        lines.append("channel  standard     reads      writes   "
                     "GB/s   bus-util")
        for c, d in bd.items():
            lines.append(
                f"{c:>7}  {d['standard']:<9}{d['reads_done']:>10,}"
                f"{d['writes_done']:>12,}{d['throughput_gbps']:>7.2f}"
                f"{d['bus_util']:>10.1%}")
    return "\n".join(lines)


def row_hit_rate(spec, stats) -> float:
    """Fraction of data commands (RD+WR) served without opening a new
    row: ``1 - ACT / (RD + WR)``, summed over every group's native
    command counts.  NaN when no data command issued.  Scalar stats only
    — see the batched-stats caveat above."""
    msys = as_system(spec)
    _check_system_stats(msys, stats)
    act = data = 0
    for grp, ch in zip(msys.groups, stats.per_group):
        counts = np.asarray(ch.cmd_counts).sum(axis=0)
        names = grp.cspec.cmd_names
        act += sum(int(counts[i]) for i, n in enumerate(names)
                   if n.startswith("ACT"))
        data += sum(int(counts[i]) for i, n in enumerate(names)
                    if n in ("RD", "WR", "RDA", "WRA"))
    return 1.0 - act / data if data else float("nan")
