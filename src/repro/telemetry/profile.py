"""Host-side run profiler: where the wall-clock goes *around* the jitted
simulation — trace + XLA compile cost, :class:`repro.core.RunCache`
hit/miss behavior, and warm-run throughput (cycles/second) — the
counterpart of the in-scan windowed telemetry.

Two entry points:

- :class:`Profiler` — a span recorder + cache-accounting delta reader for
  instrumenting arbitrary host code (DSE sweeps, benchmarks).
- :func:`profile_run` — one-shot cold/warm characterization of a
  :class:`~repro.core.Simulator` run configuration.
"""
from __future__ import annotations

import contextlib
import time

import jax

from repro.core import engine as E


class Profiler:
    """Record named wall-time spans and RunCache accounting deltas.

    Each span is also a ``jax.profiler.TraceAnnotation`` of the same name,
    so under a profiler session it lies on the device trace's clock beside
    the operations it launched (docs/observability.md §5).

    >>> prof = Profiler()
    >>> with prof.span("sweep"):
    ...     result = run_sweep(spec)
    >>> prof.report()["spans"]["sweep"]        # {"s": ..., "calls": 1}
    >>> prof.report()["cache"]                 # hits/misses/compile since
    ...                                        # construction

    Spans nest and repeat (times accumulate per name).  The cache view is
    a DELTA against the profiler's construction instant, so a process-wide
    warm :data:`repro.core.engine.RUN_CACHE` does not pollute it.
    """

    def __init__(self, cache: E.RunCache | None = None):
        self.cache = cache if cache is not None else E.RUN_CACHE
        self._base = dict(self.cache.stats())
        self._spans: dict = {}
        self._t0 = time.perf_counter()

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield self
        finally:
            dt = time.perf_counter() - t0
            s = self._spans.setdefault(name, {"s": 0.0, "calls": 0})
            s["s"] += dt
            s["calls"] += 1

    def cache_stats(self) -> dict:
        """RunCache accounting since this profiler was constructed.
        Counters (entries, hits and misses, first-call seconds and their
        compile stages) are deltas against the construction instant;
        the device and topology views pass through as they are."""
        delta_keys = {"entries", "hits", "misses", "first_call_s",
                      *E.COMPILE_EVENTS.values()}
        now = self.cache.stats()
        out = {}
        for k, v in now.items():
            if k in delta_keys and isinstance(v, (int, float)):
                base = self._base.get(k, 0)
                out[k] = round(v - base, 6) if isinstance(v, float) \
                    else v - base
            else:
                # topology views ("devices", "shard_topologies", future
                # additions) are states, not counters — pass through
                out[k] = v
        return out

    def report(self) -> dict:
        return {"wall_s": round(time.perf_counter() - self._t0, 3),
                "spans": {k: {"s": round(v["s"], 3), "calls": v["calls"]}
                          for k, v in self._spans.items()},
                "cache": self.cache_stats()}

    def summary(self) -> str:
        r = self.report()
        c = r["cache"]
        lines = [f"wall {r['wall_s']:.3f}s | cache: {c['entries']:+d} "
                 f"programs, {c['hits']} hits / {c['misses']} misses, "
                 f"first-call (trace+compile+run) {c['first_call_s']:.3f}s"]
        for name, s in sorted(r["spans"].items(), key=lambda kv: -kv[1]["s"]):
            lines.append(f"  {name:<24} {s['s']:>9.3f}s x{s['calls']}")
        return "\n".join(lines)


def profile_run(sim, n_cycles: int, repeats: int = 3, **run_kw) -> dict:
    """Cold/warm characterization of one run configuration.

    Times the first (compiling) call and the best of ``repeats`` warm
    calls, both synchronized with ``jax.block_until_ready``.  Returns::

        {"first_call_s", "warm_s", "compile_s",       # first - warm
         "cycles_per_sec",                            # warm throughput
         "scan_steps", "skipped_cycles",              # fast-forward
         "idle_fraction",                             #   accounting
         "cache": {...}}                              # RunCache delta

    ``run_kw`` is forwarded to ``sim.run`` (interval/read_ratio/telemetry
    /trace), so the telemetry-on cost is directly measurable.
    """
    prof = Profiler()
    with prof.span("first_call"):
        out = jax.block_until_ready(sim.run(n_cycles, **run_kw))
    warm = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        out = jax.block_until_ready(sim.run(n_cycles, **run_kw))
        warm.append(time.perf_counter() - t0)
    r = prof.report()
    first = r["spans"]["first_call"]["s"]
    best = min(warm)
    stats = out[0] if isinstance(out, tuple) and not hasattr(
        out, "to_dict") else out
    skipped = int(stats.skipped_cycles)
    return {"first_call_s": round(first, 4), "warm_s": round(best, 4),
            "compile_s": round(max(first - best, 0.0), 4),
            "cycles_per_sec": round(n_cycles / best, 1) if best else None,
            # event-horizon fast-forward accounting (0 skipped when off)
            "scan_steps": int(stats.scan_steps),
            "skipped_cycles": skipped,
            "idle_fraction": round(skipped / n_cycles, 4) if n_cycles
            else 0.0,
            "cache": r["cache"]}
