"""Device naming and result flattening for the chip benchmark.

``device_info`` and ``stats_leaves`` are the benchmark's own copies of
``chip_smoke.py``'s ``device_info`` and ``stats_diff`` walk, kept here so
that a change to the program cannot move the yardstick.
"""
from __future__ import annotations

import numpy as np


class NoAccelerator(SystemExit):
    """The default JAX backend is not a TPU, or has too few chips."""


def device_info(need: int) -> dict:
    """Platform, kind and count of the default JAX backend.  Raises
    :class:`NoAccelerator` (a ``SystemExit`` with a message, so exit code
    1) unless it is a TPU with at least ``need`` devices: the benchmark
    never falls back to the CPU."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise NoAccelerator(f"no TPU: the default JAX backend is "
                            f"{info['platform']!r} ({info})")
    if len(devs) < need:
        raise NoAccelerator(f"the cell needs {need} TPU chips, found "
                            f"{len(devs)}")
    return info


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest of ``devices``, where the backend
    reports it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def stats_leaves(stats) -> dict:
    """Flatten one run's ``Stats`` tree into ``{dotted path: int64
    array}``: ``reads_done``, ``per_channel.cmd_counts``,
    ``per_group.0.deferred`` and so on, one entry per leaf."""
    import jax
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(stats)[0]:
        name = ".".join(_key_name(k) for k in path)
        out[name] = np.asarray(leaf).astype(np.int64)
    return out


def _key_name(k) -> str:
    for attr in ("name", "key", "idx"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)
