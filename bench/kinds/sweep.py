"""Traffic kind ``sweep``: one ``repro.dse.execute`` call per window call.

The traffic file names the load grid (``intervals`` x ``read_ratios``);
every call simulates each point for ``n_cycles`` from empty queues and
closed rows, as one compile group whose batch is placed on the cell's
chips (sharded over them when there are several).

``execute`` returns columns, not whole ``Stats``: the per-point leaves
are the integer columns, plus ``probe_lat_sum`` recovered exactly from
``latency_ns`` (a mean over at most a few thousand probes, so float64
holds it to far below one cycle).
"""
from __future__ import annotations

import numpy as np

from programs import controller_config, frontend_config, timing_overrides

#: see kinds/scalar.py: the stream never fires in the set-up call
WARM_INTERVAL = 1e6

COLUMNS = ("cycles", "reads_done", "writes_done", "probe_cnt", "deferred",
           "scan_steps", "skipped_cycles")
#: the reference's leaves that a call gives, and so that are compared
CHECKED = ("cycles", "reads_done", "writes_done", "probe_cnt", "deferred",
           "cmd_counts", "probe_lat_sum")


class Runner:
    def __init__(self, config: dict, traffic: dict, devices: list):
        from repro.dse import SweepSpec
        self.devices = list(devices)
        self.intervals = tuple(float(i) for i in traffic["intervals"])
        self.read_ratios = tuple(float(r) for r in traffic["read_ratios"])
        self.tck_ps = int(config["timings"]["tCK_ps"])
        self._spec = lambda seed, intervals: SweepSpec(
            systems=((config["standard"], config["org_preset"],
                      config["timing_preset"],
                      timing_overrides(config) or ()),),
            intervals=intervals, read_ratios=self.read_ratios,
            controllers=(controller_config(config),),
            channels=(int(config["channels"]),),
            frontend=frontend_config(config),
            n_cycles=int(config["n_cycles"]), seed=seed)
        self.points = [{"interval": p.interval, "read_ratio": p.read_ratio}
                       for p in self._spec(1, self.intervals).expand()]

    def warm(self) -> None:
        from repro.dse import execute
        light = tuple(WARM_INTERVAL + k for k in range(len(self.intervals)))
        execute(self._spec(1, light), devices=self.devices)

    def call(self, seed: int) -> list:
        """Run one call; returns one ``{leaf: int64 array}`` per point."""
        from repro.dse import execute
        res = execute(self._spec(seed, self.intervals), devices=self.devices)
        out = []
        for i in range(len(res.points)):
            leaves = {k: np.asarray(getattr(res, k)[i], np.int64)
                      for k in COLUMNS}
            leaves["cmd_counts"] = np.asarray(res.cmd_counts[i], np.int64)
            cnt = int(res.probe_cnt[i])
            lat = float(res.latency_ns[i])
            leaves["probe_lat_sum"] = np.asarray(
                0 if cnt == 0 else round(lat / (self.tck_ps * 1e-3) * cnt),
                np.int64)
            out.append(leaves)
        return out
