"""Traffic kind ``scalar``: one ``Simulator.run`` per call.

The traffic file names one load point (``interval``, ``read_ratio``);
every call of the window simulates ``n_cycles`` of it from empty queues
and closed rows, with the call's own seed.  A one-chip cell runs with
``channel_shard=False``; a cell on ``d`` chips shards the channel axis
over exactly ``d`` devices.
"""
from __future__ import annotations

from devices import stats_leaves
from programs import controller_config, frontend_config, timing_overrides

#: inter-arrival interval of the set-up call: the stream never fires, so
#: the call compiles (or loads) the cell's own program and runs only the
#: probes and refresh of the same shapes
WARM_INTERVAL = 1e6
#: a call gives every ``Stats`` leaf, so every reference leaf is compared
CHECKED = None


class Runner:
    def __init__(self, config: dict, traffic: dict, devices: list):
        from repro.core import Simulator
        self.n_cycles = int(config["n_cycles"])
        self.interval = float(traffic["interval"])
        self.read_ratio = float(traffic["read_ratio"])
        self.points = [{"interval": self.interval,
                        "read_ratio": self.read_ratio}]
        self.sim = Simulator(
            config["standard"], config["org_preset"],
            config["timing_preset"], channels=int(config["channels"]),
            timing_overrides=timing_overrides(config),
            controller=controller_config(config),
            frontend=frontend_config(config),
            channel_shard=False if len(devices) == 1 else len(devices))

    def warm(self) -> None:
        self.sim.run(self.n_cycles, interval=WARM_INTERVAL,
                     read_ratio=self.read_ratio, seed=1)

    def call(self, seed: int) -> list:
        """Run one call; returns one ``{leaf: int64 array}`` per point."""
        stats = self.sim.run(self.n_cycles, interval=self.interval,
                             read_ratio=self.read_ratio, seed=seed)
        return [stats_leaves(stats)]
