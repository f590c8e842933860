"""Sweep scale-out: device-sharded batches, donated carries, and the
streamed (bounded in-flight) collection pipeline.

In-process tests cover the single-device invariants; the multi-device
padding/equivalence checks run in a subprocess that forces 4 host
devices before jax initializes."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import ControllerConfig, FrontendConfig
from repro.core import engine as E
from repro.core import frontend as F
from repro.dse import SweepSpec, execute
from repro.dse.executor import _shard_batch

REPO = os.path.join(os.path.dirname(__file__), "..", "..")

SPEC = SweepSpec(systems=("DDR4",), intervals=(8.0, 4.0, 2.0),
                 read_ratios=(1.0, 0.5), n_cycles=400)


def test_shard_batch_empty_devices_raises():
    fp = F.stack_params([(4.0, 1.0), (2.0, 0.5)],
                        FrontendConfig().probe_gap)
    with pytest.raises(ValueError, match="devices"):
        _shard_batch(fp, [])


def test_execute_empty_devices_raises():
    with pytest.raises(ValueError, match="devices"):
        execute(SPEC, devices=[])


def test_run_key_separates_shard_and_donation():
    from repro.core import Simulator
    sim = Simulator("DDR4", "DDR4_8Gb_x8", "DDR4_2400R", channels=4)
    base = E.run_key(sim.cspec, sim.controller, sim.frontend, 300, False,
                     False)
    k_shard = E.run_key(sim.cspec, sim.controller, sim.frontend, 300, False,
                        False, shard=2)
    k_donate = E.run_key(sim.cspec, sim.controller, sim.frontend, 300, False,
                         False, donate=True)
    assert len({base, k_shard, k_donate}) == 3


def test_streamed_collection_depth_invariant():
    """The in-flight bound is a scheduling knob, not a semantic one:
    depth-1 (fully synchronous) and depth-8 pipelines must produce
    identical sweep columns, and the meta must carry the streaming
    accounting."""
    spec = SweepSpec(systems=("DDR4", "DDR5"), intervals=(8.0, 2.0),
                     read_ratios=(1.0,), n_cycles=400)
    r1 = execute(spec, cache=E.RunCache(), max_in_flight=1)
    r8 = execute(spec, cache=E.RunCache(), max_in_flight=8)
    for k in ("throughput_gbps", "latency_ns", "reads_done", "writes_done",
              "cycles"):
        assert np.array_equal(getattr(r1, k), getattr(r8, k)), k
    for res, depth in ((r1, 1), (r8, 8)):
        m = res.meta
        assert m["max_in_flight"] == depth
        assert m["padded_points"] == 0          # single device: no padding
        spans = m["profile"]["spans"]
        for phase in ("dse.lookup", "dse.dispatch", "dse.collect"):
            assert spans[phase]["calls"] == m["n_groups"]
        # one plan span for the expansion, then one per group
        assert spans["dse.plan"]["calls"] == m["n_groups"] + 1
        for gm in m["groups"]:
            assert gm["padded"] == 0
        # the phases are disjoint spans inside the sweep's wall time (each
        # figure rounded to the millisecond)
        assert sum(s["s"] for s in spans.values()) \
            <= m["profile"]["wall_s"] + 0.001 * len(spans)


def test_executor_reports_profile_spans():
    from repro import telemetry as T
    prof = T.Profiler(E.RUN_CACHE)
    res = execute(SPEC, profiler=prof)
    spans = res.meta["profile"]["spans"]
    assert set(spans) == {"dse.plan", "dse.lookup", "dse.dispatch",
                          "dse.collect"}
    # the caller's profiler is the one that was fed
    assert prof.report()["spans"]["dse.dispatch"]["calls"] == \
        res.meta["n_groups"]


@pytest.mark.slow
def test_padded_batch_on_four_devices_matches_single_device():
    """3 points on 4 forced host devices: one repeated pad entry is
    simulated and dropped, accounted in the meta, and the unpadded
    columns match a single-device run bit for bit."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
import jax
from repro.core import engine as E
from repro.dse import SweepSpec, execute

assert jax.device_count() == 4
spec = SweepSpec(systems=("DDR4",), intervals=(8.0, 4.0, 2.0),
                 read_ratios=(1.0,), n_cycles=600)
r4 = execute(spec, cache=E.RunCache())                   # all 4 devices
r1 = execute(spec, cache=E.RunCache(), devices=jax.devices()[:1])
assert r4.meta["n_devices"] == 4 and r1.meta["n_devices"] == 1
assert r4.meta["padded_points"] == 1, r4.meta["padded_points"]
assert [g["padded"] for g in r4.meta["groups"]] == [1]
assert r1.meta["padded_points"] == 0
for k in ("throughput_gbps", "latency_ns", "reads_done", "writes_done",
          "probe_cnt", "cycles"):
    assert np.array_equal(getattr(r4, k), getattr(r1, k)), k
print("PADDED-OK")
"""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-4000:])
    assert "PADDED-OK" in r.stdout
