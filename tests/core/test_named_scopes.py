"""The cycle loop's parts reach the compiled program as named scopes
(``engine.SCOPES``) on every path (scalar, batched, channel-sharded and
the per-cycle scan), and the scopes leave every result as it was.

The paths are compiled under ``--xla_force_host_platform_device_count=4``
in a subprocess, which pins the device count before jax starts (the
idiom of ``tests/core/test_sharded_engine.py``)."""
import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(__file__), "..", "..")
LOOP_SCOPES = {"frontend", "controller", "fold", "horizon"}
EXTRA_SCOPES = {"trace_write", "telemetry_snap"}

COMPILE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, re
import jax, jax.numpy as jnp
from repro.core import Simulator
from repro.core import engine as E
from repro.core import frontend as F

assert jax.device_count() == 4

def scopes(text):
    # the named scopes found in the op names of the loop's body
    found = set()
    for path in re.findall(r'op_name="([^"]*)"', text):
        parts = path.split("/")
        if "body" in parts:
            found |= set(parts) & set(E.SCOPES)
    return sorted(found)

def collectives(text):
    # the innermost named scope of each all-reduce's op name
    out = []
    for line in text.splitlines():
        m = re.search(r'= \S+ all-reduce\(.*op_name="([^"]*)"', line)
        if m:
            parts = [p for p in m.group(1).split("/") if p in E.SCOPES]
            out.append(parts[-1] if parts else None)
    return sorted(set(map(str, out)))

cache = E.RunCache()
sim = Simulator("DDR4", "DDR4_8Gb_x8", "DDR4_2400R", channels=4)
dps, fcfg, seed = sim._dyn_params(), sim.frontend, jnp.uint32(1)
single = fcfg.params()
batch = F.stack_params([(4.0, 1.0), (1.0, 0.5)], fcfg.probe_gap)
paths = {"scalar": ({}, single), "batched": ({"batched": True}, batch),
         "sharded": ({"shard": 4}, single),
         "per_cycle": ({"fast_forward": False}, single)}
out = {}
for name, (kw, fp) in paths.items():
    for extras in (False, True):
        more = {"trace": True, "telemetry": 64} if extras else {}
        fn = cache.get(sim.cspec, sim.controller, fcfg, 300, **kw, **more)
        text = fn.fn.lower(dps, fp, seed).compile().as_text()
        out[f"{name}:{extras}"] = {"scopes": scopes(text),
                                   "all_reduce": collectives(text)}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def compiled():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)      # the snippet pins its own device count
    r = subprocess.run([sys.executable, "-c", COMPILE], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-4000:])
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("path", ["scalar", "batched", "sharded",
                                  "per_cycle"])
def test_every_path_carries_the_scopes_into_its_compiled_program(
        compiled, path):
    # fast-forward off: no horizon, the loop is the per-cycle scan
    loop = LOOP_SCOPES - ({"horizon"} if path == "per_cycle" else set())
    assert set(compiled[f"{path}:False"]["scopes"]) == loop
    # trace capture and windowed telemetry add their own parts (the
    # per-cycle scan emits its trace as scan output, with no buffer write)
    extra = EXTRA_SCOPES - ({"trace_write"} if path == "per_cycle"
                            else set())
    assert set(compiled[f"{path}:True"]["scopes"]) == loop | extra


def test_the_sharded_loops_collectives_fall_under_fold_and_horizon(compiled):
    # one psum of the fused reduction (fold) and one pmin of the horizon
    assert compiled["sharded:False"]["all_reduce"] == ["fold", "horizon"]
    assert compiled["scalar:False"]["all_reduce"] == []


# sha256 (first 16 hex digits) of every leaf of the results below, as the
# program computed them before the named scopes were added
BEFORE_SCOPES = {
    "ddr5x2_i4": "8c36376983305637",
    "ddr5x2_i4_telemetry": "16cf2fb8e9657a4c",
    "ddr5x2_i16_trace": "9b4bb549c3f542f6",
    "hbm3x4_i1": "dea9913d66851e04",
    "hbm3x4_i1_per_cycle": "dea9913d66851e04",
    "ddr5x2_batch": "3b12cdee9b9a598d",
    "sweep_ddr4": "ecf706e5015ccdfe",
}


def _sha(leaves) -> str:
    import jax
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(leaves):
        a = np.asarray(leaf)
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a, np.int64).tobytes())
    return h.hexdigest()[:16]


def _results(case):
    from repro.core import Simulator
    from repro.dse import SweepSpec, execute
    ddr5 = Simulator("DDR5", "DDR5_16Gb_x8", "DDR5_4800B", channels=2)
    hbm3 = Simulator("HBM3", "HBM3_16Gb", "HBM3_5200", channels=4)
    if case == "ddr5x2_i4":
        return ddr5.run(3000, interval=4.0, read_ratio=0.75, seed=11)
    if case == "ddr5x2_i4_telemetry":
        st, tm = ddr5.run(3000, interval=4.0, read_ratio=0.75, seed=11,
                          telemetry=256)
        g = tm.groups[0]
        return [st, tm.t_end, g.reads, g.writes, g.probe_lat_sum,
                g.probe_cnt, g.data_bus_busy, g.deferred, g.occ_sum,
                g.cmd_counts, g.lat_hist]
    if case == "ddr5x2_i16_trace":
        return ddr5.run(3000, interval=16.0, seed=5, trace=True)
    if case.startswith("hbm3x4_i1"):
        return hbm3.run(2000, interval=1.0, read_ratio=0.667, seed=3,
                        fast_forward=not case.endswith("per_cycle"))
    if case == "ddr5x2_batch":
        return ddr5.run_batch(2000, (64.0, 4.0, 1.0), (1.0, 0.5), seed=9)[1]
    r = execute(SweepSpec(systems=("DDR4",), intervals=(8.0, 2.0),
                          read_ratios=(1.0, 0.667), n_cycles=2000, seed=21))
    return [r.reads_done, r.writes_done, r.probe_cnt, r.deferred, r.cycles,
            r.scan_steps, r.skipped_cycles, np.stack(r.cmd_counts),
            r.latency_ns.view(np.int64)]


@pytest.mark.parametrize("case", sorted(BEFORE_SCOPES))
def test_results_are_bit_identical_to_the_program_without_scopes(case):
    assert _sha(_results(case)) == BEFORE_SCOPES[case]


#: a standard's own mechanisms, named inside the loop's parts
MECHANISM_SCOPES = {"split_act", "clock_sync"}


def _mechanism_scopes(standard, org, timing) -> dict:
    """``{loop part: mechanism scopes under it}`` in the op names of a
    2-channel scalar program of ``standard``, compiled on the host."""
    import re

    import jax.numpy as jnp

    from repro.core import Simulator
    from repro.core import engine as E
    sim = Simulator(standard, org, timing, channels=2, channel_shard=False)
    fn = E.RunCache().get(sim.cspec, sim.controller, sim.frontend, 300)
    text = fn.fn.lower(sim._dyn_params(), sim.frontend.params(),
                       jnp.uint32(1)).compile().as_text()
    found = {}
    for path in re.findall(r'op_name="([^"]*)"', text):
        # a scope entered under vmap reads "vmap(<scope>)"
        parts = [re.sub(r"^vmap\((.*)\)$", r"\1", p)
                 for p in path.split("/")]
        if "body" not in parts:
            continue
        loop = [p for p in parts if p in LOOP_SCOPES]
        nested = set(parts) & MECHANISM_SCOPES
        if loop and nested:
            found.setdefault(loop[0], set()).update(nested)
    return found


@pytest.mark.parametrize("standard,org,timing,want", [
    ("LPDDR5", "LPDDR5_8Gb_x16_2R", "LPDDR5_6400",
     {"controller": MECHANISM_SCOPES, "horizon": MECHANISM_SCOPES}),
    ("DDR5", "DDR5_16Gb_x8_2R", "DDR5_4800B", {}),
    ("HBM3", "HBM3_16Gb", "HBM3_5200", {}),
])
def test_a_standards_own_mechanisms_are_scoped_in_its_program_only(
        standard, org, timing, want):
    # split activation and WCK sync reach LPDDR5's compiled op names,
    # inside the controller and (the prerequisite decode and the clock
    # expiry term of the fast-forward horizon) the horizon; standards
    # without them carry neither name
    assert _mechanism_scopes(standard, org, timing) == want
