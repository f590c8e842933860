"""The LPDDR5 deployment's plain reference (``bench/reference_lpddr5.py``)
on the CPU at small sizes.

- Its own command trace keeps the rules it models: every ACT-2 follows
  its bank's ACT-1 by ``nAAD_MIN`` to ``nAAD`` cycles; every RD/WR runs on
  a data clock that a CAS of its rank started at least ``nWCKEN`` earlier
  (the control, ``nWCKEN`` one cycle short, breaks that); ACT-1s keep
  tFAW and tRRD per rank and bank group.  One channel, 2,000 cycles, at
  one request per cycle and one per 16.
- The program gives every ``Stats`` leaf the reference gives, exactly, on
  a 2-channel cut (refresh staggered into the run) at two seeds and two
  loads.
"""
import os
import sys
from collections import defaultdict

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.normpath(os.path.join(HERE, "..", ".."))
sys.path[:0] = [os.path.join(ROOT, "bench")]

import harness  # noqa: E402
from devices import stats_leaves  # noqa: E402

BENCH = harness.load_benchmark(ROOT)
CONFIG = harness.load_config(BENCH, "lpddr5_16ch2r", ROOT)
REF = harness.load_reference(CONFIG["reference"])
T = CONFIG["timings"]
#: banks per rank
BPR = (int(CONFIG["organization"]["levels"]["bankgroup"])
       * int(CONFIG["organization"]["levels"]["bank"]))
N = 2000


@pytest.fixture(scope="module", params=[1.0, 16.0], ids=["i1", "i16"])
def log(request):
    """Every command the reference issues on one channel: ``(clk, name,
    bank, rank)``, in issue order."""
    config = dict(CONFIG, channels=1)
    mem = REF.run(config, request.param, 0.667, 3_000_000_019, N)
    return [(clk, name, bank, ru) for clk, _, name, bank, ru, _ in mem.log]


def wck_faults(log, nwcken=T["nWCKEN"], idle=T["nWCKIDLE"]) -> int:
    """RD/WRs that do not run on a data clock started at least ``nwcken``
    cycles earlier by a CAS of their rank (the clock runs from a CAS to
    ``clk + idle``, and each RD/WR keeps it on to at least ``clk +
    idle``)."""
    until, started, bad = defaultdict(int), {}, 0
    for clk, name, _, ru in log:
        if name in ("CAS_RD", "CAS_WR"):
            if clk >= until[ru]:
                started[ru] = clk       # the clock was off: a new sync
            until[ru] = clk + idle
        elif name in ("RD", "WR"):
            bad += not (clk < until[ru] and clk - started[ru] >= nwcken)
            until[ru] = max(until[ru], clk + idle)
    return bad


def test_every_act2_follows_its_act1_inside_taad(log):
    act1 = {}
    pairs = 0
    for clk, name, bank, ru in log:
        if name == "ACT1":
            assert bank not in act1, f"second ACT-1 to Activating bank {bank}"
            act1[bank] = clk
        elif name == "ACT2":
            gap = clk - act1.pop(bank)
            assert T["nAAD_MIN"] <= gap <= T["nAAD"], (clk, bank, gap)
            pairs += 1
        elif name == "PRE":
            assert bank not in act1, f"PRE to Activating bank {bank}"
        elif name == "PREab":
            # an urgent refresh closes the rank's Activating banks too
            act1 = {b: t for b, t in act1.items() if b // BPR != ru}
    assert pairs > 10
    # at most the last activations are still pending when the run ends
    assert all(N - t <= T["nAAD"] for t in act1.values())


def test_every_column_access_runs_on_a_synchronised_data_clock(log):
    names = [name for _, name, _, _ in log]
    assert names.count("CAS_RD") > 10 and names.count("RD") > 10
    assert names.count("CAS_WR") > 0 and names.count("WR") > 0
    assert wck_faults(log) == 0


@pytest.mark.parametrize("interval", [1.0, 16.0])
def test_the_control_breaks_the_data_clock_guarantee(interval):
    config = dict(CONFIG, channels=1)
    mem = REF.run(config, interval, 0.667, 3_000_000_019, N, control=True)
    log = [(clk, name, bank, ru) for clk, _, name, bank, ru, _ in mem.log]
    assert wck_faults(log) > 0


def test_act1s_keep_tfaw_and_trrd(log):
    per_bank = int(CONFIG["organization"]["levels"]["bank"])
    ranks, groups = defaultdict(list), defaultdict(list)
    for clk, name, bank, ru in log:
        if name == "ACT1":
            ranks[ru].append(clk)
            groups[bank // per_bank].append(clk)
    assert sum(map(len, ranks.values())) > 10
    for times in ranks.values():
        assert all(b - a >= T["nRRD_S"] for a, b in zip(times, times[1:]))
        assert all(b - a >= T["nFAW"] for a, b in zip(times, times[4:]))
    for times in groups.values():
        assert all(b - a >= T["nRRD_L"] for a, b in zip(times, times[1:]))


@pytest.fixture(scope="module")
def two_channels():
    import programs
    from repro.core import Simulator
    config = dict(CONFIG, channels=2)
    sim = Simulator(config["standard"], config["org_preset"],
                    config["timing_preset"], channels=2,
                    timing_overrides=programs.timing_overrides(config),
                    controller=programs.controller_config(config),
                    frontend=programs.frontend_config(config),
                    channel_shard=False)
    return config, sim


@pytest.mark.parametrize("interval,read_ratio", [(1.0, 0.667), (8.0, 0.5)])
@pytest.mark.parametrize("seed", [4_000_000_007, 12_345])
def test_program_matches_the_reference_on_two_channels(
        two_channels, interval, read_ratio, seed):
    config, sim = two_channels
    got = stats_leaves(sim.run(N, interval=interval, read_ratio=read_ratio,
                               seed=seed))
    ref = REF.simulate(config, interval, read_ratio, seed, N)
    assert harness.compare(got, ref) == (0, [])
    assert int(ref["cmd_counts"][REF.NAMES.index("REFab")]) > 0
