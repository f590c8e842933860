"""Event-horizon fast-forward: bit-exactness and horizon safety.

Two complementary oracles pin the fast-forward engine:

* the classic per-cycle scan (``fast_forward=False``) executes EVERY
  cycle, so whole-run equality of stats, dense command streams, and
  windowed telemetry proves no skipped cycle could have issued anything
  — across standards, random constraint tables, and bursty/paced replay
  streams;
* the scalar ``DeviceUnderTest`` cross-checks the horizon computation
  directly: for states reached through random legal command histories,
  every cycle below ``channel_horizon`` must be issue-incapable per the
  oracle's own ``earliest``/``prereq`` semantics (queue candidates and
  the refresh engine both).

The engine's horizon reduces the controller pass's own lookup after an
idle cycle (``idle_horizon``) where that lookup outlives the cycle, and
``channel_horizon`` recomputes it otherwise: both forms are held to each
other here, state by state and run by run.
"""
import dataclasses

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                     # pragma: no cover - env dependent
    HAVE_HYPOTHESIS = False

    def settings(**kw):                 # no-op decorator stand-ins so the
        return lambda f: f              # module still collects

    def given(**kw):
        return lambda f: f

    class st:                           # noqa: N801 - mirrors the real name
        @staticmethod
        def integers(*a, **kw):
            return None

        @staticmethod
        def sampled_from(*a, **kw):
            return None

        @staticmethod
        def booleans(*a, **kw):
            return None

needs_hypothesis = pytest.mark.skipif(not HAVE_HYPOTHESIS,
                                      reason="hypothesis not installed")

import jax
import jax.numpy as jnp

from repro.core import (ControllerConfig, DeviceUnderTest, FrontendConfig,
                        Simulator, compile_spec, compile_system)
from repro.core import controller as C
from repro.core import device as D
from repro.core import engine as E
from repro.dse.spec import DEFAULT_SYSTEMS
from repro.trace import capture, to_replay

DDR4 = ("DDR4", "DDR4_8Gb_x8", "DDR4_2400R")


def _strip(stats) -> dict:
    """to_dict minus the step accounting (differs by design with ff on)."""
    d = stats.to_dict()
    d.pop("scan_steps")
    d.pop("skipped_cycles")
    return d


def _trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _telemetry_equal(a, b):
    assert a.window == b.window and a.n_cycles == b.n_cycles
    np.testing.assert_array_equal(a.t_end, b.t_end)
    for ga, gb in zip(a.groups, b.groups):
        _trees_equal(dataclasses.asdict(ga), dataclasses.asdict(gb))


def _pair(*args, **kw):
    """(fast-forward, per-cycle) Simulator twins of one configuration."""
    return (Simulator(*args, fast_forward=True, **kw),
            Simulator(*args, fast_forward=False, **kw))


# ---------------------------------------------------------------------------
# whole-run equality vs the per-cycle engine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("standard", sorted(DEFAULT_SYSTEMS))
def test_low_load_ff_equality_every_standard(standard):
    """Low-load run (the regime fast-forward targets): stats and the
    dense command stream must be bit-identical with ff on vs off for
    every registered standard, and ff must actually skip cycles."""
    org, tim = DEFAULT_SYSTEMS[standard]
    on, off = _pair(standard, org, tim,
                    controller=ControllerConfig(scheduler="FRFCFS"))
    n = 1500
    s_on, tr_on = on.run(n, interval=48.0, read_ratio=0.7, trace=True)
    s_off, tr_off = off.run(n, interval=48.0, read_ratio=0.7, trace=True)
    assert _strip(s_on) == _strip(s_off), standard
    _trees_equal(tr_on, tr_off)
    assert int(s_on.skipped_cycles) > 0, standard       # ff engaged
    assert int(s_on.scan_steps) + int(s_on.skipped_cycles) == n
    assert int(s_off.skipped_cycles) == 0
    assert int(s_off.scan_steps) == n


def test_ff_equality_four_channel_with_telemetry():
    msys = compile_system([dict(standard="DDR4", org_preset="DDR4_8Gb_x8",
                                timing_preset="DDR4_2400R", channels=4)])
    on, off = _pair(system=msys, channel_shard=False)
    s_on, tr_on, tm_on = on.run(2000, interval=24.0, trace=True,
                                telemetry=256)
    s_off, tr_off, tm_off = off.run(2000, interval=24.0, trace=True,
                                    telemetry=256)
    assert _strip(s_on) == _strip(s_off)
    _trees_equal(tr_on, tr_off)
    _telemetry_equal(tm_on, tm_off)
    assert int(s_on.skipped_cycles) > 0


def test_ff_equality_hetero_with_telemetry():
    """DDR5 + CXL-attached DDR4: group-indexed scan, link-latency arrive
    gate in the horizon, merged-namespace telemetry."""
    msys = compile_system([
        dict(standard="DDR5", org_preset="DDR5_16Gb_x8",
             timing_preset="DDR5_4800B", channels=1),
        dict(standard="DDR4", org_preset="DDR4_8Gb_x8",
             timing_preset="DDR4_2400R", channels=1, link_latency=40),
    ])
    on, off = _pair(system=msys, channel_shard=False)
    s_on, tr_on, tm_on = on.run(2000, interval=24.0, trace=True,
                                telemetry=256)
    s_off, tr_off, tm_off = off.run(2000, interval=24.0, trace=True,
                                    telemetry=256)
    assert _strip(s_on) == _strip(s_off)
    _trees_equal(tr_on, tr_off)
    _telemetry_equal(tm_on, tm_off)
    assert int(s_on.skipped_cycles) > 0


def test_ff_equality_probes_and_random_pattern():
    for fcfg in (FrontendConfig(probes=True),
                 FrontendConfig(pattern="random")):
        on, off = _pair(*DDR4, frontend=fcfg)
        s_on = on.run(2000, interval=48.0, seed=11)
        s_off = off.run(2000, interval=48.0, seed=11)
        assert _strip(s_on) == _strip(s_off), fcfg
        assert int(s_on.skipped_cycles) > 0, fcfg


def test_ff_saturated_load_still_exact():
    """At saturation there is nothing to skip — ff must degrade to the
    per-cycle program's results (near-zero skips, identical stats)."""
    on, off = _pair(*DDR4)
    s_on = on.run(2000, interval=1.0)
    s_off = off.run(2000, interval=1.0)
    assert _strip(s_on) == _strip(s_off)


def _check_constraint_table_ff_equality(drcd, drp, dras, drrd, interval):
    """Random constraint tables: inflate core timings by random deltas
    (stretching the earliest-ready horizon arbitrarily) — the ff run
    must still match the per-cycle oracle command for command."""
    base = compile_spec(*DDR4).timings
    ov = {"nRCD": int(base["nRCD"]) + drcd,
          "nRP": int(base["nRP"]) + drp,
          "nRAS": int(base["nRAS"]) + dras,
          "nRRD_S": int(base["nRRD_S"]) + drrd,
          "nRRD_L": int(base["nRRD_L"]) + drrd}
    on, off = _pair(*DDR4, timing_overrides=ov)
    s_on, tr_on = on.run(1500, interval=interval, read_ratio=0.7,
                         trace=True)
    s_off, tr_off = off.run(1500, interval=interval, read_ratio=0.7,
                            trace=True)
    assert _strip(s_on) == _strip(s_off), ov
    _trees_equal(tr_on, tr_off)


@pytest.mark.parametrize("drcd,drp,dras,drrd,interval", [
    (7, 3, 19, 2, 48.0),
    (0, 12, 0, 6, 96.0),
    (12, 0, 24, 0, 16.0),
])
def test_constraint_tables_ff_equality(drcd, drp, dras, drrd, interval):
    _check_constraint_table_ff_equality(drcd, drp, dras, drrd, interval)


@needs_hypothesis
@settings(max_examples=6)
@given(drcd=st.integers(0, 12), drp=st.integers(0, 12),
       dras=st.integers(0, 24), drrd=st.integers(0, 6),
       interval=st.sampled_from([16.0, 48.0, 96.0]))
def test_random_constraint_tables_ff_equality(drcd, drp, dras, drrd,
                                              interval):
    _check_constraint_table_ff_equality(drcd, drp, dras, drrd, interval)


def _check_bursty_paced_replay_ff_equality(seed, deps, src_interval):
    """Paced/dep'd ReplayStream traffic (bursty inter-arrival gaps from
    the source run): the arrival-horizon's paced term and the dep-hold
    no-skip rule must reproduce the per-cycle run exactly."""
    src = Simulator(*DDR4)
    _, dense = src.run(1200, interval=src_interval, read_ratio=0.5,
                       seed=seed, trace=True)
    tr = capture(src.cspec, dense, controller=src.controller,
                 frontend=src.frontend)
    rs = to_replay(tr, src.cspec, deps=deps)
    on, off = _pair(*DDR4, replay=rs,
                    frontend=FrontendConfig(pattern="trace", probes=False))
    s_on, tr_on = on.run(3000, trace=True, seed=seed)
    s_off, tr_off = off.run(3000, trace=True, seed=seed)
    assert _strip(s_on) == _strip(s_off)
    _trees_equal(tr_on, tr_off)


@pytest.mark.parametrize("seed,deps,src_interval", [
    (3, True, 32.0), (41, False, 8.0)])
def test_bursty_paced_replay_ff_equality(seed, deps, src_interval):
    _check_bursty_paced_replay_ff_equality(seed, deps, src_interval)


@needs_hypothesis
@settings(max_examples=4)
@given(seed=st.integers(0, 2**31 - 1), deps=st.booleans(),
       src_interval=st.sampled_from([8.0, 32.0]))
def test_random_bursty_paced_replay_ff_equality(seed, deps, src_interval):
    _check_bursty_paced_replay_ff_equality(seed, deps, src_interval)


@pytest.fixture
def recomputing_horizon(monkeypatch):
    """Run ``fn()`` with every group's horizon looked up anew on the
    post-cycle state (``channel_horizon``), in programs traced for it."""
    def run(fn):
        E.RUN_CACHE.clear()
        with monkeypatch.context() as m:
            m.setattr(C, "lookup_outlives_idle_cycle", lambda cspec: False)
            out = fn()
        E.RUN_CACHE.clear()
        return out
    return run


@pytest.mark.parametrize("standard,org,timing", [
    ("DDR5", "DDR5_16Gb_x8_2R", "DDR5_4800B"),
    ("HBM3", "HBM3_16Gb", "HBM3_5200"),            # two command buses
    ("LPDDR5", "LPDDR5_8Gb_x16_2R", "LPDDR5_6400"),  # keeps the recompute
])
def test_ff_steps_match_the_recomputing_horizon(standard, org, timing,
                                                recomputing_horizon):
    """The horizon read from the controller's lookup is the recomputed
    one: every ``Stats`` leaf, ``scan_steps`` and ``skipped_cycles``
    included, is identical, on the scalar and the vmapped paths; and the
    per-cycle engine agrees on everything but the step accounting."""
    sim = Simulator(standard, org, timing, channels=2, channel_shard=False)

    def runs():
        return ([sim.run(3000, interval=i, read_ratio=0.667, seed=7)
                 for i in (16.0, 4.0)],
                sim.run_batch(2000, (64.0, 8.0, 1.0), (1.0, 0.5), seed=9)[1])

    scalar, batch = runs()
    want_scalar, want_batch = recomputing_horizon(runs)
    for got, want in zip(scalar, want_scalar):
        assert got.to_dict() == want.to_dict()
        assert int(got.skipped_cycles) > 0
    _trees_equal(batch, want_batch)
    assert int(np.sum(batch.skipped_cycles)) > 0
    off = sim.run(3000, interval=16.0, read_ratio=0.667, seed=7,
                  fast_forward=False)
    assert _strip(scalar[0]) == _strip(off)


# ---------------------------------------------------------------------------
# horizon safety vs the scalar DeviceUnderTest oracle
# ---------------------------------------------------------------------------


def _random_dut_history(dut, rng, n=40):
    """Drive a random but state-legal command sequence through the DUT."""
    cspec = dut.cspec
    clk = 0
    for _ in range(n):
        sub = {lv: int(rng.integers(int(cspec.level_counts[i + 1])))
               for i, lv in enumerate(cspec.levels[1:])}
        addr = dict(sub, row=int(rng.integers(64)), col=0)
        req = "WR" if rng.random() < 0.3 else "RD"
        cmd = dut.probe(req, addr, clk=clk).preq
        if dut.probe(cmd, addr, clk=clk).timing_OK:
            if cmd == "ACT2":
                addr = dict(addr, row=int(dut.act1_row[dut._bank(addr)]))
            dut.issue(cmd, addr, clk=clk)
        clk += int(rng.integers(1, 6))
    return clk


def _mirror_state(cspec, history):
    dp = D.dyn_params(cspec)
    state = D.init_state(cspec)
    for clk, cmd, addr in history:
        sub = jnp.asarray([addr[lv] for lv in cspec.levels[1:]], jnp.int32)
        state = D.issue(cspec, dp, state, jnp.int32(cspec.cmd_id(cmd)), sub,
                        jnp.int32(addr["row"]), jnp.int32(clk),
                        jnp.asarray(True))
    return dp, state


def _check_horizon_never_skips_issuable_cycle(seed):
    """The core safety property, against the scalar oracle: from a state
    reached by a random legal history with a random pending queue, every
    cycle in ``[clk, channel_horizon)`` must be issue-incapable — no
    queue slot's candidate command is timing-ready per ``DUT.earliest``,
    and no refresh unit is both due and ready."""
    rng = np.random.default_rng(seed)
    dut = DeviceUnderTest(*DDR4)
    cspec = dut.cspec
    clk = _random_dut_history(dut, rng)
    assert len(dut.history) > 5, "oracle never issued — vacuous draw"
    dp, state = _mirror_state(cspec, dut.history)

    # random pending queue over random banks/rows
    depth = 8
    nsub = len(cspec.levels) - 1
    valid = np.zeros(depth, bool)
    is_write = np.zeros(depth, bool)
    subs = np.zeros((depth, nsub), np.int32)
    rows = np.zeros(depth, np.int32)
    slots = []
    for i in range(int(rng.integers(1, 6))):
        sub = {lv: int(rng.integers(int(cspec.level_counts[j + 1])))
               for j, lv in enumerate(cspec.levels[1:])}
        valid[i] = True
        is_write[i] = rng.random() < 0.3
        subs[i] = [sub[lv] for lv in cspec.levels[1:]]
        rows[i] = int(rng.integers(64))
        slots.append((dict(sub, row=int(rows[i]), col=0),
                      "WR" if is_write[i] else "RD"))

    cs = C.init_ctrl_state(cspec, depth)
    cs = cs._replace(
        dev=state,
        queue=cs.queue._replace(
            valid=jnp.asarray(valid), is_write=jnp.asarray(is_write),
            sub=jnp.asarray(subs), row=jnp.asarray(rows),
            arrive=jnp.full((depth,), clk, jnp.int32)))
    cfg = ControllerConfig()
    h = int(C.channel_horizon(cspec, dp, cfg, cs, jnp.int32(clk)))
    assert h >= clk

    nrefi = int(dut.timings["nREFI"])
    banks_per_ru = cspec.n_banks // cspec.n_refresh_units
    last_ref = np.asarray(state.last_ref)
    row_state = np.asarray(state.row_state)
    for t in range(clk, min(h, clk + 1200)):
        for addr, req in slots:
            cand = dut.probe(req, addr, clk=t).preq
            assert dut.earliest(cand, addr) > t, \
                (seed, t, h, req, cand, addr)
        for ru in range(cspec.n_refresh_units):
            if t < int(last_ref[ru]) + nrefi:
                continue                     # not due yet: cannot fire
            unit = row_state[ru * banks_per_ru:(ru + 1) * banks_per_ru]
            ref_cmd = "PREab" if (unit != D.ROW_CLOSED).any() else "REFab"
            rep = {lv: 0 for lv in cspec.levels[1:]}
            rep[cspec.levels[1]] = ru
            rep = dict(rep, row=0, col=0)
            assert dut.earliest(ref_cmd, rep) > t, (seed, t, h, ref_cmd, ru)


@pytest.mark.parametrize("seed", range(8))
def test_horizon_never_skips_issuable_cycle(seed):
    _check_horizon_never_skips_issuable_cycle(seed)


@needs_hypothesis
@settings(max_examples=10)
@given(seed=st.integers(0, 2**31 - 1))
def test_random_horizon_never_skips_issuable_cycle(seed):
    _check_horizon_never_skips_issuable_cycle(seed)


def _check_horizon_conservative_not_stuck(seed):
    """Liveness companion: with a non-empty queue the horizon is finite
    (some candidate eventually becomes ready — the engine can never
    fast-forward to infinity past pending work)."""
    rng = np.random.default_rng(seed)
    dut = DeviceUnderTest(*DDR4)
    cspec = dut.cspec
    clk = _random_dut_history(dut, rng, n=20)
    dp, state = _mirror_state(cspec, dut.history)
    cs = C.init_ctrl_state(cspec, 4)
    cs = cs._replace(
        dev=state,
        queue=cs.queue._replace(valid=jnp.asarray([True, False, False,
                                                   False])))
    h = int(C.channel_horizon(cspec, dp, ControllerConfig(), cs,
                              jnp.int32(clk)))
    assert clk <= h < clk + 10 * int(dut.timings["nREFI"])


@pytest.mark.parametrize("seed", range(6))
def test_horizon_conservative_not_stuck(seed):
    _check_horizon_conservative_not_stuck(seed)


@needs_hypothesis
@settings(max_examples=10)
@given(seed=st.integers(0, 2**31 - 1))
def test_random_horizon_conservative_not_stuck(seed):
    _check_horizon_conservative_not_stuck(seed)


# ---------------------------------------------------------------------------
# the idle horizon from the controller's own lookup
# ---------------------------------------------------------------------------

#: every registered standard whose prerequisite decode ignores the clock
REUSING = sorted(s for s, (o, t) in DEFAULT_SYSTEMS.items()
                 if C.lookup_outlives_idle_cycle(compile_spec(s, o, t)))
DDR5 = ("DDR5", *DEFAULT_SYSTEMS["DDR5"])
#: (standard triple, controller config, link latency) per case
IDLE_CASES = dict(
    {s: ((s, *DEFAULT_SYSTEMS[s]), ControllerConfig(), 0) for s in REUSING},
    cxl_l40=(DDR5, ControllerConfig(), 40),
    prac=(DDR5, ControllerConfig(prac_threshold=3), 0),
    blockhammer=(DDR5, ControllerConfig(blockhammer_threshold=2), 0))


def _random_request(cspec, rng):
    sub = [int(rng.integers(int(cspec.level_counts[j + 1])))
           for j in range(len(cspec.levels) - 1)]
    return (bool(rng.random() < 0.3), jnp.asarray(sub, jnp.int32),
            int(rng.integers(64)))


def _idle_horizons(cspec, cfg, link, seed, n_steps=120):
    """Walk the controller from a random reachable state (a random legal
    history, a random queue, random later arrivals), jumping to the
    horizon after each idle cycle as the engine does.  Returns, per
    idle cycle, ``(idle_horizon from the pass's lookup, channel_horizon
    recomputed on the post-cycle state)`` and the count of busy
    cycles."""
    rng = np.random.default_rng(seed)
    dut = DeviceUnderTest.from_compiled(cspec)
    clk = _random_dut_history(dut, rng)
    dp, state = _mirror_state(cspec, dut.history)
    cs = C.init_ctrl_state(cspec, 8)._replace(dev=state)

    @jax.jit
    def step(cs, clk, want, is_write, sub, row):
        q, ok = C.queue_insert(cs.queue, is_write, jnp.asarray(False), sub,
                               row, jnp.int32(0), clk, want)
        out, ev = C.controller_step(cspec, dp, cfg, cs._replace(queue=q),
                                    clk, link)
        idle = ~ok & jnp.all(ev.cmd < 0)
        nxt = clk + 1
        return (out, idle,
                C.idle_horizon(cspec, dp, cfg, out, nxt, ev.slot_ready_at,
                               ev.ref_ready_at, link),
                C.channel_horizon(cspec, dp, cfg, out, nxt, link))

    pairs, busy = [], 0
    for i in range(n_steps):
        want = i < 5 or rng.random() < 0.15
        is_write, sub, row = _random_request(cspec, rng)
        cs, idle, h_reuse, h_again = step(cs, jnp.int32(clk),
                                          jnp.asarray(want),
                                          jnp.asarray(is_write), sub,
                                          jnp.int32(row))
        if bool(idle):
            pairs.append((int(h_reuse), int(h_again)))
            clk = max(int(h_reuse), clk + 1)
        else:
            busy += 1
            clk += 1
    return pairs, busy


@pytest.mark.parametrize("case", sorted(IDLE_CASES))
def test_idle_horizon_from_the_pass_equals_the_recomputed_horizon(case):
    """After a cycle that accepted and issued nothing, the horizon the
    engine reduces from the controller pass's lookup equals the horizon
    recomputed on the post-cycle state, in every standard whose lookup
    outlives the cycle, behind a CXL link, and with PRAC and
    BlockHammer."""
    triple, cfg, link = IDLE_CASES[case]
    cspec = compile_spec(*triple)
    for seed in range(2):
        pairs, busy = _idle_horizons(cspec, cfg, link, seed)
        assert len(pairs) >= 10 and busy >= 5, (case, seed, busy)
        assert all(a == b for a, b in pairs), (case, seed, pairs)


@pytest.mark.parametrize("standard", ["LPDDR5", "GDDR7"])
def test_a_data_clock_sync_lookup_does_not_outlive_an_idle_cycle(standard):
    """Why data-clock-sync standards recompute: their decode reads the
    clock, so a rank's data clock stopping between the pass's cycle and
    the next flips a column candidate to its sync command and the pass's
    lookup no longer holds."""
    cspec = compile_spec(standard, *DEFAULT_SYSTEMS[standard])
    assert not C.lookup_outlives_idle_cycle(cspec)
    differ = 0
    for seed in range(4):
        pairs, _ = _idle_horizons(cspec, ControllerConfig(), 0, seed)
        differ += sum(a != b for a, b in pairs)
    assert differ > 0
