"""Bring-up check of the simulator on a TPU.

Drives the main path once through its public entry points and checks
every result against a reference that the chip did not produce:

1. ``device``: the default JAX backend must be a TPU; there is no CPU
   fallback.
2. ``golden``: the 13 pinned command streams of
   ``tests/trace/test_golden_equality.py`` (every default standard,
   DDR4@2ch and the DDR5x2+DDR4x2@80 composition, 3000 cycles each) must
   hash to ``tests/trace/golden_hashes.json``.
3. ``scalar``: deployment-size runs of 100k cycles (an HBM3 stack at 16
   channels, a DDR5 socket of 8 channels x 2 ranks at two loads, the
   DDR5x2+DDR4x2@80 composition) must give ``Stats`` bit-identical to
   the same program on the host CPU device of this process.
4. ``sweep``: a 48-point ``repro.dse`` sweep (4 compile groups) whose
   integer columns must equal the same sweep on the host CPU device.
5. ``cli``: ``repro.trace`` must find zero audit violations and
   ``repro.telemetry --check`` must pass.

``--four-chips`` runs only the multi-chip path and what it is compared
with: the sweep with its batch sharded over four chips against the same
sweep on one chip, and channel-sharded scalar runs against unsharded runs
and against the golden hashes.

    python chip_smoke.py
    python chip_smoke.py --four-chips

Every phase prints its seconds.  The last line of standard output is one
JSON object, ``{"ok": true, "device": {...}}``, printed only when every
phase passed; any failure ends the run with a non-zero exit code.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import ControllerConfig, Simulator, compile_system  # noqa: E402
from repro.dse import SweepSpec, execute  # noqa: E402
from repro.dse.spec import DEFAULT_SYSTEMS  # noqa: E402
from repro.trace.capture import FIELDS, capture  # noqa: E402

GOLDEN_PATH = os.path.join(ROOT, "tests", "trace", "golden_hashes.json")
GOLDEN_CYCLES = 3000
#: about 10 refresh intervals of the slowest-refreshing part (DDR5-4800B,
#: nREFI = 9360 cycles)
DEPLOY_CYCLES = 100_000
HETERO = (
    dict(standard="DDR5", org_preset="DDR5_16Gb_x8",
         timing_preset="DDR5_4800B", channels=2),
    dict(standard="DDR4", org_preset="DDR4_8Gb_x8",
         timing_preset="DDR4_2400R", channels=2, link_latency=80),
)
#: (label, Simulator arguments, streaming intervals); every run reads 70%
DEPLOYMENTS = (
    ("HBM3@16ch", dict(standard="HBM3", org_preset="HBM3_16Gb",
                       timing_preset="HBM3_5200", channels=16), (1.0,)),
    ("DDR5@8ch-2R", dict(standard="DDR5", org_preset="DDR5_16Gb_x8_2R",
                         timing_preset="DDR5_4800B", channels=8),
     (1.0, 16.0)),
    ("DDR5x2+DDR4x2@80", dict(system=list(HETERO)), (1.0,)),
)
READ_RATIO = 0.7
INT_COLUMNS = ("reads_done", "writes_done", "probe_cnt", "cycles",
               "scan_steps", "skipped_cycles")


class SmokeError(RuntimeError):
    """A result on the device differs from its reference."""


def sweep_spec(n_cycles: int = 20_000) -> SweepSpec:
    """DDR5 and HBM3 x 6 loads x 2 read ratios x 1 and 4 channels: 48
    points in 4 compile groups."""
    return SweepSpec(systems=("DDR5", "HBM3"),
                     intervals=(64.0, 16.0, 8.0, 4.0, 2.0, 1.0),
                     read_ratios=(1.0, 0.7), channels=(1, 4),
                     n_cycles=n_cycles)


def device_info(need: int) -> dict:
    """The default backend's platform, kind and device count; exits
    unless it is a TPU with at least ``need`` devices."""
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: {json.dumps(info)}", flush=True)
    if info["platform"] != "tpu":
        raise SystemExit(f"no TPU: the default JAX backend is "
                         f"{info['platform']!r}")
    if len(devs) < need:
        raise SystemExit(f"need {need} TPU devices, found {len(devs)}")
    return info


def stream_sha256(tr, extra=()) -> str:
    h = hashlib.sha256()
    for f in FIELDS + tuple(extra):
        h.update(np.ascontiguousarray(getattr(tr, f), np.int32).tobytes())
    return h.hexdigest()


def golden_cases(channel_shard=None) -> dict:
    """name -> (Simulator, extra hashed columns), with the controller,
    mapper and system settings the hashes were pinned with.
    ``channel_shard`` applies to the two multi-channel cases."""
    frfcfs = ControllerConfig(scheduler="FRFCFS")
    cases = {std: (Simulator(std, org, tim, controller=frfcfs), ())
             for std, (org, tim) in sorted(DEFAULT_SYSTEMS.items())}
    cases["DDR4@2ch"] = (Simulator(
        "DDR4", "DDR4_8Gb_x8", "DDR4_2400R", channels=2,
        mapper="RoBaRaCoCh",
        controller=ControllerConfig(refresh_stagger=False),
        channel_shard=channel_shard), ())
    cases["DDR5x2+DDR4x2@80"] = (Simulator(
        system=compile_system(list(HETERO)), controller=frfcfs,
        channel_shard=channel_shard), ("group",))
    return cases


def phase_golden(names=None, channel_shard=None) -> None:
    """Run the pinned command streams (all 13, or ``names``) and compare
    their count and sha256 with the golden file."""
    with open(GOLDEN_PATH) as f:
        golden = json.load(f)
    cases = golden_cases(channel_shard)
    bad = []
    for name in cases if names is None else names:
        sim, extra = cases[name]
        t0 = time.perf_counter()
        _, dense = sim.run(GOLDEN_CYCLES, interval=2.0, read_ratio=0.7,
                           trace=True)
        tr = capture(sim.cspec if sim.cspec is not None else sim.msys,
                     dense)
        got = {"n": len(tr), "sha256": stream_sha256(tr, extra)}
        want = {k: golden[name][k] for k in ("n", "sha256")}
        print(f"  golden {name}: n={got['n']} "
              f"{'match' if got == want else 'MISMATCH'} "
              f"({time.perf_counter() - t0:.3f} s)", flush=True)
        if got != want:
            bad.append(name)
    if bad:
        raise SmokeError(f"command streams differ from the golden hashes: "
                         f"{bad}")


def stats_diff(a, b) -> list:
    """Paths of the leaves where two Stats trees differ."""
    la, ta = jax.tree_util.tree_flatten_with_path(a)
    lb, tb = jax.tree_util.tree_flatten_with_path(b)
    if ta != tb:
        return ["<tree structure>"]
    return [jax.tree_util.keystr(p) for (p, x), (_, y) in zip(la, lb)
            if not np.array_equal(np.asarray(x), np.asarray(y))]


def _timed_run(sim, n_cycles, interval):
    t0 = time.perf_counter()
    stats = sim.run(n_cycles, interval=interval, read_ratio=READ_RATIO)
    return stats, time.perf_counter() - t0


def phase_scalar(n_cycles: int = DEPLOY_CYCLES,
                 deployments=DEPLOYMENTS) -> None:
    """Deployment-size scalar runs on the default device, each compared
    leaf for leaf with the same run on the host CPU device."""
    platform = jax.devices()[0].platform
    cpu = jax.devices("cpu")[0]
    bad = []
    for label, kwargs, intervals in deployments:
        sim = Simulator(**kwargs)
        for iv in intervals:
            got, first_s = _timed_run(sim, n_cycles, iv)
            _, warm_s = _timed_run(sim, n_cycles, iv)
            with jax.default_device(cpu):
                ref, cpu_s = _timed_run(sim, n_cycles, iv)
            diff = stats_diff(got, ref)
            print("  scalar " + json.dumps({
                "case": label, "interval": iv, "cycles": n_cycles,
                "device": platform, "first_call_s": round(first_s, 3),
                "warm_s": round(warm_s, 3),
                "scan_steps": int(got.scan_steps),
                "skipped_cycles": int(got.skipped_cycles),
                "reads_done": int(got.reads_done),
                "writes_done": int(got.writes_done),
                "cpu_reference_s": round(cpu_s, 3),
                "equal_to_cpu": not diff}), flush=True)
            if diff:
                bad.append((label, iv, diff[:8]))
    if bad:
        raise SmokeError(f"Stats differ from the CPU run: {bad}")


def _sweep_line(tag, res) -> str:
    m = res.meta
    return f"  sweep {tag} " + json.dumps({
        "points": m["n_points"], "groups": m["n_groups"],
        "devices": m["n_devices"], "padded_points": m["padded_points"],
        "wall_s": m["wall_s"], "run_cache_first_call_s":
            m["cache"]["first_call_s"]})


def _column_diff(a, b, columns) -> list:
    bad = [k for k in columns
           if not np.array_equal(getattr(a, k), getattr(b, k),
                                 equal_nan=np.issubdtype(
                                     np.asarray(getattr(a, k)).dtype,
                                     np.floating))]
    if any(not np.array_equal(x, y)
           for x, y in zip(a.cmd_counts, b.cmd_counts)):
        bad.append("cmd_counts")
    return bad


def phase_sweep(spec: SweepSpec | None = None) -> None:
    """The DSE sweep on the default devices against the same sweep on the
    host CPU device: every integer column must be equal."""
    spec = sweep_spec() if spec is None else spec
    res = execute(spec)
    print(_sweep_line(jax.devices()[0].platform, res), flush=True)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ref = execute(spec, devices=[cpu])
    print(_sweep_line("cpu-reference", ref), flush=True)
    bad = _column_diff(res, ref, INT_COLUMNS)
    if bad:
        raise SmokeError(f"sweep columns differ from the CPU run: {bad}")


def phase_cli(n_cycles: int = 20_000) -> None:
    """The trace and telemetry command lines, in this process."""
    from repro.telemetry.__main__ import main as telemetry_main
    from repro.trace.__main__ import main as trace_main
    common = ["--standard", "HBM3", "--channels", "2",
              "--cycles", str(n_cycles)]
    rc = trace_main(common + ["--fail-on-violations"])
    if rc:
        raise SmokeError(f"repro.trace found audit violations (rc={rc})")
    rc = telemetry_main(common + ["--window", "256", "--check"])
    if rc:
        raise SmokeError(f"repro.telemetry --check failed (rc={rc})")


def phase_sweep_sharded(spec: SweepSpec | None = None, n_devices: int = 4):
    """The sweep with its batch sharded over ``n_devices`` devices against
    the same sweep on the first device: every column must be equal."""
    spec = sweep_spec() if spec is None else spec
    devs = jax.devices()[:n_devices]
    full = execute(spec, devices=devs)
    print(_sweep_line(f"{len(devs)}-devices", full), flush=True)
    one = execute(spec, devices=devs[:1])
    print(_sweep_line("1-device", one), flush=True)
    bad = _column_diff(full, one, type(full)._COLUMNS)
    if bad:
        raise SmokeError(f"sharded sweep differs from one device: {bad}")


def phase_channels_sharded(n_cycles: int = DEPLOY_CYCLES,
                           deployments=DEPLOYMENTS[:2],
                           shard: int = 4) -> None:
    """Channel-sharded scalar runs against the unsharded runs, then the
    two multi-channel golden streams sharded two ways."""
    bad = []
    for label, kwargs, intervals in deployments:
        sharded = Simulator(**kwargs, channel_shard=shard)
        plain = Simulator(**kwargs, channel_shard=False)
        for iv in intervals:
            got, first_s = _timed_run(sharded, n_cycles, iv)
            _, warm_s = _timed_run(sharded, n_cycles, iv)
            ref, ref_first_s = _timed_run(plain, n_cycles, iv)
            _, ref_warm_s = _timed_run(plain, n_cycles, iv)
            diff = stats_diff(got, ref)
            print("  channels " + json.dumps({
                "case": label, "interval": iv, "cycles": n_cycles,
                "shard": shard, "device": jax.devices()[0].platform,
                "first_call_s": round(first_s, 3),
                "warm_s": round(warm_s, 3),
                "unsharded_first_call_s": round(ref_first_s, 3),
                "unsharded_warm_s": round(ref_warm_s, 3),
                "scan_steps": int(got.scan_steps),
                "equal_to_unsharded": not diff}), flush=True)
            if diff:
                bad.append((label, iv, diff[:8]))
    if bad:
        raise SmokeError(f"channel-sharded Stats differ: {bad}")
    phase_golden(names=("DDR4@2ch", "DDR5x2+DDR4x2@80"), channel_shard=2)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="chip_smoke.py",
        description="Bring-up check of the simulator on a TPU.")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip path (batch-sharded "
                         "sweep, channel-sharded runs) on four chips")
    args = ap.parse_args(argv)
    dev = device_info(4 if args.four_chips else 1)
    print(f"compile cache: {jax.config.jax_compilation_cache_dir}",
          flush=True)
    if args.four_chips:
        phases = (("sweep-sharded", phase_sweep_sharded),
                  ("channels-sharded", phase_channels_sharded))
    else:
        phases = (("golden", phase_golden), ("scalar", phase_scalar),
                  ("sweep", phase_sweep), ("cli", phase_cli))
    t_all = time.perf_counter()
    for name, fn in phases:
        t0 = time.perf_counter()
        fn()
        print(f"phase {name}: ok in {time.perf_counter() - t0:.3f} s",
              flush=True)
    print(f"all phases: {time.perf_counter() - t_all:.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    from repro.core.engine import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
