"""CPU rehearsal of ``chip_smoke.py``.

The script itself refuses to run without a TPU, which these tests check
first.  Its phase functions take their sizes as arguments, so the tests
call them directly at small sizes on the CPU backend: the comparisons
then hold between two CPU runs, which exercises every path of the script
except the chip itself.  The four-chip phases run in a child process on
four forced host devices.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_chip_smoke()


def _small_sweep():
    # 3 points per compile group: on 4 devices each group pads by one
    return cs.SweepSpec(systems=("DDR5",), intervals=(16.0, 4.0, 1.0),
                        read_ratios=(0.7,), channels=(1, 4), n_cycles=1000)


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_exits_nonzero_without_a_tpu(argv, capsys):
    with pytest.raises(SystemExit) as e:
        cs.main(argv)
    assert e.value.code not in (0, None)
    assert '"ok": true' not in capsys.readouterr().out


def test_golden_phase_matches_pinned_hashes():
    cs.phase_golden(names=("DDR4", "DDR4@2ch", "DDR5x2+DDR4x2@80"))


def test_golden_phase_raises_on_a_wrong_hash(tmp_path, monkeypatch):
    with open(cs.GOLDEN_PATH) as f:
        golden = json.load(f)
    golden["DDR4"]["sha256"] = "0" * 64
    path = tmp_path / "golden.json"
    path.write_text(json.dumps(golden))
    monkeypatch.setattr(cs, "GOLDEN_PATH", str(path))
    with pytest.raises(cs.SmokeError, match="DDR4"):
        cs.phase_golden(names=("DDR4",))


def test_scalar_phase_at_deployment_widths():
    cs.phase_scalar(n_cycles=1000)


def test_stats_diff_names_the_differing_leaf():
    from repro.core import Simulator
    sim = Simulator("DDR4", "DDR4_8Gb_x8", "DDR4_2400R")
    a = sim.run(500, interval=2.0)
    b = a._replace(reads_done=a.reads_done + 1)
    assert cs.stats_diff(a, a) == []
    assert cs.stats_diff(a, b) == [".reads_done"]


def test_sweep_phase():
    cs.phase_sweep(_small_sweep())


def test_cli_phase():
    cs.phase_cli(n_cycles=1000)


def test_four_chip_phases_on_forced_host_devices():
    code = f"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, "tests")
import test_chip_smoke as t
t.cs.phase_sweep_sharded(t._small_sweep())
t.cs.phase_channels_sharded(n_cycles=1000,
                            deployments=(t.cs.DEPLOYMENTS[1],))
print("FOUR-DEVICE-PHASES-OK")
"""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-4000:])
    assert "FOUR-DEVICE-PHASES-OK" in r.stdout
    assert '"padded_points": 2' in r.stdout
