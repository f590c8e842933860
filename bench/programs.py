"""What the benchmark hands the program, built from a configuration file.

The configuration file states the controller and frontend settings and
the published organisation and timing numbers.  The program is given the
settings explicitly, and :func:`config_mismatches` checks that the preset
the program compiles holds exactly the published numbers that the plain
reference simulates.
"""
from __future__ import annotations


def controller_config(config: dict):
    from repro.core import ControllerConfig
    c = config["controller"]
    return ControllerConfig(
        scheduler=c["scheduler"], queue_depth=int(c["queue_depth"]),
        refresh_urgent_margin=int(c["refresh_urgent_margin"]),
        refresh_stagger=bool(c["refresh_stagger"]))


def frontend_config(config: dict):
    from repro.core.frontend import FrontendConfig
    f = config["frontend"]
    return FrontendConfig(
        mapper=f["mapper"], probe_gap=int(f["probe_gap"]),
        max_backlog_fp=256 * int(f["max_backlog"]))


def timing_overrides(config: dict) -> dict | None:
    """The published timing numbers in which the configuration departs
    from the program's preset, passed to the program as overrides."""
    ov = config.get("timing_overrides")
    return {k: int(v) for k, v in ov.items()} if ov else None


def config_mismatches(config: dict) -> list:
    """Names of the published numbers that the program's compiled preset,
    with the configuration's overrides, does not hold (an empty list when
    it runs the configuration)."""
    from repro.core import compile_spec
    cs = compile_spec(config["standard"], config["org_preset"],
                      config["timing_preset"], timing_overrides(config),
                      channels=int(config["channels"]))
    bad = [k for k, v in config["timings"].items()
           if int(cs.timings.get(k, -1)) != int(v)]
    org = config["organization"]
    levels = list(cs.levels[1:])
    counts = {lv: int(cs.level_counts[i + 1]) for i, lv in enumerate(levels)}
    bad += [k for k in org["levels"] if counts.get(k) != int(org["levels"][k])]
    if list(org["levels"]) != levels:
        bad.append("levels")
    bad += [k for k in ("rows", "columns")
            if int(getattr(cs, k)) != int(org[k])]
    if int(cs.n_channels) != int(config["channels"]):
        bad.append("channels")
    return bad
