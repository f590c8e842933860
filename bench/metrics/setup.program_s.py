"""Seconds to look up and compile (or load from the persistent cache) the
cell's one program, with its light-load set-up call (host clock)."""


def read(run):
    return run.setup.get("program_s")
