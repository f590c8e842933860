"""Share of the device-busy time in which a collective operation
(all-reduce, all-gather, ...) runs, per chip, averaged over the chips,
from the profiler trace."""


def read(run):
    return None if run.trace is None else run.trace.get("collective_share")
