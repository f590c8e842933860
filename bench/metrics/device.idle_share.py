"""Share of the traced window in which no operation ran on the device
(1 - union of device-operation intervals / window, averaged over the
chips), from the profiler trace."""


def read(run):
    return None if run.trace is None else run.trace.get("idle_share")
