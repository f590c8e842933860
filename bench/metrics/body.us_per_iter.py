"""Device-busy microseconds per iteration of the cycle driver's loop, in
the traced calls: busy time from the profiler trace (averaged over the
chips) over the loop iterations executed.  A batched loop runs as many
iterations as its busiest point steps (``max scan_steps``)."""


def read(run):
    if run.trace is None or run.trace.get("busy_s") is None:
        return None
    iters = sum(max(int(p["scan_steps"]) for p in c.points)
                for c in run.traced_calls)
    return run.trace["busy_s"] / iters * 1e6 if iters else None
