"""Share of simulated cycles that the cycle driver's fast-forward skipped:
sum of ``skipped_cycles`` over sum of ``cycles``, over every design point
of every call of the run (exact counts from the program's results)."""


def read(run):
    cycles = sum(int(p["cycles"]) for c in run.calls for p in c.points)
    skipped = sum(int(p["skipped_cycles"]) for c in run.calls
                  for p in c.points)
    return skipped / cycles if cycles else None
