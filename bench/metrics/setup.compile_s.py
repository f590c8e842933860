"""Seconds of XLA compile, or of the load from JAX's persistent
compilation cache, in the set-up of the cell's timed program:
``compile_s`` of the program's own records of its programs' first calls
(``RunCache.first_calls()``), summed over those of the timed length
(``n_cycles``), which compile in the light-load set-up call; the traced
program, of another length, is left out.  Nothing where the program
keeps no such records."""


def read(run):
    from repro.core import engine as E
    first_calls = getattr(E.RUN_CACHE, "first_calls", None)
    if first_calls is None:
        return None
    n = run.config.get("n_cycles")
    timed = [r for r in first_calls() if r["n_cycles"] == n]
    return sum(r["compile_s"] for r in timed) if timed else None
