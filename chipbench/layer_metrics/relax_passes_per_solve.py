"""Device solve: relax passes the sliced-ELL ``while_loop`` ran per
solve, over the window (the program's observation
``ops.ell.relax_passes``: its sum over its count). 1 where the warm seed
was already the fixed point, the vantage's hop eccentricity where a row
restarted from the cold init. Nothing from a program that does not
observe it."""


def read(record):
    solves = record.counter("ops.ell.relax_passes.count")
    if "ops.ell.relax_passes.sum" not in record.counters or not solves:
        return None
    return record.counter("ops.ell.relax_passes.sum") / solves
