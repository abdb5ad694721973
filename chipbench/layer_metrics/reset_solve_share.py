"""Device solve: the share of the window's warm sliced-ELL solves in
which ``_warm_seed`` restarted at least one batch row from the cold
init (``decision.ell_reset_solves`` over ``decision.ell_warm_solves``),
in percent: the solves that pay the graph's diameter in passes. Nothing
from a program that does not count them."""


def read(record):
    warm = record.counter("decision.ell_warm_solves")
    if "decision.ell_reset_solves" not in record.counters or not warm:
        return None
    return 100.0 * record.counter("decision.ell_reset_solves") / warm
