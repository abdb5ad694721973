"""KSP2 engine: relax passes the warm all-pairs fixed point ran inside
the fused program of a sync (``passes`` of ``ops.ksp2_all_pairs``: the
``while_loop``'s own counter, carried out with the packed rows; the
pass that builds the init is not one of them): median over the window's
dispatches. 1 where the seed was already the fixed point; the graph's
diameter where a tight increased edge restarted rows. Nothing from a
program that does not carry the count out."""
from chipbench import spanattr


def read(record):
    return spanattr.median(record, "ops.ksp2_all_pairs", "passes")
