"""Rebuild, host side: median of ``select_ms`` as the program's
``decision.ksp2_routes`` spans say it: the time inside
``_select_best_paths_ksp2`` over the prefixes the per-prefix pass
re-derived (two clock reads a call, summed; ``selected`` is how many),
the label-stack routes built from the engine's paths. The rest of
``ksp2_routes_ms`` is the reuse gate and best-route selection. Nothing
from a program whose span does not say."""
from chipbench import spanattr


def read(record):
    return spanattr.median(record, "decision.ksp2_routes", "select_ms")
