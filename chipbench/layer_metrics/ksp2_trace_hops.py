"""KSP2 engine: links on the longest path a trace enumerated (``hops``
of ``decision.ksp2_trace``, either rank): what the native tracer, the
priming of the kth-path cache and the label stacks of
``decision.ksp2_routes`` are handed, path by path. Median over the
window's traces. Nothing from a program that does not say."""
from chipbench import spanattr


def read(record):
    return spanattr.median(record, "decision.ksp2_trace", "hops")
