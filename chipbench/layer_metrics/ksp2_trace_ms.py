"""KSP2 engine: per rebuild window, the time its ``decision.ksp2_trace``
spans took (the native or Python tracer enumerating link-disjoint paths
off distance rows, first and second rank); median over the windows that
have one. Nothing from a program that has no such span."""
from chipbench import spantree, stats


def read(record):
    sums = spantree.per_trace(
        record, "decision.ksp2_trace", lambda span, _: span.dur_ms)
    return stats.median(sums) if sums else None
