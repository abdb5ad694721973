"""KSP2 engine: per rebuild window, the time its ``ops.ksp2_all_pairs``
spans took (dispatch to readback of the one fused program: the
all-pairs fixed point, the view and the endpoint rows); median over
the windows that have one. Nothing from a
program that has no such span."""
from chipbench import spantree, stats


def read(record):
    sums = spantree.per_trace(
        record, "ops.ksp2_all_pairs", lambda span, _: span.dur_ms)
    return stats.median(sums) if sums else None
