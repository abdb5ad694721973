"""Debounce: per rebuild window, the time its ``decision.speculate``
spans took (the root's view solved on Decision's thread inside
``decision.debounce``, after the patch and ahead of the timer: view
sync, dispatch and readback lie inside it); median over the windows
that have one. With ``prewarm_ms`` it is how much of the policy wait
the two overlaps use."""
from chipbench import spantree, stats


def read(record):
    sums = spantree.per_trace(
        record, "decision.speculate", lambda span, _: span.dur_ms)
    return stats.median(sums) if sums else None
