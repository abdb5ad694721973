"""Debounce: per rebuild window, the time its ``decision.prewarm`` spans
took (the ELL patch on Decision's thread, inside ``decision.debounce``,
ahead of the timer); median over the windows that have one."""
from chipbench import spantree, stats


def read(record):
    sums = spantree.per_trace(
        record, "decision.prewarm", lambda span, _: span.dur_ms)
    return stats.median(sums) if sums else None
