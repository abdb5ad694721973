"""Debounce: how late the event loop fired the window's timer, with
whatever the window's own work outlasted the deadline by taken out
(``timer_late_ms`` of ``decision.debounce``: fire - the later of the
deadline and the end of the last callback before it); median over the
windows. Nothing from a program that does not say."""
from chipbench import spantail, stats


def read(record):
    late = spantail.window_terms(record, "timer_late_ms")
    return stats.median(late) if late else None
