"""Debounce: Decision's thread busy under the policy wait (``busy_ms`` of
``decision.debounce``: the event loop's busy time from the span's start
to the fire: the rest of the opening callback, with patch and stage, and
every callback that ran inside the window); median over the windows.
It reaches a sample only where it passes the wait: ``wait_overrun_share``."""
from chipbench import spantail, stats


def read(record):
    busy = spantail.window_terms(record, "busy_ms")
    return stats.median(busy) if busy else None
