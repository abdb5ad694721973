"""The program's spans as a tree, for the readers that need more than a
span's own duration.

``RunRecord.spans`` is flat: every span of every trace retired in the
window, with its trace id, its start on the wall clock and its
duration, and no depth. Spans of one trace that open and close on one
thread nest by time, so the tree is read off the intervals: what starts
inside a span of the same trace is nested in it.

A layer's self time is its span's duration minus the part of that
interval the spans nested in it cover. The covered part is a union, so
a grandchild inside a child is not subtracted twice.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from chipbench import stats
from chipbench.xplane import clip, merge


def by_trace(record) -> Dict[int, list]:
    out: Dict[int, list] = {}
    for s in record.spans:
        out.setdefault(s.trace_id, []).append(s)
    return out


def self_ms(span, same_trace) -> float:
    """``span``'s duration minus what the spans of ``same_trace`` that
    start inside it cover of it."""
    window = (span.ts_ms, span.ts_ms + span.dur_ms)
    nested = merge(
        (s.ts_ms, s.ts_ms + s.dur_ms)
        for s in same_trace
        if s is not span and window[0] <= s.ts_ms < window[1]
    )
    return span.dur_ms - sum(clip(iv, window) for iv in nested)


def per_trace(record, name: str, value) -> List[float]:
    """One number per trace that has a span ``name``: the sum of
    ``value(span, spans_of_its_trace)`` over the trace's spans of that
    name (a rebuild that went down the ladder opens a span again)."""
    out = []
    for spans in by_trace(record).values():
        own = [s for s in spans if s.name == name]
        if own:
            out.append(sum(value(s, spans) for s in own))
    return out


def median_self_ms(record, name: str) -> Optional[float]:
    values = per_trace(record, name, self_ms)
    return stats.median(values) if values else None
