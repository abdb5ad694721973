"""What the host stages that outlast the policy wait spend, for the
readers of the spans and attributes the program puts inside them
(PR 51): the ELL patch under ``decision.prewarm``, the dispatch inside
``ops.ell_reconverge``, the phases of ``decision.ksp2_sync``, and the
wait of ``ops.solve_readback`` against the device's own timeline.

Every reader here gives ``None`` on a record whose program has no such
span or attribute, as the parent of PR 51 has not.

``window_ms`` is the per-window sum of a span, median over the windows
that have one (``prewarm_ms``'s rule); ``window_attr`` the same of an
attribute. ``tail_excess_ms`` takes ``spantail``'s ranking (the traces
that reached ``fib.program``, by their own extent, slowest tenth) and
gives, of a per-trace value that is 0 for a trace without the span, the
decile's median minus the median over all of them: which phase of the
KSP2 sync the slow windows spend more in than the others do.
``readback_after_device_ms`` reads the profiler's clock alone.
"""

from __future__ import annotations

import bisect
from typing import Callable, Optional

from chipbench import spantail, spantree, stats
from chipbench.xplane import short_module

RECONVERGE_MODULE = "jit__ell_reconverge"
READBACK = "ops.solve_readback"


def dur_ms(span, _same_trace) -> float:
    return span.dur_ms


def window_ms(record, name: str,
              value: Callable = dur_ms) -> Optional[float]:
    """Median over the windows (traces) that have a span ``name`` of the
    sum of ``value(span, spans of its trace)`` over them."""
    sums = spantree.per_trace(record, name, value)
    return stats.median(sums) if sums else None


def window_attr(record, name: str, attr: str) -> Optional[float]:
    """Median over the windows of the sum of attribute ``attr`` over
    their spans ``name`` that carry it; ``None`` where none does."""
    sums = []
    for spans in spantree.by_trace(record).values():
        said = [s.attrs[attr] for s in spans
                if s.name == name and attr in s.attrs]
        if said:
            sums.append(sum(said))
    return stats.median(sums) if sums else None


def tail_excess_ms(record, name: str,
                   value: Callable = dur_ms) -> Optional[float]:
    """The slowest decile's median of the per-trace sum of ``value``
    over the spans ``name`` (0 for a trace without one) minus the same
    median over all the ranked traces. ``None`` under 200 traces
    (``stats``' rule for a 95th percentile) and from a program none of
    whose traces has the span."""
    rows = []
    for spans in spantree.by_trace(record).values():
        stages = spantail._stages(spans)
        if stages is None:
            continue
        own = [s for s in spans if s.name == name]
        rows.append((stages["extent"],
                     sum(value(s, spans) for s in own), bool(own)))
    if len(rows) < stats.needed(0.95) or not any(r[2] for r in rows):
        return None
    rows.sort(key=lambda row: row[0])
    slow = rows[-(len(rows) // 10):]
    return (stats.median([r[1] for r in slow])
            - stats.median([r[1] for r in rows]))


def readback_after_device_ms(record) -> Optional[float]:
    """Per ``ops.solve_readback`` event of the traced tail's host plane:
    the part of it that lies after the device finished the solve it
    waits for, its end minus the end of the last ``jit__ell_reconverge``
    launch of device 0 that began before that end, floored at 0 and no
    more than the event itself (a solve that was done before the host
    asked leaves the whole wait to the transfer); median. Both ends are
    on the profiler's clock, so the mapping of the program's wall-clock
    spans onto it (``span_clock_skew_us``) does not enter."""
    dev = record.device
    if dev is None or not dev.modules:
        return None
    launches = sorted(
        (start, end) for name, start, end in dev.modules[0]
        if short_module(name) == RECONVERGE_MODULE
    )
    starts = [start for start, _ in launches]
    after = []
    for name, start, end in dev.host:
        if name != READBACK or not dev.steady[0] <= start < dev.steady[1]:
            continue
        i = bisect.bisect_left(starts, end) - 1
        if i < 0:
            continue
        after.append(min(max(0.0, end - launches[i][1]), end - start) / 1e6)
    return stats.median(after) if after else None
