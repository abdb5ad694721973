"""Tracing: how far the wall-clock mapping of the program's spans onto
the profiler's clock is off, in microseconds.

The program's scoped spans are also ``TraceAnnotation`` events on the
trace's host plane, on the trace's own clock. For each
``decision.route_build`` span of the steady part: its start mapped as
``run.py:_program_spans`` maps every span (one ``time.time()`` read
taken beside the steady marker), against the start of the nearest host
event of that name; the median of the absolute differences. It bounds
what the idle-gap attribution can misplace."""
import bisect

from chipbench import stats

NAME = "decision.route_build"


def read(record):
    dev = record.device
    if dev is None:
        return None
    on_trace = sorted(start for name, start, _ in dev.host if name == NAME)
    if not on_trace:
        return None
    wall0 = record.steady_wall_s
    skews = []
    for s in record.spans:
        if s.name != NAME:
            continue
        mapped = dev.steady[0] + (s.ts_ms / 1e3 - wall0) * 1e9
        if not dev.steady[0] <= mapped < dev.steady[1]:
            continue
        i = bisect.bisect_left(on_trace, mapped)
        near = on_trace[max(0, i - 1):i + 1]
        skews.append(min(abs(t - mapped) for t in near) / 1e3)
    return stats.median(skews) if skews else None
