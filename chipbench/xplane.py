"""From the profiler's ``.xplane.pb`` to numbers.

The file is read with ``jax.profiler.ProfileData`` and nothing else. A
TPU's plane is named ``/device:TPU:<n>``; its line ``XLA Ops`` holds
one event per executed HLO operation and its line ``XLA Modules`` one
per executed program. Host threads are lines of ``/host:CPU``; the
benchmark's own markers (``chipbench.steady`` and, after it in a cell
that has a closing probe, ``chipbench.probe``) and
the program's ``TraceAnnotation`` tags are events there. Every time is
in nanoseconds on the trace's own clock.

- busy: the union of the intervals in which an operation ran, per
  device, averaged over devices;
- idle gaps: the complement inside the traced window, each gap charged
  to the host interval that covers most of it;
- module time: the summed duration of a program's events, by name.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEADY = "chipbench.steady"
PROBE = "chipbench.probe"
WAITING = "waiting for an event"
AFTER_WINDOW = "closing probe, after the counted window"
BETWEEN_OPS = "between operations of one program"
TINY_GAP_NS = 20e3

Interval = Tuple[float, float]  # start, end (ns)
Named = Tuple[str, float, float]  # name, start, end (ns)


@dataclass
class DeviceTrace:
    """A traced window, reduced."""

    # the traced window: the steady part and, in a cell whose traffic
    # never reaches the device, the closing probe after it
    window: Interval = (0.0, 0.0)
    # the traced part of the counted window: what the cell's traffic did
    steady: Interval = (0.0, 0.0)
    # per device: merged busy intervals and every operation
    busy: List[List[Interval]] = field(default_factory=list)
    ops: List[List[Named]] = field(default_factory=list)
    modules: List[List[Named]] = field(default_factory=list)
    host: List[Named] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self, within: Optional[Interval] = None) -> float:
        """Seconds an operation ran, averaged over the devices."""
        within = within or self.window
        if not self.busy:
            return 0.0
        per_device = [
            sum(clip(iv, within) for iv in merged) for merged in self.busy
        ]
        return sum(per_device) / len(per_device) / 1e9

    def module_seconds(self, within: Optional[Interval] = None
                       ) -> Dict[str, Tuple[float, int]]:
        """Program name -> (seconds, executions), summed over devices."""
        within = within or self.window
        out: Dict[str, Tuple[float, int]] = {}
        for dev in self.modules:
            for name, start, end in dev:
                if within[0] <= start < within[1]:
                    s, n = out.get(name, (0.0, 0))
                    out[name] = (s + (end - start) / 1e9, n + 1)
        return out

    def top_ops(self, limit: int = 10) -> List[List]:
        """The operations that took most time, by self time: a ``while``
        holds its body's operations, whose time is theirs, not its. An
        operation of the closing probe is named as such."""
        total: Dict[str, float] = {}
        for ops, modules in zip(self.ops, self.modules):
            starts = [m[1] for m in modules]
            stack: List[List] = []  # [name, end, self_ns]

            def close(upto: float) -> None:
                while stack and stack[-1][1] <= upto:
                    name, _end, own = stack.pop()
                    total[name] = total.get(name, 0.0) + own / 1e9

            for name, start, end in sorted(ops, key=lambda o: (o[1], -o[2])):
                close(start)
                if stack:
                    stack[-1][2] -= end - start
                i = bisect.bisect_right(starts, start) - 1
                module = modules[i][0] if i >= 0 else "?"
                label = short_module(module) + "/" + short_op(name)
                if start >= self.steady[1]:
                    label = "probe: " + label
                stack.append([label, end, end - start])
            close(float("inf"))
        rows = sorted(total.items(), key=lambda kv: -kv[1])[:limit]
        return [[name, secs] for name, secs in rows]

    def idle_gaps(self, spans: Iterable[Named] = (),
                  limit: int = 10) -> List[List]:
        """Idle seconds of device 0 by what the host was doing.

        Each gap between two operations is cut at the edges of the
        program's ``spans`` (already on this trace's clock) and every
        piece goes to the innermost span that covers it. A piece no span
        covers goes to the host event of the trace that covers most of
        it, or to ``WAITING`` when none covers half. Gaps under 20 us
        are the device's own, between two operations. What lies after
        the steady part is the closing probe's, whatever the host did."""
        merged = self.busy[0] if self.busy else []
        gaps: List[Interval] = []
        cursor = self.window[0]
        for start, end in merged:
            if start > cursor:
                gaps.append((cursor, min(start, self.window[1])))
            cursor = max(cursor, end)
        if cursor < self.window[1]:
            gaps.append((cursor, self.window[1]))
        spans = sorted(spans, key=lambda h: h[1])
        host = sorted(
            (h for h in self.host if not h[0].startswith("chipbench.")),
            key=lambda h: h[1],
        )
        span_starts = [h[1] for h in spans]
        host_starts = [h[1] for h in host]
        # no interval of either list is longer than this, so a search
        # may start that far before the gap
        reach_spans = max((h[2] - h[1] for h in spans), default=0.0)
        reach_host = max((h[2] - h[1] for h in host), default=0.0)
        total: Dict[str, float] = {}

        def add(name: str, ns: float) -> None:
            if ns > 0:
                total[name] = total.get(name, 0.0) + ns / 1e9

        def overlapping(rows, starts, reach, piece):
            i = bisect.bisect_left(starts, piece[0] - reach)
            while i < len(rows) and rows[i][1] < piece[1]:
                if rows[i][2] > piece[0]:
                    yield rows[i]
                i += 1

        for gap in gaps:
            if gap[1] > self.steady[1]:
                add(AFTER_WINDOW, gap[1] - max(gap[0], self.steady[1]))
                gap = (gap[0], self.steady[1])
                if gap[1] <= gap[0]:
                    continue
            if gap[1] - gap[0] < TINY_GAP_NS:
                add(BETWEEN_OPS, gap[1] - gap[0])
                continue
            inside = list(overlapping(spans, span_starts, reach_spans, gap))
            edges = sorted(
                {gap[0], gap[1]}
                | {min(max(t, gap[0]), gap[1])
                   for _, s, e in inside for t in (s, e)}
            )
            for lo, hi in zip(edges, edges[1:]):
                covering = [h for h in inside if h[1] <= lo and h[2] >= hi]
                if covering:
                    add(min(covering, key=lambda h: h[2] - h[1])[0], hi - lo)
                    continue
                best, cover = WAITING, 0.0
                for h in overlapping(host, host_starts, reach_host, (lo, hi)):
                    c = clip((h[1], h[2]), (lo, hi))
                    if c > cover:
                        best, cover = h[0], c
                add(best if cover >= 0.5 * (hi - lo) else WAITING, hi - lo)
        rows = sorted(total.items(), key=lambda kv: -kv[1])[:limit]
        return [[name, secs] for name, secs in rows]


def short_module(name: str) -> str:
    """``jit__ell_reconverge(1088676743463897092)`` -> ``jit__ell_reconverge``"""
    return name.split("(", 1)[0]


def short_op(text: str) -> str:
    """The trace names an operation by its whole HLO line; keep its name
    and result shape: ``%fusion.40 pred[122880]``."""
    lhs, _, rhs = text.partition(" = ")
    shape = re.match(r"[a-z0-9]+\[[0-9,]*\]", rhs)
    return lhs + (" " + shape.group(0) if shape else "")


def clip(iv: Interval, within: Interval) -> float:
    return max(0.0, min(iv[1], within[1]) - max(iv[0], within[0]))


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def reduce(path: str, device_plane: str = DEVICE_PLANE) -> DeviceTrace:
    """Read one ``.xplane.pb``. A trace with no ``chipbench.steady``
    marker is taken whole."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = DeviceTrace()
    first, last = None, None
    for plane in data.planes:
        if plane.name.startswith(device_plane):
            ops: List[Named] = []
            modules: List[Named] = []
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                rows = ops if line.name == OPS_LINE else modules
                for ev in line.events:
                    rows.append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    )
            out.ops.append(ops)
            out.modules.append(modules)
            out.busy.append(merge((s, e) for _, s, e in ops))
            for _, s, e in ops:
                first = s if first is None else min(first, s)
                last = e if last is None else max(last, e)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        out.host.append(
                            (ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns)
                        )
    marks = {n: (s, e) for n, s, e in out.host if n in (STEADY, PROBE)}
    if STEADY in marks:
        out.steady = marks[STEADY]
        end = marks[PROBE][1] if PROBE in marks else marks[STEADY][1]
        out.window = (marks[STEADY][0], end)
    else:
        out.window = out.steady = (first or 0.0, last or 0.0)
    return out
