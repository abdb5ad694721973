"""Host-touch accounting for the committed-dispatch contract.

The committed-dispatch invariant (ROADMAP "kill the host overhead"):
one event window touches the device exactly twice — SUBMIT (every
program launch rides one stream push, back to back) and REAP (every
readback rides a ``copy_to_host_async`` staged at submit time, drained
in one read run). A host round trip anywhere between those two phases
serializes the device pipeline, which is precisely the 600x
e2e-vs-device gap BENCH_r05 measured.

This module is the ONE sanctioned crossing point. Event-path code
never calls ``jax.device_get`` / ``.block_until_ready`` directly (the
``committed-dispatch`` lint rule enforces that); it calls:

- ``count_dispatch()``   — a device program was launched,
- ``kick_async(arr)``    — stage a readback on the async lane (free:
  rides the dispatch stream, not a host touch),
- ``reap_read(arr, kicked=...)`` — materialize a readback on host.
  ``kicked=True`` means the transfer was staged earlier and the reap
  normally finds it landed (counted ``ops.async_reaps``);
  ``kicked=False`` is a genuine blocking device->host sync (counted
  ``ops.blocking_syncs``). Either way it is also where the profiler's
  sampled dispatches of the window get their device time
  (``EventWindow.marks``): telemetry adds no wait of its own.

``event_window(tag)`` brackets one event: consecutive dispatches
collapse into one submit phase and consecutive reads into one read
phase, so ``touches = submit_phases + read_phases`` is exactly the
number of times the host turned the device around. Per-window touches
feed the ``ops.host_touches`` histogram; the counters
``ops.host_dispatches`` / ``ops.blocking_syncs`` / ``ops.async_reaps``
accumulate globally (windowed or not). Re-entrant: an inner
``event_window`` joins the active one, so a coalesced churn window
spanning N folded events still reads as ONE submit + ONE reap.

``pipeline_drain(tag)`` brackets one pipelined BURST of event windows:
window N+1's submit overlaps window N's reap, so the unit of host cost
is the drain, not the window. Every ``event_window`` opened inside a
drain joins it (same re-entrancy), which is what makes the per-drain
touch histogram honest: the reap that window N+1 drains on window N's
behalf lands in ONE shared read phase instead of being double-counted
against both windows. Per-drain touches feed ``ops.touches_per_drain``
(+ the folded window count in ``ops.windows_per_drain``); the
pipelining itself is witnessed by ``note_pipelined_dispatch`` — called
at each submit that happens while a prior window's reap is still in
flight — and ``note_overlapped_reap`` at each reap drained inside a
successor's window.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, Iterator, List, Optional

import jax

from openr_tpu.telemetry import get_registry
from openr_tpu.telemetry.flight import get_flight_recorder
from openr_tpu.telemetry.profiler import get_profiler

_TLS = threading.local()


class EventWindow:
    """Phase accounting for one committed event window."""

    __slots__ = (
        "tag", "dispatches", "blocking_syncs", "async_reaps",
        "submit_phases", "read_phases", "_last",
        "t0", "device_ms", "stages", "windows", "drain", "marks",
    )

    def __init__(self, tag: str, drain: bool = False):
        self.tag = tag
        self.dispatches = 0
        self.blocking_syncs = 0
        self.async_reaps = 0
        self.submit_phases = 0
        self.read_phases = 0
        self._last: Optional[str] = None
        self.t0 = time.perf_counter()
        # device-time attribution (fed by attribute_stage): total
        # device ms inside this window + per-tag [calls, host, device]
        self.device_ms = 0.0
        self.stages: Dict[str, List[float]] = {}
        # logical event windows folded into this one (joins bump it);
        # drain=True marks a pipeline_drain bracket, whose retirement
        # feeds the per-drain histograms instead of only per-window
        self.windows = 1
        self.drain = drain
        # sampled dispatches whose device time is still open
        # (Profiler.on_dispatch): closed by this window's next
        # reap_read, dropped with the window
        self.marks: list = []

    def _mark(self, phase: str) -> None:
        if self._last != phase:
            if phase == "submit":
                self.submit_phases += 1
            else:
                self.read_phases += 1
            self._last = phase

    @property
    def touches(self) -> int:
        return self.submit_phases + self.read_phases


def current_window() -> Optional[EventWindow]:
    stack = getattr(_TLS, "stack", None)
    return stack[-1] if stack else None


def _retire(w: EventWindow) -> None:
    """Observe a popped window and hand it to the profiling plane.
    Runs OUTSIDE the window (stack already popped): ratio bookkeeping,
    flight record, trigger checks, and any deferred post-mortem dump
    are all safe here."""
    reg = get_registry()
    reg.observe("ops.host_touches", float(w.touches))
    reg.observe(f"ops.host_touches.{w.tag}", float(w.touches))
    if w.drain:
        reg.counter_bump("ops.pipeline_drains")
        reg.observe("ops.touches_per_drain", float(w.touches))
        reg.observe("ops.windows_per_drain", float(w.windows))
    wall_ms = (time.perf_counter() - w.t0) * 1000.0
    get_profiler().on_window(w.tag, wall_ms, w.device_ms)
    get_flight_recorder().on_window(w.tag, wall_ms, w)


@contextmanager
def event_window(tag: str = "event") -> Iterator[EventWindow]:
    """Bracket one committed event. Joins an already-active window
    (same thread) instead of nesting, so the OUTERMOST caller owns the
    per-event touch observation."""
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    if stack:
        stack[-1].windows += 1
        yield stack[-1]
        return
    w = EventWindow(tag)
    stack.append(w)
    try:
        yield w
    finally:
        stack.pop()
        _retire(w)


@contextmanager
def pipeline_drain(tag: str = "drain") -> Iterator[EventWindow]:
    """Bracket one pipelined burst of event windows. The drain opens a
    drain-flagged window on the same stack, so every ``event_window``
    inside it joins (the burst's overlapped submits and reaps merge
    into shared phases — no double-counting the reap window N+1 drains
    for window N). Retirement feeds ``ops.touches_per_drain`` and
    ``ops.windows_per_drain`` on top of the per-window histograms.
    Joining an already-active window degrades to that window (the
    outermost bracket owns the observation)."""
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    if stack:
        yield stack[-1]
        return
    w = EventWindow(tag, drain=True)
    w.windows = 0  # only joined event windows count toward the burst
    stack.append(w)
    try:
        yield w
    finally:
        stack.pop()
        _retire(w)


def note_window(n: int = 1) -> None:
    """Count ``n`` logical event windows folded into the active window
    or drain WITHOUT opening a join — for burst bodies that stage their
    windows inline (one submit run, one settle run) rather than through
    nested ``event_window`` brackets. No-op outside a window."""
    w = current_window()
    if w is not None:
        w.windows += n


def note_pipelined_dispatch(depth: int = 2) -> None:
    """Witness that a window's committed dispatch was submitted while
    a prior window's reap was still in flight (the acceptance-criterion
    signal for pipeline depth >= 2). ``depth`` is the number of windows
    concurrently in flight after this submit."""
    reg = get_registry()
    reg.counter_bump("ops.pipelined_dispatches")
    reg.observe("ops.pipeline_depth", float(depth))


def note_overlapped_reap() -> None:
    """Witness that a prior window's staged reap was drained inside a
    successor window's submit/solve span (the double-buffer overlap)."""
    get_registry().counter_bump("ops.overlapped_reaps")


def attribute_stage(tag: str, host_ms: float, device_ms: float) -> None:
    """Fold one profiled dispatch into the active window's device-time
    attribution (no-op outside a window). Called by the aot_cache for
    every timed call; keeps ``touches``-style accounting untouched."""
    w = current_window()
    if w is None:
        return
    w.device_ms += device_ms
    s = w.stages.get(tag)
    if s is None:
        w.stages[tag] = [1, host_ms, device_ms]
    else:
        s[0] += 1
        s[1] += host_ms
        s[2] += device_ms


def count_dispatch(n: int = 1) -> None:
    """Record n device program launches (one submit phase while
    consecutive)."""
    get_registry().counter_bump("ops.host_dispatches", n)
    w = current_window()
    if w is not None:
        w.dispatches += n
        w._mark("submit")


def kick_async(arr) -> None:
    """Stage a device->host transfer on the async readback lane.
    Not a host touch: the copy rides the device stream and lands while
    the host does other work. Host shim arrays pass through."""
    try:
        arr.copy_to_host_async()
    except AttributeError:
        pass


def reap_read(arr, kicked: bool = False):
    """Materialize one readback on host (the sanctioned
    ``jax.device_get`` crossing). ``kicked=True`` asserts the transfer
    was staged via ``kick_async`` earlier — an async reap, not a
    blocking sync."""
    reg = get_registry()
    w = current_window()
    if kicked:
        reg.counter_bump("ops.async_reaps")
        if w is not None:
            w.async_reaps += 1
    else:
        reg.counter_bump("ops.blocking_syncs")
        if w is not None:
            w.blocking_syncs += 1
    if w is not None:
        w._mark("read")
    out = jax.device_get(arr)
    if w is not None and w.marks:
        # the program turned the device around here anyway: the
        # profiler's samples ride this read and make none of their own
        get_profiler().close_marks(w.marks)
    return out
