"""Structured end-to-end traces over the PerfEvents chain.

A ``Trace`` is born when KvStore accepts a key-set that produces a
publication, rides the in-process ``Publication`` /
``DecisionRouteUpdate`` objects through Decision's debounce and solve,
and is ``finish()``-ed by Fib after route programming. Each stage
contributes a timed ``Span``; spans may nest (the ELL warm/cold solve
span sits inside Decision's rebuild span).

Design points:

- Only *completed* traces enter the tracer's bounded ring. An
  in-flight trace lives solely on the carrying queue object, so a
  publication that Decision drops (no route impact) costs nothing and
  cannot leak.
- Deep call sites (``ops.spf_sparse``) must not know about queue
  plumbing: the tracer keeps a per-thread *active trace* stack
  (``activate()``), and ``span_active()`` attaches to whatever trace
  the enclosing module activated — a no-op when none is.
- A stage that opens and closes on one thread takes the scoped form,
  ``with tracer.span(name):``. While a ``jax.profiler`` session is
  collecting, such a span is also a ``TraceAnnotation`` on the
  session's host plane, on the profiler's own clock, beside the XLA
  launches it caused. The non-lexical stages (``decision.debounce``,
  ``decision.rebuild``, ``fib.program``: opened in one function and
  closed in another, or on another thread) keep ``begin_span`` /
  ``end_span`` and carry no annotation.
- A hand-off between threads is never an open span: the receiving
  side records it, closed, with ``Trace.gap_span()`` (from the end of
  the trace's last span to now).
- A stretch that is only known once it is over is recorded after
  the fact, closed, with ``Trace.closed_span()``: the idle end of a
  debounce window (``decision.policy_idle``, from the event loop's own
  account), and a full garbage collection that stopped the process
  while the trace was in flight (``process.gc_pause``, which
  ``finish()`` adds from the pauses the collector's hook handed to
  ``note_pause()``).
- ``finish()`` validates that every span is closed and properly
  nested; violations bump ``telemetry.traces_unclosed_spans`` /
  ``telemetry.traces_bad_nesting`` instead of raising, and the trace
  is kept (marked) so the smoke gate can fail loudly.
- Export: Chrome-trace JSON (``chrome://tracing`` / Perfetto, ``ph:X``
  complete events, µs) or JSONL (one trace per line).
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Any, ContextManager, Dict, List, Optional, Tuple

from openr_tpu.analysis.annotations import thread_confined
from openr_tpu.telemetry.registry import get_registry

_trace_ids = itertools.count(1)
# full collections remembered for the traces still in flight: at the
# cells' rates one arrives every few seconds and a trace lives ~20 ms
_PAUSE_RING = 16


class Span:
    """One timed stage of a trace. ``dur_ms`` is perf_counter-based;
    ``ts_ms`` anchors the span on the wall clock for export."""

    __slots__ = ("name", "ts_ms", "dur_ms", "attrs", "_t0", "depth")

    def __init__(
        self,
        name: str,
        depth: int = 0,
        start: Optional[Tuple[float, float]] = None,
    ) -> None:
        """``start`` is a ``(ts_ms, perf_counter)`` pair read earlier
        (``end_mark``); without it the span starts now."""
        self.name = name
        if start is None:
            start = (time.time() * 1000.0, time.perf_counter())
        self.ts_ms, self._t0 = start
        self.dur_ms: Optional[float] = None
        self.attrs: Dict[str, Any] = {}
        self.depth = depth

    @property
    def closed(self) -> bool:
        return self.dur_ms is not None

    def end(self, **attrs: Any) -> "Span":
        if self.dur_ms is None:
            self.dur_ms = (time.perf_counter() - self._t0) * 1000.0
        self.attrs.update(attrs)
        return self

    def end_mark(self) -> Tuple[float, float]:
        """Where this (closed) span ended, on both clocks."""
        return (
            self.ts_ms + self.dur_ms,
            self._t0 + self.dur_ms / 1000.0,
        )

    def mark_at(self, perf_counter: float) -> Tuple[float, float]:
        """The instant ``perf_counter`` on both clocks, by this span's
        own start: for what was timed on the one clock alone (an event
        loop's account) and belongs inside this span."""
        return (
            self.ts_ms + (perf_counter - self._t0) * 1000.0,
            perf_counter,
        )

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "ts_ms": round(self.ts_ms, 3),
            "dur_ms": round(self.dur_ms, 4) if self.closed else None,
            "depth": self.depth,
            "attrs": self.attrs,
        }


@thread_confined("owner", "spans", "_stack", "_last_end", "complete")
class Trace:
    """An ordered list of spans sharing one trace id. Not thread-safe
    by itself — a trace is owned by exactly one module thread at a
    time (it travels through the queues with the payload); the
    ``"owner"`` confinement above states exactly that hand-off
    discipline for the shared-state rule."""

    __slots__ = (
        "trace_id", "origin", "ts_ms", "spans", "_stack", "_last_end",
        "complete",
    )

    def __init__(self, origin: str = "kvstore.publish") -> None:
        self.trace_id = next(_trace_ids)
        self.origin = origin
        self.ts_ms = time.time() * 1000.0
        self.spans: List[Span] = []
        self._stack: List[Span] = []
        # where the span that closed last ended (Span.end_mark)
        self._last_end: Optional[Tuple[float, float]] = None
        self.complete = False

    def begin_span(self, name: str, **attrs: Any) -> Span:
        span = Span(name, depth=len(self._stack))
        span.attrs.update(attrs)
        self.spans.append(span)
        self._stack.append(span)
        return span

    def end_span(self, span: Span, **attrs: Any) -> Span:
        span.end(**attrs)
        self._last_end = span.end_mark()
        # pop through the stack to this span; anything above it left
        # open is a nesting bug the finish() validator will count
        while self._stack:
            top = self._stack.pop()
            if top is span:
                break
        return span

    def instant(self, name: str, **attrs: Any) -> Span:
        """A zero-duration marker (e.g. the publication itself)."""
        span = Span(name, depth=len(self._stack))
        span.attrs.update(attrs)
        span.dur_ms = 0.0
        self._last_end = span.end_mark()
        self.spans.append(span)
        return span

    def gap_span(self, name: str, **attrs: Any) -> Optional[Span]:
        """A closed span from where the trace's last span closed to
        now: the wait of a hand-off (a queue hop and the receiving
        thread's wake-up), recorded by the receiver, so that no span
        is left open while the trace changes owner. None on a trace
        in which nothing has closed yet."""
        if self._last_end is None:
            return None
        span = Span(name, depth=len(self._stack), start=self._last_end)
        self.spans.append(span)
        return self.end_span(span, **attrs)

    def closed_span(
        self, name: str, start: Tuple[float, float], dur_ms: float,
        depth: Optional[int] = None, **attrs: Any
    ) -> Span:
        """A span recorded after the fact, closed: ``start`` on both
        clocks (``Span.mark_at``, ``end_mark``) and its length. It nests
        in whatever is open, unless ``depth`` places it: a stretch that
        another thread worked beside the trace's owner (PrefixManager's
        redistribution beside Fib) is inside nothing the owner has
        open. It is not a hand-off, so the next ``gap_span`` still
        starts where the last live span closed."""
        span = Span(
            name,
            depth=len(self._stack) if depth is None else depth,
            start=start,
        )
        span.dur_ms = max(0.0, dur_ms)
        span.attrs.update(attrs)
        self.spans.append(span)
        return span

    @property
    def e2e_ms(self) -> Optional[float]:
        if not self.spans:
            return None
        ends = [s.ts_ms + s.dur_ms for s in self.spans if s.closed]
        if not ends:
            return None
        return max(ends) - self.ts_ms

    def well_formed(self) -> bool:
        """Every span closed and the open/close order properly nested
        (a child span never outlives its parent's duration window)."""
        if any(not s.closed for s in self.spans):
            return False
        if self._stack:
            return False
        for i, s in enumerate(self.spans):
            for t in self.spans[i + 1 :]:
                if t.depth > s.depth and t.ts_ms < s.ts_ms + s.dur_ms:
                    # t starts inside s: it must also end inside s
                    # (tolerance for clock granularity)
                    if t.ts_ms + t.dur_ms > s.ts_ms + s.dur_ms + 0.5:
                        return False
        return True

    def to_dict(self) -> Dict[str, Any]:
        return {
            "trace_id": self.trace_id,
            "origin": self.origin,
            "ts_ms": round(self.ts_ms, 3),
            "e2e_ms": round(self.e2e_ms, 4) if self.e2e_ms is not None else None,
            "complete": self.complete,
            "spans": [s.to_dict() for s in self.spans],
        }


class _ScopedSpan:
    """``Tracer.span``'s context manager: one span, opened and closed
    on the entering thread, inside a profiler annotation of the same
    name when the process has a ``jax`` to annotate with."""

    __slots__ = ("_trace", "_name", "_attrs", "_annotation", "_span")

    def __init__(self, trace: Trace, name: str, attrs, annotation) -> None:
        self._trace = trace
        self._name = name
        self._attrs = attrs
        self._annotation = annotation

    def __enter__(self) -> Span:
        if self._annotation is not None:
            self._annotation.__enter__()
        self._span = self._trace.begin_span(self._name, **self._attrs)
        return self._span

    def __exit__(self, *exc_info) -> None:
        self._trace.end_span(self._span)
        if self._annotation is not None:
            self._annotation.__exit__(*exc_info)


_NO_SPAN = contextlib.nullcontext()


class Tracer:
    """Process-wide sink for completed traces + per-thread active-trace
    stack for deep call sites.

    The ring depth defaults from ``OPENR_TRACE_RING`` (256): at 200+
    events/s the default overflows in ~1 s, which is why every retired
    trace's overflow is counted (``telemetry.trace_ring_overflows``)
    and a compact summary also lands in the flight recorder's much
    cheaper ring."""

    def __init__(self, ring: Optional[int] = None) -> None:
        if ring is None:
            ring = int(os.environ.get("OPENR_TRACE_RING", "256"))
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(1, ring))
        self._tls = threading.local()
        # finish listeners: the sustained-load harness samples e2e per
        # retired trace through these instead of polling the ring (the
        # 256-deep ring overflows in ~1s at 200+ events/s)
        self._finish_listeners: List[Any] = []
        # the last full garbage collections, handed over by the
        # collector's hook (telemetry/gc_pauses.py) on whichever thread
        # tripped one: a fixed ring and a count, written with no lock
        # (that thread may hold any) and read by finish()
        self._pauses: List[Optional[tuple]] = [None] * _PAUSE_RING
        self._pauses_noted = 0

    # -- lifecycle --------------------------------------------------
    def start(self, origin: str = "kvstore.publish", **attrs: Any) -> Trace:
        t = Trace(origin)
        t.instant(origin, **attrs)
        get_registry().counter_bump("telemetry.traces_started")
        return t

    def finish(self, trace: Optional[Trace], ok: bool = True) -> None:
        """Validate and retire a trace into the export ring."""
        if trace is None:
            return
        reg = get_registry()
        unclosed = sum(1 for s in trace.spans if not s.closed)
        if unclosed:
            reg.counter_bump("telemetry.traces_unclosed_spans", unclosed)
        elif not trace.well_formed():
            reg.counter_bump("telemetry.traces_bad_nesting")
        if self._pauses_noted:
            self._attach_pauses(trace)
        trace.complete = ok and unclosed == 0
        reg.counter_bump("telemetry.traces_finished")
        e2e = trace.e2e_ms
        if trace.complete and e2e is not None:
            reg.observe("convergence.e2e_ms", e2e)
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                reg.counter_bump("telemetry.trace_ring_overflows")
            self._ring.append(trace)
            listeners = list(self._finish_listeners)
        # compact summary into the flight recorder's deeper ring — the
        # evidence that survives this ring's ~1 s overflow horizon.
        # Lazy import: flight imports this module for chrome export.
        from openr_tpu.telemetry.flight import get_flight_recorder

        fr = get_flight_recorder()
        if fr.enabled:
            fr.note(
                "trace",
                origin=trace.origin,
                trace_id=trace.trace_id,
                e2e_ms=round(e2e, 4) if e2e is not None else None,
                complete=trace.complete,
                spans=[s.name for s in trace.spans],
            )
        for fn in listeners:
            try:
                fn(trace, ok)
            except Exception:  # noqa: BLE001 - observers never poison Fib
                reg.counter_bump("telemetry.finish_listener_errors")

    def note_pause(
        self, start: Tuple[float, float], dur_ms: float, generation: int
    ) -> None:
        """A collection that stopped every thread of the process from
        ``start`` (both clocks) for ``dur_ms``."""
        self._pauses[self._pauses_noted % _PAUSE_RING] = (
            start, dur_ms, generation
        )
        self._pauses_noted += 1

    def _attach_pauses(self, trace: Trace) -> None:
        """Put each noted pause that fell inside ``trace``'s extent on
        it as a closed ``process.gc_pause`` span: the sample says which
        collection it was stopped by. Every mark of a span is taken by
        running Python, which a collection holds up on all threads, so a
        pause lies inside the extent whole or not at all."""
        closed = [s for s in trace.spans if s.closed]
        if not closed:
            return
        t0 = closed[0]._t0
        t1 = max(s.end_mark()[1] for s in closed)
        paused = False
        for pause in self._pauses:
            if pause is None:
                continue
            start, dur_ms, generation = pause
            if t0 <= start[1] < t1:
                trace.closed_span(
                    "process.gc_pause", start, dur_ms,
                    generation=generation,
                )
                paused = True
        if paused:
            get_registry().counter_bump("telemetry.traces_paused")

    def add_finish_listener(self, fn) -> None:
        """Register ``fn(trace, ok)`` called after every finish(). Runs
        on the finishing thread (Fib's event base) — keep it cheap."""
        with self._lock:
            self._finish_listeners.append(fn)

    def remove_finish_listener(self, fn) -> None:
        with self._lock:
            if fn in self._finish_listeners:
                self._finish_listeners.remove(fn)

    # -- thread-local activation ------------------------------------
    def activate(self, trace: Optional[Trace]) -> None:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        stack.append(trace)

    def deactivate(self) -> None:
        stack = getattr(self._tls, "stack", None)
        if stack:
            stack.pop()

    def active(self) -> Optional[Trace]:
        stack = getattr(self._tls, "stack", None)
        return stack[-1] if stack else None

    def span_active(self, name: str, **attrs: Any) -> Optional[Span]:
        """Open a span on the current thread's active trace (None if no
        trace is active — callers must pass the result back through
        ``end_span_active``, which tolerates None)."""
        t = self.active()
        return t.begin_span(name, **attrs) if t is not None else None

    def end_span_active(self, span: Optional[Span], **attrs: Any) -> None:
        t = self.active()
        if t is not None and span is not None:
            t.end_span(span, **attrs)

    def span(
        self, name: str, trace: Optional[Trace] = None, **attrs: Any
    ) -> ContextManager[Optional[Span]]:
        """``with tracer.span("decision.route_build") as s:`` — a span
        around the block, on ``trace`` or else on this thread's active
        trace, closed when the block exits, by exception too. ``s`` is
        the span (set what is only known afterwards on ``s.attrs``), or
        None when there is no trace to put it on: the block then runs
        untraced."""
        t = trace if trace is not None else self.active()
        if t is None:
            return _NO_SPAN
        return _ScopedSpan(t, name, attrs, self._annotation(name))

    @staticmethod
    def _annotation(name: str):
        """A ``jax.profiler.TraceAnnotation`` for a scoped span, from a
        jax this process has already imported; never imports it, so
        the JAX-free users of this module (ctrl clients, breeze) stay
        so. Looked up per span (two dictionary reads) rather than kept:
        spans open on several threads. Inactive, and near free, unless
        a profiler session is collecting."""
        cls = getattr(
            sys.modules.get("jax.profiler"), "TraceAnnotation", None
        )
        return cls(name) if cls is not None else None

    # -- export -----------------------------------------------------
    def traces(self, limit: int = 0) -> List[Trace]:
        with self._lock:
            out = list(self._ring)
        return out[-limit:] if limit else out

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def jsonl(self, limit: int = 0) -> str:
        return "\n".join(
            json.dumps(t.to_dict()) for t in self.traces(limit)
        )

    def chrome_trace(self, limit: int = 0) -> Dict[str, Any]:
        """Chrome-trace / Perfetto ``traceEvents`` document. One "pid"
        per trace so concurrent churn events render as parallel rows;
        span depth maps to "tid" to keep nesting visible."""
        events: List[Dict[str, Any]] = []
        for t in self.traces(limit):
            for s in t.spans:
                events.append(
                    {
                        "name": s.name,
                        "cat": t.origin,
                        "ph": "X",
                        "pid": t.trace_id,
                        "tid": s.depth,
                        "ts": s.ts_ms * 1000.0,
                        "dur": (s.dur_ms or 0.0) * 1000.0,
                        "args": dict(s.attrs),
                    }
                )
        return {"traceEvents": events, "displayTimeUnit": "ms"}


_TRACER: Optional[Tracer] = None
_TRACER_LOCK = threading.Lock()


def get_tracer() -> Tracer:
    global _TRACER
    if _TRACER is None:
        with _TRACER_LOCK:
            if _TRACER is None:
                _TRACER = Tracer()
    return _TRACER
