"""Process runtime: full garbage collections, counted.

With a few thousand nodes' LSDB on the heap a generation-2 collection
stops every thread of the process for hundreds of milliseconds; it
lands in convergence's tail and, without these two counters, nothing
names it:

- ``process.gc_gen2_collections``: generation-2 collections finished;
- ``process.gc_gen2_pause_ms``: their summed duration.

Each pause is also handed to the tracer (``Tracer.note_pause``: where
it began on the tracer's two clocks, and how long it lasted), which
puts it as a closed ``process.gc_pause`` span on every trace it fell
into and counts those traces (``telemetry.traces_paused``): the sample
then says which collection stopped it.

One ``gc.callbacks`` hook. The collector calls it on whichever thread
tripped the threshold, never re-entrantly, so ``start`` and ``stop``
of one collection arrive in order on one thread. Collections of the
young generations (hundreds a second under churn) return at the first
comparison. The registry's lock is re-entrant, so a collection that
starts inside a counter bump of the same thread cannot deadlock on it.

``settle_heap`` is the one place the process's collector policy is
set: whoever has just built something large that will live long
(Decision, after a rebuild in which a KSP2 engine built cold) calls it
once, from the thread that owns the rebuild.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Optional, Tuple

from openr_tpu.telemetry.registry import get_registry
from openr_tpu.telemetry.trace import get_tracer

COLLECTIONS = "process.gc_gen2_collections"
PAUSE_MS = "process.gc_gen2_pause_ms"
TRACES_PAUSED = "telemetry.traces_paused"


class _Gen2Pauses:
    def __init__(self) -> None:
        # (wall clock in ms, perf_counter): a span's start pair
        self._start: Optional[Tuple[float, float]] = None
        # bound at install: nothing here may take a lock the collecting
        # thread could already hold (get_tracer's, on first use)
        self.tracer = None

    def __call__(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._start = (time.time() * 1000.0, time.perf_counter())
        elif self._start is not None:
            start, self._start = self._start, None
            pause_ms = (time.perf_counter() - start[1]) * 1000.0
            reg = get_registry()
            reg.counter_bump(COLLECTIONS)
            reg.counter_bump(PAUSE_MS, pause_ms)
            self.tracer.note_pause(start, pause_ms, 2)


def settle_heap() -> None:
    """Reclaim what has died, then take what lives out of the
    collector's way: thaw the permanent generation, run one full
    collection, freeze the survivors. The full collections of the
    churn that follows then walk what the churn itself allocated and
    not the long-lived heap (a few hundred thousand path lists and
    index entries per thousand KSP2 destinations: 130 ms a collection
    at 1016 nodes unfrozen, 20 ms frozen). Thawing first is what keeps
    repeated calls from piling garbage up in the permanent generation:
    what an earlier call froze and has since died is collected here."""
    gc.unfreeze()
    gc.collect()
    gc.freeze()


_HOOK = _Gen2Pauses()
_INSTALL_LOCK = threading.Lock()


def install_gc_hook() -> None:
    """Idempotent: count this process's generation-2 collections from
    now on. The counters exist (at 0) from the first call."""
    with _INSTALL_LOCK:
        if _HOOK in gc.callbacks:
            return
        reg = get_registry()
        reg.counter_bump(COLLECTIONS, 0)
        reg.counter_bump(PAUSE_MS, 0)
        reg.counter_bump(TRACES_PAUSED, 0)
        _HOOK.tracer = get_tracer()
        gc.callbacks.append(_HOOK)
