"""Process runtime: full garbage collections, counted.

With a few thousand nodes' LSDB on the heap a generation-2 collection
stops every thread of the process for hundreds of milliseconds; it
lands in convergence's tail and, without these two counters, nothing
names it:

- ``process.gc_gen2_collections``: generation-2 collections finished;
- ``process.gc_gen2_pause_ms``: their summed duration.

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
from typing import Optional

from openr_tpu.telemetry.registry import get_registry

COLLECTIONS = "process.gc_gen2_collections"
PAUSE_MS = "process.gc_gen2_pause_ms"


class _Gen2Pauses:
    def __init__(self) -> None:
        self._t0: Optional[float] = None

    def __call__(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            pause_ms = (time.perf_counter() - self._t0) * 1000.0
            self._t0 = None
            reg = get_registry()
            reg.counter_bump(COLLECTIONS)
            reg.counter_bump(PAUSE_MS, pause_ms)


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
    now on. Both counters exist (at 0) from the first call."""
    with _INSTALL_LOCK:
        if _HOOK in gc.callbacks:
            return
        reg = get_registry()
        reg.counter_bump(COLLECTIONS, 0)
        reg.counter_bump(PAUSE_MS, 0)
        gc.callbacks.append(_HOOK)
