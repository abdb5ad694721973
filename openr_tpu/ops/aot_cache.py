"""AOT executable cache: the committed-dispatch hot path calls a
compiled executable directly, with zero Python retrace / signature
checks.

``jax.jit``'s call path re-derives the (args -> executable) key on
every invocation: pytree flatten, static-argument hashing, signature
canonicalization — tens of microseconds of host work per dispatch that
the churn path pays thousands of times per second. This cache hoists
that work to the FIRST call per shape: ``jit(fn).lower(*dyn,
**statics).compile()`` bakes the statics into a ``Compiled`` executable
that is then invoked with the dynamic operands only (passing a static
again at call time is a pytree mismatch — the statics no longer exist
as parameters). Every later event with the same shape key goes
``dict lookup -> executable`` and nothing else.

Keying: ``(tag, statics, dynamic signature)`` where the dynamic
signature is the pytree structure plus per-leaf (shape, dtype,
sharding). Sharding is part of the key on purpose: an executable
compiled for single-chip operands cannot consume mesh-sharded
residents, and the single-chip and mesh engines of one test process
share this process-global cache.

Fallback ladder (never raises past the jitted semantics): a failed
lower/compile poisons the key and the call rides the plain jitted
function; a failed EXECUTABLE call (placement drift, donated-buffer
reuse, transfer guards) falls back the same way per call. Neither is
quiet: each logs the exception with its traceback and counts
``ops.aot_fallbacks``, which ``chip_smoke.py`` requires to be zero on
the chip. The executables themselves ride jax's persistent compilation
cache (utils.compile_cache), so "compile on miss" is a disk load, not
an XLA run, across processes.

Counters: ``ops.aot_compiles`` / ``ops.aot_hits`` /
``ops.aot_fallbacks``. Every call counts one committed dispatch via
``dispatch_accounting.count_dispatch``.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Dict, Tuple

import jax
import numpy as np

from openr_tpu.ops import dispatch_accounting
from openr_tpu.telemetry import get_registry
from openr_tpu.telemetry.profiler import get_profiler

log = logging.getLogger(__name__)

_UNCOMPILABLE = object()  # poison marker: lower/compile failed once


def _profiled(tag: str, thunk, unread: bool = False):
    """Run one dispatch under device-time attribution: host wall time
    always, sampled device time per the profiler's cadence (a mark on
    the active event window, closed by its next ``reap_read``; a block
    for the result only outside any window), both folded into the
    active event window's stage table. ``unread``: nobody reads the
    output, so the dispatch is never sampled. Disabled profiler == the
    bare call (one attribute read)."""
    prof = get_profiler()
    if not prof.enabled:
        return thunk()
    with prof.annotate(tag):
        t0 = time.perf_counter()
        out = thunk()
        host_ms = (time.perf_counter() - t0) * 1000.0
    window = dispatch_accounting.current_window()
    device_ms = prof.on_dispatch(
        tag, out, host_ms, t0=t0,
        marks=None if window is None else window.marks, unread=unread,
    )
    dispatch_accounting.attribute_stage(tag, host_ms, device_ms)
    return out


def _leaf_sig(leaf: Any) -> Tuple:
    if isinstance(leaf, jax.Array):
        try:
            sh = leaf.sharding
        except Exception:  # noqa: BLE001 - deleted/traced arrays
            sh = None
        return (tuple(leaf.shape), str(leaf.dtype), sh)
    if isinstance(leaf, np.ndarray):
        return (tuple(leaf.shape), str(leaf.dtype), "host")
    return (type(leaf).__name__, leaf if isinstance(
        leaf, (bool, int, float, str, type(None))) else None)


def signature(dyn_args: Tuple) -> Tuple:
    leaves, treedef = jax.tree_util.tree_flatten(dyn_args)
    return (treedef, tuple(_leaf_sig(x) for x in leaves))


class AotDispatchCache:
    """Process-global (tag, statics, signature) -> Compiled map."""

    def __init__(self) -> None:
        self._exes: Dict[Tuple, Any] = {}
        self._lock = threading.Lock()

    def stats(self) -> Dict[str, int]:
        reg = get_registry()
        return {
            "entries": len(self._exes),
            "compiles": int(reg.counter_get("ops.aot_compiles")),
            "hits": int(reg.counter_get("ops.aot_hits")),
            "fallbacks": int(reg.counter_get("ops.aot_fallbacks")),
        }

    def clear(self) -> None:
        with self._lock:
            self._exes.clear()

    def _lookup(self, tag: str, fn, dyn_args: Tuple,
                statics: Dict[str, Any]):
        try:
            key = (tag, tuple(sorted(statics.items())),
                   signature(dyn_args))
            hash(key)
        except TypeError:
            return None, None  # unhashable statics: jitted path
        exe = self._exes.get(key)
        return key, exe

    def call(self, tag: str, fn, dyn_args: Tuple,
             statics: Dict[str, Any], unread: bool = False):
        """Dispatch ``fn(*dyn_args, **statics)`` through the cached
        executable for this shape key, compiling it on first miss.
        ``unread``: the caller waits for nothing and no host read
        follows this dispatch's output (``_profiled``)."""
        reg = get_registry()
        dispatch_accounting.count_dispatch()

        def jitted():
            return fn(*dyn_args, **statics)

        key, exe = self._lookup(tag, fn, dyn_args, statics)
        if key is None or exe is _UNCOMPILABLE:
            reg.counter_bump("ops.aot_fallbacks")
            return _profiled(tag, jitted, unread)
        if exe is None:
            try:
                exe = fn.lower(*dyn_args, **statics).compile()
            except Exception:  # noqa: BLE001 - poison + jitted path
                log.exception(
                    "aot %s: lower/compile failed, key poisoned, "
                    "riding the jitted function", tag,
                )
                with self._lock:
                    self._exes[key] = _UNCOMPILABLE
                reg.counter_bump("ops.aot_fallbacks")
                return _profiled(tag, jitted, unread)
            with self._lock:
                self._exes[key] = exe
            reg.counter_bump("ops.aot_compiles")
        else:
            reg.counter_bump("ops.aot_hits")
        try:
            # dynamic operands ONLY: the statics were baked at lower
            # time and no longer exist as parameters of the executable
            return _profiled(tag, lambda: exe(*dyn_args), unread)
        except Exception:  # noqa: BLE001 - absorb into jitted path
            # a donated operand the failed call already consumed makes
            # the retry raise in its own right; that one propagates
            log.exception(
                "aot %s: executable call failed, retrying through the "
                "jitted function", tag,
            )
            reg.counter_bump("ops.aot_fallbacks")
            return _profiled(tag, jitted, unread)

    def warm(self, tag: str, fn, dyn_args: Tuple,
             statics: Dict[str, Any]) -> bool:
        """Build (or load from jax's persistent cache) the executable
        for this shape key without running it — the engine-construction
        prewarm."""
        key, exe = self._lookup(tag, fn, dyn_args, statics)
        if key is None or exe is _UNCOMPILABLE:
            return False
        if exe is not None:
            return True
        try:
            exe = fn.lower(*dyn_args, **statics).compile()
        except Exception:  # noqa: BLE001 - poison; the call path counts
            log.exception("aot %s: prewarm lower/compile failed", tag)
            with self._lock:
                self._exes[key] = _UNCOMPILABLE
            return False
        with self._lock:
            self._exes[key] = exe
        get_registry().counter_bump("ops.aot_compiles")
        return True


_CACHE = AotDispatchCache()


def get_aot_cache() -> AotDispatchCache:
    return _CACHE


def aot_call(tag: str, fn, dyn_args: Tuple, statics: Dict[str, Any],
             unread: bool = False):
    return _CACHE.call(tag, fn, dyn_args, statics, unread)
