"""Always-on device-time attribution with bounded overhead.

The ROADMAP's open claim — ``host_overhead_ratio`` within 2x of
``device_only_ms`` — was only checkable by arithmetic outside the
program (a model of a chained dispatch; nothing measured one). This
module makes device time a *measured, always-on* output of the
dispatch plane itself:

- every ``aot_call`` dispatch is wall-timed on the host
  (``ops.host_ms.<tag>``), and every ``sample_every``-th call per tag
  is sampled: its submit-to-read time lands in ``ops.device_ms.<tag>``
  — the timed-dispatch sampling fallback that works on CPU where
  ``jax.profiler`` device traces don't exist. Inside a
  ``dispatch_accounting`` window the sample never waits: it leaves a
  mark on the window, and the window's next ``reap_read`` that finds
  the dispatch's output ready closes it (``close_marks``), so the
  number is an upper bound — dispatch start to the end of the host
  read the program made anyway, the host work between them included.
  Outside any window nothing was deferred behind the dispatch and the
  sample blocks for the result, as it always did;
- where a ``jax.profiler`` session IS collecting, ``annotate(tag)``
  wraps the same dispatches in ``TraceAnnotation`` so the XLA timeline
  carries the stage names (free when no session is active);
- call sites label dispatches (``labels(bucket=..., slo=...)``) so the
  sampled device time also lands per tenant bucket and per SLO class
  (``ops.device_ms.by_<key>.<value>``);
- ``dispatch_accounting.event_window`` reports every window's wall
  clock here, so ``ops.host_overhead_ratio`` is a live gauge of
  window-wall over attributed device time — the measured number that
  replaces the modelled one.

Overhead budget (<5% on the churn bench, gated by ``make obs-smoke``):
the un-sampled path is one ``perf_counter`` pair, one histogram
observe, and a thread-local read. The sampled path inside a window
adds a tuple on a list and, at the next ``reap_read``, one
``is_ready`` query and the observes (``ops.profile_samples`` counts
marks closed); outside a window it adds ONE ``block_until_ready`` per
``sample_every`` dispatches. A mark no read closes before its window
retires is dropped, and a dispatch whose output nobody reads
(``unread``: the KSP2 engine's deferred matrix solve) is never sampled
— a wait or a mark there would charge it another program's time.

Disabled (``OPENR_PROFILE=0``) the plane costs one attribute read per
dispatch and nothing else.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Any, Dict, Iterator, Optional, Tuple

from openr_tpu.telemetry.registry import get_registry

_EWMA = 0.2  # weight of the newest device-time sample per tag


def _sanitize(value: Any) -> str:
    """fb303-safe label value: lowercase alnum + underscore."""
    s = str(value).lower()
    return "".join(c if c.isalnum() else "_" for c in s).strip("_") or "x"


def _first_array(out: Any) -> Any:
    """The first leaf of a dispatch's output that can say whether it
    is ready (a ``jax.Array``), None where none can (host shims)."""
    stack = [out]
    while stack:
        x = stack.pop()
        if isinstance(x, (tuple, list)):
            stack.extend(reversed(x))
        elif hasattr(x, "is_ready"):
            return x
    return None


def _is_ready(leaf: Any) -> bool:
    try:
        return bool(leaf.is_ready())
    except Exception:  # noqa: BLE001 - donated since: it ran
        return True


class _TagState:
    __slots__ = ("calls", "device_ewma_ms")

    def __init__(self) -> None:
        self.calls = 0
        self.device_ewma_ms: Optional[float] = None


class Profiler:
    """Process-wide device-time attributor. All methods thread-safe."""

    def __init__(
        self,
        sample_every: Optional[int] = None,
        enabled: Optional[bool] = None,
    ) -> None:
        if sample_every is None:
            sample_every = int(os.environ.get("OPENR_PROFILE_SAMPLE", "8"))
        if enabled is None:
            enabled = os.environ.get("OPENR_PROFILE", "1") != "0"
        self.sample_every = max(1, sample_every)
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        self._tags: Dict[str, _TagState] = {}
        self._tls = threading.local()
        self._warm = False
        # recent (window_wall_ms, window_device_ms) pairs: the ratio
        # gauge reads these, bounded so it tracks current behaviour
        self._windows: deque = deque(maxlen=256)
        self._annotation_cls: Any = None
        get_registry().gauge(
            "ops.host_overhead_ratio", self.host_overhead_ratio
        )

    # -- warmup marker ----------------------------------------------
    def mark_warm(self) -> None:
        """Callers declare warmup done; compiles after this point are
        anomalies (see flight.CompileAfterWarmupTrigger)."""
        self._warm = True

    @property
    def warm(self) -> bool:
        return self._warm

    # -- labels ------------------------------------------------------
    @contextmanager
    def labels(self, **kv: Any) -> Iterator[None]:
        """Attach label dimensions (bucket=..., slo=...) to every
        sampled dispatch inside the block. Thread-local; nests by
        overlay."""
        if not self.enabled:
            yield
            return
        prev = getattr(self._tls, "labels", None)
        merged = dict(prev or ())
        merged.update({k: _sanitize(v) for k, v in kv.items()})
        self._tls.labels = merged
        try:
            yield
        finally:
            self._tls.labels = prev

    def _active_labels(self) -> Optional[Dict[str, str]]:
        return getattr(self._tls, "labels", None)

    # -- jax.profiler annotations -----------------------------------
    def annotate(self, tag: str):
        """``jax.profiler.TraceAnnotation(tag)`` when available — names
        the dispatch on the XLA timeline when a profiler session is
        collecting; a fast no-op TraceMe otherwise."""
        if not self.enabled:
            return nullcontext()
        cls = self._annotation_cls
        if cls is None:
            try:
                from jax.profiler import TraceAnnotation as cls  # noqa: N813
            except Exception:  # noqa: BLE001 - no jax / old jax
                cls = nullcontext
            self._annotation_cls = cls
        try:
            return cls(tag)
        except Exception:  # noqa: BLE001 - annotation never breaks dispatch
            return nullcontext()

    # -- per-dispatch attribution -----------------------------------
    def on_dispatch(
        self, tag: str, out: Any, host_ms: float,
        t0: Optional[float] = None, marks: Optional[list] = None,
        unread: bool = False,
    ) -> float:
        """Record one dispatch's host wall time, and on sampled calls
        its device time: a mark on ``marks`` (the active accounting
        window's list, closed by that window's next ``reap_read``
        through ``close_marks``) where the caller is inside a window,
        a block for the result where it is not. ``t0``: the dispatch's
        start on ``perf_counter`` (now less ``host_ms`` without it).
        ``unread``: nobody reads this dispatch's output, so it is
        never sampled. Returns the best device-time estimate for this
        call (measured, else the tag's EWMA, else the host time)."""
        if not self.enabled:
            return host_ms
        reg = get_registry()
        reg.observe(f"ops.host_ms.{tag}", host_ms)
        with self._lock:
            st = self._tags.get(tag)
            if st is None:
                st = self._tags[tag] = _TagState()
            st.calls += 1
            sampled = not unread and (
                (st.calls % self.sample_every) == 1
                or self.sample_every == 1
            )
            ewma = st.device_ewma_ms
        estimate = ewma if ewma is not None else host_ms
        if not sampled:
            return estimate
        if t0 is None:
            t0 = time.perf_counter() - host_ms / 1000.0
        if marks is not None:
            marks.append((tag, t0, self._active_labels(), _first_array(out)))
            return estimate
        try:
            import jax

            jax.block_until_ready(out)
        except Exception:  # noqa: BLE001 - host shims / non-arrays
            pass
        return self._sample(
            tag, (time.perf_counter() - t0) * 1000.0, self._active_labels()
        )

    def close_marks(self, marks: list) -> None:
        """The host has just read from the device: close, as measured
        device time from their dispatch's start to now, the marks of
        ``marks`` whose output is ready, and keep the rest (a batch
        dispatched behind the one just read) for the next read. Never
        blocks."""
        now = time.perf_counter()
        still = []
        for mark in marks:
            tag, t0, labels, leaf = mark
            if leaf is not None and not _is_ready(leaf):
                still.append(mark)
                continue
            self._sample(tag, (now - t0) * 1000.0, labels)
        marks[:] = still

    def _sample(
        self, tag: str, device_ms: float, labels: Optional[Dict[str, str]]
    ) -> float:
        reg = get_registry()
        reg.counter_bump("ops.profile_samples")
        reg.observe(f"ops.device_ms.{tag}", device_ms)
        if labels:
            for key, val in labels.items():
                reg.observe(f"ops.device_ms.by_{key}.{val}", device_ms)
        with self._lock:
            st = self._tags[tag]
            if st.device_ewma_ms is None:
                st.device_ewma_ms = device_ms
            else:
                st.device_ewma_ms = (
                    (1.0 - _EWMA) * st.device_ewma_ms + _EWMA * device_ms
                )
        return device_ms

    # -- per-window attribution -------------------------------------
    def on_window(self, tag: str, wall_ms: float, device_ms: float) -> None:
        """One committed event window retired: its host wall clock and
        the device time attributed inside it. Feeds the live
        ``ops.host_overhead_ratio`` gauge."""
        if not self.enabled or device_ms <= 0.0:
            return
        with self._lock:
            self._windows.append((wall_ms, device_ms))

    def host_overhead_ratio(self) -> float:
        """Measured window-wall over attributed device time across the
        recent windows (the ROADMAP's target: < 2.0 on real hardware)."""
        with self._lock:
            pairs = list(self._windows)
        wall = sum(p[0] for p in pairs)
        dev = sum(p[1] for p in pairs)
        return round(wall / dev, 4) if dev > 0.0 else 0.0

    # -- export ------------------------------------------------------
    def attribution(self) -> Dict[str, Dict[str, float]]:
        """Per-tag measured stage costs: ``{tag: {device_ms_p50,
        device_ms_p99, host_ms_p50, host_ms_p99, calls,
        device_samples}}`` read straight from the registry histograms
        (label histograms ``by_*`` excluded)."""
        hists = get_registry().histograms()
        out: Dict[str, Dict[str, float]] = {}
        for name, h in hists.items():
            for prefix, dev in (("ops.device_ms.", True),
                                ("ops.host_ms.", False)):
                if not name.startswith(prefix):
                    continue
                tag = name[len(prefix):]
                if tag.startswith("by_"):
                    continue
                row = out.setdefault(tag, {})
                kind = "device_ms" if dev else "host_ms"
                row[f"{kind}_p50"] = round(h.percentile(0.50), 4)
                row[f"{kind}_p99"] = round(h.percentile(0.99), 4)
                if dev:
                    row["device_samples"] = float(h.count)
                else:
                    row["calls"] = float(h.count)
        return out


_PROFILER: Optional[Profiler] = None
_PROFILER_LOCK = threading.Lock()


def get_profiler() -> Profiler:
    global _PROFILER
    if _PROFILER is None:
        with _PROFILER_LOCK:
            if _PROFILER is None:
                _PROFILER = Profiler()
    return _PROFILER


def reset_profiler(**kwargs: Any) -> Profiler:
    """Tests / smoke gates: replace the singleton (re-reads env unless
    overridden by kwargs)."""
    global _PROFILER
    with _PROFILER_LOCK:
        _PROFILER = Profiler(**kwargs)
    return _PROFILER
