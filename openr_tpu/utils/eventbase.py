"""Per-module event loop: the daemon's async runtime substrate.

Behavioral parity with the reference ``openr/common/OpenrEventBase.h``
(folly EventBase wrapper): every protocol module owns exactly one
OpenrEventBase running on its own named thread; all module state is
touched only from that thread. Cross-module communication happens through
``openr_tpu.messaging`` queues, whose readers are registered here (the
analogue of the reference's fiber tasks, OpenrEventBase.h:48
addFiberTask) and delivered as callbacks on the module thread.

Also hosts the coalescing/rate-limiting primitives the modules rely on:
- ``ExponentialBackoff``  (reference: common/ExponentialBackoff.h)
- ``AsyncThrottle``       (reference: common/AsyncThrottle.h)
- ``AsyncDebounce``       (reference: common/AsyncDebounce.h:27-62)
"""

from __future__ import annotations

import heapq
import itertools
import queue as _queue
import random
import threading
import time
from typing import Callable, List, NamedTuple, Optional, Tuple

from openr_tpu.analysis.annotations import thread_confined
from openr_tpu.messaging.queue import QueueClosedError, RQueue
from openr_tpu.telemetry.registry import get_registry

# upper bound on the event loop's idle wait so last_loop_ts stays fresh
# for the Watchdog even on a completely quiet event base; small enough
# that it stays well under any plausible watchdog threshold
_WATCHDOG_TICK_S = 0.1
# timer latenesses kept between two flushes of a loop's account; a loop
# that never finds its queue empty drops the rest, its counters do not
_LATE_BUFFER = 1024


class TimerHandle:
    """``deadline`` is on ``time.perf_counter``, the clock of the loop's
    own account and of the tracer's spans; ``late_s`` is how long after
    it the loop got to fire (set just before ``fn`` runs)."""

    __slots__ = ("deadline", "seq", "fn", "cancelled", "late_s")

    def __init__(self, deadline: float, seq: int, fn: Callable[[], None]):
        self.deadline = deadline
        self.seq = seq
        self.fn = fn
        self.cancelled = False
        self.late_s = 0.0

    def cancel(self) -> None:
        self.cancelled = True

    def __lt__(self, other: "TimerHandle") -> bool:
        return (self.deadline, self.seq) < (other.deadline, other.seq)


class OpenrEventBase:
    """Single-threaded event loop with timers and queue-reader tasks."""

    def __init__(self, name: str = "evb"):
        self.name = name
        self._callbacks: "_queue.Queue[Callable[[], None]]" = _queue.Queue()
        self._timers: List[TimerHandle] = []
        self._timer_lock = threading.Lock()
        self._seq = itertools.count()
        self._running = threading.Event()
        self._stop_requested = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._reader_threads: List[threading.Thread] = []
        # liveness for the watchdog (reference: Watchdog.h monitors evbs)
        self.last_loop_ts: float = time.monotonic()
        # the loop's own account (folly's EventBaseObserver gives an
        # operator the same: busy / idle per loop), on perf_counter,
        # written by the loop thread alone: a clock read before and one
        # after each callback and each timer function. Everything
        # between two callbacks is idle (blocked in get(), the loop's
        # own few lines), so busy + idle tile the time since run().
        self.busy_s = 0.0  # callbacks and timer functions that returned
        self.idle_s = 0.0
        self.callbacks_run = 0
        # when the callback now running began (None between callbacks)
        # and when the last one ended: the instant the loop went idle
        self._running_since: Optional[float] = None
        self.idle_since: float = time.perf_counter()
        # exported as evb.<module>.busy_ms / idle_ms / callbacks and
        # the observation evb.<module>.timer_late_ms; <module> is the
        # loop's name up to the colon (decision:node-7 -> decision).
        # Flushed when the loop is about to block, never per callback.
        module = "evb." + name.split(":", 1)[0]
        self._metrics = tuple(
            module + suffix for suffix in
            (".busy_ms", ".idle_ms", ".callbacks", ".timer_late_ms")
        )
        self._flushed = (0.0, 0.0, 0)
        self._late_ms: List[float] = []

    # -- lifecycle --------------------------------------------------------

    def run(self) -> None:
        """Run the loop on the calling thread until stop()."""
        self._running.set()
        self.idle_since = time.perf_counter()
        try:
            while not self._stop_requested.is_set():
                self.last_loop_ts = time.monotonic()
                timeout = self._run_due_timers()
                # bound the idle wait: an evb with no timers and no
                # traffic (Monitor on a quiet network) would otherwise
                # block forever in get(), its last_loop_ts would go
                # stale, and the Watchdog would abort a HEALTHY daemon.
                # Idle-blocked is healthy; a hung callback still never
                # returns here and still trips the watchdog.
                if timeout is None or timeout > _WATCHDOG_TICK_S:
                    timeout = _WATCHDOG_TICK_S
                if self._callbacks.empty():
                    self._flush_account()
                try:
                    cb = self._callbacks.get(timeout=timeout)
                except _queue.Empty:
                    continue
                self._enter(time.perf_counter())
                try:
                    cb()
                except Exception:  # noqa: BLE001
                    # a module callback must never kill the module loop
                    import logging

                    logging.getLogger(__name__).exception(
                        "%s: unhandled exception in event callback", self.name
                    )
                self._leave()
        finally:
            self._running.clear()
            self._flush_account()

    # -- the loop's own account -------------------------------------------

    def _enter(self, now: float) -> None:
        self.idle_s += now - self.idle_since
        self._running_since = now

    def _leave(self) -> float:
        now = time.perf_counter()
        self.busy_s += now - self._running_since
        self._running_since = None
        self.callbacks_run += 1
        self.idle_since = now
        return now

    def busy_seconds(self) -> float:
        """Busy time so far, the part of the running callback that has
        already passed included. For the loop's own thread: a window's
        owner takes it when the window opens and subtracts it from
        ``busy_s`` as its timer fires (a timer function's own time is
        added only when it returns)."""
        since = self._running_since
        if since is None:
            return self.busy_s
        return self.busy_s + (time.perf_counter() - since)

    def _flush_account(self) -> None:
        """The account's deltas since the last flush, to the registry."""
        busy, idle, ran = self.busy_s, self.idle_s, self.callbacks_run
        busy0, idle0, ran0 = self._flushed
        self._flushed = (busy, idle, ran)
        busy_ms, idle_ms, callbacks, timer_late_ms = self._metrics
        reg = get_registry()
        if ran != ran0:
            reg.counter_bump(busy_ms, (busy - busy0) * 1e3)
            reg.counter_bump(callbacks, ran - ran0)
        if idle != idle0:
            reg.counter_bump(idle_ms, (idle - idle0) * 1e3)
        if self._late_ms:
            late, self._late_ms = self._late_ms, []
            for ms in late:
                reg.observe(timer_late_ms, ms)

    def run_in_thread(self) -> None:
        assert self._thread is None
        self._thread = threading.Thread(
            target=self.run, name=self.name, daemon=True
        )
        self._thread.start()
        self.wait_until_running()

    def wait_until_running(self, timeout: float = 5.0) -> None:
        if not self._running.wait(timeout=timeout):
            raise TimeoutError(f"{self.name}: loop did not start")

    def stop(self) -> None:
        self._stop_requested.set()
        # wake the loop
        self._callbacks.put(lambda: None)

    def join(self, timeout: float = 10.0) -> None:
        if self._thread is not None:
            self._thread.join(timeout=timeout)
        for t in self._reader_threads:
            t.join(timeout=timeout)

    @property
    def is_running(self) -> bool:
        return self._running.is_set()

    def in_event_base_thread(self) -> bool:
        return threading.current_thread() is self._thread

    # -- scheduling -------------------------------------------------------

    def run_in_event_base(self, fn: Callable[[], None]) -> None:
        """Enqueue fn to run on the loop thread."""
        self._callbacks.put(fn)

    def run_immediately_or_in_event_base(self, fn: Callable[[], None]) -> None:
        if self.in_event_base_thread():
            fn()
        else:
            self.run_in_event_base(fn)

    def call_and_wait(self, fn: Callable[[], object], timeout: float = 10.0):
        """Run fn on the loop thread, block for its result (the analogue of
        the reference's folly::SemiFuture module read APIs)."""
        if self.in_event_base_thread():
            return fn()
        done = threading.Event()
        result: list = [None, None]

        def wrapper() -> None:
            try:
                result[0] = fn()
            except BaseException as e:  # noqa: BLE001 - relayed to caller
                result[1] = e
            finally:
                done.set()

        self.run_in_event_base(wrapper)
        if not done.wait(timeout=timeout):
            raise TimeoutError(f"{self.name}: call_and_wait timed out")
        if result[1] is not None:
            raise result[1]
        return result[0]

    def schedule_timeout(
        self, delay_s: float, fn: Callable[[], None]
    ) -> TimerHandle:
        handle = TimerHandle(
            time.perf_counter() + max(0.0, delay_s), next(self._seq), fn
        )
        with self._timer_lock:
            heapq.heappush(self._timers, handle)
        # wake the loop so it recomputes its sleep
        self._callbacks.put(lambda: None)
        return handle

    def schedule_periodic(
        self, interval_s: float, fn: Callable[[], None], jitter_first: bool = False
    ) -> "PeriodicHandle":
        return PeriodicHandle(self, interval_s, fn, jitter_first)

    def _run_due_timers(self) -> Optional[float]:
        """Fire expired timers; return seconds until the next one."""
        now = time.perf_counter()
        while True:
            with self._timer_lock:
                while self._timers and self._timers[0].cancelled:
                    heapq.heappop(self._timers)
                if not self._timers:
                    return None
                if self._timers[0].deadline > now:
                    return self._timers[0].deadline - now
                handle = heapq.heappop(self._timers)
            if handle.cancelled:
                continue
            # how late the loop is for it: a callback that outlasted the
            # deadline, or the wake-up out of get() (both, for the timer)
            handle.late_s = now - handle.deadline
            if len(self._late_ms) < _LATE_BUFFER:
                self._late_ms.append(handle.late_s * 1e3)
            self._enter(now)
            try:
                handle.fn()
            except Exception:  # noqa: BLE001
                import logging

                logging.getLogger(__name__).exception(
                    "%s: unhandled exception in timer", self.name
                )
            now = self._leave()

    # -- queue reader tasks (the "fibers") --------------------------------

    def add_queue_reader(
        self, rqueue: RQueue, callback: Callable[[object], None]
    ) -> None:
        """Deliver every message from rqueue as a callback on the loop
        thread (reference: fiber reading loops like Decision.cpp:1433)."""

        def deliver(item: object) -> None:
            rqueue.delivered()
            callback(item)

        def forward() -> None:
            while not self._stop_requested.is_set():
                try:
                    # handed off, not consumed: until the loop takes
                    # it, the item is still the reader's backlog
                    item = rqueue.get(timeout=0.2, hand_off=True)
                except QueueClosedError:
                    return
                except Exception:
                    continue
                self.run_in_event_base(lambda item=item: deliver(item))

        t = threading.Thread(
            target=forward, name=f"{self.name}::reader", daemon=True
        )
        t.start()
        self._reader_threads.append(t)


class PeriodicHandle:
    """Repeating timer bound to an event base."""

    def __init__(
        self,
        evb: OpenrEventBase,
        interval_s: float,
        fn: Callable[[], None],
        jitter_first: bool,
    ):
        self._evb = evb
        self._interval = interval_s
        self._fn = fn
        self._cancelled = False
        first = interval_s if jitter_first else 0.0
        self._handle = evb.schedule_timeout(first, self._tick)

    def _tick(self) -> None:
        if self._cancelled:
            return
        self._fn()
        if not self._cancelled:
            self._handle = self._evb.schedule_timeout(self._interval, self._tick)

    def cancel(self) -> None:
        self._cancelled = True
        self._handle.cancel()


# per-instance pacing state owned by whichever single loop created the
# backoff (an evb retry loop, the journal streamer thread, a client's
# reconnect path) — never shared across threads. The shared-state rule
# merges instances by class, so cross-role access to one instance is
# impossible by construction — hence "owner" confinement.
@thread_confined("owner", "_current", "_last_error_ts")
class ExponentialBackoff:
    """reference: common/ExponentialBackoff.h — per-key retry pacing.

    ``jitter=True`` opts into DECORRELATED jitter (the AWS
    exponential-backoff-and-jitter scheme): each error re-draws the
    delay uniformly from ``[initial, 3 * previous]`` (clamped to
    ``max``) from a private seeded stream, so N breakers that opened on
    the same event spread their re-probes instead of re-hammering the
    device in lockstep. Default OFF: the deterministic doubling path is
    byte-identical to the reference and some callers pin its exact
    sequence."""

    def __init__(self, initial_s: float, max_s: float,
                 jitter: bool = False, seed: Optional[int] = None):
        assert initial_s > 0 and max_s >= initial_s
        self._initial = initial_s
        self._max = max_s
        self._current = 0.0
        self._last_error_ts = 0.0
        self._jitter = bool(jitter)
        self._rng = random.Random(seed) if jitter else None

    def can_try_now(self) -> bool:
        return self.get_time_remaining_until_retry() <= 0

    def report_success(self) -> None:
        self._current = 0.0

    def report_error(self) -> None:
        self._last_error_ts = time.monotonic()
        if self._jitter:
            prev = self._current if self._current > 0.0 else self._initial
            self._current = min(
                self._max,
                self._rng.uniform(
                    self._initial, max(self._initial, prev * 3.0)
                ),
            )
        elif self._current == 0.0:
            self._current = self._initial
        else:
            self._current = min(self._current * 2, self._max)

    def at_max_backoff(self) -> bool:
        return self._current >= self._max

    @property
    def max_backoff(self) -> float:
        return self._max

    def set_max(self, max_s: float) -> None:
        """Retarget the ceiling (rate-adaptive debounce). Raising the max
        lets the next report_error extend further; lowering it clamps any
        in-flight backoff so the change takes effect immediately."""
        assert max_s >= self._initial
        self._max = max_s
        if self._current > max_s:
            self._current = max_s

    def get_current_backoff(self) -> float:
        return self._current

    def get_time_remaining_until_retry(self) -> float:
        if self._current == 0.0:
            return 0.0
        return max(0.0, self._last_error_ts + self._current - time.monotonic())


class AsyncThrottle:
    """Coalesce bursts: callback runs at most once per ``timeout_s``.
    reference: common/AsyncThrottle.h."""

    def __init__(
        self, evb: OpenrEventBase, timeout_s: float, callback: Callable[[], None]
    ):
        self._evb = evb
        self._timeout = timeout_s
        self._callback = callback
        self._handle: Optional[TimerHandle] = None

    def __call__(self) -> None:
        if self._handle is not None and not self._handle.cancelled:
            return
        if self._timeout <= 0:
            self._callback()
            return
        self._handle = self._evb.schedule_timeout(self._timeout, self._fire)

    def _fire(self) -> None:
        self._handle = None
        self._callback()

    def is_active(self) -> bool:
        return self._handle is not None and not self._handle.cancelled

    def cancel(self) -> None:
        if self._handle is not None:
            self._handle.cancel()
            self._handle = None


class FiredWindow(NamedTuple):
    """The terms of the debounce window whose timer is firing, all on
    ``time.perf_counter``; ``deadline + late_s`` is the fire itself."""

    armed_at: float  # the FIRST arm of the window
    deadline: float  # the deadline it fired for (the last arm's)
    late_s: float  # fire - deadline
    idle_since: float  # end of the last callback the loop ran before it
    busy_s: float  # the loop's busy_s at the fire


class AsyncDebounce:
    """Debounce with exponential extension: every invocation while pending
    pushes the deadline out (doubling from min toward max); once the
    backoff is saturated further invocations no longer delay the fire.
    reference: common/AsyncDebounce.h:27-62.

    While the callback runs, ``fired`` holds the window's terms
    (``FiredWindow``) for the owner to read; None at any other time."""

    def __init__(
        self,
        evb: OpenrEventBase,
        min_backoff_s: float,
        max_backoff_s: float,
        callback: Callable[[], None],
    ):
        self._evb = evb
        self._backoff = ExponentialBackoff(min_backoff_s, max_backoff_s)
        self._callback = callback
        self._handle: Optional[TimerHandle] = None
        self._armed_at: Optional[float] = None
        self.fired: Optional[FiredWindow] = None

    def __call__(self) -> None:
        if not self._backoff.at_max_backoff():
            self._backoff.report_error()
            if self._handle is not None:
                self._handle.cancel()
            self._handle = self._evb.schedule_timeout(
                self._backoff.get_current_backoff(), self._fire
            )
            if self._armed_at is None:
                self._armed_at = self._handle.deadline - (
                    self._backoff.get_current_backoff()
                )
        assert self._handle is not None and not self._handle.cancelled

    def _fire(self) -> None:
        handle, armed_at = self._handle, self._armed_at
        self._handle = None
        self._armed_at = None
        self._backoff.report_success()
        evb = self._evb
        if handle is not None and armed_at is not None:
            self.fired = FiredWindow(
                armed_at, handle.deadline, handle.late_s, evb.idle_since,
                evb.busy_s,
            )
        try:
            self._callback()
        finally:
            self.fired = None

    def is_scheduled(self) -> bool:
        return self._handle is not None and not self._handle.cancelled

    @property
    def max_backoff_s(self) -> float:
        return self._backoff.max_backoff

    def set_max_backoff(self, max_s: float) -> None:
        """Adjust the extension ceiling in place (the admission path's
        rate-adaptive debounce). A pending fire keeps its deadline; only
        future extensions see the new ceiling — except that lowering the
        ceiling clamps the backoff immediately, so a saturated debounce
        under a narrowed ceiling fires sooner on the next invocation."""
        self._backoff.set_max(max_s)
