"""The event loop's own account (busy / idle / callbacks / timer
lateness) and the terms ``AsyncDebounce`` keeps of its window.

Counts and identities, not times: the loop's busy and idle seconds tile
the time it has run, a timer behind a long callback is late by at least
the callback's overhang, and the window's terms add up to its fire.
Every inequality is one a stalled thread cannot break: a sleep only
ever runs long, and an instant is held between two reads of the clock
that bracket it.
"""

import threading
import time

import pytest

from openr_tpu.telemetry import get_registry
from openr_tpu.utils.eventbase import (
    AsyncDebounce,
    AsyncThrottle,
    FiredWindow,
    OpenrEventBase,
)


@pytest.fixture
def evb():
    loop = OpenrEventBase("acct:node-1")
    loop.run_in_thread()
    yield loop
    loop.stop()
    loop.join()


def _account(loop):
    """(before, busy + idle so far, after), read on the loop's thread:
    ``busy_seconds()`` reads the clock once, between the two reads
    here, and at that instant busy + idle is the time since ``run()``."""
    return loop.call_and_wait(
        lambda: (time.perf_counter(), loop.busy_seconds() + loop.idle_s,
                 time.perf_counter())
    )


def _until(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return cond()


def _evb_counters(module):
    return {
        k: v for k, v in get_registry().snapshot().items()
        if k.startswith(f"evb.{module}.")
    }


class TestLoopAccount:
    def test_busy_and_idle_tile_the_time_the_loop_has_run(self, evb):
        before1, ran1, after1 = _account(evb)
        busy1, idle1 = evb.call_and_wait(lambda: (evb.busy_s, evb.idle_s))
        evb.call_and_wait(lambda: time.sleep(0.05))  # the slow callback
        time.sleep(0.03)  # the loop blocked in get()
        busy2, idle2 = evb.call_and_wait(lambda: (evb.busy_s, evb.idle_s))
        before2, ran2, after2 = _account(evb)
        # the account grew by the time between its two readings, each
        # of which lies between the clock reads around it
        # (to the rounding of a sum of floats)
        assert before2 - after1 - 1e-9 <= ran2 - ran1
        assert ran2 - ran1 <= after2 - before1 + 1e-9
        assert busy2 - busy1 >= 0.05
        # the loop idled while this thread slept, less whatever it took
        # the loop's thread to get from the callback's end to its own
        # note of it: half the sleep is far below any reading seen
        assert idle2 - idle1 >= 0.015

    def test_a_callback_is_busy_time_only_once_it_has_returned(self, evb):
        seen = {}

        def slow():
            seen["done_before"] = evb.busy_s
            t0 = time.perf_counter()
            time.sleep(0.02)
            seen["slept"] = time.perf_counter() - t0
            seen["running"] = evb.busy_seconds() - evb.busy_s

        evb.call_and_wait(slow)
        done_after = evb.call_and_wait(lambda: evb.busy_s)
        # while it ran, busy_seconds() held its part so far; busy_s did not
        assert seen["running"] >= seen["slept"]
        assert done_after - seen["done_before"] >= seen["slept"]

    def test_callbacks_and_timer_functions_are_counted(self, evb):
        ran0 = evb.call_and_wait(lambda: evb.callbacks_run)
        hits = []
        for i in range(7):
            evb.run_in_event_base(lambda i=i: hits.append(i))
        evb.schedule_timeout(0.01, lambda: hits.append("timer"))
        assert _until(lambda: len(hits) == 8)
        ran1 = evb.call_and_wait(lambda: evb.callbacks_run)
        # 7 callbacks, the timer's function, the wake-up its arming
        # queued, and the two reads here: no fewer
        assert ran1 - ran0 >= 8

    def test_a_raising_callback_is_still_accounted(self, evb):
        ran0 = evb.call_and_wait(lambda: evb.callbacks_run)

        def boom():
            time.sleep(0.01)
            raise RuntimeError("contained by the loop")

        busy0 = evb.call_and_wait(lambda: evb.busy_s)
        evb.run_in_event_base(boom)
        # the first read's own callback, the second's, and boom
        assert evb.call_and_wait(lambda: evb.callbacks_run) >= ran0 + 3
        assert evb.call_and_wait(lambda: evb.busy_s) - busy0 >= 0.01
        # and the loop is between callbacks again
        assert _until(lambda: evb._running_since is None)

    def test_idle_since_is_where_the_last_callback_ended(self, evb):
        """Read from inside the next callback, it lies between the last
        line of the one before and the first line of this one."""
        ended = evb.call_and_wait(time.perf_counter)
        time.sleep(0.02)
        began, idle_since = evb.call_and_wait(
            lambda: (time.perf_counter(), evb.idle_since))
        assert ended <= idle_since <= began


class TestTimerLateness:
    def test_a_timer_behind_a_long_callback_is_late_by_its_overhang(self, evb):
        out = {}

        def long_callback():
            out["handle"] = evb.schedule_timeout(
                0.01, lambda: out.setdefault("fired", True)
            )
            time.sleep(0.05)
            out["end"] = time.perf_counter()

        evb.run_in_event_base(long_callback)
        assert _until(lambda: out.get("fired"))
        handle = out["handle"]
        overhang = out["end"] - handle.deadline
        assert overhang > 0.03
        assert handle.late_s >= overhang

    def test_a_timer_on_an_idle_loop_is_late_by_the_wake_up_alone(self, evb):
        fired = []
        handle = evb.schedule_timeout(
            0.02, lambda: fired.append(time.perf_counter()))
        assert _until(lambda: fired)
        # measured before the function runs, from the deadline on
        assert 0.0 <= handle.late_s
        assert handle.deadline + handle.late_s <= fired[0]

    def test_a_cancelled_timer_reports_nothing(self, evb):
        evb.call_and_wait(lambda: None)
        handle = evb.schedule_timeout(0.01, lambda: None)
        handle.cancel()
        time.sleep(0.05)
        assert handle.late_s == 0.0


class TestRegistryExport:
    def test_the_account_is_in_the_snapshot_under_the_loops_module(self):
        loop = OpenrEventBase("acctexport:node-9")
        loop.run_in_thread()
        try:
            loop.call_and_wait(lambda: time.sleep(0.01))
            fired = []
            loop.schedule_timeout(0.005, lambda: fired.append(1))
            assert _until(lambda: fired)
            # flushed as the loop goes idle, not per callback
            assert _until(
                lambda: "evb.acctexport.timer_late_ms.count"
                in _evb_counters("acctexport")
            )
            got = _evb_counters("acctexport")
        finally:
            loop.stop()
            loop.join()
        assert {
            "evb.acctexport.busy_ms", "evb.acctexport.idle_ms",
            "evb.acctexport.callbacks",
            "evb.acctexport.timer_late_ms.count",
            "evb.acctexport.timer_late_ms.p50",
        } <= set(got)
        assert got["evb.acctexport.busy_ms"] >= 10.0
        assert got["evb.acctexport.callbacks"] >= 2
        assert got["evb.acctexport.timer_late_ms.count"] == 1
        # the node's name is not part of the metric's
        assert not any("node-9" in k for k in get_registry().snapshot())

    def test_a_name_without_a_colon_is_the_module(self):
        loop = OpenrEventBase("acctplain")
        loop.run_in_thread()
        loop.call_and_wait(lambda: None)
        loop.stop()
        loop.join()
        assert "evb.acctplain.callbacks" in _evb_counters("acctplain")

    def test_no_flush_while_callbacks_are_queued(self):
        """The registry's lock is not on the callback path: a backlog
        of callbacks runs through with no flush between them."""
        loop = OpenrEventBase("acctstorm")
        flushes = []
        flush = loop._flush_account
        loop._flush_account = lambda: (flushes.append(1), flush())[1]
        loop.run_in_thread()
        gate = threading.Event()
        loop.run_in_event_base(gate.wait)
        _until(lambda: loop._running_since is not None)
        hits = []
        for _ in range(200):
            loop.run_in_event_base(lambda: hits.append(len(flushes)))
        gate.set()
        assert _until(lambda: len(hits) == 200)
        loop.stop()
        loop.join()
        # every queued callback saw the same number of flushes
        assert len(set(hits)) == 1
        assert get_registry().counter_get("evb.acctstorm.callbacks") >= 201

    def test_the_flushed_counters_add_up_to_the_loops_account(self):
        loop = OpenrEventBase("acctsum")
        loop.run_in_thread()
        for _ in range(5):
            loop.call_and_wait(lambda: time.sleep(0.002))
            time.sleep(0.005)
        loop.stop()
        loop.join()
        reg = get_registry()
        # run()'s exit flushes the rest
        assert reg.counter_get("evb.acctsum.callbacks") == loop.callbacks_run
        assert reg.counter_get("evb.acctsum.busy_ms") == pytest.approx(
            loop.busy_s * 1e3, abs=1e-6)
        assert reg.counter_get("evb.acctsum.idle_ms") == pytest.approx(
            loop.idle_s * 1e3, abs=1e-6)


class TestDebounceWindowTerms:
    def _fire_terms(self, evb, arm):
        """Run ``arm(debounce)`` on the loop; the terms seen in the
        callback, and what ``fired`` reads after it."""
        seen = []
        db = AsyncDebounce(evb, 0.02, 0.2, lambda: seen.append(db.fired))
        evb.run_in_event_base(lambda: arm(db))
        assert _until(lambda: seen)
        return seen[0], db

    def test_one_arm_asks_for_the_minimum_and_fires_after_it(self, evb):
        fired, db = self._fire_terms(evb, lambda db: db())
        assert isinstance(fired, FiredWindow)
        assert fired.deadline - fired.armed_at == pytest.approx(0.02, abs=1e-9)
        assert fired.late_s >= 0.0
        # the arming callback ended after the arm and before the fire
        assert fired.armed_at < fired.idle_since
        assert fired.idle_since <= fired.deadline + fired.late_s
        assert fired.busy_s > 0.0
        # only while the callback runs
        assert db.fired is None and not db.is_scheduled()

    def test_a_second_arm_extends_the_window_from_its_first(self, evb):
        def arm(db):
            db()
            time.sleep(0.004)
            db()

        fired, _ = self._fire_terms(evb, arm)
        # first arm -> the second arm's deadline (now + 0.04)
        assert fired.deadline - fired.armed_at >= 0.04 + 0.004 - 1e-6

    def test_the_next_window_has_terms_of_its_own(self, evb):
        seen = []
        db = AsyncDebounce(evb, 0.01, 0.1, lambda: seen.append(db.fired))
        evb.run_in_event_base(db)
        assert _until(lambda: len(seen) == 1)
        evb.run_in_event_base(db)
        assert _until(lambda: len(seen) == 2)
        assert seen[1].armed_at > seen[0].deadline
        assert seen[1].busy_s > seen[0].busy_s

    def test_work_that_outlasts_the_deadline_shows_in_the_terms(self, evb):
        def arm(db):
            db()
            time.sleep(0.06)  # 0.04 past the 0.02 deadline

        fired, _ = self._fire_terms(evb, arm)
        overrun = fired.idle_since - fired.deadline
        assert overrun >= 0.035
        # the lateness is the overrun plus the loop's own few lines
        assert fired.late_s >= overrun

    def test_a_raising_callback_still_clears_the_terms(self, evb):
        def boom():
            raise RuntimeError("contained by the loop")

        db = AsyncDebounce(evb, 0.005, 0.05, boom)
        evb.run_in_event_base(db)
        assert _until(lambda: not db.is_scheduled())
        evb.call_and_wait(lambda: None)
        assert db.fired is None

    def test_throttle_keeps_its_callback_signature(self, evb):
        hits = []
        th = AsyncThrottle(evb, 0.01, lambda: hits.append(1))
        # five calls in one callback: no timer can fire between them
        evb.run_in_event_base(lambda: [th() for _ in range(5)])
        assert _until(lambda: hits)
        time.sleep(0.03)
        assert hits == [1]
