"""Runtime substrate tests (reference analogues:
openr/messaging/tests/*, openr/common/tests/*)."""

import threading
import time

import pytest

from openr_tpu.messaging.queue import (
    QueueClosedError,
    QueueTimeoutError,
    ReplicateQueue,
)
from openr_tpu.utils.eventbase import (
    AsyncDebounce,
    AsyncThrottle,
    ExponentialBackoff,
    OpenrEventBase,
)
from openr_tpu.utils.stepdetector import StepDetector, StepDetectorConfig


class TestReplicateQueue:
    def test_fanout_to_all_readers(self):
        q = ReplicateQueue(name="test")
        r1, r2 = q.get_reader(), q.get_reader()
        q.push(1)
        q.push(2)
        assert [r1.get(0.1), r1.get(0.1)] == [1, 2]
        assert [r2.get(0.1), r2.get(0.1)] == [1, 2]
        assert q.num_writes == 2

    def test_reader_after_push_misses_history(self):
        q = ReplicateQueue()
        q.push("early")
        r = q.get_reader()
        with pytest.raises(QueueTimeoutError):
            r.get(timeout=0.05)

    def test_close_unblocks_readers(self):
        q = ReplicateQueue()
        r = q.get_reader()
        results = []

        def consume():
            try:
                r.get(timeout=5)
            except QueueClosedError:
                results.append("closed")

        t = threading.Thread(target=consume)
        t.start()
        time.sleep(0.05)
        q.close()
        t.join(timeout=2)
        assert results == ["closed"]

    def test_drain_before_closed_error(self):
        q = ReplicateQueue()
        r = q.get_reader()
        q.push(7)
        q.close()
        assert r.get(0.1) == 7
        with pytest.raises(QueueClosedError):
            r.get(0.1)

    def test_push_after_close_refused(self):
        q = ReplicateQueue()
        q.get_reader()
        q.close()
        assert q.push(1) is False


class TestEventBase:
    def test_run_in_event_base(self):
        evb = OpenrEventBase("t")
        evb.run_in_thread()
        hits = []
        evb.run_in_event_base(lambda: hits.append(threading.current_thread().name))
        time.sleep(0.1)
        evb.stop()
        evb.join()
        assert hits == ["t"]

    def test_call_and_wait_returns_value(self):
        evb = OpenrEventBase("t2")
        evb.run_in_thread()
        assert evb.call_and_wait(lambda: 41 + 1) == 42
        evb.stop()
        evb.join()

    def test_call_and_wait_propagates_exception(self):
        evb = OpenrEventBase("t3")
        evb.run_in_thread()

        def boom():
            raise ValueError("x")

        with pytest.raises(ValueError):
            evb.call_and_wait(boom)
        evb.stop()
        evb.join()

    def test_timers_fire_in_order(self):
        evb = OpenrEventBase("t4")
        evb.run_in_thread()
        hits = []
        evb.schedule_timeout(0.10, lambda: hits.append("b"))
        evb.schedule_timeout(0.02, lambda: hits.append("a"))
        time.sleep(0.3)
        evb.stop()
        evb.join()
        assert hits == ["a", "b"]

    def test_timer_cancel(self):
        evb = OpenrEventBase("t5")
        evb.run_in_thread()
        hits = []
        h = evb.schedule_timeout(0.05, lambda: hits.append("x"))
        h.cancel()
        time.sleep(0.15)
        evb.stop()
        evb.join()
        assert hits == []

    def test_queue_reader_delivers_on_loop_thread(self):
        evb = OpenrEventBase("t6")
        evb.run_in_thread()
        q = ReplicateQueue()
        r = q.get_reader()
        got = []
        evb.add_queue_reader(r, lambda m: got.append((m, threading.current_thread().name)))
        q.push("hello")
        time.sleep(0.3)
        evb.stop()
        evb.join()
        assert got == [("hello", "t6")]

    def test_a_reader_behind_a_loop_still_counts_what_the_loop_has_not_taken(
        self,
    ):
        """The event base's reader thread pops a message at once and
        queues a callback on the loop. The message stays in the
        reader's ``size()`` until the loop takes it, so a callback
        reads what waits behind the message it is handling (Decision's
        gates on its backlog), and a drained reader reads 0."""
        evb = OpenrEventBase("t7")
        evb.run_in_thread()
        q = ReplicateQueue()
        r = q.get_reader()
        entered, release = threading.Event(), threading.Event()
        seen = []

        def on_message(m):
            if m == "first":
                entered.set()
                release.wait(5.0)
            seen.append((m, r.size()))

        evb.add_queue_reader(r, on_message)
        q.push("first")
        assert entered.wait(5.0)
        q.push("second")
        q.push("third")
        deadline = time.time() + 5.0
        while r.size() != 2 and time.time() < deadline:
            time.sleep(0.005)
        # both are behind "first", whether or not the reader thread
        # has popped them yet
        assert r.size() == 2
        release.set()
        deadline = time.time() + 5.0
        while len(seen) < 3 and time.time() < deadline:
            time.sleep(0.005)
        evb.stop()
        evb.join()
        assert seen == [("first", 2), ("second", 1), ("third", 0)]
        assert r.size() == 0

    def test_hand_off_keeps_a_popped_message_in_the_depth(self):
        q = ReplicateQueue()
        r = q.get_reader()
        q.push("a")
        q.push("b")
        assert r.get(hand_off=True) == "a"
        assert r.size() == 2
        r.delivered()
        assert r.size() == 1
        assert r.get() == "b"  # a plain get is consumed at once
        assert r.size() == 0


class TestBackoffPrimitives:
    def test_exponential_backoff_doubles(self):
        b = ExponentialBackoff(0.1, 0.4)
        assert b.can_try_now()
        b.report_error()
        assert b.get_current_backoff() == pytest.approx(0.1)
        assert not b.can_try_now()
        b.report_error()
        assert b.get_current_backoff() == pytest.approx(0.2)
        b.report_error()
        b.report_error()
        assert b.get_current_backoff() == pytest.approx(0.4)
        assert b.at_max_backoff()
        b.report_success()
        assert b.can_try_now()

    def test_throttle_coalesces(self):
        evb = OpenrEventBase("th")
        evb.run_in_thread()
        hits = []
        th = AsyncThrottle(evb, 0.1, lambda: hits.append(1))
        for _ in range(20):
            th()
        time.sleep(0.3)
        assert len(hits) == 1
        evb.stop()
        evb.join()

    def test_debounce_extends_then_fires_once(self):
        evb = OpenrEventBase("db")
        evb.run_in_thread()
        hits = []
        db = AsyncDebounce(evb, 0.02, 0.2, lambda: hits.append(time.monotonic()))
        t0 = time.monotonic()
        for _ in range(5):
            db()
            time.sleep(0.005)
        time.sleep(0.6)
        assert len(hits) == 1
        # the repeated invocations should have extended beyond min backoff
        assert hits[0] - t0 > 0.02
        evb.stop()
        evb.join()

    def test_debounce_refires_after_idle(self):
        evb = OpenrEventBase("db2")
        evb.run_in_thread()
        hits = []
        db = AsyncDebounce(evb, 0.02, 0.1, lambda: hits.append(1))
        db()
        time.sleep(0.2)
        db()
        time.sleep(0.2)
        assert len(hits) == 2
        evb.stop()
        evb.join()


class TestStepDetector:
    def test_detects_step(self):
        steps = []
        sd = StepDetector(
            StepDetectorConfig(
                fast_window_size=3,
                slow_window_size=9,
                lower_threshold=2.0,
                upper_threshold=8.0,
                abs_threshold=10_000,
            ),
            steps.append,
        )
        for _ in range(20):
            sd.add_value(1000.0)
        assert steps == []
        for _ in range(20):
            sd.add_value(2000.0)
        assert len(steps) >= 1
        assert steps[0] == pytest.approx(2000.0, rel=0.05)

    def test_ignores_noise(self):
        steps = []
        sd = StepDetector(
            StepDetectorConfig(
                fast_window_size=3,
                slow_window_size=9,
                lower_threshold=2.0,
                upper_threshold=8.0,
                abs_threshold=10_000,
            ),
            steps.append,
        )
        import random

        rng = random.Random(1)
        for _ in range(100):
            sd.add_value(1000.0 + rng.uniform(-20, 20))
        assert steps == []
