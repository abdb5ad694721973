"""What ``decision.debounce`` says it waited for: the four terms it
closes with (``policy_ms``, ``busy_ms``, ``slack_ms``,
``timer_late_ms``), the ``decision.policy_idle`` span inside it, and a
full collection's ``process.gc_pause`` on the traces it stopped.

KvStore -> Decision through real queues, as ``test_decision_module.py``
drives them; the trace is read off the route update Decision emits.
Counts and identities: a time only ever appears beside the sleep that
made it, and only in an inequality a stalled thread cannot break (a
sleep only ever runs long; what lies inside a span stays inside it).
The terms' arithmetic is held to the microsecond on hand-made fires.
"""

import gc
import sys
import threading
import time
from dataclasses import replace

import pytest

from openr_tpu.decision.decision import Decision, DecisionPendingUpdates
from openr_tpu.kvstore.wrapper import KvStoreWrapper
from openr_tpu.messaging.queue import QueueTimeoutError, ReplicateQueue
from openr_tpu.models import topologies
from openr_tpu.telemetry import get_registry, get_tracer, install_gc_hook
from openr_tpu.telemetry.trace import _PAUSE_RING, Tracer
from openr_tpu.utils import keys as keyutil
from openr_tpu.utils import wire
from openr_tpu.utils.eventbase import FiredWindow, OpenrEventBase

TERMS = ("policy_ms", "busy_ms", "slack_ms", "timer_late_ms")
MIN_MS = 10.0


class Harness:
    """KvStore + Decision (host solver: nothing here is the device's)
    with the initial topology converged."""

    def __init__(self, debounce_max_s=0.25):
        self.store = KvStoreWrapper("store:a")
        self.route_q = ReplicateQueue(name="routeUpdates")
        self.reader = self.route_q.get_reader("test")
        self.decision = Decision(
            "a",
            kvstore_updates_queue=self.store.store.updates_queue,
            route_updates_queue=self.route_q,
            debounce_min_s=MIN_MS / 1e3,
            debounce_max_s=debounce_max_s,
            solver_backend="host",
        )
        self.store.start()
        self.decision.start()
        self.topo = topologies.build_topology(
            "line", [("a", "b", 1), ("b", "c", 2)])
        self._versions = {}
        for db in self.topo.adj_dbs.values():
            self.publish_adj(db)
        for pdb in self.topo.prefix_dbs.values():
            key = keyutil.prefix_db_key(pdb.this_node_name)
            self.store.set_key(key, wire.dumps(pdb), version=1,
                               originator=pdb.this_node_name)
        self.updates(first_timeout=10.0)

    def stop(self):
        self.decision.stop()
        self.store.stop()

    def publish_adj(self, adj_db):
        key = keyutil.adj_key(adj_db.this_node_name)
        v = self._versions[key] = self._versions.get(key, 0) + 1
        self.store.set_key(key, wire.dumps(adj_db), version=v,
                           originator=adj_db.this_node_name)

    def recost(self, node, metric):
        """Publish ``node``'s adjacencies at another metric."""
        db = self.topo.adj_dbs[node]
        self.publish_adj(replace(db, adjacencies=tuple(
            replace(a, metric=metric) for a in db.adjacencies)))

    def updates(self, first_timeout=5.0, quiet=0.3):
        out, wait = [], first_timeout
        while True:
            try:
                out.append(self.reader.get(timeout=wait))
                wait = quiet
            except QueueTimeoutError:
                return out

    def window(self):
        """The one traced update of the window just published."""
        traced = [u for u in self.updates() if u.trace is not None]
        assert len(traced) == 1, traced
        return traced[0].trace


@pytest.fixture
def harness():
    h = Harness()
    yield h
    h.stop()


def _span(trace, name):
    found = [s for s in trace.spans if s.name == name]
    assert len(found) == 1, (name, [s.name for s in trace.spans])
    return found[0]


def _account_ms(attrs):
    """What the terms say the window lasted, from its first arm to its
    fire: the policy, what the work outlasted it by, the timer."""
    return (attrs["policy_ms"] + max(0.0, -attrs["slack_ms"])
            + attrs["timer_late_ms"])


def _slow_prewarm(harness, seconds):
    """Make the opening callback's patch take ``seconds``."""
    calls = []

    def prewarm(area_link_states, trace=None):
        calls.append(time.perf_counter())
        time.sleep(seconds)

    harness.decision.spf_solver.prewarm = prewarm
    return calls


class TestWindowOfOnePublication:
    def test_the_span_closes_with_the_four_terms(self, harness):
        harness.recost("b", 3)
        debounce = _span(harness.window(), "decision.debounce")
        assert set(TERMS) | {"merged_updates"} == set(debounce.attrs)
        assert debounce.attrs["merged_updates"] == 1
        assert all(isinstance(debounce.attrs[t], float) for t in TERMS)

    def test_the_policy_asked_for_the_minimum(self, harness):
        harness.recost("b", 4)
        attrs = _span(harness.window(), "decision.debounce").attrs
        assert attrs["policy_ms"] == pytest.approx(MIN_MS, abs=1e-6)

    def test_the_terms_add_up_to_a_stretch_inside_the_span(self, harness):
        """policy + overrun + timer lateness = first arm -> fire, which
        the span holds: it opened before the arm (the arm's offset) and
        closes after the fire (the few lines to its close). The fire is
        where ``decision.policy_idle`` ends."""
        harness.recost("b", 5)
        trace = harness.window()
        debounce = _span(trace, "decision.debounce")
        idle = _span(trace, "decision.policy_idle")
        account = _account_ms(debounce.attrs)
        fire = idle.end_mark()[1]
        assert debounce._t0 <= fire - account / 1e3 + 1e-9
        assert fire <= debounce.end_mark()[1]
        assert account <= debounce.dur_ms

    def test_busy_and_idle_share_the_span_and_the_signs_hold(self, harness):
        harness.recost("b", 6)
        trace = harness.window()
        debounce = _span(trace, "decision.debounce")
        attrs = debounce.attrs
        # the opening callback ended after the arm it made
        assert attrs["slack_ms"] < attrs["policy_ms"]
        assert 0.0 <= attrs["timer_late_ms"]
        # callbacks and the policy's idle stretch do not overlap
        idle = _span(trace, "decision.policy_idle")
        assert 0.0 <= attrs["busy_ms"] <= debounce.dur_ms - idle.dur_ms

    def test_policy_idle_is_nested_and_is_slack_plus_lateness(self, harness):
        harness.recost("b", 7)
        trace = harness.window()
        debounce = _span(trace, "decision.debounce")
        idle = _span(trace, "decision.policy_idle")
        assert idle.closed and idle.depth == debounce.depth + 1
        assert idle.attrs == {}
        assert debounce.ts_ms <= idle.ts_ms
        assert (idle.ts_ms + idle.dur_ms
                <= debounce.ts_ms + debounce.dur_ms + 1e-6)
        assert idle.dur_ms == pytest.approx(
            debounce.attrs["slack_ms"] + debounce.attrs["timer_late_ms"],
            abs=1e-6)
        # recorded before the debounce span closes, after what ran in it
        names = [s.name for s in trace.spans]
        assert names.index("decision.policy_idle") \
            == names.index("decision.rebuild") - 1
        assert trace.well_formed()

    def test_the_hand_off_to_fib_still_starts_where_emit_closed(
            self, harness):
        """A span recorded after the fact is no hand-off: the next
        ``gap_span`` starts at the last live span's end."""
        harness.recost("b", 8)
        trace = harness.window()
        emit = _span(trace, "decision.emit")
        gap = trace.gap_span("fib.queue_wait")
        assert gap.ts_ms == pytest.approx(emit.ts_ms + emit.dur_ms, abs=1e-6)

    def test_existing_spans_keep_their_names_and_order(self, harness):
        harness.recost("b", 9)
        names = [s.name for s in harness.window().spans]
        assert names == [
            "kvstore.publish", "decision.queue_wait", "decision.debounce",
            "decision.policy_idle", "decision.rebuild",
            "decision.route_build", "decision.route_diff", "decision.emit",
        ]


class TestWorkThatOutlastsTheWait:
    def test_slack_is_negative_and_the_timer_is_not_blamed(self, harness):
        _slow_prewarm(harness, 0.03)  # 20 ms past the 10 ms deadline
        harness.recost("b", 11)
        trace = harness.window()
        debounce = _span(trace, "decision.debounce")
        attrs = debounce.attrs
        assert attrs["slack_ms"] < -15.0
        assert attrs["busy_ms"] >= 30.0
        assert attrs["policy_ms"] == pytest.approx(MIN_MS, abs=1e-6)
        assert 0.0 <= attrs["timer_late_ms"]
        assert _account_ms(attrs) <= debounce.dur_ms
        # the timer is charged from the callback's return, not from the
        # deadline 20 ms before it: nothing was left of the wait but
        # that stretch, and the idle span is it
        idle = _span(trace, "decision.policy_idle")
        assert idle.dur_ms == pytest.approx(attrs["timer_late_ms"], abs=1e-6)
        assert trace.well_formed()


class TestExtendedWindow:
    def test_two_publications_extend_the_policy_and_both_are_busy(
            self, harness):
        calls = _slow_prewarm(harness, 0.004)
        # both queue behind a held loop, so the second is taken as the
        # first's callback returns, however late this thread runs
        decision = harness.decision
        held, release = threading.Event(), threading.Event()
        decision.evb.run_in_event_base(
            lambda: (held.set(), release.wait(10.0)))
        assert held.wait(5.0)
        harness.recost("b", 12)
        harness.recost("c", 13)
        deadline = time.monotonic() + 5.0
        while decision._kv_reader.size() < 2 and time.monotonic() < deadline:
            time.sleep(0.001)
        assert decision._kv_reader.size() == 2
        release.set()
        trace = harness.window()
        debounce = _span(trace, "decision.debounce")
        attrs = debounce.attrs
        assert len(calls) == 2
        assert attrs["merged_updates"] == 2
        # first arm -> the second arm's deadline, 20 ms after an arm
        # that the first patch (4 ms) ran before: each patch follows its
        # callback's arm, and the span opened before the first
        assert attrs["policy_ms"] >= 2 * MIN_MS + 4.0
        assert attrs["policy_ms"] <= 2 * MIN_MS + (
            calls[1] - debounce._t0) * 1e3
        # both callbacks' patches ran inside the window
        assert attrs["busy_ms"] >= 8.0
        assert _account_ms(attrs) <= debounce.dur_ms
        assert trace.well_formed()


class TestWindowsThatNeverFire:
    def _decision(self):
        return Decision(
            "a",
            kvstore_updates_queue=ReplicateQueue(name="kv"),
            route_updates_queue=ReplicateQueue(name="routes"),
            solver_backend="host",
        )

    def test_an_aborted_window_leaves_no_unclosed_span(self):
        d = self._decision()
        reg, tracer = get_registry(), get_tracer()
        unclosed0 = reg.counter_get("telemetry.traces_unclosed_spans")
        bad0 = reg.counter_get("telemetry.traces_bad_nesting")
        trace = tracer.start("kvstore.publish")
        d.pending.adopt_trace(trace, d.evb)
        d.pending.release_trace()
        debounce = _span(trace, "decision.debounce")
        assert debounce.closed and debounce.attrs == {"aborted": True}
        assert not [s for s in trace.spans
                    if s.name == "decision.policy_idle"]
        assert trace.well_formed()
        tracer.finish(trace, ok=False)
        assert reg.counter_get("telemetry.traces_unclosed_spans") == unclosed0
        assert reg.counter_get("telemetry.traces_bad_nesting") == bad0
        # and the next window starts clean
        assert d.pending._busy_at_open is None

    def test_a_rebuild_that_no_timer_fired_says_nothing_of_a_wait(self):
        """``rebuild_routes`` reached directly (cold start's end, warm
        boot): the span closes as it did, with no term and no idle."""
        pending = DecisionPendingUpdates("a")
        trace = get_tracer().start("kvstore.publish")
        pending.adopt_trace(trace, OpenrEventBase("decision:a"))
        assert pending.move_out_trace() is trace
        debounce = _span(trace, "decision.debounce")
        assert debounce.attrs == {"merged_updates": 0}
        assert [s.name for s in trace.spans] == [
            "kvstore.publish", "decision.debounce"]

    def test_a_window_adopted_without_a_loop_says_nothing_either(self):
        pending = DecisionPendingUpdates("a")
        trace = get_tracer().start("kvstore.publish")
        pending.adopt_trace(trace)
        now = time.perf_counter()
        fired = FiredWindow(now - 0.01, now, 0.0003, now - 0.004, 1.0)
        pending.move_out_trace(fired)
        assert _span(trace, "decision.debounce").attrs == {
            "merged_updates": 0}

    def test_the_terms_of_a_hand_made_fire(self):
        """deadline 10 ms after the arm, last callback ended 4 ms before
        it, fired 0.3 ms late, 2.5 ms of callbacks since the span opened."""
        evb = OpenrEventBase("decision:a")
        evb.busy_s = 7.0
        pending = DecisionPendingUpdates("a")
        trace = get_tracer().start("kvstore.publish")
        pending.adopt_trace(trace, evb)
        t0 = _span(trace, "decision.debounce").mark_at(0.0)
        armed = time.perf_counter()
        fired = FiredWindow(
            armed, armed + 0.010, 0.0003, armed + 0.006, 7.0025)
        pending.move_out_trace(fired)
        attrs = _span(trace, "decision.debounce").attrs
        assert attrs["policy_ms"] == pytest.approx(10.0)
        assert attrs["busy_ms"] == pytest.approx(2.5)
        assert attrs["slack_ms"] == pytest.approx(4.0)
        assert attrs["timer_late_ms"] == pytest.approx(0.3)
        # the identity, to the microsecond: first arm -> fire
        assert _account_ms(attrs) == pytest.approx(10.3, abs=1e-3)
        idle = _span(trace, "decision.policy_idle")
        assert idle.dur_ms == pytest.approx(4.3)
        assert idle.end_mark()[1] == pytest.approx(armed + 0.0103, abs=1e-6)
        # on both clocks where the last callback ended
        assert idle.ts_ms - t0[0] == pytest.approx(
            (armed + 0.006) * 1e3, abs=1e-3)

    def test_the_terms_of_a_hand_made_overrun(self):
        evb = OpenrEventBase("decision:a")
        pending = DecisionPendingUpdates("a")
        trace = get_tracer().start("kvstore.publish")
        pending.adopt_trace(trace, evb)
        armed = time.perf_counter()
        # the callback ended 3 ms after the deadline, the fire 0.05 ms on
        fired = FiredWindow(
            armed, armed + 0.010, 0.00305, armed + 0.013, 0.013)
        pending.move_out_trace(fired)
        attrs = _span(trace, "decision.debounce").attrs
        assert attrs["slack_ms"] == pytest.approx(-3.0)
        assert attrs["timer_late_ms"] == pytest.approx(0.05)
        # policy + overrun + lateness = the deadline's 10 + late_s
        assert _account_ms(attrs) == pytest.approx(13.05, abs=1e-3)
        assert _span(trace, "decision.policy_idle").dur_ms \
            == pytest.approx(0.05)


class TestPausesLandOnTheTracesTheyStopped:
    def _trace(self, tracer):
        """A trace with one 2 ms span; (trace, a perf_counter inside it)."""
        trace = tracer.start("kvstore.publish")
        span = trace.begin_span("decision.rebuild")
        inside = time.perf_counter()
        time.sleep(0.002)
        trace.end_span(span)
        return trace, inside

    def test_a_pause_inside_the_extent_becomes_a_closed_span(self):
        tracer = Tracer(ring=4)
        paused0 = get_registry().counter_get("telemetry.traces_paused")
        trace, inside = self._trace(tracer)
        tracer.note_pause((time.time() * 1e3, inside), 1.25, 2)
        tracer.finish(trace)
        pause = _span(trace, "process.gc_pause")
        assert pause.closed and pause.dur_ms == 1.25
        assert pause.attrs == {"generation": 2}
        assert trace.complete and trace.well_formed()
        assert get_registry().counter_get("telemetry.traces_paused") \
            == paused0 + 1

    def test_a_pause_outside_the_extent_is_not_the_traces(self):
        tracer = Tracer(ring=4)
        before = time.perf_counter()
        paused0 = get_registry().counter_get("telemetry.traces_paused")
        trace, _ = self._trace(tracer)
        tracer.note_pause((time.time() * 1e3, before), 5.0, 2)
        tracer.note_pause((time.time() * 1e3, time.perf_counter()), 5.0, 2)
        tracer.finish(trace)
        assert [s.name for s in trace.spans] == [
            "kvstore.publish", "decision.rebuild"]
        assert get_registry().counter_get("telemetry.traces_paused") == paused0

    def test_two_pauses_in_one_trace_count_the_trace_once(self):
        tracer = Tracer(ring=4)
        paused0 = get_registry().counter_get("telemetry.traces_paused")
        trace, inside = self._trace(tracer)
        tracer.note_pause((time.time() * 1e3, inside), 0.4, 2)
        tracer.note_pause((time.time() * 1e3, inside + 1e-3), 0.4, 2)
        tracer.finish(trace)
        assert sum(s.name == "process.gc_pause" for s in trace.spans) == 2
        assert get_registry().counter_get("telemetry.traces_paused") \
            == paused0 + 1

    def test_the_ring_is_bounded(self):
        tracer = Tracer(ring=4)
        for i in range(3 * _PAUSE_RING + 1):
            tracer.note_pause((0.0, float(i)), 1.0, 2)
        assert len(tracer._pauses) == _PAUSE_RING
        kept = sorted(p[0][1] for p in tracer._pauses)
        assert kept == [float(i) for i in range(
            2 * _PAUSE_RING + 1, 3 * _PAUSE_RING + 1)]

    def test_a_tracer_that_saw_no_pause_adds_nothing(self):
        tracer = Tracer(ring=4)
        trace, _ = self._trace(tracer)
        tracer.finish(trace)
        assert [s.name for s in trace.spans] == [
            "kvstore.publish", "decision.rebuild"]

    def test_pauses_noted_from_many_threads_while_traces_finish(self):
        """The ring is written with no lock by whichever thread the
        collector runs on and read by the finishing thread: every pause
        a trace is given is a whole one and lies inside its extent."""
        tracer = Tracer(ring=4)
        stop = threading.Event()

        def note(worker):
            while not stop.is_set():
                tracer.note_pause(
                    (time.time() * 1e3, time.perf_counter()),
                    float(worker), 2)

        workers = [threading.Thread(target=note, args=(w,), daemon=True)
                   for w in range(1, 9)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for w in workers:
                w.start()
            deadline = time.monotonic() + 0.5
            finished = 0
            while time.monotonic() < deadline:
                trace = tracer.start("kvstore.publish")
                span = trace.begin_span("decision.rebuild")
                time.sleep(0.0005)
                trace.end_span(span)
                tracer.finish(trace)
                finished += 1
                t0, t1 = trace.spans[0].end_mark()[1], span.end_mark()[1]
                for p in trace.spans[2:]:
                    assert p.name == "process.gc_pause" and p.closed
                    assert p.dur_ms in {float(w) for w in range(1, 9)}
                    assert p.attrs == {"generation": 2}
                    assert t0 <= p.end_mark()[1] - p.dur_ms / 1e3 < t1
                assert trace.complete
        finally:
            stop.set()
            sys.setswitchinterval(interval)
            for w in workers:
                w.join(timeout=5.0)
        assert finished > 0
        assert not any(w.is_alive() for w in workers)

    @pytest.mark.parametrize("generation, spans", [(2, 1), (1, 0), (0, 0)])
    def test_the_collectors_hook_hands_over_full_collections_only(
            self, generation, spans):
        install_gc_hook()
        tracer, reg = get_tracer(), get_registry()
        assert "telemetry.traces_paused" in reg.snapshot()
        trace = tracer.start("kvstore.publish")
        span = trace.begin_span("decision.rebuild")
        collections0 = reg.counter_get("process.gc_gen2_collections")
        gc.collect(generation)
        trace.end_span(span)
        tracer.finish(trace)
        assert reg.counter_get("process.gc_gen2_collections") \
            == collections0 + spans
        pauses = [s for s in trace.spans if s.name == "process.gc_pause"]
        assert len(pauses) == spans
        for p in pauses:
            assert p.attrs == {"generation": 2} and p.dur_ms > 0.0
            # inside the span it stopped, on the spans' own clock
            assert span._t0 <= p._t0
            assert p.end_mark()[1] <= span.end_mark()[1]
        assert trace.well_formed()
