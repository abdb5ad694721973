"""Service-plane tests: queue backpressure instrumentation, the
rate-adaptive debounce FSM, shed-by-coalescing admission (oracle
parity: a seeded overload burst with shedding produces a RouteDatabase
bit-identical to the unshedded replay), Decision's emit stage, the
debounce-span reclaim path, the seedable load generator with
its ``load.generator`` fault seam, and a short end-to-end sustained run
through the real KvStore→Decision→Fib pipeline."""

import time

import pytest

from openr_tpu.decision.decision import Decision
from openr_tpu.faults import FaultSchedule, get_injector
from openr_tpu.load import (
    AdmissionConfig,
    AdmissionControl,
    DebounceController,
    EventMix,
    LoadGenerator,
    coalesce_publications,
)
from openr_tpu.load.harness import SustainedLoadHarness, percentiles
from openr_tpu.messaging.queue import ReplicateQueue, RQueue
from openr_tpu.models import topologies
from openr_tpu.telemetry import get_registry, get_tracer
from openr_tpu.types import Publication, Value
from openr_tpu.utils import wire
from openr_tpu.utils.eventbase import AsyncDebounce, ExponentialBackoff, OpenrEventBase

SEED = 20260805


@pytest.fixture(autouse=True)
def _clean_injector():
    get_injector().reset()
    yield
    get_injector().reset()


def _counter(name):
    return get_registry().counter_get(name)


# ---------------------------------------------------------------------------
# queue instrumentation
# ---------------------------------------------------------------------------


class TestQueueInstrumentation:
    def test_depth_age_hwm_export(self):
        q = ReplicateQueue(name="kv")
        r = q.get_reader("tst:depthq")
        q.push(1)
        q.push(2)
        q.push(3)
        snap = get_registry().snapshot()
        assert snap["messaging.queue.depth.tst_depthq"] == 3
        assert snap["messaging.queue.age_ms.tst_depthq"] >= 0.0
        assert r.high_watermark == 3
        assert _counter("messaging.queue.hwm.tst_depthq") == 3
        assert r.get() == 1
        assert get_registry().snapshot()[
            "messaging.queue.depth.tst_depthq"
        ] == 2
        # high-watermark is sticky
        assert r.high_watermark == 3

    def test_age_tracks_head_of_line(self):
        r = RQueue("tst:ageq")
        r._push("x")
        time.sleep(0.05)
        assert r.oldest_age_ms() >= 40.0
        r.get()
        assert r.oldest_age_ms() == 0.0

    def test_maxlen_drops_oldest_and_counts(self):
        q = ReplicateQueue(name="kv")
        r = q.get_reader("tst:boundedq", maxlen=2)
        before = _counter("messaging.queue.overflow.tst_boundedq")
        q.push("a")
        q.push("b")
        q.push("c")  # drops "a"
        assert r.size() == 2
        assert r.overflows == 1
        assert _counter("messaging.queue.overflow.tst_boundedq") == before + 1
        assert r.get() == "b"  # oldest was shed, newest state kept
        assert r.get() == "c"


# ---------------------------------------------------------------------------
# rate-adaptive debounce FSM
# ---------------------------------------------------------------------------


class _FakeDebounce:
    def __init__(self):
        self.maxes = []

    def set_max_backoff(self, max_s):
        self.maxes.append(max_s)


class TestDebounceControllerFSM:
    def test_widens_geometrically_to_cap(self):
        fake = _FakeDebounce()
        c = DebounceController(
            base_max_s=0.25, cap_s=2.0, widen_depth=8, narrow_depth=2,
            debounce=fake, metric_prefix="tstfsm1",
        )
        w0 = _counter("tstfsm1.debounce_widenings")
        assert c.observe(10) == DebounceController.WIDEN
        assert c.current_max_s == 0.5
        assert c.observe(10) == DebounceController.WIDEN
        assert c.observe(10) == DebounceController.WIDEN
        assert c.current_max_s == 2.0
        # saturated at the cap: no further widening
        assert c.observe(50) == DebounceController.STEADY
        assert c.current_max_s == 2.0
        assert fake.maxes == [0.5, 1.0, 2.0]
        assert _counter("tstfsm1.debounce_widenings") == w0 + 3

    def test_narrows_back_to_base(self):
        fake = _FakeDebounce()
        c = DebounceController(
            base_max_s=0.25, cap_s=1.0, widen_depth=8, narrow_depth=2,
            debounce=fake, metric_prefix="tstfsm2",
        )
        c.observe(9)
        c.observe(9)
        assert c.current_max_s == 1.0
        assert c.observe(0) == DebounceController.NARROW
        assert c.current_max_s == 0.5
        assert c.observe(1) == DebounceController.NARROW
        assert c.current_max_s == 0.25
        # at base: nothing to narrow
        assert c.observe(0) == DebounceController.STEADY
        assert c.current_max_s == 0.25

    def test_hysteresis_band_is_steady(self):
        c = DebounceController(
            base_max_s=0.25, cap_s=1.0, widen_depth=8, narrow_depth=2,
            metric_prefix="tstfsm3",
        )
        c.observe(9)
        assert c.current_max_s == 0.5
        # depth between narrow (2) and widen (8): hold position
        for depth in (3, 5, 7):
            assert c.observe(depth) == DebounceController.STEADY
        assert c.current_max_s == 0.5

    def test_gauge_exports_current_max(self):
        c = DebounceController(
            base_max_s=0.25, cap_s=1.0, metric_prefix="tstfsm4"
        )
        c.observe(9)
        assert get_registry().snapshot()["tstfsm4.debounce_max_ms"] == 500.0

    def test_applies_to_real_async_debounce(self):
        evb = OpenrEventBase("tst")
        fired = []
        deb = AsyncDebounce(evb, 0.01, 0.25, lambda: fired.append(1))
        c = DebounceController(
            base_max_s=0.25, cap_s=1.0, debounce=deb, metric_prefix="tstfsm5"
        )
        c.observe(9)
        assert deb.max_backoff_s == 0.5
        c.observe(0)
        assert deb.max_backoff_s == 0.25

    def test_exponential_backoff_set_max_clamps_current(self):
        b = ExponentialBackoff(0.01, 1.0)
        for _ in range(10):
            b.report_error()
        assert b.get_current_backoff() == 1.0
        b.set_max(0.1)
        assert b.get_current_backoff() == 0.1
        assert b.at_max_backoff()
        b.set_max(2.0)
        assert not b.at_max_backoff()


# ---------------------------------------------------------------------------
# shed-by-coalescing
# ---------------------------------------------------------------------------


def _pub(area="0", trace=None, expired=(), **kv):
    return Publication(
        key_vals={
            k: Value(version=v, originator_id="n", value=b"x%d" % v)
            for k, v in kv.items()
        },
        expired_keys=list(expired),
        area=area,
        trace=trace,
    )


class TestCoalescing:
    def test_last_version_wins(self):
        batch = coalesce_publications(
            [_pub(k1=1), _pub(k1=2), _pub(k1=3, k2=1)]
        )
        assert len(batch.publications) == 1
        merged = batch.publications[0]
        assert merged.key_vals["k1"].version == 3
        assert merged.key_vals["k2"].version == 1
        assert batch.keys_in == 4
        assert batch.keys_out == 2
        assert batch.keys_shed == 2

    def test_expiry_cancels_pending_value(self):
        batch = coalesce_publications(
            [_pub(k1=1), _pub(expired=("k1",)), _pub(k2=1)]
        )
        merged = batch.publications[0]
        assert "k1" not in merged.key_vals
        assert merged.expired_keys == ["k1"]
        assert merged.key_vals["k2"].version == 1

    def test_value_cancels_pending_expiry(self):
        batch = coalesce_publications(
            [_pub(expired=("k1",)), _pub(k1=5)]
        )
        merged = batch.publications[0]
        assert merged.expired_keys == []
        assert merged.key_vals["k1"].version == 5

    def test_areas_stay_separate(self):
        batch = coalesce_publications(
            [_pub(area="0", k1=1), _pub(area="1", k1=7)]
        )
        assert [p.area for p in batch.publications] == ["0", "1"]
        assert batch.publications[0].key_vals["k1"].version == 1
        assert batch.publications[1].key_vals["k1"].version == 7
        assert batch.keys_shed == 0

    def test_traces_arrival_ordered(self):
        t1, t2 = object(), object()
        batch = coalesce_publications(
            [_pub(trace=t1, k1=1), _pub(k1=2), _pub(trace=t2, k1=3)]
        )
        assert batch.traces == [t1, t2]


class TestAdmissionControl:
    def test_below_threshold_is_passthrough(self):
        ac = AdmissionControl(
            AdmissionConfig(shed_depth=4), metric_prefix="tstadm1"
        )
        reader = RQueue()
        pub = _pub(k1=1)
        batch = ac.admit(pub, reader)
        assert batch.publications == [pub]
        assert batch.pubs_in == 1
        assert batch.keys_shed == 0

    def test_deep_backlog_drains_and_sheds(self):
        ac = AdmissionControl(
            AdmissionConfig(shed_depth=3), metric_prefix="tstadm2"
        )
        reader = RQueue()
        for v in (2, 3, 4):
            reader._push(_pub(k1=v))
        s0 = _counter("tstadm2.admission.shed_keys")
        batch = ac.admit(_pub(k1=1), reader)
        assert reader.size() == 0
        assert batch.pubs_in == 4
        assert len(batch.publications) == 1
        assert batch.publications[0].key_vals["k1"].version == 4
        assert batch.keys_shed == 3
        assert _counter("tstadm2.admission.shed_keys") == s0 + 3

    def test_prewarm_gating(self):
        ac = AdmissionControl(
            AdmissionConfig(prewarm_depth_limit=2), metric_prefix="tstadm3"
        )
        assert ac.allow_prewarm(0)
        assert ac.allow_prewarm(2)
        p0 = _counter("tstadm3.admission.prewarm_skipped")
        assert not ac.allow_prewarm(3)
        assert _counter("tstadm3.admission.prewarm_skipped") == p0 + 1


# ---------------------------------------------------------------------------
# admission parity: seeded overload burst, shedded vs unshedded replay
# ---------------------------------------------------------------------------


def _decision(node, backend="host", **kw):
    return Decision(
        node,
        kvstore_updates_queue=ReplicateQueue(name="kv"),
        route_updates_queue=ReplicateQueue(name="routes"),
        solver_backend=backend,
        **kw,
    )


def _event_pub(ev, area="0"):
    return Publication(
        key_vals={
            ev.key: Value(
                version=ev.version, originator_id=ev.node, value=ev.payload
            )
        },
        area=area,
    )


def _route_db_bytes(d, node):
    return wire.dumps(d.route_db.to_route_db(node))


class TestAdmissionParity:
    def test_coalesced_burst_bit_identical_to_full_replay(self):
        topo = topologies.fat_tree_nodes(24)
        node = next(n for n in sorted(topo.adj_dbs) if n.startswith("rsw"))
        gen = LoadGenerator(topo, seed=SEED)
        initial = gen.initial_key_vals()
        burst = [
            _event_pub(ev, topo.area)
            for ev in gen.events(120)
            if not ev.dropped
        ]

        full = _decision(node)
        shed = _decision(node)
        for d in (full, shed):
            d.process_publication(
                Publication(key_vals=dict(initial), area=topo.area)
            )
            d.rebuild_routes("INIT")

        # unshedded: every publication replayed individually
        for pub in burst:
            full.process_publication(pub)
        full.rebuild_routes("FULL")

        # shedded: the whole burst coalesced to net effect
        batch = coalesce_publications(burst)
        assert batch.keys_shed > 0, "seeded burst must actually shed"
        for pub in batch.publications:
            shed.process_publication(pub)
        shed.rebuild_routes("SHED")

        assert _route_db_bytes(full, node) == _route_db_bytes(shed, node)

    def test_burst_with_flaps_and_prefix_churn_parity(self):
        topo = topologies.fat_tree_nodes(24)
        node = next(n for n in sorted(topo.adj_dbs) if n.startswith("rsw"))
        gen = LoadGenerator(
            topo,
            seed=SEED + 1,
            mix=EventMix(metric_churn=0.3, link_flap=0.4, prefix_update=0.3),
        )
        initial = gen.initial_key_vals()
        burst = [_event_pub(ev, topo.area) for ev in gen.events(80)]

        full = _decision(node)
        shed = _decision(node)
        for d in (full, shed):
            d.process_publication(
                Publication(key_vals=dict(initial), area=topo.area)
            )
            d.rebuild_routes("INIT")
        for pub in burst:
            full.process_publication(pub)
        full.rebuild_routes("FULL")
        for pub in coalesce_publications(burst).publications:
            shed.process_publication(pub)
        shed.rebuild_routes("SHED")
        assert _route_db_bytes(full, node) == _route_db_bytes(shed, node)


# ---------------------------------------------------------------------------
# emit stage
# ---------------------------------------------------------------------------


class TestEmitStage:
    def test_emit_stage_closes_rebuild_span(self):
        topo = topologies.fat_tree_nodes(24)
        node = next(n for n in sorted(topo.adj_dbs) if n.startswith("rsw"))
        gen = LoadGenerator(topo, seed=SEED)
        d = _decision(node)
        d.process_publication(
            Publication(key_vals=dict(gen.initial_key_vals()), area=topo.area)
        )
        trace = get_tracer().start("kvstore.publish")
        d.pending.adopt_trace(trace)
        d.rebuild_routes("STEP")
        assert all(s.closed for s in trace.spans)
        assert trace.well_formed()


# ---------------------------------------------------------------------------
# debounce-span reclaim (the overload leak fix)
# ---------------------------------------------------------------------------


class TestSpanReclaim:
    def test_reset_closes_adopted_span(self):
        from openr_tpu.decision.decision import DecisionPendingUpdates

        pending = DecisionPendingUpdates("a")
        trace = get_tracer().start("kvstore.publish")
        pending.adopt_trace(trace)
        assert any(not s.closed for s in trace.spans)
        r0 = _counter("decision.debounce_spans_reclaimed")
        pending.reset()
        assert all(s.closed for s in trace.spans)
        assert _counter("decision.debounce_spans_reclaimed") == r0 + 1
        assert pending.trace is None

    def test_move_out_then_reset_reclaims_nothing(self):
        from openr_tpu.decision.decision import DecisionPendingUpdates

        pending = DecisionPendingUpdates("a")
        trace = get_tracer().start("kvstore.publish")
        pending.adopt_trace(trace)
        assert pending.move_out_trace() is trace
        r0 = _counter("decision.debounce_spans_reclaimed")
        pending.reset()
        assert _counter("decision.debounce_spans_reclaimed") == r0


# ---------------------------------------------------------------------------
# tracer finish listeners
# ---------------------------------------------------------------------------


class TestFinishListener:
    def test_listener_sees_finishes_and_removes_cleanly(self):
        tracer = get_tracer()
        seen = []
        fn = lambda trace, ok: seen.append((trace.trace_id, ok))  # noqa: E731
        tracer.add_finish_listener(fn)
        try:
            t = tracer.start("kvstore.publish")
            tracer.finish(t, ok=True)
            assert seen == [(t.trace_id, True)]
        finally:
            tracer.remove_finish_listener(fn)
        t2 = tracer.start("kvstore.publish")
        tracer.finish(t2, ok=True)
        assert len(seen) == 1

    def test_raising_listener_never_poisons_finish(self):
        tracer = get_tracer()

        def bad(trace, ok):
            raise RuntimeError("listener bug")

        tracer.add_finish_listener(bad)
        try:
            e0 = _counter("telemetry.finish_listener_errors")
            tracer.finish(tracer.start("kvstore.publish"), ok=True)
            assert _counter("telemetry.finish_listener_errors") == e0 + 1
        finally:
            tracer.remove_finish_listener(bad)


# ---------------------------------------------------------------------------
# load generator + load.generator fault seam
# ---------------------------------------------------------------------------


class TestLoadGenerator:
    def test_deterministic_schedule(self):
        topo = topologies.fat_tree_nodes(24)
        runs = []
        for _ in range(2):
            g = LoadGenerator(topologies.fat_tree_nodes(24), seed=SEED)
            g.initial_key_vals()
            runs.append(
                [(e.kind, e.key, e.version, e.payload) for e in g.events(60)]
            )
        assert runs[0] == runs[1]
        assert topo.area == "0"

    def test_mix_weights_respected(self):
        g = LoadGenerator(topologies.fat_tree_nodes(24), seed=SEED)
        g.initial_key_vals()
        kinds = [e.kind for e in g.events(600)]
        assert kinds.count("metric_churn") > kinds.count("link_flap")
        assert kinds.count("link_flap") > 0
        assert kinds.count("prefix_update") > 0

    def test_fault_seam_drops_without_mutation(self):
        g = LoadGenerator(topologies.fat_tree_nodes(24), seed=SEED)
        g.initial_key_vals()
        get_injector().arm("load.generator", FaultSchedule.fail_n(5))
        f0 = _counter("faults.injected.load.generator")
        versions_before = dict(g.versions)
        evs = g.events(5)
        assert all(e.dropped for e in evs)
        assert g.dropped == 5
        assert g.versions == versions_before  # no state mutated
        assert _counter("faults.injected.load.generator") == f0 + 5
        get_injector().disarm("load.generator")
        # stream resumes normally after the storm
        ev = g.next_event()
        assert not ev.dropped and ev.payload is not None

    def test_flap_withdraw_then_restore_round_trips(self):
        g = LoadGenerator(
            topologies.fat_tree_nodes(24),
            seed=SEED,
            mix=EventMix(metric_churn=0.0, link_flap=1.0, prefix_update=0.0),
        )
        g.initial_key_vals()
        evs = g.events(40)
        assert all(e.kind == "link_flap" for e in evs)
        # every withdrawn adjacency either returns or is tracked down
        total_adjs = sum(len(db.adjacencies) for db in g.adj_dbs.values())
        orig = sum(
            len(db.adjacencies)
            for db in topologies.fat_tree_nodes(24).adj_dbs.values()
        )
        assert total_adjs + len(g._down) == orig


# ---------------------------------------------------------------------------
# percentile helper
# ---------------------------------------------------------------------------


def test_percentiles_interpolation():
    out = percentiles(list(map(float, range(1, 101))))
    assert out["p50"] == 50.5
    assert out["p99"] == pytest.approx(99.01)
    assert percentiles([])["p99"] is None
    assert percentiles([7.0])["p50"] == 7.0


# ---------------------------------------------------------------------------
# end-to-end: short sustained run through the real pipeline
# ---------------------------------------------------------------------------


class TestSustainedMiniRun:
    def test_fixed_rate_run_bounded_and_parity(self):
        h = SustainedLoadHarness(
            nodes=16,
            seed=SEED,
            solver_backend="host",
            debounce_max_s=0.05,
            admission=AdmissionConfig(shed_depth=4, cap_s=0.4),
        )
        h.start(initial_timeout_s=120.0)
        try:
            report = h.run_fixed_rate(120, 1.2, p99_slo_ms=2000.0)
            assert report.published > 0
            assert report.drained, "pipeline failed to drain after window"
            assert report.traces_malformed == 0
            assert report.e2e_samples > 0
            assert report.e2e_ms["p99"] is not None
            assert h.check_parity(), (
                "shedded live route db != unshedded oracle replay"
            )
        finally:
            h.stop()
