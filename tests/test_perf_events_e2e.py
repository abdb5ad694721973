"""End-to-end PerfEvents + trace propagation:
KvStore -> Decision (debounced, oldest-chain merge) -> Fib.perf_db.

Covers the convergence-accounting invariants the telemetry spine
reports against:
- an adjacency update's perf chain survives Decision's oldest-chain
  merge (PendingUpdates._add_update) and lands in Fib.perf_db,
- the surviving chain is the OLDEST of a debounced batch,
- event timestamps are monotonically non-decreasing along the chain,
- the telemetry trace born at kvstore publication is finished by Fib
  with every span closed (publication -> debounce -> rebuild ->
  program),
- with the device backend the trace is the whole span tree (queue
  waits, route build with the view's sync / dispatch / readback inside
  it, diff, emit), well nested in both solver formulations.
"""

import dataclasses
import threading
import time

import pytest

from openr_tpu.decision import spf_solver
from openr_tpu.decision.decision import Decision
from openr_tpu.fib.fib import Fib
from openr_tpu.kvstore.wrapper import KvStoreWrapper
from openr_tpu.messaging.queue import ReplicateQueue
from openr_tpu.models import topologies
from openr_tpu.platform.fib_service import MockFibAgent
from openr_tpu.telemetry import get_registry, get_tracer
from openr_tpu.types import AdjacencyDatabase, PerfEvent, PerfEvents
from openr_tpu.utils import keys as keyutil
from openr_tpu.utils import wire


def wait_until(pred, timeout=10.0, step=0.01):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(step)
    return pred()


class PipelineHarness:
    """KvStore -> Decision -> Fib wired through real queues (host
    solver: these tests assert accounting, not kernels)."""

    def __init__(self, my_node="a", solver_backend="host",
                 debounce_min_s=0.05, debounce_max_s=0.25):
        self.store = KvStoreWrapper(f"store:{my_node}")
        self.route_q = ReplicateQueue(name="routeUpdates")
        self.decision = Decision(
            my_node,
            kvstore_updates_queue=self.store.store.updates_queue,
            route_updates_queue=self.route_q,
            debounce_min_s=debounce_min_s,
            debounce_max_s=debounce_max_s,
            solver_backend=solver_backend,
        )
        self.agent = MockFibAgent()
        self.fib = Fib(
            my_node,
            self.agent,
            self.route_q,
            keepalive_interval_s=5.0,
        )
        self.store.start()
        self.decision.start()
        self.fib.start()
        self._versions = {}

    def stop(self):
        self.fib.stop()
        self.decision.stop()
        self.store.stop()

    def publish_adj(self, adj_db: AdjacencyDatabase):
        key = keyutil.adj_key(adj_db.this_node_name)
        v = self._versions[key] = self._versions.get(key, 0) + 1
        self.store.set_key(
            key,
            wire.dumps(adj_db),
            version=v,
            originator=adj_db.this_node_name,
        )

    def publish_prefixes(self, prefix_db):
        key = keyutil.prefix_db_key(prefix_db.this_node_name)
        v = self._versions[key] = self._versions.get(key, 0) + 1
        self.store.set_key(
            key,
            wire.dumps(prefix_db),
            version=v,
            originator=prefix_db.this_node_name,
        )


def line_topology():
    return topologies.build_topology(
        "line", [("a", "b", 1), ("b", "c", 2)]
    )


def with_perf(adj_db: AdjacencyDatabase, unix_ts: int) -> AdjacencyDatabase:
    """Stamp an origination chain, as LinkMonitor does on advertise."""
    return AdjacencyDatabase(
        this_node_name=adj_db.this_node_name,
        is_overloaded=adj_db.is_overloaded,
        adjacencies=adj_db.adjacencies,
        node_label=adj_db.node_label,
        area=adj_db.area,
        perf_events=PerfEvents(
            events=[
                PerfEvent(
                    node_name=adj_db.this_node_name,
                    event_descr="ADJ_DB_UPDATED",
                    unix_ts=unix_ts,
                )
            ]
        ),
    )


@pytest.fixture
def harness():
    h = PipelineHarness()
    yield h
    h.stop()


class TestPerfEventsEndToEnd:
    def test_adj_chain_reaches_fib_perf_db_monotone(self, harness):
        topo = line_topology()
        now_ms = int(time.time() * 1000)
        for db in topo.adj_dbs.values():
            harness.publish_adj(with_perf(db, now_ms))
        for pdb in topo.prefix_dbs.values():
            harness.publish_prefixes(pdb)

        assert wait_until(lambda: len(harness.fib.perf_db) >= 1)
        chain = harness.fib.perf_db[-1]
        descrs = [e.event_descr for e in chain.events]
        assert descrs[0] == "ADJ_DB_UPDATED"
        assert "DECISION_RECEIVED" in descrs
        assert "ROUTE_UPDATE" in descrs
        assert descrs[-1] == "FIB_ROUTE_DB_RECVD"
        stamps = [e.unix_ts for e in chain.events]
        assert stamps == sorted(stamps), (
            f"perf chain timestamps not monotone: {list(zip(descrs, stamps))}"
        )

    def test_oldest_chain_survives_debounce_merge(self, harness):
        """Two adjacency updates in one debounce window: the NEWER
        chain arrives first, the OLDER second — the merged batch must
        report convergence from the oldest origination."""
        topo = line_topology()
        for pdb in topo.prefix_dbs.values():
            harness.publish_prefixes(pdb)
        now_ms = int(time.time() * 1000)
        # newer chain first (ts = now), older chain second (ts = -2s)
        harness.publish_adj(with_perf(topo.adj_dbs["a"], now_ms))
        harness.publish_adj(
            with_perf(topo.adj_dbs["b"], now_ms - 2000)
        )
        harness.publish_adj(with_perf(topo.adj_dbs["c"], now_ms))

        assert wait_until(lambda: len(harness.fib.perf_db) >= 1)
        chain = harness.fib.perf_db[-1]
        assert chain.events[0].event_descr == "ADJ_DB_UPDATED"
        assert chain.events[0].unix_ts == now_ms - 2000
        assert chain.events[0].node_name == "b"

    def test_trace_completes_publication_to_fib(self, harness):
        tracer = get_tracer()
        # by trace id, not by the ring's length: the ring is bounded,
        # and full once earlier tests of this process retired 256 traces
        newest = max((t.trace_id for t in tracer.traces()), default=0)
        topo = line_topology()
        for db in topo.adj_dbs.values():
            harness.publish_adj(db)
        for pdb in topo.prefix_dbs.values():
            harness.publish_prefixes(pdb)

        def retired_since():
            return [t for t in tracer.traces() if t.trace_id > newest]

        assert wait_until(lambda: bool(retired_since()))
        new = retired_since()
        done = [t for t in new if t.complete]
        assert done, [t.to_dict() for t in new]
        t = done[-1]
        names = [s.name for s in t.spans]
        assert names[0] == "kvstore.publish"
        assert "decision.debounce" in names
        assert "decision.rebuild" in names
        assert names[-1] == "fib.program"
        assert t.well_formed()
        assert t.e2e_ms is not None and t.e2e_ms >= 0.0
        # debounce ran: its span must be >= the configured minimum
        debounce = next(
            s for s in t.spans if s.name == "decision.debounce"
        )
        assert debounce.dur_ms >= 40.0  # 50ms debounce, clock slack


# the span tree of one adjacency event through the dense device path,
# in the order the spans open, with their depths: the publication that
# opens the window stages the view solve inside the debounce span, the
# rest of the wait is recorded at the fire as decision.policy_idle, and
# the rebuild lands on the solved view (nothing of the solver's under
# the route build)
ADJ_EVENT_TREE = [
    ("kvstore.publish", 0),
    ("decision.queue_wait", 0),
    ("decision.debounce", 0),
    ("decision.speculate", 1),
    ("graph.view_sync", 2),
    ("ops.spf_view_batch", 2),
    ("ops.solve_readback", 2),
    ("decision.policy_idle", 1),
    ("decision.rebuild", 0),
    ("decision.route_build", 1),
    ("decision.route_diff", 1),
    ("decision.emit", 0),
    ("fib.queue_wait", 0),
    ("fib.program", 0),
]
# the same event over the resident sliced-ELL bands: the patch runs
# first inside the debounce span, and the staged solve is the fused
# reconverge
ELL_ADJ_EVENT_TREE = (
    ADJ_EVENT_TREE[:3]
    + [("decision.prewarm", 1), ("ops.ell_patch", 2),
       ("ops.ell_scatter", 2)]
    + ADJ_EVENT_TREE[3:5]
    + [("ops.ell_reconverge", 2)]
    + ADJ_EVENT_TREE[6:]
)
# a window whose stage was not there for the rebuild (none made, or
# superseded): the rebuild solves for itself, inside the route build
SERIAL_VIEW_SPANS = {
    "dense": [("graph.view_sync", 2), ("ops.spf_view_batch", 2),
              ("ops.solve_readback", 2)],
    "ell": [("graph.view_sync", 2), ("ops.ell_reconverge", 2),
            ("ops.solve_readback", 2)],
}
PREFIX_EVENT_TREE = [
    ("kvstore.publish", 0),
    ("decision.queue_wait", 0),
    ("decision.debounce", 0),
    ("decision.policy_idle", 1),
    ("decision.rebuild", 0),
    ("decision.route_build", 1),
    ("decision.emit", 0),
    ("fib.queue_wait", 0),
    ("fib.program", 0),
]
SLACK_MS = 0.05  # two clocks (wall for starts, perf_counter for lengths)


def _end(span):
    return span.ts_ms + span.dur_ms


def _assert_tree(trace, expected):
    assert trace.complete and trace.well_formed(), trace.to_dict()
    assert [(s.name, s.depth) for s in trace.spans] == expected
    stack = []  # the open ancestors of the span at hand
    last_at_depth = {}
    for span in trace.spans:
        del stack[span.depth:]
        if stack:
            parent = stack[-1]
            assert parent.ts_ms - SLACK_MS <= span.ts_ms
            assert _end(span) <= _end(parent) + SLACK_MS, (
                span.name, parent.name)
        older = last_at_depth.get(span.depth)
        if older is not None:
            # siblings (and successive top-level stages) never overlap
            assert _end(older) <= span.ts_ms + SLACK_MS, (
                older.name, span.name)
        # a deeper span seen earlier belongs to an older parent
        last_at_depth = {
            d: s for d, s in last_at_depth.items() if d < span.depth
        }
        last_at_depth[span.depth] = span
        stack.append(span)


class TestSpanTreeEndToEnd:
    @pytest.mark.parametrize("formulation", ["dense", "ell"])
    def test_device_pipeline_yields_the_whole_tree(
        self, formulation, monkeypatch
    ):
        reg, tracer = get_registry(), get_tracer()
        if formulation == "ell":
            monkeypatch.setattr(spf_solver, "SPARSE_NODE_THRESHOLD", 2)

        def counters():
            return {
                name: reg.counter_get(name)
                for name in (
                    "decision.device_solves",
                    "telemetry.traces_bad_nesting",
                    "telemetry.traces_unclosed_spans",
                    "ops.host_dispatches",
                )
            }

        def event_trace(key, n_before):
            """The finished trace of the publication of ``key``."""
            def find():
                return [
                    t for t in tracer.traces()
                    if t.trace_id > n_before
                    and t.spans[0].attrs.get("keys") == [key]
                ]
            assert wait_until(lambda: bool(find())), [
                t.to_dict() for t in tracer.traces()[-3:]]
            return find()[-1]

        def decision_idle():
            """Nothing queued for Decision, no window open, no rebuild
            under way (the question is asked on its own loop)."""
            d = h.decision
            return d.evb.call_and_wait(lambda: (
                d._kv_reader.size() == 0
                and not d._rebuild_debounced.is_scheduled()
                and d.pending.count == 0
            ))

        h = PipelineHarness(solver_backend="device")
        try:
            topo = line_topology()
            # adjacencies last. On a loaded machine the load falls into
            # several debounce windows, and a last window of prefix keys
            # alone runs the per-prefix pass, which leaves the solver's
            # table stamped with the older prefix state: the event's
            # build below then fills a new table (touched: 5, the diff's
            # path "whole"). With an adjacency in it, the load's last
            # window is a whole build over the final prefix state
            # however the windows fall.
            for pdb in topo.prefix_dbs.values():
                h.publish_prefixes(pdb)
            for db in topo.adj_dbs.values():
                h.publish_adj(db)
            assert wait_until(lambda: len(h.fib.unicast_routes) >= 2)
            assert wait_until(decision_idle)
            before = counters()
            newest = max(t.trace_id for t in tracer.traces())

            # an adjacency event: a metric change on b's links
            b = topo.adj_dbs["b"]
            h.publish_adj(dataclasses.replace(b, adjacencies=tuple(
                dataclasses.replace(adj, metric=adj.metric + 3)
                for adj in b.adjacencies
            )))
            trace = event_trace("adj:b", newest)
            by_name = {s.name: s for s in trace.spans}
            if formulation == "ell":
                _assert_tree(trace, ELL_ADJ_EVENT_TREE)
                # prewarm patched b and its two neighbours' rows inside
                # the window, so the rebuild finds the bands current
                assert by_name["decision.prewarm"].attrs == {"rows": 3}
                assert by_name["graph.view_sync"].attrs == {
                    "formulation": "ell", "rows": 0}
            else:
                _assert_tree(trace, ADJ_EVENT_TREE)
                assert by_name["graph.view_sync"].attrs == {
                    "formulation": "dense", "rows": 3}
                assert set(by_name["ops.spf_view_batch"].attrs) == {
                    "batch", "n_pad"}
            # the build patched the solver's table at b's prefix and b's
            # label route, and the diff read those two keys
            assert by_name["decision.route_build"].attrs == {
                "full": True, "prefixes": 3, "rung": "warm", "touched": 2}
            assert by_name["ops.solve_readback"].attrs["bytes"] > 0
            assert set(by_name["decision.route_diff"].attrs) == {
                "updated", "deleted", "identical", "compared",
                "path", "why"}
            assert by_name["decision.route_diff"].attrs["path"] == "carried"
            # the route engine's accounting is not the rebuild's
            assert set(by_name["decision.rebuild"].attrs) == {
                "full_rebuild", "routes_updated", "routes_deleted",
                "areas", "areas_moved"}
            # one area, and the event moved its graph
            assert by_name["decision.rebuild"].attrs["areas"] == 1
            assert by_name["decision.rebuild"].attrs["areas_moved"] == 1
            after_adj = counters()
            assert after_adj["decision.device_solves"] == (
                before["decision.device_solves"] + 1)

            # a prefix-only event: the per-prefix branch, no view, no
            # device solve, no full-db diff
            h.publish_prefixes(dataclasses.replace(
                topo.prefix_dbs["c"], prefix_entries=()))
            trace = event_trace("prefix:c", newest)
            _assert_tree(trace, PREFIX_EVENT_TREE)
            build = next(
                s for s in trace.spans if s.name == "decision.route_build")
            assert build.attrs == {
                "full": False, "prefixes": 1, "rung": "warm"}
            after_prefix = counters()
            assert after_prefix["decision.device_solves"] == (
                after_adj["decision.device_solves"])
            for name in ("telemetry.traces_bad_nesting",
                         "telemetry.traces_unclosed_spans",
                         "ops.host_dispatches"):
                assert after_prefix[name] == before[name], name
        finally:
            h.stop()

    def test_prewarm_is_entered_with_the_window_already_armed(
        self, monkeypatch
    ):
        """``_on_publication`` arms the debounce timer BEFORE it calls
        ``SpfSolver.prewarm``, so the publication-time band patch runs
        inside the policy wait instead of ahead of it. The span tree is
        what it was (``decision.prewarm`` inside ``decision.debounce``,
        same ``rows``), and a publication with no route impact arms
        nothing and patches nothing."""
        reg, tracer = get_registry(), get_tracer()
        monkeypatch.setattr(spf_solver, "SPARSE_NODE_THRESHOLD", 2)
        h = PipelineHarness(solver_backend="device")
        entered = []  # (window armed?, a resident band is stale?)
        solver = h.decision.spf_solver
        real_prewarm = solver.prewarm

        def spy(area_link_states, trace=None):
            stale = False
            for ls in area_link_states.values():
                entry = spf_solver._ELL_RESIDENT._cache.get(ls)
                stale = stale or (
                    entry is not None
                    and entry[0] != ls.topology_version
                )
            entered.append(
                (h.decision._rebuild_debounced.is_scheduled(), stale)
            )
            return real_prewarm(area_link_states, trace=trace)

        monkeypatch.setattr(solver, "prewarm", spy)
        try:
            topo = line_topology()
            for db in topo.adj_dbs.values():
                h.publish_adj(db)
            for pdb in topo.prefix_dbs.values():
                h.publish_prefixes(pdb)
            assert wait_until(lambda: len(h.fib.unicast_routes) >= 2)
            time.sleep(0.4)  # the last debounce window of the load
            assert not h.decision._rebuild_debounced.is_scheduled()
            del entered[:]
            prewarms = reg.counter_get("decision.ell_prewarms")
            no_impact = reg.counter_get("telemetry.traces_no_route_impact")
            newest = max(t.trace_id for t in tracer.traces())

            # no route impact: nothing armed, prewarm never entered
            h.store.set_key(
                keyutil.fib_time_key("b"), b"12.5", version=1,
                originator="b",
            )
            assert wait_until(
                lambda: reg.counter_get("telemetry.traces_no_route_impact")
                == no_impact + 1
            )
            assert not h.decision._rebuild_debounced.is_scheduled()
            assert entered == []

            # an adjacency event over resident ELL state: the patch has
            # work to do, and finds the timer running when it starts
            b = topo.adj_dbs["b"]
            h.publish_adj(dataclasses.replace(b, adjacencies=tuple(
                dataclasses.replace(adj, metric=adj.metric + 3)
                for adj in b.adjacencies
            )))

            def finished():
                return [
                    t for t in tracer.traces()
                    if t.trace_id > newest
                    and t.spans[0].attrs.get("keys") == ["adj:b"]
                ]
            assert wait_until(lambda: bool(finished()))
            assert entered == [(True, True)]
            assert reg.counter_get("decision.ell_prewarms") == prewarms + 1
            trace = finished()[-1]
            _assert_tree(trace, ELL_ADJ_EVENT_TREE)
            by_name = {s.name: s for s in trace.spans}
            assert by_name["decision.prewarm"].attrs == {"rows": 3}
            assert by_name["graph.view_sync"].attrs == {
                "formulation": "ell", "rows": 0}
        finally:
            h.stop()


SPEC_COUNTERS = (
    "ops.spec_dispatches", "ops.spec_hits", "ops.spec_cancels",
    "ops.spec_skips", "decision.device_solves",
)
SPECULATION_CASES = (
    "one_publication", "two_in_one_window", "prefix_only",
    "backlog_behind_the_opener", "prefix_opens_adjacency_joins",
    "armed_fault", "span_tree",
)


def _reweighted(adj_db, by):
    return dataclasses.replace(adj_db, adjacencies=tuple(
        dataclasses.replace(adj, metric=adj.metric + by)
        for adj in adj_db.adjacencies
    ))


def _host_routes(decision):
    """What the host backend builds from the LSDB Decision holds."""
    solver = spf_solver.SpfSolver(decision.my_node_name, backend="host")
    return solver.build_route_db(
        decision.my_node_name, decision.area_link_states,
        decision.prefix_state,
    ).unicast_routes


class TestSpeculationAtWindowOpening:
    """The first route-affecting publication of a debounce window that
    finds nothing queued behind it (alone in its window: the one that
    opens it) stages the root's view solve under the policy wait,
    after the timer is armed and the patch has run; the rebuild lands
    on the solved view. Counts, not times, in both solver
    formulations."""

    @pytest.mark.parametrize("case", SPECULATION_CASES)
    @pytest.mark.parametrize("formulation", ["dense", "ell"])
    def test_the_opening_publication_stages_the_view(
        self, formulation, case, monkeypatch
    ):
        from openr_tpu.faults.injector import FaultSchedule, get_injector

        reg, tracer = get_registry(), get_tracer()
        if formulation == "ell":
            monkeypatch.setattr(spf_solver, "SPARSE_NODE_THRESHOLD", 2)
        # a window long enough for a second publication to join it
        h = PipelineHarness(
            solver_backend="device", debounce_min_s=0.2, debounce_max_s=0.4
        )
        decision, solver = h.decision, h.decision.spf_solver
        entered = []  # (timer armed?, reader backlog) at each stage
        real = solver.speculate_views

        def spy(*args):
            entered.append((
                decision._rebuild_debounced.is_scheduled(),
                decision._kv_reader.size(),
            ))
            return real(*args)

        monkeypatch.setattr(solver, "speculate_views", spy)

        def counters():
            out = {n: reg.counter_get(n) for n in SPEC_COUNTERS}
            out["rebuilds"] = decision.counters["decision.route_build_runs"]
            return out

        def moved(before):
            after = counters()
            return {k: after[k] - before[k] for k in after
                    if after[k] != before[k]}

        def quiet():
            return (
                not decision._rebuild_debounced.is_scheduled()
                and decision._kv_reader.size() == 0
            )

        def window_trace(key, newer_than):
            def find():
                return [
                    t for t in tracer.traces()
                    if t.trace_id > newer_than
                    and t.spans[0].attrs.get("keys") == [key]
                ]
            assert wait_until(lambda: bool(find())), [
                t.to_dict() for t in tracer.traces()[-3:]]
            assert wait_until(quiet)
            return find()[-1]

        def newest():
            return max(t.trace_id for t in tracer.traces())

        try:
            topo = line_topology()
            for db in topo.adj_dbs.values():
                h.publish_adj(db)
            for pdb in topo.prefix_dbs.values():
                h.publish_prefixes(pdb)
            assert wait_until(lambda: len(h.fib.unicast_routes) >= 2)
            assert wait_until(quiet)
            # one adjacency window to compile the patch programs, so
            # that no case's stage outlasts its window compiling
            mark = newest()
            h.publish_adj(_reweighted(topo.adj_dbs["b"], 1))
            window_trace("adj:b", mark)
            del entered[:]
            before, mark = counters(), newest()
            nesting = {n: reg.counter_get(n) for n in (
                "telemetry.traces_bad_nesting",
                "telemetry.traces_unclosed_spans", "ops.host_dispatches")}
            names = None

            if case in ("one_publication", "span_tree"):
                h.publish_adj(_reweighted(topo.adj_dbs["b"], 3))
                trace = window_trace("adj:b", mark)
                # entered with the window armed and nothing queued;
                # one solve, and it is the staged one
                assert entered == [(True, 0)]
                assert moved(before) == {
                    "ops.spec_dispatches": 1, "ops.spec_hits": 1,
                    "decision.device_solves": 1, "rebuilds": 1,
                }
                if case == "span_tree":
                    _assert_tree(trace, ELL_ADJ_EVENT_TREE
                                 if formulation == "ell"
                                 else ADJ_EVENT_TREE)
                    by_name = {s.name: s for s in trace.spans}
                    assert by_name["decision.speculate"].attrs == {
                        "staged": 1}
                    stage = by_name["decision.speculate"]
                    window = by_name["decision.debounce"]
                    build = by_name["decision.route_build"]
                    for name, _ in SERIAL_VIEW_SPANS[formulation]:
                        span = by_name[name]
                        assert stage.ts_ms - SLACK_MS <= span.ts_ms
                        assert _end(span) <= _end(stage) + SLACK_MS
                        assert _end(span) <= _end(window) + SLACK_MS
                        assert _end(span) <= build.ts_ms + SLACK_MS
            elif case == "two_in_one_window":
                h.publish_adj(_reweighted(topo.adj_dbs["b"], 3))
                assert wait_until(
                    lambda: reg.counter_get("ops.spec_dispatches")
                    == before["ops.spec_dispatches"] + 1, step=0.001)
                assert decision._rebuild_debounced.is_scheduled()
                h.publish_adj(_reweighted(topo.adj_dbs["c"], 2))
                trace = window_trace("adj:b", mark)
                # one stage only; the joining publication moved the
                # version, so the rebuild threw it away and re-solved
                assert entered == [(True, 0)]
                assert moved(before) == {
                    "ops.spec_dispatches": 1, "ops.spec_cancels": 1,
                    "decision.device_solves": 2, "rebuilds": 1,
                }
                names = [(s.name, s.depth) for s in trace.spans]
                view = SERIAL_VIEW_SPANS[formulation]
                at = names.index(("decision.route_build", 1))
                assert names[at + 1:at + 1 + len(view)] == view
                assert names.count(("decision.speculate", 1)) == 1
            elif case == "prefix_only":
                h.publish_prefixes(dataclasses.replace(
                    topo.prefix_dbs["c"], prefix_entries=()))
                trace = window_trace("prefix:c", mark)
                assert entered == []
                assert moved(before) == {"rebuilds": 1}
                _assert_tree(trace, PREFIX_EVENT_TREE)
            elif case == "backlog_behind_the_opener":
                # hold Decision's thread while two publications queue
                held, release = threading.Event(), threading.Event()
                decision.evb.run_in_event_base(
                    lambda: (held.set(), release.wait(10.0)))
                assert held.wait(5.0)
                h.publish_adj(_reweighted(topo.adj_dbs["b"], 3))
                h.publish_adj(_reweighted(topo.adj_dbs["c"], 2))
                assert wait_until(lambda: decision._kv_reader.size() == 2)
                release.set()
                trace = window_trace("adj:b", mark)
                # the opener saw one behind it and staged nothing; the
                # one that emptied the queue staged, once, for the
                # composition the rebuild then computed
                assert entered == [(True, 0)]
                assert moved(before) == {
                    "ops.spec_dispatches": 1, "ops.spec_hits": 1,
                    "decision.device_solves": 1, "rebuilds": 1,
                }
                names = [(s.name, s.depth) for s in trace.spans]
                assert names.count(("decision.speculate", 1)) == 1
                at = names.index(("decision.route_build", 1))
                assert names[at + 1] == ("decision.route_diff", 1)
            elif case == "prefix_opens_adjacency_joins":
                # a window opened by a publication that moves no
                # topology (seen on the chip: 1 window of 300): the
                # adjacency publication that joins it still stages
                h.publish_prefixes(dataclasses.replace(
                    topo.prefix_dbs["c"], prefix_entries=()))
                assert wait_until(
                    decision._rebuild_debounced.is_scheduled, step=0.001)
                assert entered == []
                h.publish_adj(_reweighted(topo.adj_dbs["b"], 3))
                trace = window_trace("prefix:c", mark)
                assert entered == [(True, 0)]
                assert moved(before) == {
                    "ops.spec_dispatches": 1, "ops.spec_hits": 1,
                    "decision.device_solves": 1, "rebuilds": 1,
                }
            elif case == "armed_fault":
                # any armed charge: the stage stands down, counted,
                # and the committed rebuild solves for itself
                get_injector().arm(
                    "route_engine.dispatch", FaultSchedule.fail_once())
                h.publish_adj(_reweighted(topo.adj_dbs["b"], 3))
                trace = window_trace("adj:b", mark)
                assert entered == [(True, 0)]
                assert moved(before) == {
                    "ops.spec_skips": 1,
                    "decision.device_solves": 1, "rebuilds": 1,
                }
                assert get_injector().any_armed
                by_name = {s.name: s for s in trace.spans}
                assert by_name["decision.speculate"].attrs == {"staged": 0}
            assert trace.complete and trace.well_formed(), trace.to_dict()
            assert (
                decision.route_db.unicast_routes == _host_routes(decision)
            ), names
            assert {n: reg.counter_get(n) for n in nesting} == nesting
        finally:
            get_injector().reset()
            h.stop()
