"""End-to-end Decision pipeline tests: KvStore -> Decision -> route deltas.

The slice the reference exercises in
openr/decision/tests/DecisionTest.cpp by pushing synthetic Publications
into a real Decision and asserting on emitted DecisionRouteUpdates.
"""

import time

import pytest

from openr_tpu.decision.decision import Decision
from openr_tpu.kvstore.wrapper import KvStoreWrapper
from openr_tpu.messaging.queue import QueueTimeoutError, ReplicateQueue
from openr_tpu.models import topologies
from openr_tpu.types import (
    AdjacencyDatabase,
    PrefixDatabase,
    PrefixEntry,
    IpPrefix,
)
from openr_tpu.utils import keys as keyutil
from openr_tpu.utils import wire


class DecisionHarness:
    """KvStore + Decision wired through real queues."""

    def __init__(self, my_node, solver_backend="device", areas=None):
        self.store = KvStoreWrapper(f"store:{my_node}", areas=areas)
        self.route_q = ReplicateQueue(name="routeUpdates")
        self.route_reader = self.route_q.get_reader("test")
        self.decision = Decision(
            my_node,
            kvstore_updates_queue=self.store.store.updates_queue,
            route_updates_queue=self.route_q,
            debounce_min_s=0.01,
            debounce_max_s=0.05,
            solver_backend=solver_backend,
        )
        self.store.start()
        self.decision.start()
        self._versions = {}

    def stop(self):
        self.decision.stop()
        self.store.stop()

    def _publish(self, key, db):
        """Into the area the db names (one of the store's)."""
        slot = (db.area, key)
        v = self._versions[slot] = self._versions.get(slot, 0) + 1
        self.store.set_key(key, wire.dumps(db), version=v, area=db.area,
                           originator=db.this_node_name)

    def publish_adj(self, adj_db: AdjacencyDatabase):
        self._publish(keyutil.adj_key(adj_db.this_node_name), adj_db)

    def publish_prefixes(self, prefix_db: PrefixDatabase):
        self._publish(
            keyutil.prefix_db_key(prefix_db.this_node_name), prefix_db)

    def publish_topology(self, topo):
        for db in topo.adj_dbs.values():
            self.publish_adj(db)
        for pdb in topo.prefix_dbs.values():
            self.publish_prefixes(pdb)

    def next_update(self, timeout=5.0):
        return self.route_reader.get(timeout=timeout)

    def drain_updates(self, timeout=0.3, first_timeout=10.0):
        """Collect updates until the queue goes quiet. The first wait is
        generous: the solver's first device compile happens lazily."""
        updates = []
        wait = first_timeout
        while True:
            try:
                updates.append(self.route_reader.get(timeout=wait))
                wait = timeout
            except QueueTimeoutError:
                return updates


@pytest.fixture
def harness():
    h = DecisionHarness("a")
    yield h
    h.stop()


def line_topology():
    return topologies.build_topology("line", [("a", "b", 1), ("b", "c", 2)])


class TestDecisionPipeline:
    def test_initial_convergence(self, harness):
        topo = line_topology()
        harness.publish_topology(topo)
        updates = harness.drain_updates()
        assert updates
        # after convergence the accumulated route db has routes to b and c
        routes = harness.decision.get_decision_route_db()
        b_pfx = topo.prefix_dbs["b"].prefix_entries[0].prefix
        c_pfx = topo.prefix_dbs["c"].prefix_entries[0].prefix
        assert b_pfx in routes.unicast_routes
        assert c_pfx in routes.unicast_routes
        # perf events ride the updates
        assert any(u.perf_events is not None for u in updates)

    def test_incremental_prefix_update(self, harness):
        topo = line_topology()
        harness.publish_topology(topo)
        harness.drain_updates()
        # now c advertises one more prefix: expect a delta with only it
        extra = IpPrefix.from_str("fd00:100::/64")
        pdb = topo.prefix_dbs["c"]
        harness.publish_prefixes(
            PrefixDatabase(
                this_node_name="c",
                prefix_entries=pdb.prefix_entries
                + (PrefixEntry(prefix=extra),),
                area=topo.area,
            )
        )
        updates = harness.drain_updates()
        touched = set()
        for u in updates:
            touched |= set(u.unicast_routes_to_update)
            touched |= set(u.unicast_routes_to_delete)
        assert extra in touched
        # the unrelated route to b must not be touched by the delta
        b_pfx = topo.prefix_dbs["b"].prefix_entries[0].prefix
        assert b_pfx not in touched

    def test_adjacency_change_triggers_full_rebuild(self, harness):
        topo = line_topology()
        harness.publish_topology(topo)
        harness.drain_updates()
        # metric change on b->c: route to c's prefix changes metric
        db = topo.adj_dbs["b"]
        from openr_tpu.types import Adjacency

        new_adjs = tuple(
            Adjacency(
                other_node_name=adj.other_node_name,
                if_name=adj.if_name,
                metric=40 if adj.other_node_name == "c" else adj.metric,
                next_hop_v6=adj.next_hop_v6,
                next_hop_v4=adj.next_hop_v4,
                other_if_name=adj.other_if_name,
                adj_label=adj.adj_label,
            )
            for adj in db.adjacencies
        )
        harness.publish_adj(
            AdjacencyDatabase(
                this_node_name="b",
                adjacencies=new_adjs,
                node_label=db.node_label,
                area=db.area,
            )
        )
        harness.drain_updates()
        routes = harness.decision.get_decision_route_db()
        c_pfx = topo.prefix_dbs["c"].prefix_entries[0].prefix
        (nh,) = routes.unicast_routes[c_pfx].nexthops
        assert nh.metric == 41

    def test_node_down_deletes_routes(self, harness):
        topo = line_topology()
        harness.publish_topology(topo)
        harness.drain_updates()
        c_pfx = topo.prefix_dbs["c"].prefix_entries[0].prefix
        # c's adjacency and prefix keys expire (ttl'd out)
        harness.store.set_key(
            keyutil.adj_key("c"), wire.dumps(AdjacencyDatabase(
                this_node_name="c", area=topo.area)), version=99,
            originator="c", ttl=120)
        harness.store.set_key(
            keyutil.prefix_db_key("c"),
            wire.dumps(PrefixDatabase(this_node_name="c", area=topo.area)),
            version=99, originator="c", ttl=120)
        time.sleep(0.5)
        harness.drain_updates()
        routes = harness.decision.get_decision_route_db()
        assert c_pfx not in routes.unicast_routes

    def test_any_source_route_computation(self, harness):
        topo = line_topology()
        harness.publish_topology(topo)
        harness.drain_updates()
        # compute routes from c's perspective (first-class API)
        routes_c = harness.decision.get_decision_route_db("c")
        a_pfx = topo.prefix_dbs["a"].prefix_entries[0].prefix
        assert a_pfx in routes_c.unicast_routes
        (nh,) = routes_c.unicast_routes[a_pfx].nexthops
        assert nh.neighbor_node_name == "b"
        assert nh.metric == 3

    def test_per_prefix_keys(self, harness):
        topo = line_topology()
        for db in topo.adj_dbs.values():
            harness.publish_adj(db)
        # advertise b's loopback via a per-prefix key
        b_pfx = topo.prefix_dbs["b"].prefix_entries[0].prefix
        key = keyutil.per_prefix_key("b", topo.area, b_pfx)
        pdb = PrefixDatabase(
            this_node_name="b",
            prefix_entries=(PrefixEntry(prefix=b_pfx),),
            area=topo.area,
        )
        harness.store.set_key(key, wire.dumps(pdb), version=1, originator="b")
        harness.drain_updates()
        routes = harness.decision.get_decision_route_db()
        assert b_pfx in routes.unicast_routes

    def test_debounce_coalesces_churn(self, harness):
        topo = line_topology()
        harness.publish_topology(topo)
        harness.drain_updates()
        runs_before = harness.decision.get_counters()[
            "decision.route_build_runs"
        ]
        # 10 rapid prefix updates
        extra = IpPrefix.from_str("fd00:200::/64")
        for i in range(10):
            harness.publish_prefixes(
                PrefixDatabase(
                    this_node_name="c",
                    prefix_entries=topo.prefix_dbs["c"].prefix_entries
                    + (PrefixEntry(prefix=extra),)[: i % 2 + 1],
                    area=topo.area,
                )
            )
        harness.drain_updates()
        runs_after = harness.decision.get_counters()[
            "decision.route_build_runs"
        ]
        assert runs_after - runs_before < 10  # debounced into fewer rebuilds


class TestDecisionSpReuse:
    def test_sp_reuse_active_through_daemon_path(self):
        """SP_ECMP per-prefix route reuse operates through the Decision
        module's publication-driven full rebuilds: remote churn events
        arriving as KvStore publications serve untouched prefixes from
        the cache (spf_solver._sp_dirty_nodes), with the accumulated
        route DB staying byte-identical to a fresh host solver."""
        from dataclasses import replace

        from openr_tpu.decision.prefix_state import PrefixState
        from openr_tpu.decision.spf_solver import (
            SPF_COUNTERS,
            SpfSolver,
        )
        from openr_tpu.graph.linkstate import LinkState
        from openr_tpu.types.lsdb import (
            PrefixForwardingAlgorithm,
            PrefixForwardingType,
        )

        topo = topologies.fat_tree_nodes(
            120,
            forwarding_algorithm=PrefixForwardingAlgorithm.SP_ECMP,
            forwarding_type=PrefixForwardingType.SR_MPLS,
        )
        rsw = next(
            k for k in sorted(topo.adj_dbs) if k.startswith("rsw")
        )
        fsw = next(
            k for k in sorted(topo.adj_dbs) if k.startswith("fsw")
        )
        h = DecisionHarness(rsw)
        try:
            h.publish_topology(topo)
            assert h.drain_updates(), "no initial routes"
            adj_dbs = dict(topo.adj_dbs)

            def churn(steps, base=0):
                for step in range(steps):
                    db = adj_dbs[fsw]
                    adjs = list(db.adjacencies)
                    adjs[0] = replace(
                        adjs[0], metric=2 + (base + step) % 5
                    )
                    adj_dbs[fsw] = replace(
                        db, adjacencies=tuple(adjs)
                    )
                    h.publish_adj(adj_dbs[fsw])
                    h.drain_updates(first_timeout=5.0)

            churn(2)  # warm: signature store + cache populate
            before = SPF_COUNTERS["decision.sp_route_reuses"]
            churn(3, base=2)
            assert (
                SPF_COUNTERS["decision.sp_route_reuses"] - before
                > 100
            ), "no SP route reuse through the daemon path"

            # parity: accumulated daemon route DB vs a fresh host
            # solver over the same final adjacency state
            ls = LinkState(area=topo.area)
            for name in sorted(adj_dbs):
                ls.update_adjacency_database(adj_dbs[name])
            ps = PrefixState()
            for pdb in topo.prefix_dbs.values():
                ps.update_prefix_database(pdb)
            want = SpfSolver(rsw, backend="host").build_route_db(
                rsw, {topo.area: ls}, ps
            )
            got = h.decision.get_decision_route_db()
            assert got.unicast_routes == want.unicast_routes
            assert got.mpls_routes == want.mpls_routes
        finally:
            h.stop()


class TestDecisionKsp2Engine:
    def test_engine_active_through_daemon_path(self, monkeypatch):
        """The incremental KSP2 engine operates through the Decision
        module's publication-driven rebuild: churn events arriving as
        KvStore publications run incremental syncs with route reuse,
        not cold rebuilds (reference rebuild driver:
        Decision.cpp:1860 rebuildRoutes)."""
        from dataclasses import replace

        from openr_tpu.decision import spf_solver as ss
        from openr_tpu.decision.spf_solver import SPF_COUNTERS
        from openr_tpu.types.lsdb import (
            PrefixForwardingAlgorithm,
            PrefixForwardingType,
        )

        monkeypatch.setattr(ss, "KSP2_DEVICE_MIN_DSTS", 1)
        topo = topologies.fat_tree_nodes(
            120,
            forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
            forwarding_type=PrefixForwardingType.SR_MPLS,
        )
        rsw = next(k for k in sorted(topo.adj_dbs) if k.startswith("rsw"))
        fsw = next(k for k in sorted(topo.adj_dbs) if k.startswith("fsw"))
        h = DecisionHarness(rsw)
        try:
            h.publish_topology(topo)
            assert h.drain_updates(), "no initial routes"
            adj_dbs = dict(topo.adj_dbs)

            def churn(steps):
                for step in range(steps):
                    db = adj_dbs[fsw]
                    adjs = list(db.adjacencies)
                    adjs[0] = replace(adjs[0], metric=2 + step % 5)
                    adj_dbs[fsw] = replace(db, adjacencies=tuple(adjs))
                    h.publish_adj(adj_dbs[fsw])
                    h.drain_updates(first_timeout=5.0)

            churn(5)  # warm: cold build + tie transitions
            before = dict(SPF_COUNTERS)
            churn(3)
            syncs = (
                SPF_COUNTERS["decision.ksp2_incremental_syncs"]
                - before["decision.ksp2_incremental_syncs"]
            )
            reuses = (
                SPF_COUNTERS["decision.ksp2_route_reuses"]
                - before["decision.ksp2_route_reuses"]
            )
            assert syncs >= 3, "daemon-path rebuilds were not incremental"
            assert reuses > 0, "no routes reused through the daemon path"
        finally:
            h.stop()


# ---------------------------------------------------------------------------
# the constructor the daemon uses; route_db's one owner
# ---------------------------------------------------------------------------

# Decision keywords that daemon.OpenrNode does not pass, each with the
# reason it may stay. Anything else needs a production caller.
_NOT_FROM_THE_DAEMON = {
    "admission": "load/harness.py passes it; whether the daemon wires "
    "AdmissionControl is ROADMAP D6",
    "state_plane": "durability plane (state/, recovery tests); ROADMAP "
    "D8/R9",
    "cold_start_s": "upstream's eor_time_s; passed by nobody today: wire "
    "it from OpenrConfig or remove it, ROADMAP D5",
}


def _decision_keywords(module):
    """What ``module`` passes to its first ``Decision(...)`` call (the
    pipeline's), read off its source: (positional count, keyword
    names)."""
    import ast
    import inspect

    calls = [
        n for n in ast.walk(ast.parse(inspect.getsource(module)))
        if isinstance(n, ast.Call)
        and isinstance(n.func, ast.Name)
        and n.func.id == "Decision"
    ]
    call = min(calls, key=lambda n: n.lineno)
    assert all(kw.arg is not None for kw in call.keywords), "no **kwargs"
    return len(call.args), {kw.arg for kw in call.keywords}


class TestDecisionConstructor:
    def test_every_keyword_has_a_production_caller(self):
        import inspect

        from openr_tpu import daemon

        params = list(inspect.signature(Decision.__init__).parameters)[1:]
        n_positional, passed = _decision_keywords(daemon)
        passed |= set(params[:n_positional])
        assert passed <= set(params), passed - set(params)
        unexplained = set(params) - passed - set(_NOT_FROM_THE_DAEMON)
        assert not unexplained, (
            f"Decision.__init__ takes {sorted(unexplained)}, which "
            "daemon.OpenrNode never passes: an option needs a production "
            "caller"
        )
        # the exemption list does not outlive its entries
        assert set(_NOT_FROM_THE_DAEMON) <= set(params) - passed

    def test_load_harness_builds_decision_as_the_daemon_plus_admission(self):
        """What load/harness.py's docstring says, held: its pipeline's
        Decision gets no keyword the daemon does not pass, admission
        aside (ROADMAP D6)."""
        from openr_tpu import daemon
        from openr_tpu.load import harness as load_harness

        _, from_daemon = _decision_keywords(daemon)
        n_positional, from_harness = _decision_keywords(load_harness)
        assert n_positional == 1  # my_node_name
        assert from_harness - from_daemon == {"admission"}


class TestRouteDbOwnership:
    def test_route_db_is_touched_on_the_event_base_only(self, harness):
        """Through the real queues, every diff against and every
        mutation of the installed table runs on Decision's event-base
        thread."""
        import threading
        from dataclasses import replace

        seen = []
        db = harness.decision.route_db
        for name in ("update", "calculate_update",
                     "calculate_touched_update"):
            def wrapper(*a, _real=getattr(db, name), _name=name, **kw):
                seen.append((_name, threading.current_thread().name))
                return _real(*a, **kw)

            setattr(db, name, wrapper)

        topo = line_topology()
        harness.publish_topology(topo)
        assert harness.drain_updates()
        b = topo.adj_dbs["b"]
        for bump in (3, 5, 7):  # three debounced rebuilds, one by one
            harness.publish_adj(replace(b, adjacencies=tuple(
                replace(adj, metric=adj.metric + bump)
                for adj in b.adjacencies
            )))
            assert harness.drain_updates(first_timeout=5.0)

        assert sum(n == "update" for n, _ in seen) >= 4
        # a diff of the whole table, or of the keys the build touched
        assert sum(n.startswith("calculate_") for n, _ in seen) >= 3
        assert {t for _, t in seen} == {"decision:a"}, seen

    def test_stop_leaves_no_decision_thread(self):
        import threading

        before = set(threading.enumerate())
        h = DecisionHarness("a")
        started = {
            t for t in set(threading.enumerate()) - before
            if t.name.startswith("decision")
        }
        assert started, "the event base and its reader run as threads"
        try:
            h.publish_topology(line_topology())
            assert h.drain_updates()
            started |= {
                t for t in set(threading.enumerate()) - before
                if t.name.startswith("decision")
            }
        finally:
            h.stop()
        alive = [t.name for t in started if t.is_alive()]
        assert alive == [], alive
