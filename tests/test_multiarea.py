"""Multi-area: per-interface areas, per-area LSDBs, and cross-area
route redistribution.

Reference semantics: a border router participates in several areas (one
KvStoreDb / LinkState per area), and its PrefixManager re-originates
Decision's best routes into the areas they were not learned from, with
``area_stack`` loop suppression (openr/prefix-manager/PrefixManager.cpp,
openr/decision/Decision.h:390 per-area link states; BASELINE.json config
"Multi-area Decision with inter-area prefix redistribution").
"""

import time

import pytest

from openr_tpu.daemon import OpenrNode
from openr_tpu.decision.rib import DecisionRouteUpdate, RibUnicastEntry
from openr_tpu.messaging.queue import ReplicateQueue
from openr_tpu.prefixmgr.prefix_manager import PrefixManager
from openr_tpu.spark.io_provider import MockIoProvider
from openr_tpu.types import IpPrefix, PrefixEntry, PrefixType
from openr_tpu.types.lsdb import PrefixMetrics


def wait_until(pred, timeout=10.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


class FakeClient:
    """Captures KvStore client calls: area -> {key: payload}."""

    def __init__(self):
        self.persisted = {}

    def persist_key(self, area, key, value):
        self.persisted.setdefault(area, {})[key] = value

    def set_key(self, area, key, value):
        self.persisted.setdefault(area, {})[key] = value

    def unset_key(self, area, key):
        self.persisted.get(area, {}).pop(key, None)

    def clear_key(self, area, key, value, ttl=None):
        self.persisted.get(area, {}).pop(key, None)


class TestRedistributionUnit:
    def make_pm(self):
        q = ReplicateQueue(name="routeUpdates")
        client = FakeClient()
        pm = PrefixManager(
            "border",
            client,
            decision_route_updates_queue=q,
            areas=["1", "2"],
        )
        pm.start()
        return pm, q, client

    def route_update(self, prefix, best_area, area_stack=()):
        update = DecisionRouteUpdate()
        update.unicast_routes_to_update[prefix] = RibUnicastEntry(
            prefix=prefix,
            best_prefix_entry=PrefixEntry(
                prefix=prefix,
                metrics=PrefixMetrics(path_preference=700),
                area_stack=area_stack,
            ),
            best_area=best_area,
        )
        return update

    def test_reoriginated_into_other_area_only(self):
        pm, q, client = self.make_pm()
        try:
            prefix = IpPrefix.from_str("fd00:a::1/128")
            q.push(self.route_update(prefix, best_area="1"))
            assert wait_until(
                lambda: any(
                    "fd00:a::1" in k for k in client.persisted.get("2", {})
                )
            )
            # never echoed back into the source area
            assert not any(
                "fd00:a::1" in k for k in client.persisted.get("1", {})
            )
            (entry, targets) = pm.get_redistributed()[prefix]
            assert entry.type == PrefixType.RIB
            assert entry.area_stack == ("1",)
            assert entry.metrics.path_preference == 700
            # the copy must always lose best-route selection to the
            # original, else two borders' identical copies oscillate
            assert entry.metrics.distance == 1
            assert targets == ("2",)
        finally:
            pm.stop()

    def test_area_stack_loop_suppression(self):
        pm, q, client = self.make_pm()
        try:
            # best route already traversed both areas: nowhere to go
            prefix = IpPrefix.from_str("fd00:b::1/128")
            q.push(self.route_update(prefix, "1", area_stack=("2",)))
            time.sleep(0.3)
            assert pm.get_redistributed() == {}
            assert not any(
                "fd00:b::1" in k
                for area in ("1", "2")
                for k in client.persisted.get(area, {})
            )
        finally:
            pm.stop()

    def test_own_prefixes_not_redistributed(self):
        pm, q, client = self.make_pm()
        try:
            prefix = IpPrefix.from_str("fd00:c::1/128")
            pm.advertise_prefixes(
                [PrefixEntry(prefix=prefix, type=PrefixType.LOOPBACK)]
            )
            q.push(self.route_update(prefix, "1"))
            time.sleep(0.3)
            assert pm.get_redistributed() == {}
        finally:
            pm.stop()

    def test_withdraw_on_route_delete(self):
        pm, q, client = self.make_pm()
        try:
            prefix = IpPrefix.from_str("fd00:d::1/128")
            q.push(self.route_update(prefix, "1"))
            assert wait_until(lambda: prefix in pm.get_redistributed())
            update = DecisionRouteUpdate()
            update.unicast_routes_to_delete.append(prefix)
            q.push(update)
            assert wait_until(lambda: pm.get_redistributed() == {})
            assert not any(
                "fd00:d::1" in k for k in client.persisted.get("2", {})
            )
        finally:
            pm.stop()


class TestAdvertisementModes:
    def test_full_db_mode_reaches_every_area(self):
        client = FakeClient()
        pm = PrefixManager(
            "n", client, areas=["1", "2"], per_prefix_keys=False
        )
        pm.start()
        try:
            pm.advertise_prefixes(
                [PrefixEntry(prefix=IpPrefix.from_str("fd00:1::/64"))]
            )
            assert wait_until(
                lambda: all(
                    client.persisted.get(a) for a in ("1", "2")
                )
            ), client.persisted
        finally:
            pm.stop()

    def test_same_prefix_two_types_advertises_best(self):
        client = FakeClient()
        pm = PrefixManager("n", client, areas=["1"])
        pm.start()
        try:
            prefix = IpPrefix.from_str("fd00:2::/64")
            pm.advertise_prefixes(
                [
                    PrefixEntry(
                        prefix=prefix,
                        type=PrefixType.BGP,
                        metrics=PrefixMetrics(path_preference=500),
                    ),
                    PrefixEntry(
                        prefix=prefix,
                        type=PrefixType.LOOPBACK,
                        metrics=PrefixMetrics(path_preference=900),
                    ),
                ]
            )
            from openr_tpu.types import PrefixDatabase
            from openr_tpu.utils import wire

            [(key, payload)] = client.persisted["1"].items()
            db = wire.loads(payload, PrefixDatabase)
            assert len(db.prefix_entries) == 1
            assert db.prefix_entries[0].type == PrefixType.LOOPBACK
            # withdrawing the winner falls back to the other type
            pm.withdraw_prefixes([])  # no-op keeps state machinery warm
        finally:
            pm.stop()

    def test_sync_by_type_applies_origination_defaults(self):
        client = FakeClient()
        pm = PrefixManager("n", client, areas=["1"])
        pm.start()
        try:
            pm.sync_prefixes_by_type(
                PrefixType.PREFIX_ALLOCATOR,
                [PrefixEntry(prefix=IpPrefix.from_str("fd00:3::/64"))],
            )
            [entry] = pm.get_prefixes()
            assert entry.metrics.path_preference == 1000
            assert entry.metrics.source_preference == 200
        finally:
            pm.stop()

    def test_daemon_rejects_unconfigured_areas(self):
        from openr_tpu.daemon import OpenrNode
        from openr_tpu.spark.io_provider import MockIoProvider

        io = MockIoProvider()
        try:
            with pytest.raises(ValueError):
                OpenrNode(
                    "x", io, areas=["1", "2"],
                    interface_areas={"eth0": "3"}, area="1",
                )
            with pytest.raises(ValueError):
                OpenrNode("y", io, areas=["1", "2"])  # default area "0"
        finally:
            io.stop()


SPARK_FAST = dict(
    hello_interval_s=0.05,
    fast_hello_interval_s=0.03,
    handshake_interval_s=0.03,
    heartbeat_interval_s=0.05,
    hold_time_s=0.6,
    graceful_restart_time_s=2.0,
)


class TestMultiAreaSystem:
    """a -(area 1)- border -(area 2)- c : end-to-end redistribution."""

    @pytest.fixture
    def net(self):
        io = MockIoProvider()
        registry = {}
        nodes = {
            "a": OpenrNode(
                "a", io, node_registry=registry, area="1",
                v6_addr="fe80::1", spark_config=SPARK_FAST,
            ),
            "border": OpenrNode(
                "border", io, node_registry=registry, area="1",
                areas=["1", "2"],
                interface_areas={"if_border_c": "2"},
                v6_addr="fe80::2", spark_config=SPARK_FAST,
            ),
            "c": OpenrNode(
                "c", io, node_registry=registry, area="2",
                v6_addr="fe80::3", spark_config=SPARK_FAST,
            ),
        }
        io.connect_pair("if_a_border", "if_border_a", 1)
        io.connect_pair("if_border_c", "if_c_border", 1)
        for n in nodes.values():
            n.start()
        nodes["a"].add_interface("if_a_border")
        nodes["border"].add_interface("if_border_a")
        nodes["border"].add_interface("if_border_c")
        nodes["c"].add_interface("if_c_border")
        yield nodes
        for n in nodes.values():
            n.stop()
        io.stop()

    def has_route(self, node, prefix):
        db = node.get_fib_routes()
        return any(r.dest == prefix for r in db.unicast_routes)

    def test_cross_area_propagation(self, net):
        a_pfx = net["a"].advertise_loopback("fd00:a::1/128")
        c_pfx = net["c"].advertise_loopback("fd00:c::1/128")

        # intra-area first
        assert wait_until(lambda: self.has_route(net["border"], a_pfx))
        assert wait_until(lambda: self.has_route(net["border"], c_pfx))
        # cross-area via the border's re-origination
        assert wait_until(lambda: self.has_route(net["c"], a_pfx))
        assert wait_until(lambda: self.has_route(net["a"], c_pfx))

        # c's route to a's loopback goes through the border
        db = net["c"].get_fib_routes()
        route = next(r for r in db.unicast_routes if r.dest == a_pfx)
        assert {nh.neighbor_node_name for nh in route.next_hops} == {"border"}

        # the redistributed advertisement carries the source area stack
        redist = net["border"].prefix_manager.get_redistributed()
        assert redist[a_pfx][0].area_stack == ("1",)
        assert redist[a_pfx][1] == ("2",)
        assert redist[c_pfx][0].area_stack == ("2",)
        assert redist[c_pfx][1] == ("1",)

        # loop prevention: a's own prefix never comes back as a route on a
        assert not self.has_route(net["a"], a_pfx)

    def test_cross_area_withdraw(self, net):
        a_pfx = net["a"].advertise_loopback("fd00:a::2/128")
        assert wait_until(lambda: self.has_route(net["c"], a_pfx))
        net["a"].prefix_manager.withdraw_prefixes([a_pfx])
        assert wait_until(lambda: not self.has_route(net["c"], a_pfx))


class TestRedistributionIsADelta:
    """A route update touches only the keys of the prefixes it names;
    what KvStore holds afterwards is what a sync of the whole table
    would have left."""

    AREAS = ["1", "2", "3"]

    def make_node(self, name="border"):
        from openr_tpu.kvstore.client import KvStoreClient
        from openr_tpu.kvstore.store import KvStore
        from openr_tpu.utils.eventbase import OpenrEventBase

        store = KvStore(node_id=name, areas=self.AREAS)
        evb = OpenrEventBase(name=f"kvclient:{name}")
        client = KvStoreClient(evb, name, store)
        q = ReplicateQueue(name="routeUpdates")
        pm = PrefixManager(
            name, client, decision_route_updates_queue=q, areas=self.AREAS
        )
        store.start()
        evb.run_in_thread()
        pm.start()

        def stop():
            pm.stop()
            client.stop()
            evb.stop()
            evb.join()
            store.stop()

        return pm, q, store, stop

    @staticmethod
    def entry(prefix, best_area, stack=(), distance=0):
        return RibUnicastEntry(
            prefix=prefix,
            best_prefix_entry=PrefixEntry(
                prefix=prefix,
                metrics=PrefixMetrics(path_preference=700, distance=distance),
                area_stack=stack,
            ),
            best_area=best_area,
        )

    def live_keys(self, store):
        """area -> {key: the entry it holds}; a tombstone is absent."""
        from openr_tpu.types import KeyDumpParams, PrefixDatabase
        from openr_tpu.utils import wire

        out = {}
        for area in self.AREAS:
            pub = store.dump_with_filters(area, KeyDumpParams(prefix="prefix:"))
            out[area] = {}
            for key, value in pub.key_vals.items():
                db = wire.loads(value.value, PrefixDatabase)
                if not db.delete_prefix:
                    (out[area][key],) = db.prefix_entries
        return out

    @staticmethod
    def redistribute(q, updates):
        """Push, and wait until each update has been redistributed (one
        PrefixManager works at a time here, and each update it reads is
        one ``prefixmgr.redistribute_runs``)."""
        from openr_tpu.telemetry import get_registry

        reg = get_registry()
        done = reg.counter_get("prefixmgr.redistribute_runs") + len(updates)
        for update in updates:
            q.push(update)
        assert wait_until(
            lambda: reg.counter_get("prefixmgr.redistribute_runs") == done
        )

    @pytest.mark.parametrize("seed", [1, 7, 2300000011])
    def test_a_stream_of_updates_leaves_what_a_whole_sync_leaves(self, seed):
        import random

        rng = random.Random(seed)
        prefixes = [IpPrefix.from_str(f"fd00:{i:x}::/64") for i in range(40)]
        own = prefixes[0]
        pm, q, store, stop = self.make_node()
        fresh, fresh_q, fresh_store, fresh_stop = self.make_node()
        try:
            rib, updates = {}, []
            for _ in range(60):
                update = DecisionRouteUpdate()
                for prefix in rng.sample(prefixes[1:], rng.randint(1, 6)):
                    if rng.random() < 0.3 and prefix in rib:
                        del rib[prefix]
                        update.unicast_routes_to_delete.append(prefix)
                        continue
                    area = rng.choice(self.AREAS)
                    others = [a for a in self.AREAS if a != area]
                    # now and then a best entry that has crossed areas
                    # already: one, or every one there is
                    stack = rng.choice(
                        [(), (), (others[0],), tuple(others)])
                    rib[prefix] = self.entry(
                        prefix, area, stack, distance=len(stack))
                    update.unicast_routes_to_update[prefix] = rib[prefix]
                updates.append(update)
            # half way, a prefix that was redistributed becomes the
            # node's own; Decision goes on naming it now and then
            learned = DecisionRouteUpdate(
                unicast_routes_to_update={own: self.entry(own, "1")})
            self.redistribute(q, updates[:30] + [learned])
            assert own in pm.get_redistributed()
            pm.advertise_prefixes([PrefixEntry(
                prefix=own, type=PrefixType.LOOPBACK)])
            self.redistribute(q, updates[30:45] + [learned] + updates[45:])
            # a fresh node: its own prefix, then the whole RIB at once
            fresh.advertise_prefixes([PrefixEntry(
                prefix=own, type=PrefixType.LOOPBACK)])
            rib[own] = self.entry(own, "1")
            self.redistribute(fresh_q, [DecisionRouteUpdate(
                unicast_routes_to_update=dict(rib))])
            assert pm.get_redistributed() == fresh.get_redistributed()
            assert own not in pm.get_redistributed()
            held, want = self.live_keys(store), self.live_keys(fresh_store)
            assert held == want
            assert sum(map(len, want.values())) > 20
            # never into an area on the entry's stack, nor its own
            for area, keys in held.items():
                for e in keys.values():
                    assert area not in e.area_stack
                    if e.type == PrefixType.RIB:
                        assert e.metrics.distance == len(e.area_stack)
                assert any(k.endswith(f"[{own.to_str()}]") for k in keys)
        finally:
            stop()
            fresh_stop()

    def test_a_one_prefix_update_costs_the_areas_not_the_table(self):
        from openr_tpu.telemetry import get_registry

        pm, q, store, stop = self.make_node()
        reg = get_registry()
        try:
            table = DecisionRouteUpdate()
            for i in range(300):
                p = IpPrefix.from_str(f"fd01:{i:x}::/64")
                table.unicast_routes_to_update[p] = self.entry(p, "1")
            self.redistribute(q, [table])
            assert len(pm.get_redistributed()) == 300
            calls0 = reg.counter_get("prefixmgr.kvstore_calls")
            runs0 = reg.counter_get("prefixmgr.redistribute_runs")
            assert calls0 >= 600  # 300 prefixes into two other areas

            one = IpPrefix.from_str("fd02::/64")
            self.redistribute(q, [DecisionRouteUpdate(
                unicast_routes_to_update={one: self.entry(one, "2")})])
            assert one in pm.get_redistributed()
            assert reg.counter_get("prefixmgr.redistribute_runs") == runs0 + 1
            assert reg.counter_get("prefixmgr.kvstore_calls") - calls0 \
                == len(self.AREAS) - 1
            # an update that changes no redistribution reaches no key
            calls1 = reg.counter_get("prefixmgr.kvstore_calls")
            self.redistribute(q, [table])
            assert reg.counter_get("prefixmgr.kvstore_calls") == calls1
            # the withdraw clears as many keys as the add set
            gone = DecisionRouteUpdate()
            gone.unicast_routes_to_delete.append(one)
            withdrawn0 = reg.counter_get("prefixmgr.withdrawn_keys")
            self.redistribute(q, [gone])
            assert one not in pm.get_redistributed()
            assert reg.counter_get("prefixmgr.withdrawn_keys") - withdrawn0 \
                == len(self.AREAS) - 1
            assert not any(
                "fd02::" in k for keys in self.live_keys(store).values()
                for k in keys)
        finally:
            stop()

    def test_the_span_rides_the_updates_trace(self):
        from openr_tpu.telemetry import get_tracer

        pm, q, store, stop = self.make_node()
        try:
            trace = get_tracer().start("kvstore.publish")
            held_open = trace.begin_span("fib.program")
            p = IpPrefix.from_str("fd03::/64")
            update = DecisionRouteUpdate(
                unicast_routes_to_update={p: self.entry(p, "1")})
            update.trace = trace
            q.push(update)
            assert wait_until(lambda: any(
                s.name == "prefixmgr.redistribute" for s in trace.spans))
            (span,) = [s for s in trace.spans
                       if s.name == "prefixmgr.redistribute"]
            assert span.closed and span.depth == 0
            assert (span.attrs["routes"], span.attrs["keys_set"],
                    span.attrs["keys_cleared"]) == (1, 2, 0)
            # beside what Fib has open, never inside it
            trace.end_span(held_open)
            assert trace.well_formed()
        finally:
            stop()
