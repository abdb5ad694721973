"""A full rebuild installs what its route build touched, not a table it
has to search: the equivalence, and the handshake that guards it.

``SpfSolver.build_routes`` patches the solver's own table in place and
hands ``Decision`` the keys it wrote; ``Decision._emit_update`` diffs
those keys alone, as long as ``route_db`` holds the solver's own object
under every other key (``Decision._route_anchor``). Everything that can
put the two tables out of step has to break that handshake and send one
rebuild through ``calculate_update``.

Random event streams through ``DecisionHarness`` over an SP_ECMP fat
tree, a KSP2 + SR-MPLS fat tree and a fabric joined to a grid in a
second area; after EVERY rebuild the emitted update is held to the
whole-table diff of a shadow copy against a fresh host solver's db, and
the installed table to that db, entry for entry and object for object.
Then each way out of step, one by one. Counts, never times.
"""

from __future__ import annotations

import random
import time
from dataclasses import replace
from types import SimpleNamespace

import pytest

from openr_tpu.decision.rib import DecisionRouteDb
from openr_tpu.decision.rib_policy import (
    RibPolicy,
    RibPolicyStatement,
    RibRouteAction,
    RibRouteActionWeight,
)
from openr_tpu.decision.spf_solver import FAULT_SPF_SOLVE, SpfSolver
from openr_tpu.faults import FaultSchedule, LadderExhausted, get_injector
from openr_tpu.graph.linkstate import LinkState
from openr_tpu.messaging.queue import QueueTimeoutError
from openr_tpu.models import topologies
from openr_tpu.telemetry.registry import get_registry
from openr_tpu.types import (
    Adjacency,
    AdjacencyDatabase,
    BinaryAddress,
    MplsAction,
    MplsActionCode,
    MplsRoute,
    NextHop,
)
from openr_tpu.types.lsdb import (
    PrefixForwardingAlgorithm,
    PrefixForwardingType,
)
from tests.test_decision_module import DecisionHarness

ROOT = "rsw-0-0"
KSP2 = dict(
    forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
    forwarding_type=PrefixForwardingType.SR_MPLS,
)
KINDS = ("sp-ecmp", "ksp2-sr-mpls", "two-areas")


@pytest.fixture(autouse=True)
def _engine_everywhere_and_no_armed_fault(monkeypatch):
    from openr_tpu.decision import spf_solver as ss

    monkeypatch.setattr(ss, "KSP2_DEVICE_MIN_DSTS", 1)
    get_injector().reset()
    yield
    get_injector().reset()


def _delta_counters():
    registry = get_registry()
    return (registry.counter_get("decision.route_delta_builds"),
            registry.counter_get("decision.route_delta_fallbacks"))


class _World:
    """A loaded Decision, the events that move it, and the check."""

    def __init__(self, kind, solver_backend="device", load=True):
        how = KSP2 if kind == "ksp2-sr-mpls" else {}
        main = topologies.fat_tree(
            3, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=4,
            area="0", **how)
        self.dbs = {("0", n): db for n, db in main.adj_dbs.items()}
        self.prefix_dbs = {("0", n): p for n, p in main.prefix_dbs.items()}
        areas = ["0"]
        if kind == "two-areas":
            self.second_area()
            areas.append("b")
        self.h = DecisionHarness(ROOT, solver_backend, areas=areas)
        self.decision = self.h.decision
        self.extra = {}
        self.held = {}
        self.down = set()
        self.next_label = 30000
        self.shadow = DecisionRouteDb()
        self.checked = 0
        if load:
            self.load()

    def second_area(self):
        """A 4 x 4 grid in area b, the root joined to its corner: the
        same loopbacks as the fabric's first sixteen nodes (two
        originators a prefix, one an area), labels of its own."""
        grid = topologies.grid(4, area="b")
        for n, db in grid.adj_dbs.items():
            self.dbs["b", n] = replace(db, node_label=db.node_label + 1000)
        for n, pdb in grid.prefix_dbs.items():
            self.prefix_dbs["b", n] = pdb

        def adj(a, b):
            return Adjacency(
                other_node_name=b, if_name=f"if_{a}_{b}",
                other_if_name=f"if_{b}_{a}", metric=1,
                next_hop_v6=BinaryAddress.from_str("fe80::b"))

        self.dbs["b", ROOT] = AdjacencyDatabase(
            this_node_name=ROOT, adjacencies=(adj(ROOT, "node-0"),),
            node_label=self.dbs["0", ROOT].node_label, area="b")
        corner = self.dbs["b", "node-0"]
        self.dbs["b", "node-0"] = replace(
            corner, adjacencies=corner.adjacencies + (adj("node-0", ROOT),))

    def load(self):
        for db in self.dbs.values():
            self.h.publish_adj(db)
        for pdb in self.prefix_dbs.values():
            self.h.publish_prefixes(pdb)
        assert self.h.drain_updates(timeout=1.0)
        self.shadow = self.installed()
        # the load's last window may have been a per-prefix pass: one
        # full rebuild to anchor on, whichever path it takes
        self.rebuild(self.metric, ("0", "ssw-1-1"), 0)

    def stop(self):
        self.h.stop()

    # -- reading Decision, on its own thread ------------------------------

    def on_evb(self, fn):
        return self.decision.evb.call_and_wait(fn)

    def installed(self):
        db = self.decision.route_db
        return self.on_evb(lambda: DecisionRouteDb(
            unicast_routes=dict(db.unicast_routes),
            mpls_routes=dict(db.mpls_routes)))

    def from_scratch(self):
        """What a solver that has never built anything makes of the
        LSDB as it stands (policy applied as Decision applies it), and
        the solver's table beside the installed one."""
        d = self.decision

        def read():
            solver = SpfSolver(ROOT, backend="host")
            solver.static_mpls_routes = {
                k: list(v)
                for k, v in d.spf_solver.static_mpls_routes.items()}
            fresh = solver.build_route_db(
                ROOT, d.area_link_states, d.prefix_state
            ) or DecisionRouteDb()
            if d.rib_policy is not None and d.rib_policy.is_active():
                d.rib_policy.apply_policy(fresh.unicast_routes)
            table = d.spf_solver._route_table
            in_step = (
                d._route_anchor is not None and table is not None
                and d._route_anchor == (d.spf_solver, table.seq))
            mismatched = []
            if in_step:
                for held, mine in (
                        (table.unicast, d.route_db.unicast_routes),
                        (table.mpls, d.route_db.mpls_routes)):
                    assert held is not mine
                    assert set(held) == set(mine)
                    mismatched += [k for k in mine if mine[k] is not held[k]]
            return fresh, in_step, mismatched

        return self.on_evb(read)

    # -- one rebuild ------------------------------------------------------

    def rebuild(self, event, *args):
        """Run one event, wait for its rebuild, hold the result to a
        from-scratch build. Returns what the rebuild's diff span said
        (None: a per-prefix pass, or a rebuild no publication traced)
        and how the two counters moved."""
        before = _delta_counters()
        event(*args)
        update = self.h.next_update(timeout=30.0)
        return self.check(update, before)

    def check(self, update, before):
        fresh, in_step, mismatched = self.from_scratch()
        expected = self.shadow.calculate_update(fresh)
        assert update.unicast_routes_to_update == \
            expected.unicast_routes_to_update
        # (the per-prefix pass also names a withdrawn prefix that never
        # had a route: the root's own)
        assert sorted(p for p in update.unicast_routes_to_delete
                      if p in self.shadow.unicast_routes) == \
            sorted(expected.unicast_routes_to_delete)
        by_label = lambda e: e.label  # noqa: E731
        assert sorted(update.mpls_routes_to_update, key=by_label) == \
            sorted(expected.mpls_routes_to_update, key=by_label)
        assert sorted(update.mpls_routes_to_delete) == \
            sorted(expected.mpls_routes_to_delete)
        now = self.installed()
        assert now.unicast_routes == fresh.unicast_routes
        assert now.mpls_routes == fresh.mpls_routes
        assert not mismatched, mismatched[:5]
        self.shadow = now
        self.checked += 1
        after = _delta_counters()
        span = None
        if update.trace is not None:
            spans = [s for s in update.trace.spans
                     if s.name == "decision.route_diff"]
            if spans:
                (span,) = spans
                table = len(now.unicast_routes) + len(now.mpls_routes)
                assert span.attrs["identical"] + span.attrs["compared"] \
                    == table
                assert span.attrs["compared"] == update.diff_compared
                # the rung that made it: a failed one leaves a span too
                build = [s for s in update.trace.spans
                         if s.name == "decision.route_build"][-1]
                if span.attrs["path"] == "carried":
                    assert span.attrs["why"] == ""
                    assert in_step
                    assert span.attrs["compared"] <= build.attrs["touched"]
                else:
                    assert span.attrs["why"]
        return SimpleNamespace(
            path=span.attrs["path"] if span else None,
            why=span.attrs["why"] if span else None,
            carried=after[0] - before[0],
            whole=after[1] - before[1],
            in_step=in_step,
            update=update,
        )

    # -- events: one publication each, each one a change ------------------

    def _publish(self, slot, **changes):
        self.dbs[slot] = replace(self.dbs[slot], **changes)
        self.h.publish_adj(self.dbs[slot])

    def _up(self, slot):
        """Indices of the node's adjacencies whose link is up: a change
        to one side of a link that is down moves nothing."""
        return [i for i, a in enumerate(self.dbs[slot].adjacencies)
                if (slot[0], frozenset((slot[1], a.other_node_name)))
                not in self.down]

    def metric(self, slot, index, value=None):
        adjs = list(self.dbs[slot].adjacencies)
        up = self._up(slot)
        if not up:  # cut off: bring a link back instead
            return self.flap(min(self.held))
        index = up[index % len(up)]
        if value is None:
            value = adjs[index].metric % 7 + 1
        adjs[index] = replace(adjs[index], metric=value)
        self._publish(slot, adjacencies=tuple(adjs))

    def flap(self, slot):
        """Down if the node has all its links, back up if one is held."""
        if slot in self.held:
            adj = self.held.pop(slot)
            self.down.discard(
                (slot[0], frozenset((slot[1], adj.other_node_name))))
            self._publish(slot, adjacencies=(
                self.dbs[slot].adjacencies + (adj,)))
            return
        adjs = list(self.dbs[slot].adjacencies)
        up = self._up(slot)
        if len(up) < 2:
            return self.metric(slot, 0)
        self.held[slot] = adjs.pop(up[-1])
        self.down.add(
            (slot[0], frozenset((slot[1], self.held[slot].other_node_name))))
        self._publish(slot, adjacencies=tuple(adjs))

    def overload(self, slot):
        if not self._up(slot):
            return self.flap(min(self.held))
        self._publish(slot, is_overloaded=not self.dbs[slot].is_overloaded)

    def label(self, slot):
        self.next_label += 1
        self._publish(slot, node_label=self.next_label)

    def prefix(self, slot):
        """One more prefix from this node, or that one withdrawn."""
        pdb = self.prefix_dbs[slot]
        if slot in self.extra:
            gone = self.extra.pop(slot)
            entries = tuple(e for e in pdb.prefix_entries
                            if e.prefix != gone)
        else:
            self.next_label += 1
            first = pdb.prefix_entries[0]
            self.extra[slot] = topologies._loopback_prefix(self.next_label)
            entries = pdb.prefix_entries + (
                replace(first, prefix=self.extra[slot]),)
        self.prefix_dbs[slot] = replace(pdb, prefix_entries=entries)
        self.h.publish_prefixes(self.prefix_dbs[slot])

    def policy(self, ttl_secs=300.0):
        """Weight 2 on every next hop of three nodes' loopbacks."""
        prefixes = tuple(
            self.prefix_dbs[slot].prefix_entries[0].prefix
            for slot in sorted(self.prefix_dbs)[3:6])
        self.decision.set_rib_policy(RibPolicy([RibPolicyStatement(
            name="w", prefixes=prefixes,
            action=RibRouteAction(
                set_weight=RibRouteActionWeight(default_weight=2)),
        )], ttl_secs=ttl_secs))

    def expire_policy(self):
        d = self.decision

        def expire():
            d.rib_policy._valid_until = time.monotonic()
            d._on_rib_policy_expiry()

        self.on_evb(expire)

    def static_mpls(self, label=70001):
        nh = NextHop(
            address=BinaryAddress.from_str("fe80::5"),
            mpls_action=MplsAction(action=MplsActionCode.PHP))
        have = label in self.decision.spf_solver.static_mpls_routes
        delta = SimpleNamespace(
            mpls_routes_to_update=(
                [] if have else [MplsRoute(top_label=label, next_hops=(nh,))]),
            mpls_routes_to_delete=[label] if have else [])
        self.on_evb(lambda: self.decision._on_static_routes(delta))

    def ctrl_query(self, node=None):
        return self.decision.get_decision_route_db(node)

    def slots(self, rng, k=1):
        return rng.sample(sorted(self.dbs), k)


@pytest.fixture(params=KINDS)
def world(request):
    w = _World(request.param)
    yield w
    w.stop()


def _step(w, rng, names):
    """One random event and its rebuild."""
    name = rng.choice(names)
    (slot,) = w.slots(rng)
    if name == "metric":
        return w.rebuild(w.metric, slot, rng.randrange(8))
    if name == "flap":
        held = sorted(w.held)
        return w.rebuild(w.flap, rng.choice(held) if held and
                         rng.random() < 0.6 else slot)
    if name == "overload":
        return w.rebuild(w.overload, slot)
    if name == "label":
        # not the root's: in two areas it would then show two labels,
        # which no later build patches (test_sp_route_reuse has that)
        others = [s for s in sorted(w.dbs) if s[1] != ROOT]
        return w.rebuild(w.label, rng.choice(others))
    if name == "prefix":
        return w.rebuild(w.prefix, rng.choice(sorted(w.prefix_dbs)))
    if name == "policy":
        if w.decision.rib_policy is not None \
                and w.decision.rib_policy.is_active():
            return w.rebuild(w.expire_policy)
        return w.rebuild(w.policy)
    if name == "static":
        return w.rebuild(w.static_mpls)
    if name == "ctrl":
        # no rebuild of Decision's: the query builds on the same solver
        w.ctrl_query(rng.choice((None, "fsw-1-0")))
        return None
    if name == "fault":
        get_injector().arm(FAULT_SPF_SOLVE, FaultSchedule.fail_once())
        try:
            got = w.rebuild(w.metric, slot, rng.randrange(8))
        finally:
            get_injector().reset()
        return got
    assert name == "backend", name
    w.on_evb(lambda: w.decision.spf_solver.set_backend("host"))
    return w.rebuild(w.metric, slot, rng.randrange(8))


# metric changes, flaps and drains are what the benchmark's traffic is
# made of: they come up most, and carry
_MIX = (["metric"] * 8 + ["flap"] * 4 + ["overload"] * 3 + ["label"] * 2
        + ["prefix"] * 2 + ["policy"] * 2 + ["ctrl"] * 2
        + ["static", "fault", "backend"])


@pytest.mark.parametrize("seed", [7, 2147483659])
def test_every_rebuild_emits_the_whole_diff_and_leaves_the_tables_in_step(
        world, seed):
    rng = random.Random(seed)
    carried = whole = 0
    for _ in range(60):
        got = _step(world, rng, _MIX)
        if got is not None:
            carried += got.carried
            whole += got.whole
    assert world.checked >= 50
    # the stream is built to break the handshake every few events, and
    # the rebuilds between the breaks still carry
    assert carried >= 12, (carried, whole)
    assert whole >= 5, (carried, whole)


def test_churn_alone_carries_every_rebuild_after_the_first(world):
    """Metric changes, flaps and drains away from the root: what the
    benchmark's solver cells offer. Every rebuild carries."""
    rng = random.Random(11)
    far = [s for s in sorted(world.dbs)
           if s[1] != ROOT and ROOT not in
           {a.other_node_name for a in world.dbs[s].adjacencies}]
    for i in range(24):
        slot = rng.choice(far)
        event = (world.metric, world.flap, world.overload)[i % 3]
        got = world.rebuild(
            event, *((slot, rng.randrange(8)) if event == world.metric
                     else (slot,)))
        assert (got.path, got.why) == ("carried", ""), (i, got.why)
        assert (got.carried, got.whole) == (1, 0)


# -- the handshake, case by case -------------------------------------------


def _carries(w, rng):
    """An event away from the root, which nothing keeps from carrying."""
    slot = rng.choice([s for s in sorted(w.dbs) if s[1].startswith("ssw")])
    got = w.rebuild(w.metric, slot, rng.randrange(8))
    assert (got.path, got.why, got.carried, got.whole) == \
        ("carried", "", 1, 0), got.why
    return got


def _whole_once_then_carried(w, rng, why, event=None, *args):
    """The next rebuild (``event``, or a plain metric change) takes the
    whole path for ``why``, the one after it carries."""
    if event is None:
        slot = rng.choice(
            [s for s in sorted(w.dbs) if s[1].startswith("ssw")])
        event, args = w.metric, (slot, rng.randrange(8))
    got = w.rebuild(event, *args)
    assert (got.carried, got.whole) == (0, 1), (got.path, got.why)
    if got.path is not None:
        assert (got.path, got.why) == ("whole", why)
    _carries(w, rng)
    return got


def test_a_prefix_delta_between_two_builds_breaks_the_handshake(world):
    rng = random.Random(1)
    _carries(world, rng)
    got = world.rebuild(world.prefix, ("0", "rsw-1-1"))
    # the per-prefix pass: no diff of either kind, no anchor left
    assert (got.path, got.carried, got.whole) == (None, 0, 0)
    assert not got.in_step
    assert world.decision._anchor_lost == "prefix_delta"
    _whole_once_then_carried(world, rng, "inputs_changed")


def test_a_rib_policy_keeps_the_whole_path_until_it_has_expired(world):
    rng = random.Random(2)
    _carries(world, rng)
    before = _delta_counters()
    world.policy(ttl_secs=1.5)
    # installed: the build it asked for rewrites a copy of the table
    set_build = world.check(world.h.next_update(timeout=30.0), before)
    assert (set_build.carried, set_build.whole) == (0, 1)
    assert not set_build.in_step
    assert any(nh.weight == 2
               for e in world.shadow.unicast_routes.values()
               for nh in e.nexthops)
    # expired: the build its timer asks for finds route_db on the
    # policy's objects, puts the solver's back and anchors
    before = _delta_counters()
    expiry = world.check(world.h.next_update(timeout=30.0), before)
    assert (expiry.carried, expiry.whole) == (0, 1)
    assert expiry.in_step
    assert not any(nh.weight == 2
                   for e in world.shadow.unicast_routes.values()
                   for nh in e.nexthops)
    _carries(world, rng)


def test_a_rib_policy_build_is_whole_although_the_solver_patched(world):
    """The same through traced rebuilds, which say why."""
    rng = random.Random(3)
    _carries(world, rng)
    world.rebuild(world.policy)
    slot = ("0", "ssw-0-0")
    got = world.rebuild(world.metric, slot, 3)
    assert (got.path, got.why, got.whole) == ("whole", "rib_policy", 1)
    world.rebuild(world.expire_policy)
    _carries(world, rng)


def test_a_static_mpls_update_breaks_the_handshake(world):
    rng = random.Random(4)
    _carries(world, rng)
    got = _whole_once_then_carried(
        world, rng, "inputs_changed", world.static_mpls)
    assert [e.label for e in got.update.mpls_routes_to_update] == [70001]
    got = _whole_once_then_carried(
        world, rng, "inputs_changed", world.static_mpls)
    assert got.update.mpls_routes_to_delete == [70001]


def test_a_cold_rung_is_whole_and_the_next_rebuild_carries(world):
    rng = random.Random(5)
    _carries(world, rng)
    get_injector().arm(FAULT_SPF_SOLVE, FaultSchedule.fail_once())
    _whole_once_then_carried(world, rng, "rung_cold")


def test_a_warm_rung_that_raises_after_it_patched_leaves_no_table(world):
    """The rung fails where the unicast routes and the node labels are
    already patched: the table is out of the solver by then, the cold
    rung fills a new one, and route_db never saw the half-built one."""
    rng = random.Random(6)
    _carries(world, rng)
    solver = world.decision.spf_solver
    real = solver._own_label_routes
    seen = []

    def raising(*args):
        solver._own_label_routes = real
        seen.append(solver._route_table)
        raise RuntimeError("after the patch")

    solver._own_label_routes = raising
    _whole_once_then_carried(world, rng, "rung_cold")
    assert seen == [None]


def test_an_exhausted_ladder_leaves_no_anchor(world):
    rng = random.Random(7)
    _carries(world, rng)
    d = world.decision
    real = d.spf_solver.build_routes
    left = [3]

    def failing(*args):
        left[0] -= 1
        if not left[0]:
            d.spf_solver.build_routes = real
        raise RuntimeError("every rung")

    def rebuild():
        d.spf_solver.build_routes = failing
        d.pending.set_needs_full_rebuild()
        with pytest.raises(LadderExhausted):
            d.rebuild_routes("TEST")
        return d._route_anchor, d._anchor_lost

    assert world.on_evb(rebuild) == (None, "ladder_exhausted")
    with pytest.raises(QueueTimeoutError):
        world.h.next_update(timeout=0.2)
    # whole, whichever rung the breaker starts the next walk at: the
    # host rung it holds, or the warm one flipping the backend back
    got = world.rebuild(world.metric, ("0", "ssw-0-0"), 5)
    assert (got.path, got.whole) == ("whole", 1)
    assert got.why in ("rung_host", "no_table")
    assert got.in_step


def test_a_backend_flip_is_whole_and_the_next_rebuild_carries(world):
    rng = random.Random(8)
    _carries(world, rng)
    world.on_evb(lambda: world.decision.spf_solver.set_backend("host"))
    # the warm rung flips back to the configured backend: no table
    _whole_once_then_carried(world, rng, "no_table")


@pytest.mark.parametrize("node", [None, "fsw-1-0"])
def test_a_ctrl_query_between_two_rebuilds_breaks_the_handshake(world, node):
    """For the hot root it patches the solver's table and uses up the
    dirty signature and the engine's carry (what the whole diff used to
    hide: ``base_seq`` is then not the anchor); for another root it
    takes the table's slot."""
    rng = random.Random(9)
    _carries(world, rng)
    # the event lands first, the query after it and before the timer
    # fires: what the query's build re-derives is what the rebuild's
    # build then no longer touches
    queried = []
    d = world.decision
    debounce = d._rebuild_debounced
    fire = debounce._callback

    def query_then_fire():
        queried.append(d.spf_solver.build_route_db(
            node or ROOT, d.area_link_states, d.prefix_state))
        fire()

    world.on_evb(lambda: setattr(debounce, "_callback", query_then_fire))
    try:
        got = world.rebuild(world.metric, ("0", "ssw-0-1"), 6)
    finally:
        world.on_evb(lambda: setattr(debounce, "_callback", fire))
    assert len(queried) == 1
    assert (got.carried, got.whole) == (0, 1)
    assert (got.path, got.why) == (
        "whole", "built_in_between" if node is None else "inputs_changed")
    _carries(world, rng)
    # and a query on a quiet Decision does the same to the next rebuild
    world.ctrl_query(node)
    _whole_once_then_carried(
        world, rng, "built_in_between" if node is None else "inputs_changed")


def test_what_names_no_dirty_set_takes_the_whole_path(world):
    """A change of the root's own links, a node the graph has not seen
    (a re-index), an area that appears: no dirty set, no patch."""
    rng = random.Random(10)
    _carries(world, rng)
    # the root's link signature is part of every route
    _whole_once_then_carried(
        world, rng, "no_dirty_set", world.metric, ("0", ROOT), 0, 9)
    # a node joins: the snapshot is re-indexed
    new = AdjacencyDatabase(
        this_node_name="rsw-9-9", node_label=29999, area="0",
        adjacencies=(Adjacency(
            other_node_name="fsw-1-0", if_name="if_rsw-9-9_fsw-1-0",
            other_if_name="if_fsw-1-0_rsw-9-9", metric=1,
            next_hop_v6=BinaryAddress.from_str("fe80::99")),))
    world.dbs["0", "rsw-9-9"] = new
    world.rebuild(lambda: world.h.publish_adj(new))
    fsw = world.dbs["0", "fsw-1-0"]
    back = Adjacency(
        other_node_name="rsw-9-9", if_name="if_fsw-1-0_rsw-9-9",
        other_if_name="if_rsw-9-9_fsw-1-0", metric=1,
        next_hop_v6=BinaryAddress.from_str("fe80::9a"))
    got = world.rebuild(lambda: world._publish(
        ("0", "fsw-1-0"), adjacencies=fsw.adjacencies + (back,)))
    assert (got.path, got.whole) == ("whole", 1)
    _carries(world, rng)


def test_an_area_that_appears_takes_the_whole_path():
    # a store of two areas from the start, the second one empty
    w = _World("two-areas", load=False)
    try:
        rng = random.Random(12)
        second = {s for s in w.dbs if s[0] == "b"}
        for slot, db in w.dbs.items():
            if slot not in second:
                w.h.publish_adj(db)
        for slot, pdb in w.prefix_dbs.items():
            if slot not in second:
                w.h.publish_prefixes(pdb)
        assert w.h.drain_updates(timeout=1.0)
        w.shadow = w.installed()
        _carries(w, rng)
        for slot in sorted(second):
            w.h.publish_adj(w.dbs[slot])
        assert w.h.drain_updates(timeout=1.0)
        w.shadow = w.installed()
        got = w.rebuild(w.metric, ("0", "ssw-0-0"), 2)
        assert got.in_step
        _carries(w, rng)
    finally:
        w.stop()


def test_a_host_backend_never_carries():
    w = _World("sp-ecmp", solver_backend="host")
    try:
        for value in (3, 4):
            got = w.rebuild(w.metric, ("0", "ssw-0-0"), 1, value)
            assert (got.path, got.why, got.carried, got.whole) == \
                ("whole", "no_table", 0, 1)
    finally:
        w.stop()


def test_lfa_never_carries():
    w = _World("sp-ecmp", load=False)
    try:
        w.on_evb(lambda: setattr(
            w.decision.spf_solver, "compute_lfa_paths", True))
        w.load()

        def lfa_db():
            d = w.decision
            solver = SpfSolver(ROOT, backend="host", compute_lfa_paths=True)
            return solver.build_route_db(
                ROOT, d.area_link_states, d.prefix_state)

        for value in (3, 4):
            before = _delta_counters()
            w.metric(("0", "ssw-0-0"), 1, value)
            update = w.h.next_update(timeout=30.0)
            (span,) = [s for s in update.trace.spans
                       if s.name == "decision.route_diff"]
            assert (span.attrs["path"], span.attrs["why"]) == \
                ("whole", "no_table")
            after = _delta_counters()
            assert (after[0] - before[0], after[1] - before[1]) == (0, 1)
            fresh = w.on_evb(lfa_db)
            now = w.installed()
            assert now.unicast_routes == fresh.unicast_routes
            assert now.mpls_routes == fresh.mpls_routes
    finally:
        w.stop()


def test_warm_boot_anchors_by_the_whole_path():
    """``warm_boot`` rebuilds through the same stage: its first build
    has no table to patch, and what follows carries."""
    w = _World("sp-ecmp", load=False)
    try:
        before = _delta_counters()

        def boot():
            d = w.decision
            for db in w.dbs.values():
                d.area_link_states.setdefault(
                    db.area, LinkState(db.area)
                ).update_adjacency_database(db)
            for pdb in w.prefix_dbs.values():
                d.prefix_state.update_prefix_database(pdb)
            d.pending.set_needs_full_rebuild()
            d.rebuild_routes("WARM_BOOT")
            return d._route_anchor

        anchor = w.on_evb(boot)
        assert anchor is not None
        w.check(w.h.next_update(timeout=30.0), before)
        # the LSDB went in behind the store's back: publish what it
        # holds, so that a later event is a change against it
        for db in w.dbs.values():
            w.h.publish_adj(db)
        for pdb in w.prefix_dbs.values():
            w.h.publish_prefixes(pdb)
        with pytest.raises(QueueTimeoutError):
            w.h.next_update(timeout=0.5)
        rng = random.Random(13)
        _carries(w, rng)
    finally:
        w.stop()


# -- what build_route_db hands out is the caller's --------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_a_db_from_build_route_db_is_not_written_by_later_builds(kind):
    w = _World(kind)
    try:
        d = w.decision
        held = w.ctrl_query()
        kept = DecisionRouteDb(
            unicast_routes=dict(held.unicast_routes),
            mpls_routes=dict(held.mpls_routes))
        objects = {k: id(v) for k, v in held.unicast_routes.items()}
        moved = 0
        # a leaf's link down, a metric up, the link back: three builds
        # that each patch the solver's table in place
        for event, args in (
                (w.flap, (("0", "rsw-1-2"),)),
                (w.metric, (("0", "fsw-1-0"), 0, 9)),
                (w.flap, (("0", "rsw-1-2"),))):
            got = w.rebuild(event, *args)
            moved += len(got.update.unicast_routes_to_update) \
                + len(got.update.unicast_routes_to_delete)
            assert held.unicast_routes == kept.unicast_routes
            assert held.mpls_routes == kept.mpls_routes
            assert {k: id(v) for k, v in held.unicast_routes.items()} \
                == objects
            table = d.spf_solver._route_table
            assert held.unicast_routes is not table.unicast
            assert held.mpls_routes is not table.mpls
        assert moved > 0
        # and the record of a build is no good once a later one has
        # patched the table it points at
        first = w.on_evb(lambda: d.spf_solver.build_routes(
            ROOT, d.area_link_states, d.prefix_state))
        assert first.materialise().unicast_routes == \
            w.installed().unicast_routes
        w.rebuild(w.metric, ("0", "ssw-1-0"), 0, 7)
        with pytest.raises(RuntimeError):
            first.materialise()
    finally:
        w.stop()
