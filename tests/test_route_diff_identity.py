"""The full-db diff through ``Decision``: what it compares field by
field is what the route build re-derived, and stays so.

A small fat tree, the device backend on the CPU. Bulk load, then metric
changes, a flap and one node-label change on a neighbour of the root:
that one makes the SP dirty test re-derive every prefix the neighbour
first-hops for, and every re-derived unicast route comes out equal to
the installed one. Equal routes are not in the delta, so unless the
installed db takes the new objects, the solver's cache and the installed
db hold different objects from then on and every later diff pays
``__eq__`` for them (43 -> 14 identical of 43, for good, on this
topology). These are counts, not times.
"""

from __future__ import annotations

from dataclasses import replace
from types import SimpleNamespace

import pytest

from openr_tpu.decision.spf_solver import SPF_COUNTERS
from openr_tpu.models import topologies
from openr_tpu.telemetry.registry import get_registry
from tests.test_decision_module import DecisionHarness

ROOT = "rsw-0-0"


class _Fabric:
    def __init__(self, labels=False, **shape):
        shape = shape or dict(
            pods=3, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=4)
        topo = topologies.fat_tree(**shape)
        # no SR node labels to start with (unless asked for): the table
        # is the unicast routes alone until the label event adds one
        # MPLS route
        self.dbs = {
            name: db if labels else replace(db, node_label=0)
            for name, db in topo.adj_dbs.items()
        }
        self.prefix_dbs = topo.prefix_dbs
        self.h = DecisionHarness(ROOT)
        self.installed = set()

    def bulk_load(self):
        for db in self.dbs.values():
            self.h.publish_adj(db)
        for pdb in self.prefix_dbs.values():
            self.h.publish_prefixes(pdb)
        updates = self.h.drain_updates(timeout=1.0)
        for u in updates:
            self._apply(u)
        return updates

    def _apply(self, update):
        keys = set(update.unicast_routes_to_update) | {
            e.label for e in update.mpls_routes_to_update}
        adds = len(keys - self.installed)
        self.installed |= keys
        self.installed -= set(update.unicast_routes_to_delete)
        self.installed -= set(update.mpls_routes_to_delete)
        return adds

    def _index(self, node, other):
        return [a.other_node_name for a in self.dbs[node].adjacencies].index(other)

    def metric(self, node, other, value):
        adjs = list(self.dbs[node].adjacencies)
        i = self._index(node, other)
        adjs[i] = replace(adjs[i], metric=value)
        self._publish(node, adjacencies=tuple(adjs))

    def drop(self, node, other):
        adjs = list(self.dbs[node].adjacencies)
        gone = adjs.pop(self._index(node, other))
        self._publish(node, adjacencies=tuple(adjs))
        return gone

    def restore(self, node, adj):
        self._publish(node, adjacencies=self.dbs[node].adjacencies + (adj,))

    def label(self, node, value):
        self._publish(node, node_label=value)

    def _publish(self, node, **changes):
        self.dbs[node] = replace(self.dbs[node], **changes)
        self.h.publish_adj(self.dbs[node])

    def rebuild(self, event):
        """Run one event through Decision; what its diff counted."""
        reuses0 = SPF_COUNTERS["decision.sp_route_reuses"]
        registry = get_registry()
        counted0 = (registry.counter_get("decision.route_diff_identical"),
                    registry.counter_get("decision.route_diff_compared"))
        event(self)
        update = self.h.next_update(timeout=20.0)
        adds = self._apply(update)
        (span,) = [s for s in update.trace.spans
                   if s.name == "decision.route_diff"]
        # the span, the update's riders and the registry say the same
        assert span.attrs["identical"] == update.diff_identical
        assert span.attrs["compared"] == update.diff_compared
        assert span.attrs["updated"] == len(update.unicast_routes_to_update)
        assert (registry.counter_get("decision.route_diff_identical")
                - counted0[0]) == update.diff_identical
        assert (registry.counter_get("decision.route_diff_compared")
                - counted0[1]) == update.diff_compared
        prefixes = len(self.prefix_dbs)
        return {
            "identical": span.attrs["identical"],
            "compared": span.attrs["compared"],
            "updated": (len(update.unicast_routes_to_update)
                        + len(update.mpls_routes_to_update)),
            "adds": adds,
            "rederived": prefixes - (
                SPF_COUNTERS["decision.sp_route_reuses"] - reuses0),
        }


EVENTS = [
    # a route changes: fsw-0-0 stops being a next hop to rsw-0-1
    ("metric, one route changes",
     lambda f: f.metric("fsw-0-0", "rsw-0-1", 5)),
    # ECMP over the other spine keeps every route as it was
    ("metric, nothing changes", lambda f: f.metric("ssw-0-0", "fsw-1-0", 3)),
    # the equal re-derivation: see the module's docstring
    ("label of a neighbour", lambda f: f.label("fsw-0-0", 60001)),
    ("flap down", lambda f: setattr(f, "held", f.drop("rsw-1-2", "fsw-1-0"))),
    ("flap up", lambda f: f.restore("rsw-1-2", f.held)),
    ("metric back", lambda f: f.metric("fsw-0-0", "rsw-0-1", 1)),
    ("metric, nothing changes again",
     lambda f: f.metric("ssw-0-0", "fsw-1-0", 4)),
]


def test_the_diff_compares_what_the_build_rederived_and_the_share_holds():
    fabric = _Fabric()
    try:
        first = next(
            u for u in fabric.bulk_load() if u.unicast_routes_to_update)
        # nothing installed, nothing to reuse: every entry is compared
        assert first.diff_identical == 0
        assert first.diff_compared == len(first.unicast_routes_to_update)
        assert len(fabric.installed) == 21

        seen = []
        for name, event in EVENTS:
            got = fabric.rebuild(event)
            seen.append(got)
            table = len(fabric.installed)
            assert got["identical"] + got["compared"] == table, name
            # at most one MPLS route exists (the labelled neighbour's),
            # and label routes have no reuse counter of their own
            assert got["compared"] <= got["rederived"] + got["adds"] + 1, (
                name, got)
            assert got["updated"] <= got["compared"], (name, got)

        by_name = dict(zip((n for n, _ in EVENTS), seen))
        label = by_name["label of a neighbour"]
        # the case the guard is for: re-derived, compared, found equal
        assert label["rederived"] >= 10
        assert label["compared"] >= label["rederived"]
        assert label["updated"] == label["adds"] == 1  # the label route
        assert by_name["metric, one route changes"]["updated"] >= 1

        def share(got):
            return got["identical"] / (got["identical"] + got["compared"])

        assert share(seen[1]) == 1.0
        assert share(seen[-1]) >= share(seen[1])
    finally:
        fabric.h.stop()


class _Counting(dict):
    """A route table that counts how it is read: key by key, or as a
    whole (``len`` is neither)."""

    def __init__(self, *args):
        super().__init__(*args)
        self.keyed = []
        self.passes = 0

    def get(self, key, default=None):
        self.keyed.append(key)
        return super().get(key, default)

    def __getitem__(self, key):
        self.keyed.append(key)
        return super().__getitem__(key)

    def __contains__(self, key):
        self.keyed.append(key)
        return super().__contains__(key)

    def __setitem__(self, key, value):
        self.keyed.append(key)
        super().__setitem__(key, value)

    def pop(self, key, *default):
        self.keyed.append(key)
        return super().pop(key, *default)

    def __iter__(self):
        self.passes += 1
        return super().__iter__()

    def keys(self):
        self.passes += 1
        return super().keys()

    def values(self):
        self.passes += 1
        return super().values()

    def items(self):
        self.passes += 1
        return super().items()

    def copy(self):
        self.passes += 1
        return super().copy()


_SIZES = {
    # name: (fat_tree shape, routes in the table: a prefix and a label
    # route a node, less the root's own prefix)
    "43 routes": (dict(
        pods=3, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=4), 43),
    "367 routes": (dict(
        pods=6, ssw_per_plane=4, fsw_per_pod=4, rsw_per_pod=24), 367),
}


@pytest.mark.parametrize("size", sorted(_SIZES))
def test_a_carried_rebuild_reads_the_keys_its_build_touched_and_no_more(
        size):
    """The cost of a rebuild's diff follows what the build re-derived,
    not the table: the installed table is read at the keys the build
    touched, each once for the diff and once more where the update
    writes it, and never as a whole. The same three events on a table
    of 43 routes and on one of 367."""
    shape, routes = _SIZES[size]
    fabric = _Fabric(labels=True, **shape)
    decision = fabric.h.decision
    try:
        fabric.bulk_load()
        assert len(fabric.installed) == routes
        # one rebuild to anchor on whatever the bulk load's last window
        # left, then the counting tables go in under the same objects
        fabric.rebuild(lambda f: f.metric("ssw-0-0", "fsw-1-0", 2))
        route_db = decision.route_db

        def count():
            route_db.unicast_routes = _Counting(route_db.unicast_routes)
            route_db.mpls_routes = _Counting(route_db.mpls_routes)

        decision.evb.call_and_wait(count)

        def read(event):
            tables = (route_db.unicast_routes, route_db.mpls_routes)
            for table in tables:
                table.keyed.clear()
                table.passes = 0
            registry = get_registry()
            carried0 = registry.counter_get("decision.route_delta_builds")
            event(fabric)
            update = fabric.h.next_update(timeout=20.0)
            spans = {s.name: s for s in update.trace.spans}
            changed = (
                len(update.unicast_routes_to_update)
                + len(update.unicast_routes_to_delete)
                + len(update.mpls_routes_to_update)
                + len(update.mpls_routes_to_delete))
            return SimpleNamespace(
                carried=registry.counter_get(
                    "decision.route_delta_builds") - carried0,
                path=spans["decision.route_diff"].attrs["path"],
                touched=spans["decision.route_build"].attrs["touched"],
                compared=spans["decision.route_diff"].attrs["compared"],
                identical=spans["decision.route_diff"].attrs["identical"],
                changed=changed,
                keyed=sum(len(t.keyed) for t in tables),
                keys=set().union(*(t.keyed for t in tables)),
                passes=sum(t.passes for t in tables),
            )

        seen = {}
        for name, event in (
            # nothing the root routes over moves: ECMP over the other spine
            ("nothing", lambda f: f.metric("ssw-0-0", "fsw-1-0", 3)),
            # a rack's uplink leaves the root's paths to it, and is back
            ("one rack", lambda f: f.metric("fsw-1-0", "rsw-1-1", 5)),
            ("and back", lambda f: f.metric("fsw-1-0", "rsw-1-1", 1)),
        ):
            got = seen[name] = read(event)
            assert (got.carried, got.path) == (1, "carried"), name
            assert got.passes == 0, name
            # read once a touched key, written once a changed one
            assert got.keyed == got.touched + got.changed, (name, got)
            assert len(got.keys) == got.touched, (name, got)
            assert got.compared == got.touched, name
            assert got.identical == routes - got.compared, name
        assert seen["nothing"].touched == 0
        # the rack's prefix and its label route, at either size
        assert seen["one rack"].touched == 2
        assert seen["one rack"].changed == 2
        assert seen["and back"].touched == 2

        # the counter does see a whole pass when there is one: a ctrl
        # query for the root moves the solver's table on without
        # route_db, and the next rebuild goes through calculate_update
        decision.get_decision_route_db()
        got = read(lambda f: f.metric("ssw-0-0", "fsw-1-0", 4))
        assert (got.carried, got.path) == (0, "whole")
        assert got.passes >= 2
        assert got.identical + got.compared == routes
    finally:
        fabric.h.stop()
