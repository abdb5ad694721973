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

from openr_tpu.decision.spf_solver import SPF_COUNTERS
from openr_tpu.models import topologies
from openr_tpu.telemetry.registry import get_registry
from tests.test_decision_module import DecisionHarness

ROOT = "rsw-0-0"


class _Fabric:
    def __init__(self):
        topo = topologies.fat_tree(
            3, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=4)
        # no SR node labels to start with: the table is the unicast
        # routes alone until the label event adds one MPLS route
        self.dbs = {
            name: replace(db, node_label=0)
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
