"""The KSP2 engine's carry as an index into the prefixes.

``SpfSolver.build_route_db``'s bulk path shows the reuse gate only the
prefixes that the carry (``Ksp2Engine.take_affected``) or the SP dirty
test names an advertiser of, and the KSP2 prefixes with an advertiser no
engine tracks; the rest of the solver's route table stays as it is.
The visit set only has to be a superset of what the gate would refuse,
so the cases here are the deployments where a narrower one could drop a
route that had to be re-derived: KSP2 and SP_ECMP prefixes side by side
(on one node too), a prefix with two advertisers of which one is in the
carry, an advertiser present in an area whose engine does not track it,
a change of the static routes. Each stream holds the device solver to a
fresh host solver after every event; the rest reads the mechanism's own
account (``visited`` on ``decision.ksp2_routes``,
``decision.ksp2_routes_visited``). Counts, never times: this is the CPU.
"""

from __future__ import annotations

import random
from dataclasses import replace

import pytest

from openr_tpu.decision import ksp2_engine
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.spf_solver import SPF_COUNTERS, SpfSolver
from openr_tpu.graph.linkstate import LinkState
from openr_tpu.models import topologies
from openr_tpu.telemetry import get_tracer
from openr_tpu.types import Adjacency, AdjacencyDatabase, PrefixEntry
from openr_tpu.types.lsdb import (
    PrefixForwardingAlgorithm,
    PrefixForwardingType,
)
from tests.test_sp_route_reuse import _mutate_metric

KSP2 = dict(
    forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
    forwarding_type=PrefixForwardingType.SR_MPLS,
)
SP = dict(
    forwarding_algorithm=PrefixForwardingAlgorithm.SP_ECMP,
    forwarding_type=PrefixForwardingType.IP,
)
COUNTERS = (
    "decision.ksp2_route_reuses", "decision.sp_route_reuses",
    "decision.ksp2_routes_visited", "decision.ksp2_cold_builds",
    "decision.ksp2_host_fallbacks",
)


@pytest.fixture(autouse=True)
def _engine_everywhere(monkeypatch):
    from openr_tpu.decision import spf_solver as ss

    monkeypatch.setattr(ss, "KSP2_DEVICE_MIN_DSTS", 1)


def _topology(kind: str, area: str = "0"):
    if kind == "grid":
        return topologies.grid(12, area=area, **KSP2)
    if kind == "grid4":
        return topologies.grid(4, area=area, **KSP2)
    return topologies.fat_tree(
        3, ssw_per_plane=2, fsw_per_pod=4, rsw_per_pod=12, area=area, **KSP2)


def _mixed_world(kind: str, all_ksp2: bool = False):
    """One area. Unless ``all_ksp2``: every third node's prefix is
    SP_ECMP over IP, every fifth node advertises a second prefix of its
    own that way beside its KSP2 one, the last KSP2 node's prefix is
    advertised by an early one as well (``shared``: two advertisers, far
    apart), and the KSP2 prefix before it also by an SP_ECMP-only node,
    which no engine tracks (``foreign``)."""
    topo = _topology(kind)
    ls = LinkState(area=topo.area)
    for name in sorted(topo.adj_dbs):
        ls.update_adjacency_database(topo.adj_dbs[name])
    ps = PrefixState()
    names = sorted(topo.prefix_dbs)
    ksp2_nodes, sp_nodes, named = [], [], {}
    for i, name in enumerate(names):
        pdb = topo.prefix_dbs[name]
        (entry,) = pdb.prefix_entries
        if all_ksp2:
            pass
        elif i % 3 == 1:
            pdb = replace(pdb, prefix_entries=(replace(entry, **SP),))
            sp_nodes.append(name)
        else:
            ksp2_nodes.append(name)
            if i % 5 == 0:
                own = topologies._loopback_prefix(5000 + i)
                pdb = replace(pdb, prefix_entries=(
                    entry, PrefixEntry(prefix=own, **SP)))
        topo.prefix_dbs[name] = pdb
    if not all_ksp2:
        for what, also, owner, how in (
                ("shared", ksp2_nodes[1], ksp2_nodes[-1], KSP2),
                ("foreign", sp_nodes[1], ksp2_nodes[-2], SP)):
            entry = topo.prefix_dbs[owner].prefix_entries[0]
            topo.prefix_dbs[also] = replace(
                topo.prefix_dbs[also], prefix_entries=(
                    topo.prefix_dbs[also].prefix_entries
                    + (replace(entry, **how),)))
            named[what] = entry.prefix
    for name in names:
        ps.update_prefix_database(topo.prefix_dbs[name])
    return topo, {topo.area: ls}, ps, named


def _two_area_world():
    """A 4 x 4 grid in area a and a fabric in area b, every prefix
    KSP2, the root ``node-0`` in both (linked to a rack switch of b).
    ``node-5`` of the grid is PRESENT in b as well (linked to another
    rack switch) and advertises nothing there: b's engine does not
    track it, and its paths are computed in b's graph all the same."""
    areas, ps, rsws = {}, PrefixState(), []
    for area, kind in (("a", "grid4"), ("b", "fabric")):
        topo = _topology(kind, area)
        ls = LinkState(area=area)
        for name in sorted(topo.adj_dbs):
            ls.update_adjacency_database(topo.adj_dbs[name])
        areas[area] = ls
        for pdb in topo.prefix_dbs.values():
            ps.update_prefix_database(pdb)
        rsws = [k for k in sorted(topo.adj_dbs) if k.startswith("rsw")]

    def adj(a, b):
        return Adjacency(
            other_node_name=b, if_name=f"if_{a}_{b}",
            other_if_name=f"if_{b}_{a}", metric=1)

    for label, (node, rsw) in enumerate(
            (("node-0", rsws[0]), ("node-5", rsws[17])), 9000):
        areas["b"].update_adjacency_database(AdjacencyDatabase(
            this_node_name=node, adjacencies=(adj(node, rsw),),
            node_label=label, area="b"))
        rdb = areas["b"].get_adjacency_databases()[rsw]
        areas["b"].update_adjacency_database(replace(
            rdb, adjacencies=tuple(rdb.adjacencies) + (adj(rsw, node),)))
    return areas, ps


def _event(rng: random.Random, names, pulled: dict):
    """One change as a function of a LinkState, to apply to both twins:
    a metric change, a node re-costing all its links (the grid cell's
    event), or a link flap (down, and up again the next time round)."""
    node = rng.choice(names)
    r, m, pick = rng.random(), 1 + rng.randrange(9), rng.random()

    def apply(ls):
        db = ls.get_adjacency_databases()[node]
        adjs = list(db.adjacencies)
        if not adjs:
            return
        if r < 0.45:
            i = int(pick * len(adjs))
            adjs[i] = replace(adjs[i], metric=m)
        elif r < 0.8:
            adjs = [replace(a, metric=m) for a in adjs]
        elif (ls.area, node) in pulled.get(id(ls), {}):
            adjs.append(pulled[id(ls)].pop((ls.area, node)))
        elif len(adjs) > 1:
            gone = adjs.pop(int(pick * len(adjs)))
            pulled.setdefault(id(ls), {})[(ls.area, node)] = gone
        ls.update_adjacency_database(replace(db, adjacencies=tuple(adjs)))

    return apply


class _Probe:
    """A device solver whose every build runs under a trace, with what
    the build was handed written down: the engine's carry, the SP dirty
    set, the prefixes it derived."""

    def __init__(self, root, monkeypatch, **solver_kwargs):
        self.root = root
        self.dev = SpfSolver(root, backend="device", **solver_kwargs)
        self.carries, self.derived, self.sp_dirty = [], [], None
        take = ksp2_engine.Ksp2Engine.take_affected

        def taking(engine):
            carry = take(engine)
            self.carries.append(None if carry is None else set(carry))
            return carry

        monkeypatch.setattr(ksp2_engine.Ksp2Engine, "take_affected", taking)
        dirty_nodes, create = (
            self.dev._sp_dirty_nodes, self.dev.create_route_for_prefix)

        def sp_dirty_nodes(*args):
            stored, dirty = dirty_nodes(*args)
            self.sp_dirty = dirty
            return stored, dirty

        def creating(me, areas, ps, prefix):
            self.derived.append(prefix)
            return create(me, areas, ps, prefix)

        self.dev._sp_dirty_nodes = sp_dirty_nodes
        self.dev.create_route_for_prefix = creating

    def build(self, areas, ps):
        tracer = get_tracer()
        trace = tracer.start("test.build")
        before = {k: SPF_COUNTERS[k] for k in COUNTERS}
        self.carries, self.derived, self.sp_dirty = [], [], None
        tracer.activate(trace)
        try:
            db = self.dev.build_route_db(self.root, areas, ps)
        finally:
            tracer.deactivate()
            tracer.finish(trace)
        assert trace.well_formed()
        (span,) = [
            s for s in trace.spans if s.name == "decision.ksp2_routes"]
        moved = {k: SPF_COUNTERS[k] - before[k] for k in COUNTERS}
        assert moved["decision.ksp2_routes_visited"] \
            == span.attrs["visited"] >= len(self.derived)
        return db, span.attrs, moved

    def carry(self):
        """The union of what the build's engines handed over, None
        where one handed over everything."""
        if any(c is None for c in self.carries):
            return None
        return set().union(*self.carries)

    def index_bound(self):
        """|prefixes of the carry and of the SP-dirty nodes| plus
        |untracked KSP2 prefixes|: the most an indexed build may
        visit."""
        _key, _amap, adv_index, _ksp2 = self.dev._advertisers_cache
        named = set()
        for n in self.carry() | self.sp_dirty:
            named |= adv_index.get(n, set())
        return len(named | self.dev._ksp2_untracked_prefixes())


def _advertisers(ps):
    return {
        p: {node for node, _area in entries}
        for p, entries in ps.prefixes().items()}


def _same_routes(db, host_root, areas_h, ps_h, step):
    fresh = SpfSolver(host_root, backend="host").build_route_db(
        host_root, areas_h, ps_h)
    assert db.to_route_db(host_root) == fresh.to_route_db(host_root), step


@pytest.mark.parametrize("kind, root, seed, events", [
    ("fabric", "rsw-0-0", 11, 40), ("fabric", "rsw-0-0", 2147483659, 40),
    ("grid", "node-0", 13, 30), ("grid", "node-0", 4294967311, 30)])
def test_mixed_ksp2_and_sp_prefixes_stay_the_fresh_host_solvers(
        kind, root, seed, events, monkeypatch):
    """KSP2 and SP_ECMP prefixes side by side and on one node, and a
    KSP2 prefix with two advertisers: after every event the route
    database is a fresh host solver's, and a window that did not go
    cold visited no more than the index names. Somewhere along the
    stream only one of the two advertisers is in the carry, and the
    prefix they share is shown to the gate and re-derived; the KSP2
    prefix that an SP_ECMP-only node advertises too is, in every one."""
    _topo, areas_d, ps_d, named = _mixed_world(kind)
    _topo, areas_h, ps_h, _named = _mixed_world(kind)
    (ls_d,), (ls_h,) = areas_d.values(), areas_h.values()
    probe = _Probe(root, monkeypatch)
    db, attrs, _moved = probe.build(areas_d, ps_d)
    total = len(ps_d.prefixes())
    assert attrs["visited"] == attrs["prefixes"] == total  # the load
    _same_routes(db, root, areas_h, ps_h, "load")
    shared, foreign = named["shared"], named["foreign"]
    shared_advs = _advertisers(ps_d)[shared]
    assert len(shared_advs) == 2 and root not in shared_advs
    rng, names, pulled = random.Random(seed), sorted(_topo.adj_dbs), {}
    indexed = one_of_two = 0
    for step in range(events):
        apply = _event(rng, names, pulled)
        apply(ls_d)
        apply(ls_h)
        db, attrs, moved = probe.build(areas_d, ps_d)
        _same_routes(db, root, areas_h, ps_h, step)
        assert moved["decision.ksp2_host_fallbacks"] == 0
        # every prefix is answered for by exactly one of the three
        assert moved["decision.ksp2_route_reuses"] == attrs["reused"]
        assert moved["decision.ksp2_route_reuses"] \
            + moved["decision.sp_route_reuses"] + len(probe.derived) == total
        carry = probe.carry()
        if carry is None or probe.sp_dirty is None:
            continue
        indexed += 1
        # the one KSP2 prefix no carry can answer for, every build
        assert set(probe.dev._ksp2_untracked_prefixes()) == {foreign}
        assert foreign in probe.derived, step
        assert attrs["visited"] <= probe.index_bound(), step
        assert attrs["visited"] <= attrs["prefixes"] <= total
        if len(shared_advs & carry) == 1:
            one_of_two += 1
            assert shared in probe.derived, step
    assert indexed >= events - 4
    assert one_of_two > 0, "no window had one of the two in its carry"


def test_an_advertiser_present_in_an_area_that_does_not_track_it(
        monkeypatch):
    """Two areas with an engine each. ``node-5`` advertises in a and is
    present in b, whose engine does not track it: no carry of b's ever
    names it, so its prefix is shown to the gate, and re-derived, in
    EVERY build, whichever area churns; the rest is adopted in bulk.
    (The two topologies number their loopbacks alike, so most prefixes
    here are advertised from both areas.)"""
    areas_d, ps_d = _two_area_world()
    areas_h, ps_h = _two_area_world()
    probe = _Probe("node-0", monkeypatch)
    db, attrs, _moved = probe.build(areas_d, ps_d)
    total = len(ps_d.prefixes())
    assert attrs["visited"] == total
    _same_routes(db, "node-0", areas_h, ps_h, "load")
    assert "node-5" not in probe.dev._ksp2_tracked
    assert "node-6" in probe.dev._ksp2_tracked
    strays = {
        p for p, advs in _advertisers(ps_d).items() if "node-5" in advs}
    assert strays
    tracked = probe.dev._ksp2_tracked
    rng, pulled = random.Random(29), {}
    for step in range(24):
        area = "ab"[step % 2]
        names = sorted(areas_d[area].get_adjacency_databases())
        apply = _event(rng, names, pulled)
        apply(areas_d[area])
        apply(areas_h[area])
        db, attrs, moved = probe.build(areas_d, ps_d)
        _same_routes(db, "node-0", areas_h, ps_h, step)
        assert len(probe.carries) == 2  # one engine an area
        assert strays == set(probe.dev._ksp2_untracked_prefixes())
        assert strays <= set(probe.derived), step
        if probe.carry() is not None and probe.sp_dirty is not None:
            assert len(strays) <= attrs["visited"] <= probe.index_bound()
            assert attrs["prefixes"] == total
        # the same strays: the tracked set kept its identity, and the
        # untracked prefixes were not made again
        assert probe.dev._ksp2_tracked is tracked
    # node-5 leaves b: b's engine has nothing to track of it any more
    for areas in (areas_d, areas_h):
        rsw = areas["b"].get_adjacency_databases()["node-5"] \
            .adjacencies[0].other_node_name
        areas["b"].delete_adjacency_database("node-5")
        rdb = areas["b"].get_adjacency_databases()[rsw]
        areas["b"].update_adjacency_database(replace(
            rdb, adjacencies=tuple(
                a for a in rdb.adjacencies
                if a.other_node_name != "node-5")))
    db, attrs, _moved = probe.build(areas_d, ps_d)
    _same_routes(db, "node-0", areas_h, ps_h, "left")
    assert "node-5" in probe.dev._ksp2_tracked
    assert not probe.dev._ksp2_untracked_prefixes()


@pytest.mark.parametrize("kind, root", [
    ("fabric", "rsw-0-0"), ("grid", "node-0")])
def test_an_incremental_window_visits_what_the_carry_names(
        kind, root, monkeypatch):
    """Every prefix KSP2, one link's metric a window (the fabric cell's
    event): the loop walks the prefixes of the carry and of the
    SP-dirty nodes and no other, ``prefixes`` stays the prefixes the
    build answered for and ``reused`` those served from the cache, and
    the prefixes adopted in bulk are booked to
    ``decision.ksp2_route_reuses``, none to ``decision.sp_route_reuses``."""
    _topo, areas, ps, _named = _mixed_world(kind, all_ksp2=True)
    (ls,) = areas.values()
    probe = _Probe(root, monkeypatch)
    probe.build(areas, ps)
    total = len(ps.prefixes())
    rng, names = random.Random(3), sorted(_topo.adj_dbs)
    walked = answered = 0
    for step in range(20):
        node = rng.choice(names)
        links = len(ls.get_adjacency_databases()[node].adjacencies)
        _mutate_metric(ls, node, rng.randrange(links), 1 + rng.randrange(9))
        _db, attrs, moved = probe.build(areas, ps)
        assert moved["decision.ksp2_cold_builds"] == 0
        carry = probe.carry()
        # one prefix a node here: the index is the carry and the SP
        # dirty nodes themselves, the root's own prefix aside
        assert attrs["visited"] <= len((carry | probe.sp_dirty) - {root}) \
            == probe.index_bound() - (root in probe.sp_dirty)
        assert attrs["prefixes"] == total
        assert attrs["reused"] == total - len(probe.derived)
        assert attrs["reused"] >= total - attrs["visited"]
        assert attrs["reused"] >= total - 1 - len(carry)
        assert moved["decision.ksp2_route_reuses"] == attrs["reused"]
        assert moved["decision.sp_route_reuses"] == 0
        walked += attrs["visited"]
        answered += attrs["prefixes"]
    # how often the index engages: a small share of what was answered
    assert walked * 4 < answered


def _prefix_event(areas, ps, topo, root):
    """A node that advertises a KSP2 prefix adds one of its own: the
    engine's destinations stay, the prefix state's version moves."""
    node = next(n for n in sorted(topo.prefix_dbs) if n != root)
    pdb = topo.prefix_dbs[node]
    ps.update_prefix_database(replace(pdb, prefix_entries=(
        pdb.prefix_entries + (PrefixEntry(
            prefix=topologies._loopback_prefix(7000), **KSP2),))))


@pytest.mark.parametrize("case", [
    "cold_engine_build", "lfa_solver", "prefix_event", "static_routes"])
def test_where_no_carry_can_serve_every_prefix_is_visited(
        case, monkeypatch):
    """The builds the index stands aside for, each read off what the
    build observes: an engine that built cold mid-stream (its carry is
    every destination), a solver with LFA on (the carry does not model
    what LFA reads), a prefix event (the cache is another prefix
    state's) and a change of the static routes (merged into routes the
    cache holds). The loop walks every prefix of another node's, as it
    did before the index; the windows around them are indexed again."""
    root = "rsw-0-0"
    topo, areas, ps, _named = _mixed_world("fabric", all_ksp2=True)
    (ls,) = areas.values()
    probe = _Probe(
        root, monkeypatch, compute_lfa_paths=(case == "lfa_solver"))
    _db, attrs, _moved = probe.build(areas, ps)
    assert attrs["visited"] == attrs["prefixes"] == len(ps.prefixes())

    for m in (3, 5):  # the SP dirty test's first stored comparison
        _mutate_metric(ls, "fsw-1-2", 0, m)
        _db, attrs, moved = probe.build(areas, ps)
    total = len(ps.prefixes())
    if case == "lfa_solver":
        assert attrs["visited"] == attrs["prefixes"] == total
        assert attrs["reused"] == 0
        return
    assert attrs["visited"] < total // 2 and attrs["prefixes"] == total
    if case == "cold_engine_build":
        (engine,) = probe.dev._ksp2_engines.values()
        engine.invalidate()
        _mutate_metric(ls, "fsw-1-2", 0, 7)
    elif case == "prefix_event":
        _prefix_event(areas, ps, topo, root)
        total += 1
    else:
        probe.dev.update_static_mpls_routes({70000: []}, [])
    _db, attrs, moved = probe.build(areas, ps)
    assert moved["decision.ksp2_cold_builds"] \
        == (case == "cold_engine_build")
    # (the root's own prefix has no advertiser a carry could name)
    assert attrs["visited"] >= total - 1 and attrs["prefixes"] == total
    assert len(probe.derived) >= total - 1
    assert moved["decision.ksp2_routes_visited"] == attrs["visited"]
    _mutate_metric(ls, "fsw-1-2", 0, 2)
    _db, attrs, _moved = probe.build(areas, ps)
    assert attrs["visited"] < total // 2 and attrs["prefixes"] == total
