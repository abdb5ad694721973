"""Incremental KSP2 engine: byte-exact parity with the host solver
under every churn class the invalidation logic models.

The engine (openr_tpu/decision/ksp2_engine.py) persists first/second
paths across topology changes and re-solves only destinations its
distance-algebra test marks affected; these tests drive the SAME
mutation stream through a device solver (engine on) and a fresh host
solver and require identical RouteDatabases every step — an unsound
invalidation (a destination wrongly kept) shows up as a parity break.
Reference semantics: LinkState.cpp:763 getKthPaths, Decision.cpp:908
selectBestPathsKsp2.
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
from openr_tpu.types import AdjacencyDatabase
from openr_tpu.types.lsdb import (
    PrefixForwardingAlgorithm,
    PrefixForwardingType,
)


@pytest.fixture(autouse=True)
def _engine_everywhere(monkeypatch):
    from openr_tpu.decision import spf_solver as ss

    monkeypatch.setattr(ss, "KSP2_DEVICE_MIN_DSTS", 1)


def _ksp2_network(
    kind: str, n: int,
    algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
):
    kwargs = dict(
        forwarding_algorithm=algorithm,
        forwarding_type=PrefixForwardingType.SR_MPLS,
    )
    topo = (
        topologies.grid(n, **kwargs)
        if kind == "grid"
        else topologies.fat_tree_nodes(n, **kwargs)
    )
    ls = LinkState(area=topo.area)
    for name in sorted(topo.adj_dbs):
        ls.update_adjacency_database(topo.adj_dbs[name])
    ps = PrefixState()
    for pdb in topo.prefix_dbs.values():
        ps.update_prefix_database(pdb)
    return topo, {topo.area: ls}, ps


def _mutate_metric(ls, node, i, metric):
    db = ls.get_adjacency_databases()[node]
    adjs = list(db.adjacencies)
    adjs[i] = replace(adjs[i], metric=metric)
    ls.update_adjacency_database(replace(db, adjacencies=tuple(adjs)))


def _drop_adj(ls, node, i):
    """Remove one adjacency (link down: the reverse side still
    advertises, so the Link disappears — bidirectional check)."""
    db = ls.get_adjacency_databases()[node]
    adjs = list(db.adjacencies)
    dropped = adjs.pop(i)
    ls.update_adjacency_database(replace(db, adjacencies=tuple(adjs)))
    return dropped


def _restore_adj(ls, node, adj):
    db = ls.get_adjacency_databases()[node]
    ls.update_adjacency_database(
        replace(db, adjacencies=tuple(list(db.adjacencies) + [adj]))
    )


def _set_overload(ls, node, overloaded):
    db = ls.get_adjacency_databases()[node]
    ls.update_adjacency_database(replace(db, is_overloaded=overloaded))


def _set_label(ls, node, label):
    db = ls.get_adjacency_databases()[node]
    ls.update_adjacency_database(replace(db, node_label=label))


def _ksp2_churn(nodes, events, ksp2_dst_count=0, sp_only=False):
    """Fabric metric churn through the full SpfSolver (device backend)
    from an rsw's vantage: one fsw adjacency cycling its metric, one
    rebuild per event after a warm metric cycle. Returns counter deltas
    over the ``events`` rebuilds.

    ``ksp2_dst_count`` > 0 marks only that many (evenly sampled)
    prefixes KSP2_ED_ECMP and leaves the rest SP_ECMP — KSP2 is a
    per-prefix opt-in, and this is the shape that takes the engine past
    4096 nodes: the all-pairs dispatch covers the whole graph while host
    path tracing stays bounded by the KSP2 destination count.
    ``sp_only`` keeps every prefix SP_ECMP (no engine state at all)."""
    all_ksp2 = ksp2_dst_count <= 0 and not sp_only
    topo, area_ls, ps = _ksp2_network(
        "fabric",
        nodes,
        algorithm=(
            PrefixForwardingAlgorithm.KSP2_ED_ECMP
            if all_ksp2
            else PrefixForwardingAlgorithm.SP_ECMP
        ),
    )
    (ls,) = area_ls.values()
    if ksp2_dst_count > 0:
        names = sorted(topo.prefix_dbs)
        stride = max(1, len(names) // ksp2_dst_count)
        for name in names[::stride][:ksp2_dst_count]:
            pdb = topo.prefix_dbs[name]
            ps.update_prefix_database(replace(
                pdb,
                prefix_entries=tuple(
                    replace(
                        e,
                        forwarding_algorithm=(
                            PrefixForwardingAlgorithm.KSP2_ED_ECMP
                        ),
                    )
                    for e in pdb.prefix_entries
                ),
            ))
    rsw = next(k for k in sorted(topo.adj_dbs) if k.startswith("rsw"))
    fsw = next(k for k in sorted(topo.adj_dbs) if k.startswith("fsw"))
    solver = SpfSolver(rsw, backend="device")
    solver.build_route_db(rsw, area_ls, ps)
    # one full metric cycle first: the engine's cold build and every
    # masked-batch bucket exist before the counted events
    for step in range(5):
        _mutate_metric(ls, fsw, 0, 2 + step % 5)
        solver.build_route_db(rsw, area_ls, ps)
    before = dict(SPF_COUNTERS)
    applied = 0
    for step in range(events):
        _mutate_metric(ls, fsw, 0, 2 + step % 5)
        applied += solver.build_route_db(rsw, area_ls, ps) is not None

    def delta(name):
        return SPF_COUNTERS[name] - before[name]

    return {
        "events": applied,
        "ksp2_host_fallbacks": delta("decision.ksp2_host_fallbacks"),
        "incremental_syncs": delta("decision.ksp2_incremental_syncs"),
        "ksp2_device_batches": delta("decision.ksp2_device_batches"),
        "sp_route_reuses_per_event": (
            delta("decision.sp_route_reuses") / max(1, events)
        ),
    }


class TestEngineChurnParity:
    def _stream(self, kind, n, root, mutations):
        """Apply each mutation to twin graphs; device (engine) and host
        route DBs must match after every step."""
        topo, area_d, ps = _ksp2_network(kind, n)
        _topo, area_h, ps_h = _ksp2_network(kind, n)
        (ls_d,) = area_d.values()
        (ls_h,) = area_h.values()
        dev = SpfSolver(root, backend="device")
        host = SpfSolver(root, backend="host")
        d = dev.build_route_db(root, area_d, ps)
        h = host.build_route_db(root, area_h, ps_h)
        assert d.to_route_db(root) == h.to_route_db(root), "cold"
        for step, fn in enumerate(mutations):
            fn(ls_d)
            fn(ls_h)
            d = dev.build_route_db(root, area_d, ps)
            h = host.build_route_db(root, area_h, ps_h)
            assert d.to_route_db(root) == h.to_route_db(root), step
        return dev

    def test_single_link_metric_cycle_fabric(self):
        """The decision-bench scenario: one fsw adjacency metric
        cycling through ECMP-tie and non-tie values."""
        topo, _, _ = _ksp2_network("fabric", 120)
        fsw = next(
            k for k in sorted(topo.adj_dbs) if k.startswith("fsw")
        )
        rsw = next(
            k for k in sorted(topo.adj_dbs) if k.startswith("rsw")
        )
        before = dict(SPF_COUNTERS)
        self._stream(
            "fabric",
            120,
            rsw,
            [
                (lambda s: (lambda ls: _mutate_metric(ls, fsw, 0, s)))(
                    2 + step % 5
                )
                for step in range(8)
            ],
        )
        syncs = (
            SPF_COUNTERS["decision.ksp2_incremental_syncs"]
            - before["decision.ksp2_incremental_syncs"]
        )
        assert syncs >= 4  # steady-state events ran incrementally

    def test_random_metric_churn_grid(self):
        rng = random.Random(13)
        topo, _, _ = _ksp2_network("grid", 5)
        nodes = sorted(topo.adj_dbs)

        def mk(step):
            victim = rng.choice(nodes)
            metric = rng.randint(1, 9)

            def m(ls):
                db = ls.get_adjacency_databases()[victim]
                if db.adjacencies:
                    _mutate_metric(
                        ls, victim, step % len(db.adjacencies), metric
                    )

            return m

        self._stream("grid", 5, "node-0", [mk(s) for s in range(15)])

    def test_link_down_up(self):
        topo, _, _ = _ksp2_network("fabric", 120)
        fsw = next(
            k for k in sorted(topo.adj_dbs) if k.startswith("fsw")
        )
        rsw = next(
            k for k in sorted(topo.adj_dbs) if k.startswith("rsw")
        )
        dropped = {}

        def down(ls):
            dropped[id(ls)] = _drop_adj(ls, fsw, 0)

        def up(ls):
            _restore_adj(ls, fsw, dropped[id(ls)])

        def metric(ls):
            _mutate_metric(ls, fsw, 0, 4)

        self._stream("fabric", 120, rsw, [metric, down, metric, up])

    def test_overload_flip_transit_node(self):
        """Draining a transit fsw must dirty every destination routed
        through it (node_users index + distance tests)."""
        topo, _, _ = _ksp2_network("fabric", 120)
        fsws = [k for k in sorted(topo.adj_dbs) if k.startswith("fsw")]
        rsw = next(
            k for k in sorted(topo.adj_dbs) if k.startswith("rsw")
        )
        self._stream(
            "fabric",
            120,
            rsw,
            [
                lambda ls: _set_overload(ls, fsws[0], True),
                lambda ls: _mutate_metric(ls, fsws[1], 0, 3),
                lambda ls: _set_overload(ls, fsws[0], False),
            ],
        )

    def test_overloaded_advertiser_drain_filter(self):
        """Draining a DESTINATION (advertiser) changes best-route
        filtering even when no path through it changes."""
        topo, _, _ = _ksp2_network("fabric", 120)
        rsws = [k for k in sorted(topo.adj_dbs) if k.startswith("rsw")]
        self._stream(
            "fabric",
            120,
            rsws[0],
            [
                lambda ls: _set_overload(ls, rsws[5], True),
                lambda ls: _set_overload(ls, rsws[5], False),
            ],
        )

    def test_node_label_change_transit(self):
        """A transit node's SR label is embedded in KSP2 label stacks;
        flipping it must dirty the routes through that node."""
        topo, _, _ = _ksp2_network("fabric", 120)
        fsws = [k for k in sorted(topo.adj_dbs) if k.startswith("fsw")]
        rsw = next(
            k for k in sorted(topo.adj_dbs) if k.startswith("rsw")
        )
        self._stream(
            "fabric",
            120,
            rsw,
            [lambda ls: _set_label(ls, fsws[0], 60000)],
        )

    def test_quiet_churn_dispatch_economy(self):
        """Steady-state metric churn that moves no first path and no
        masked row must not issue the follow-up masked dispatch: the
        fused all-pairs dispatch is the event's one round trip, and
        what the walks read is re-traced off the rows the engine
        holds."""
        topo, area_d, ps = _ksp2_network("fabric", 120)
        (ls,) = area_d.values()
        rsw = next(
            k for k in sorted(topo.adj_dbs) if k.startswith("rsw")
        )
        fsw = next(
            k for k in sorted(topo.adj_dbs) if k.startswith("fsw")
        )
        dev = SpfSolver(rsw, backend="device")
        dev.build_route_db(rsw, area_d, ps)
        # warm one full metric cycle (covers cold/tie transitions)
        for step in range(5):
            _mutate_metric(ls, fsw, 0, 2 + step % 5)
            dev.build_route_db(rsw, area_d, ps)
        # steady state: metric cycles where the churned link stays off
        # every first path (3 -> 4 -> 5: strictly worse than the
        # metric-1 siblings) must cost zero masked dispatches
        quiet = 0
        for metric in (4, 5):
            _mutate_metric(ls, fsw, 0, metric)
            before = dict(SPF_COUNTERS)
            dev.build_route_db(rsw, area_d, ps)
            batches = (
                SPF_COUNTERS["decision.ksp2_device_batches"]
                - before["decision.ksp2_device_batches"]
            )
            syncs = (
                SPF_COUNTERS["decision.ksp2_incremental_syncs"]
                - before["decision.ksp2_incremental_syncs"]
            )
            assert syncs == 1, "event did not run incrementally"
            if batches == 0:
                quiet += 1
        assert quiet == 2, "a quiet event issued masked dispatches"

    def test_route_reuse_counts(self):
        """Steady-state no-op rebuild reuses every cached route."""
        topo, area_d, ps = _ksp2_network("fabric", 120)
        (ls_d,) = area_d.values()
        rsw = next(
            k for k in sorted(topo.adj_dbs) if k.startswith("rsw")
        )
        dev = SpfSolver(rsw, backend="device")
        dev.build_route_db(rsw, area_d, ps)
        before = dict(SPF_COUNTERS)
        dev.build_route_db(rsw, area_d, ps)
        reuses = (
            SPF_COUNTERS["decision.ksp2_route_reuses"]
            - before["decision.ksp2_route_reuses"]
        )
        assert reuses > 100  # nearly every prefix reused

    def test_undrain_reconnects_masked_second_path(self):
        """Draining then undraining the ONLY transit node of a
        destination's second path: the masked graph disconnects and
        must RECONNECT on undrain (code-review regression: the
        link-appeared guard must use effective weights, or the stale
        empty second path survives the undrain)."""
        topo, _, _ = _ksp2_network("fabric", 120)
        fsws = [k for k in sorted(topo.adj_dbs) if k.startswith("fsw")]
        rsw = next(
            k for k in sorted(topo.adj_dbs) if k.startswith("rsw")
        )
        # drain every fsw except two: first paths ride one, the only
        # second path rides the other — draining it disconnects the
        # masked graph for many destinations
        keep = fsws[:2]
        muts = []
        for f in fsws[2:]:
            muts.append(
                (lambda node: lambda ls: _set_overload(ls, node, True))(f)
            )
        muts.append(lambda ls: _set_overload(ls, keep[1], True))
        muts.append(lambda ls: _set_overload(ls, keep[1], False))
        self._stream("fabric", 120, rsw, muts)

    def test_mixed_sp_ecmp_advertiser_not_reused_stale(self):
        """An SP_ECMP-only advertiser is OUTSIDE the engine's tracked
        destination set: its routes must be re-derived every build, not
        reused from a cache the affected set cannot speak for
        (code-review regression: stale ECMP next-hops after churn)."""
        topo, area_d, ps = _ksp2_network("grid", 5)
        _t, area_h, ps_h = _ksp2_network("grid", 5)
        (ls_d,) = area_d.values()
        (ls_h,) = area_h.values()
        # flip node-12's prefixes to SP_ECMP/IP in both worlds
        for p, world_ls in ((ps, ls_d), (ps_h, ls_h)):
            pdb = topo.prefix_dbs["node-12"]
            p.update_prefix_database(
                replace(
                    pdb,
                    prefix_entries=tuple(
                        replace(
                            e,
                            forwarding_type=PrefixForwardingType.IP,
                            forwarding_algorithm=(
                                PrefixForwardingAlgorithm.SP_ECMP
                            ),
                        )
                        for e in pdb.prefix_entries
                    ),
                )
            )
        dev = SpfSolver("node-0", backend="device")
        host = SpfSolver("node-0", backend="host")
        dev.build_route_db("node-0", area_d, ps)
        host.build_route_db("node-0", area_h, ps_h)
        # churn a link on the shortest path toward node-12
        for ls in (ls_d, ls_h):
            _mutate_metric(ls, "node-7", 0, 9)
            _mutate_metric(ls, "node-11", 0, 9)
        d = dev.build_route_db("node-0", area_d, ps)
        h = host.build_route_db("node-0", area_h, ps_h)
        assert d.to_route_db("node-0") == h.to_route_db("node-0")

    def test_multi_area_ksp2_device_parity(self):
        """Two areas, each KSP2-rich, a border root in both: the
        per-area engines batch both graphs and stay byte-exact with the
        host solver under churn in either area (previously multi-area
        KSP2 was host-only)."""
        from openr_tpu.types import PrefixDatabase

        def build_world():
            area_ls = {}
            ps = PrefixState()
            for area, kind, n in (("a", "grid", 4), ("b", "fabric", 120)):
                topo = (
                    topologies.grid(
                        n,
                        area=area,
                        forwarding_algorithm=(
                            PrefixForwardingAlgorithm.KSP2_ED_ECMP
                        ),
                        forwarding_type=PrefixForwardingType.SR_MPLS,
                    )
                    if kind == "grid"
                    else topologies.fat_tree_nodes(
                        n,
                        area=area,
                        forwarding_algorithm=(
                            PrefixForwardingAlgorithm.KSP2_ED_ECMP
                        ),
                        forwarding_type=PrefixForwardingType.SR_MPLS,
                    )
                )
                ls = LinkState(area=area)
                for name in sorted(topo.adj_dbs):
                    ls.update_adjacency_database(topo.adj_dbs[name])
                area_ls[area] = ls
                for pdb in topo.prefix_dbs.values():
                    ps.update_prefix_database(pdb)
            # border root: present in area a's grid as node-0 and in
            # area b via an adjacency to a rack switch
            rsw = sorted(
                k
                for k in area_ls["b"].get_adjacency_databases()
                if k.startswith("rsw")
            )[0]
            from openr_tpu.types import Adjacency, AdjacencyDatabase

            def border_adj(other, metric=1):
                return Adjacency(
                    other_node_name=other,
                    if_name=f"if_node-0_{other}",
                    other_if_name=f"if_{other}_node-0",
                    metric=metric,
                )

            area_ls["b"].update_adjacency_database(
                AdjacencyDatabase(
                    this_node_name="node-0",
                    adjacencies=(border_adj(rsw),),
                    node_label=9000,
                    area="b",
                )
            )
            bdb = area_ls["b"].get_adjacency_databases()[rsw]
            area_ls["b"].update_adjacency_database(
                AdjacencyDatabase(
                    this_node_name=rsw,
                    adjacencies=tuple(bdb.adjacencies)
                    + (border_adj("node-0"),),
                    node_label=bdb.node_label,
                    area="b",
                )
            )
            return area_ls, ps, rsw

        area_d, ps, rsw = build_world()
        area_h, ps_h, _ = build_world()
        dev = SpfSolver("node-0", backend="device")
        host = SpfSolver("node-0", backend="host")

        def check(step):
            d = dev.build_route_db("node-0", area_d, ps)
            h = host.build_route_db("node-0", area_h, ps_h)
            assert d.to_route_db("node-0") == h.to_route_db("node-0"), step

        check("cold")
        fsw = sorted(
            k
            for k in area_d["b"].get_adjacency_databases()
            if k.startswith("fsw")
        )[0]
        before = dict(SPF_COUNTERS)
        for step in range(3):  # churn area b
            for ls in (area_d["b"], area_h["b"]):
                _mutate_metric(ls, fsw, 0, 2 + step)
            check(f"b-{step}")
        for step in range(3):  # churn area a
            for ls in (area_d["a"], area_h["a"]):
                _mutate_metric(ls, "node-2", 0, 3 + step)
            check(f"a-{step}")
        # the multi-area engine path actually engaged: both area
        # engines synced incrementally and untouched routes were reused
        # (MIN_DSTS is 1 via the fixture, so both areas signal)
        assert (
            SPF_COUNTERS["decision.ksp2_incremental_syncs"]
            - before["decision.ksp2_incremental_syncs"]
            >= 6
        )
        assert (
            SPF_COUNTERS["decision.ksp2_route_reuses"]
            - before["decision.ksp2_route_reuses"]
            > 0
        )

    def test_soak_seed_9013_stale_mask_regression(self):
        """Soak-found regression, kept as a parity stream: under
        compound churn (overload flips + link drops) the masks the
        speculative path kept resident drifted, a masked row went
        bogus (total 6 vs true 8), the re-trace silently dropped a
        second path, and stale reused routes diverged from the host 12
        steps later. That path left the tree (PR 32); the engine
        builds a solve's masks from slots it read off the slot map as
        it is now (_batch_masks drops what a re-packed row held), and
        the stream holds it to the host."""
        from tools.soak_ksp2 import soak_one

        out = soak_one(9013, "fabric", 120, 60)
        assert out["parity"] == "ok", out

    def test_soak_seed_40018_slot_map_drift_regression(self):
        """The root cause behind both soak breaks, kept as a parity
        stream: a band patch that changes a node's in-edge SET
        re-packs its slot assignments, which re-aimed every mask bit
        the speculative path kept resident for that row — a dropped
        link shifted two slots and the masked solve excluded the
        wrong edges (metric-15 second path where the truth was 8).
        A solve's masks are built from the current ``slot_of``: slots
        kept from an earlier solve go when their row is re-packed."""
        from tools.soak_ksp2 import soak_one

        out = soak_one(40018, "grid", 5, 60)
        assert out["parity"] == "ok", out

    @pytest.mark.parametrize("seed", [3, 11])
    def test_the_slots_held_are_the_masks_a_fresh_walk_builds(
        self, seed, monkeypatch
    ):
        """The engine keeps, per destination, the slots its exclusion
        set holds (``_batch_masks``) and walks the set again only when
        the first paths move or ell_patch re-packs a row one of its
        links ends in. Held to ``build_edge_masks`` over the live slot
        map after every event of a stream that drops and restores
        links (a row that loses a link shifts the others' slots), on
        every destination; and most of them are not walked again."""
        import numpy as np

        from openr_tpu.ops import spf_sparse

        rng = random.Random(seed)
        _topo, areas, ps = _ksp2_network("grid", 6)
        (ls,) = areas.values()
        names = sorted(ls.get_adjacency_databases())
        dev = SpfSolver("node-0", backend="device")
        walks = []
        walk = spf_sparse.excluded_slots
        monkeypatch.setattr(
            spf_sparse, "excluded_slots",
            lambda *a, **k: walks.append(1) or walk(*a, **k),
        )

        def check() -> int:
            (engine,) = dev._ksp2_engines.values()
            graph = engine.state.graph
            dsts = [d for d in engine.dsts if d not in engine.host_dsts]
            got, ok = engine._batch_masks(graph, dsts, len(dsts) + 3)
            walked = len(walks)
            want, want_ok = spf_sparse.build_edge_masks(
                graph, [engine.excl[d] for d in dsts] + [set()] * 3
            )
            del walks[walked:]
            assert (ok == want_ok).all()
            for a, b in zip(got, want):
                assert np.array_equal(a, b)
            return len(dsts)

        dev.build_route_db("node-0", areas, ps)
        checked = check()
        cold = len(walks)
        down = None
        for _ in range(40):
            victim = names[rng.randrange(len(names))]
            db = ls.get_adjacency_databases()[victim]
            kind = rng.random()
            if kind < 0.35 and down is None:
                down = (victim, _drop_adj(ls, victim, 0))
            elif kind < 0.6 and down is not None:
                _restore_adj(ls, *down)
                down = None
            else:
                _mutate_metric(
                    ls, victim, rng.randrange(len(db.adjacencies)),
                    rng.randrange(1, 6),
                )
            dev.build_route_db("node-0", areas, ps)
            checked += check()
        assert 0 < len(walks) - cold < checked // 4, (len(walks), checked)

    def test_soak_tool_slice(self):
        """CI slice of tools/soak_ksp2: randomized mixed churn with
        byte-exact device-vs-host parity, engine + fast path active."""
        from tools.soak_ksp2 import soak_one

        for seed, kind, n in ((0, "grid", 5), (1, "fabric", 120)):
            out = soak_one(seed, kind, n, 20)
            assert out["parity"] == "ok", out
            assert out["incremental_syncs"] > 0

    def test_fuzz_mixed_churn_random_mesh(self):
        """Adversarial soundness net: a random weighted mesh under a
        random stream of MIXED churn (metric changes, link drops and
        restores, drain/undrain, label flips) must keep the
        engine-backed device solver byte-exact with the host solver at
        every step. Any unsound invalidation (a destination wrongly
        kept cached) breaks parity here."""

        from openr_tpu.models import topologies

        rng = random.Random(0xF00D)
        topo = topologies.random_mesh(30, seed=7)
        area_d = {topo.area: LinkState(area=topo.area)}
        area_h = {topo.area: LinkState(area=topo.area)}
        ps = PrefixState()
        ps_h = PrefixState()
        for name in sorted(topo.adj_dbs):
            area_d[topo.area].update_adjacency_database(
                topo.adj_dbs[name]
            )
            area_h[topo.area].update_adjacency_database(
                topo.adj_dbs[name]
            )
        for pdb in topo.prefix_dbs.values():
            pdb2 = replace(
                pdb,
                prefix_entries=tuple(
                    replace(
                        e,
                        forwarding_type=PrefixForwardingType.SR_MPLS,
                        forwarding_algorithm=(
                            PrefixForwardingAlgorithm.KSP2_ED_ECMP
                        ),
                    )
                    for e in pdb.prefix_entries
                ),
            )
            ps.update_prefix_database(pdb2)
            ps_h.update_prefix_database(pdb2)
        (ls_d,) = area_d.values()
        (ls_h,) = area_h.values()
        nodes = sorted(topo.adj_dbs)
        root = nodes[0]
        dev = SpfSolver(root, backend="device")
        host = SpfSolver(root, backend="host")
        dropped = {}

        def mutate(step):
            kind = rng.choice(
                ["metric", "metric", "metric", "drop", "restore",
                 "drain", "undrain", "label"]
            )
            victim = rng.choice(nodes[1:])
            for ls in (ls_d, ls_h):
                db = ls.get_adjacency_databases()[victim]
                if kind == "metric" and db.adjacencies:
                    # deterministic picks inside the twin loop: an rng
                    # draw here would advance the stream differently
                    # for each twin and desynchronize the graphs
                    i = step % len(db.adjacencies)
                    m = (step * 7 + i) % 90 + 1
                    adjs = list(db.adjacencies)
                    adjs[i] = replace(adjs[i], metric=m)
                    ls.update_adjacency_database(
                        replace(db, adjacencies=tuple(adjs))
                    )
                elif kind == "drop" and len(db.adjacencies) > 1:
                    adjs = list(db.adjacencies)
                    gone = adjs.pop(step % len(adjs))
                    dropped[(id(ls), victim)] = gone
                    ls.update_adjacency_database(
                        replace(db, adjacencies=tuple(adjs))
                    )
                elif kind == "restore":
                    gone = dropped.pop((id(ls), victim), None)
                    if gone is not None:
                        ls.update_adjacency_database(
                            replace(
                                db,
                                adjacencies=tuple(
                                    list(db.adjacencies) + [gone]
                                ),
                            )
                        )
                elif kind == "drain":
                    ls.update_adjacency_database(
                        replace(db, is_overloaded=True)
                    )
                elif kind == "undrain":
                    ls.update_adjacency_database(
                        replace(db, is_overloaded=False)
                    )
                elif kind == "label":
                    ls.update_adjacency_database(
                        replace(db, node_label=50000 + step)
                    )

        d = dev.build_route_db(root, area_d, ps)
        h = host.build_route_db(root, area_h, ps_h)
        assert d.to_route_db(root) == h.to_route_db(root), "cold"
        for step in range(25):
            mutate(step)
            d = dev.build_route_db(root, area_d, ps)
            h = host.build_route_db(root, area_h, ps_h)
            assert d.to_route_db(root) == h.to_route_db(root), step

    def test_prefix_change_invalidates_route_cache(self):
        """A changed prefix advertisement must not serve stale routes."""
        topo, area_d, ps = _ksp2_network("fabric", 120)
        _t, area_h, ps_h = _ksp2_network("fabric", 120)
        rsws = [k for k in sorted(topo.adj_dbs) if k.startswith("rsw")]
        root = rsws[0]
        dev = SpfSolver(root, backend="device")
        host = SpfSolver(root, backend="host")
        dev.build_route_db(root, area_d, ps)
        host.build_route_db(root, area_h, ps_h)
        # withdraw one node's prefixes in both worlds
        for p in (ps, ps_h):
            p.delete_prefix_database(rsws[3], topo.area)
        d = dev.build_route_db(root, area_d, ps)
        h = host.build_route_db(root, area_h, ps_h)
        assert d.to_route_db(root) == h.to_route_db(root)


def _lag_network(metric2: int = 2):
    """2-tier leaf/spine where every leaf-spine pair is a 2-member LAG
    (parallel links, metrics 1 and ``metric2``) — the shape that used
    to force host fallbacks + engine cold rebuilds."""
    kwargs = dict(
        forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
        forwarding_type=PrefixForwardingType.SR_MPLS,
    )
    edges = []
    for leaf in range(4):
        for spine in range(2):
            edges.append((f"leaf-{leaf}", f"spine-{spine}", 1))
            edges.append((f"leaf-{leaf}", f"spine-{spine}", metric2))
    topo = topologies.build_topology("lag-fabric", edges, **kwargs)
    ls = LinkState(area=topo.area)
    for name in sorted(topo.adj_dbs):
        ls.update_adjacency_database(topo.adj_dbs[name])
    ps = PrefixState()
    for pdb in topo.prefix_dbs.values():
        ps.update_prefix_database(pdb)
    return topo, {topo.area: ls}, ps


class TestAffectedCarry:
    """A sync may run before the build that uses it (the solver stages
    one under the debounce's policy wait), so the engine keeps what
    every sync found moved until a build takes it. The invariant: the
    set handed to a ``build_route_db`` covers every destination whose
    paths or routes moved since the previous build took one."""

    @staticmethod
    def _engine(seed):
        """A 5-pod fabric's engine at an RSW, cold-built and taken, and
        a stream of metric changes that each move some destination."""
        topo, area_ls, _ps = _ksp2_network("fabric", 60)
        (ls,) = area_ls.values()
        names = sorted(topo.adj_dbs)
        root = next(k for k in names if k.startswith("rsw"))
        dsts = [k for k in names if k != root]
        engine = ksp2_engine.Ksp2Engine(root)
        assert engine.sync(ls, dsts) is None  # cold build
        assert engine.take_affected() is None  # ... is "all"
        rng = random.Random(seed)

        def step():
            """Mutate until a sync names a destination."""
            for _ in range(200):
                node = rng.choice(names)
                db = ls.get_adjacency_databases()[node]
                i = rng.randrange(len(db.adjacencies))
                _mutate_metric(ls, node, i, 2 + rng.randrange(8))
                affected = engine.sync(ls, dsts)
                assert affected is not None
                if affected:
                    return affected
            raise AssertionError("no mutation moved a destination")

        return engine, ls, dsts, step

    @pytest.mark.parametrize("seed", [3, 2147483659])
    def test_two_syncs_then_one_take_is_the_union(self, seed):
        engine, _ls, _dsts, step = self._engine(seed)
        assert engine.take_affected() == set()
        first = set(step())
        second = set(step())
        assert first and second
        assert engine.take_affected() == first | second
        # taken: the next build starts from nothing
        assert engine.take_affected() == set()

    def test_a_cold_build_between_absorbs_to_all(self, monkeypatch):
        engine, ls, dsts, step = self._engine(5)
        step()
        with monkeypatch.context() as m:
            m.setattr(
                ksp2_engine.Ksp2Engine, "_diff_pairs",
                lambda *a, **k: None,
            )
            _mutate_metric(ls, dsts[0], 0, 7)
            cold = SPF_COUNTERS["decision.ksp2_cold_builds"]
            assert engine.sync(ls, dsts) is None
            assert SPF_COUNTERS["decision.ksp2_cold_builds"] == cold + 1
        step()  # an incremental sync after it does not narrow "all"
        assert engine.take_affected() is None
        assert engine.take_affected() == set()

    def test_a_sync_at_the_engines_version_does_no_work(self):
        """... and says so: the empty set, no ``decision.ksp2_sync``
        span (the metric ``ksp2_sync_ms`` is the median over those
        spans, one a rebuild), neither counter that
        ``ksp2_affected_per_sync`` divides."""
        from openr_tpu.telemetry import get_tracer

        engine, ls, dsts, step = self._engine(7)
        tracer = get_tracer()
        trace = tracer.start()
        tracer.activate(trace)
        try:
            moved = set(step())
            spans = [s.name for s in trace.spans]
            assert spans.count("decision.ksp2_sync") >= 1
            assert engine.take_affected() >= moved
            before = dict(SPF_COUNTERS)
            worked = engine.syncs_worked
            assert engine.sync(ls, dsts) == set()
            assert engine.sync(ls, dsts) == set()
        finally:
            tracer.deactivate()
            tracer.finish(trace)
        assert [s.name for s in trace.spans] == spans
        assert engine.syncs_worked == worked
        for name in ("decision.ksp2_incremental_syncs",
                     "decision.ksp2_affected_dsts",
                     "decision.ksp2_cold_builds",
                     "decision.ksp2_warm_dispatches"):
            assert SPF_COUNTERS[name] == before[name], name
        assert engine.take_affected() == set()

    def test_a_sync_that_raises_leaves_a_cold_build_behind(
            self, monkeypatch):
        """Torn between two versions, the engine is not valid: the next
        sync builds cold and the next take is "all"."""
        engine, ls, dsts, step = self._engine(11)
        step()
        assert engine.take_affected()
        real = ksp2_engine.Ksp2Engine._prime_all

        def torn(self, ls_):
            real(self, ls_)
            raise RuntimeError("torn")

        with monkeypatch.context() as m:
            m.setattr(ksp2_engine.Ksp2Engine, "_prime_all", torn)
            _mutate_metric(ls, dsts[0], 0, 9)
            with pytest.raises(RuntimeError):
                engine.sync(ls, dsts)
        assert not engine.valid
        cold = SPF_COUNTERS["decision.ksp2_cold_builds"]
        assert engine.sync(ls, dsts) is None and engine.valid
        assert SPF_COUNTERS["decision.ksp2_cold_builds"] == cold + 1
        assert engine.take_affected() is None


class TestParallelLinksFirstClass:
    """VERDICT item 6: LAG members are individually maskable, so the
    incremental engine stays warm and no destination falls back to the
    host path on parallel-link fabrics (reference: LinkState.h:82)."""

    def test_lag_fabric_device_host_parity_under_churn(self):
        topo, area_d, ps = _lag_network()
        _t, area_h, ps_h = _lag_network()
        (ls_d,) = area_d.values()
        (ls_h,) = area_h.values()
        root = "leaf-0"
        before = dict(SPF_COUNTERS)
        dev = SpfSolver(root, backend="device")
        host = SpfSolver(root, backend="host")
        d = dev.build_route_db(root, area_d, ps)
        h = host.build_route_db(root, area_h, ps_h)
        assert d.to_route_db(root) == h.to_route_db(root), "cold"

        # churn BOTH LAG members on leaf-1<->spine-0: the min member
        # (adjacency 0) and its sibling (adjacency 1); each step must
        # stay in device/host parity
        steps = []
        for s in range(6):
            steps.append(
                (lambda m: lambda ls: _mutate_metric(
                    ls, "leaf-1", 0, m
                ))(1 + s % 3)
            )
            steps.append(
                (lambda m: lambda ls: _mutate_metric(
                    ls, "leaf-1", 1, m
                ))(2 + s % 4)
            )
        for step, fn in enumerate(steps):
            fn(ls_d)
            fn(ls_h)
            d = dev.build_route_db(root, area_d, ps)
            h = host.build_route_db(root, area_h, ps_h)
            assert d.to_route_db(root) == h.to_route_db(root), step

        fallbacks = (
            SPF_COUNTERS["decision.ksp2_host_fallbacks"]
            - before["decision.ksp2_host_fallbacks"]
        )
        assert fallbacks == 0, fallbacks
        syncs = (
            SPF_COUNTERS["decision.ksp2_incremental_syncs"]
            - before["decision.ksp2_incremental_syncs"]
        )
        assert syncs >= 6  # the engine stayed warm through LAG churn

    def test_lag_member_down_up_parity(self):
        topo, area_d, ps = _lag_network()
        _t, area_h, ps_h = _lag_network()
        (ls_d,) = area_d.values()
        (ls_h,) = area_h.values()
        root = "leaf-0"
        dev = SpfSolver(root, backend="device")
        host = SpfSolver(root, backend="host")
        dev.build_route_db(root, area_d, ps)
        host.build_route_db(root, area_h, ps_h)

        dropped_d = _drop_adj(ls_d, "leaf-0", 0)
        dropped_h = _drop_adj(ls_h, "leaf-0", 0)
        d = dev.build_route_db(root, area_d, ps)
        h = host.build_route_db(root, area_h, ps_h)
        assert d.to_route_db(root) == h.to_route_db(root), "down"

        _restore_adj(ls_d, "leaf-0", dropped_d)
        _restore_adj(ls_h, "leaf-0", dropped_h)
        d = dev.build_route_db(root, area_d, ps)
        h = host.build_route_db(root, area_h, ps_h)
        assert d.to_route_db(root) == h.to_route_db(root), "up"

    def test_equal_cost_lag_members_both_excluded(self):
        """Equal-cost parallel members are BOTH on the first-path ECMP
        set; the second path must avoid the whole group."""
        topo, area_d, ps = _lag_network(metric2=1)
        _t, area_h, ps_h = _lag_network(metric2=1)
        root = "leaf-0"
        dev = SpfSolver(root, backend="device")
        host = SpfSolver(root, backend="host")
        d = dev.build_route_db(root, area_d, ps)
        h = host.build_route_db(root, area_h, ps_h)
        assert d.to_route_db(root) == h.to_route_db(root)


class TestEngineBeyondLegacyBound:
    @pytest.mark.slow
    def test_engine_active_above_4096_nodes(self):
        """VERDICT item 8: the incremental engine runs with the
        all-pairs matrix resident at >4096 nodes (the old
        ENGINE_MAX_NODES). Realistic shape: KSP2 is a per-prefix
        opt-in, so destinations are a subset while the graph is big.
        (~15 s on CPU: each event is one [4224, 4224] all-pairs
        dispatch — single-digit ms on a real accelerator.)"""
        assert ksp2_engine.ENGINE_MAX_NODES > 4096
        result = _ksp2_churn(4200, 1, ksp2_dst_count=128)
        assert result["ksp2_host_fallbacks"] == 0
        assert result["incremental_syncs"] >= 1, result


class TestKsp2ChurnLeg:
    def test_ksp2_churn_smoke(self):
        """All-KSP2 fabric churn runs end to end: engine churn rebuilds
        with zero host fallbacks on a parallel-link-free fabric."""
        out = _ksp2_churn(120, 3)
        assert out["events"] == 3
        assert out["ksp2_host_fallbacks"] == 0
        assert out["incremental_syncs"] == 3

    def test_sp_only_churn_smoke(self):
        """Full-SPF reconvergence of one node's RouteDb, every prefix
        SP_ECMP: no KSP2 engine state at all, host rebuild bounded by
        the SP route reuse dirty test."""
        out = _ksp2_churn(120, 3, sp_only=True)
        assert out["events"] == 3
        assert out["ksp2_device_batches"] == 0
        assert out["incremental_syncs"] == 0  # no engine in play
        assert out["sp_route_reuses_per_event"] > 50


class TestBandWideningOnSolverPath:
    """ell_patch(widen=True) on the Decision/KSP2 path: a node at
    exactly its slot-class capacity gaining a NEW adjacency widens the
    resident band in place (no full recompile of the graph), the
    reconverge dispatch re-uploads the widened band wholesale, and the
    KSP2 engine — whose masked buckets were compiled for the old band
    — re-seeds cleanly instead of shape-mismatching."""

    def test_new_adjacency_widens_and_stays_correct(self):
        from openr_tpu.types import Adjacency

        topo, area_d, ps = _ksp2_network("fabric", 120)
        _t2, area_h, ps_h = _ksp2_network("fabric", 120)
        (ls_d,) = area_d.values()
        (ls_h,) = area_h.values()
        rsws = [k for k in sorted(topo.adj_dbs)
                if k.startswith("rsw")]
        a, b = rsws[0], rsws[-1]
        root = rsws[1]
        dev = SpfSolver(root, backend="device")
        host = SpfSolver(root, backend="host")
        d = dev.build_route_db(root, area_d, ps)
        h = host.build_route_db(root, area_h, ps_h)
        assert d.to_route_db(root) == h.to_route_db(root), "cold"

        before = dict(SPF_COUNTERS)
        from openr_tpu.decision import spf_solver as _ss

        state = _ss._ELL_RESIDENT.state_for(ls_d)
        bands_before = tuple(state.graph.bands)
        # enough NEW adjacencies from `a` to overflow its slot class:
        # per-link "in" graphs give every link its own slot, so +len
        # targets pushes a's in-slot count past any pow2 bound below
        targets = [r for r in rsws if r not in (a, root)][:9]
        assert len(targets) >= 6

        def add_links(ls):
            for v in targets:
                for u, w in ((a, v), (v, a)):
                    db = ls.get_adjacency_databases()[u]
                    link = Adjacency(
                        other_node_name=w, if_name=f"xw-{u}-{w}",
                        metric=2, other_if_name=f"xw-{w}-{u}",
                    )
                    ls.update_adjacency_database(
                        replace(
                            db,
                            adjacencies=tuple(
                                list(db.adjacencies) + [link]
                            ),
                        )
                    )

        add_links(ls_d)
        add_links(ls_h)
        d = dev.build_route_db(root, area_d, ps)
        h = host.build_route_db(root, area_h, ps_h)
        assert d.to_route_db(root) == h.to_route_db(root), "widened"
        # the widening GENUINELY happened: some band's k grew in place
        # while the band partition (starts/rows) stayed fixed
        state = _ss._ELL_RESIDENT.state_for(ls_d)
        bands_after = tuple(state.graph.bands)
        assert [
            (x.start, x.rows) for x in bands_after
        ] == [(x.start, x.rows) for x in bands_before]
        assert any(
            x.k > y.k for x, y in zip(bands_after, bands_before)
        ), (bands_before, bands_after)
        # the resident bands took the PATCH path (widening), not a
        # full recompile
        assert (
            SPF_COUNTERS["decision.ell_patches"]
            > before["decision.ell_patches"]
        )
        assert (
            SPF_COUNTERS["decision.ell_full_compiles"]
            == before["decision.ell_full_compiles"]
        )
        # follow-up metric churn on the widened graph still works
        fsw = next(k for k in sorted(topo.adj_dbs)
                   if k.startswith("fsw"))
        for step in range(3):
            _mutate_metric(ls_d, fsw, 0, 3 + step)
            _mutate_metric(ls_h, fsw, 0, 3 + step)
            d = dev.build_route_db(root, area_d, ps)
            h = host.build_route_db(root, area_h, ps_h)
            assert d.to_route_db(root) == h.to_route_db(root), step


class TestMeshShardedEngine:
    """The engine's all-pairs residency sharded over the device mesh
    (set_engine_mesh): per-device footprint n^2/ndev, activation bound
    scaled by sqrt(ndev) — the path past the single-chip 12k ceiling.
    The masked batches run mesh-wide too: each pads to a device
    multiple and stripes its destinations over the batch axis."""

    @pytest.fixture()
    def engine_mesh(self):
        import jax

        from openr_tpu.parallel.mesh import make_mesh

        ksp2_engine.set_engine_mesh(make_mesh(jax.devices()))
        try:
            yield ksp2_engine.get_engine_mesh()
        finally:
            ksp2_engine.set_engine_mesh(None)

    def test_bound_scales_with_mesh(self, engine_mesh):
        ndev = engine_mesh.devices.size
        assert ksp2_engine.engine_max_nodes() == int(
            ksp2_engine.ENGINE_MAX_NODES * ndev ** 0.5
        )
        ksp2_engine.set_engine_mesh(None)
        assert (
            ksp2_engine.engine_max_nodes()
            == ksp2_engine.ENGINE_MAX_NODES
        )

    def test_sharded_churn_parity(self, engine_mesh):
        """Twin graphs through the device (sharded engine) and host
        solvers across metric churn: identical RouteDbs, incremental
        syncs engaged, zero host fallbacks."""
        topo, area_d, ps = _ksp2_network("fabric", 120)
        _t2, area_h, ps_h = _ksp2_network("fabric", 120)
        (ls_d,) = area_d.values()
        (ls_h,) = area_h.values()
        fsw = next(k for k in sorted(topo.adj_dbs)
                   if k.startswith("fsw"))
        rsw = next(k for k in sorted(topo.adj_dbs)
                   if k.startswith("rsw"))
        dev = SpfSolver(rsw, backend="device")
        host = SpfSolver(rsw, backend="host")
        before = dict(SPF_COUNTERS)
        d = dev.build_route_db(rsw, area_d, ps)
        h = host.build_route_db(rsw, area_h, ps_h)
        assert d.to_route_db(rsw) == h.to_route_db(rsw), "cold"
        for step in range(4):
            _mutate_metric(ls_d, fsw, 0, 2 + step % 3)
            _mutate_metric(ls_h, fsw, 0, 2 + step % 3)
            d = dev.build_route_db(rsw, area_d, ps)
            h = host.build_route_db(rsw, area_h, ps_h)
            assert d.to_route_db(rsw) == h.to_route_db(rsw), step
        assert (
            SPF_COUNTERS["decision.ksp2_incremental_syncs"]
            > before["decision.ksp2_incremental_syncs"]
        )
        assert (
            SPF_COUNTERS["decision.ksp2_host_fallbacks"]
            == before["decision.ksp2_host_fallbacks"]
        )

    def test_mesh_warm_dispatches_stay_on_mesh(self, engine_mesh):
        """Metric churn under the mesh warm-starts the SHARDED fused
        dispatch: the resident all-pairs matrix stays striped over
        every device from one event to the next, warm dispatches are
        counted, and routes stay host-exact (no silent drop to the
        single-chip dispatch)."""
        topo, area_d, ps = _ksp2_network("fabric", 120)
        _t2, area_h, ps_h = _ksp2_network("fabric", 120)
        (ls_d,) = area_d.values()
        (ls_h,) = area_h.values()
        fsw = next(k for k in sorted(topo.adj_dbs)
                   if k.startswith("fsw"))
        rsw = next(k for k in sorted(topo.adj_dbs)
                   if k.startswith("rsw"))
        dev = SpfSolver(rsw, backend="device")
        host = SpfSolver(rsw, backend="host")
        before = dict(SPF_COUNTERS)
        d = dev.build_route_db(rsw, area_d, ps)
        h = host.build_route_db(rsw, area_h, ps_h)
        assert d.to_route_db(rsw) == h.to_route_db(rsw), "cold"
        engine = next(iter(dev._ksp2_engines.values()))
        assert engine._mesh is not None
        ndev = engine_mesh.devices.size
        for step in range(4):
            _mutate_metric(ls_d, fsw, 0, 2 + step % 3)
            _mutate_metric(ls_h, fsw, 0, 2 + step % 3)
            d = dev.build_route_db(rsw, area_d, ps)
            h = host.build_route_db(rsw, area_h, ps_h)
            assert d.to_route_db(rsw) == h.to_route_db(rsw), step
            assert len(
                {s.device for s in engine.d_prev_dev.addressable_shards}
            ) == ndev, "resident all-pairs matrix left the mesh"
        assert (
            SPF_COUNTERS["decision.ksp2_warm_dispatches"]
            - before["decision.ksp2_warm_dispatches"]
        ) == 4, "sharded metric churn must count warm dispatches"

    def test_mesh_mask_budget_chunks_the_batches(self, engine_mesh,
                                                  monkeypatch):
        """A mask budget that admits one masked graph a dispatch
        splits the sharded masked solve into device-multiple batches;
        routes stay host-exact."""
        from openr_tpu.decision import spf_solver as ss

        monkeypatch.setattr(ss, "KSP2_DEVICE_MASK_BUDGET", 1)
        topo, area_d, ps = _ksp2_network("fabric", 120)
        _t2, area_h, ps_h = _ksp2_network("fabric", 120)
        (ls_d,) = area_d.values()
        (ls_h,) = area_h.values()
        fsw = next(k for k in sorted(topo.adj_dbs)
                   if k.startswith("fsw"))
        rsw = next(k for k in sorted(topo.adj_dbs)
                   if k.startswith("rsw"))
        dev = SpfSolver(rsw, backend="device")
        host = SpfSolver(rsw, backend="host")
        before = dict(SPF_COUNTERS)
        d = dev.build_route_db(rsw, area_d, ps)
        h = host.build_route_db(rsw, area_h, ps_h)
        assert d.to_route_db(rsw) == h.to_route_db(rsw), "cold"
        assert (
            SPF_COUNTERS["decision.ksp2_device_batches"]
            - before["decision.ksp2_device_batches"]
        ) >= len(topo.adj_dbs) - 1, "one batch a destination"
        _mutate_metric(ls_d, fsw, 0, 7)
        _mutate_metric(ls_h, fsw, 0, 7)
        d = dev.build_route_db(rsw, area_d, ps)
        h = host.build_route_db(rsw, area_h, ps_h)
        assert d.to_route_db(rsw) == h.to_route_db(rsw), "churn"

    def test_activates_past_single_chip_bound(self, engine_mesh,
                                              monkeypatch):
        """With the single-chip bound shrunk below the graph size, the
        mesh-scaled bound still activates the engine — the composition
        that breaks the ceiling — and routes stay host-exact."""
        monkeypatch.setattr(ksp2_engine, "ENGINE_MAX_NODES", 64)
        assert ksp2_engine.engine_max_nodes() >= 120
        topo, area_d, ps = _ksp2_network("fabric", 120)
        _t2, area_h, ps_h = _ksp2_network("fabric", 120)
        (ls_d,) = area_d.values()
        (ls_h,) = area_h.values()
        fsw = next(k for k in sorted(topo.adj_dbs)
                   if k.startswith("fsw"))
        rsw = next(k for k in sorted(topo.adj_dbs)
                   if k.startswith("rsw"))
        dev = SpfSolver(rsw, backend="device")
        host = SpfSolver(rsw, backend="host")
        before = dict(SPF_COUNTERS)
        d = dev.build_route_db(rsw, area_d, ps)
        h = host.build_route_db(rsw, area_h, ps_h)
        assert d.to_route_db(rsw) == h.to_route_db(rsw), "cold"
        # several small wiggles: a big first delta legitimately trips
        # the most-destinations-affected cold-rebuild heuristic
        for step in range(4):
            _mutate_metric(ls_d, fsw, 0, 2 + step % 3)
            _mutate_metric(ls_h, fsw, 0, 2 + step % 3)
            d = dev.build_route_db(rsw, area_d, ps)
            h = host.build_route_db(rsw, area_h, ps_h)
            assert d.to_route_db(rsw) == h.to_route_db(rsw), step
        assert (
            SPF_COUNTERS["decision.ksp2_incremental_syncs"]
            > before["decision.ksp2_incremental_syncs"]
        ), "engine must be ACTIVE past the single-chip bound"

    def test_mesh_knob_change_reseeds(self, engine_mesh):
        """Flipping the mesh knob mid-life cold-rebuilds instead of
        mixing shardings in the resident state."""
        topo, area_d, ps = _ksp2_network("fabric", 120)
        (ls_d,) = area_d.values()
        rsw = next(k for k in sorted(topo.adj_dbs)
                   if k.startswith("rsw"))
        fsw = next(k for k in sorted(topo.adj_dbs)
                   if k.startswith("fsw"))
        dev = SpfSolver(rsw, backend="device")
        dev.build_route_db(rsw, area_d, ps)
        ksp2_engine.set_engine_mesh(None)  # knob change
        _mutate_metric(ls_d, fsw, 0, 9)
        before = dict(SPF_COUNTERS)
        dev.build_route_db(rsw, area_d, ps)
        assert (
            SPF_COUNTERS["decision.ksp2_cold_builds"]
            > before["decision.ksp2_cold_builds"]
        )


class TestNativeTraceBatch:
    """Differential gate for the native batch tracer (spfcore.cpp
    ksp2_trace_batch): over randomized topologies with exclusions,
    overloaded transit nodes and unreachable destinations, the native
    paths must be BYTE-IDENTICAL (content and order) to the Python
    tracer it replaces."""

    def _graphs(self):
        import numpy as np

        from openr_tpu.decision import spf_solver as ss

        for seed, kind in ((3, "mesh"), (5, "mesh"), (1, "fabric")):
            if kind == "mesh":
                topo = topologies.random_mesh(
                    28, degree=4, seed=seed, max_metric=9
                )
            else:
                topo = topologies.fat_tree(
                    pods=2, ssw_per_plane=2, fsw_per_pod=2,
                    rsw_per_pod=3,
                )
            ls = LinkState(area=topo.area)
            for name in sorted(topo.adj_dbs):
                ls.update_adjacency_database(topo.adj_dbs[name])
            # drain one transit node so blocked filtering is exercised
            names = sorted(topo.adj_dbs)
            drained = names[len(names) // 2]
            db = ls.get_adjacency_databases()[drained]
            ls.update_adjacency_database(
                AdjacencyDatabase(
                    this_node_name=db.this_node_name,
                    is_overloaded=True,
                    adjacencies=db.adjacencies,
                    node_label=db.node_label,
                    area=db.area,
                )
            )
            state = ss._ELL_RESIDENT.state_for(ls)
            yield ls, state.graph, np.random.default_rng(seed)

    def test_matches_python_tracer(self):
        import numpy as np

        from openr_tpu.graph import native_spf

        if not native_spf.is_available():
            pytest.skip("native core unavailable")
        for ls, graph, rng in self._graphs():
            names = list(graph.node_names)
            src = names[0]
            sid = graph.node_index[src]
            cands_of = ksp2_engine.make_cands_of(ls, graph.node_index)
            transit_blocked = {
                nm for nm in names
                if ls.is_node_overloaded(nm) and nm != src
            }
            arrays = ksp2_engine._TraceArrays(
                graph, cands_of, transit_blocked
            )
            # a distance row from the HOST oracle
            spf = ls.get_spf_result(src)
            row = np.full(graph.n_pad, ksp2_engine.INF, np.int32)
            for nm, res in spf.items():
                row[graph.node_index[nm]] = res.metric
            dsts = [nm for nm in names if nm != src]
            # shared-row, no exclusions (first-path shape)
            got = arrays.trace(
                sid,
                np.asarray(
                    [graph.node_index[d] for d in dsts], np.int32
                ),
                row, True, [set()] * len(dsts),
            )
            want = [
                ksp2_engine.trace_paths_from_row(
                    src, d, graph.node_index, row.tolist(), set(),
                    cands_of, transit_blocked,
                )
                for d in dsts
            ]
            assert got == want, "shared-row trace diverged"
            # per-dst rows with first-path exclusions (second-path
            # shape). Every destination gets a DISTINCT perturbed row
            # (random entries bumped) so a row-indexing bug in the
            # shared_row=0 stride arithmetic cannot hide behind
            # identical rows; expectations re-derive from the same
            # perturbed row through the Python tracer.
            excls = [
                {l for p in w for l in p} for w in want
            ]
            rows = np.tile(row, (len(dsts), 1))
            for i in range(len(dsts)):
                bump = rng.integers(0, graph.n_pad, size=3)
                rows[i, bump] = np.minimum(
                    rows[i, bump].astype(np.int64) + 1 + i,
                    int(ksp2_engine.INF),
                ).astype(np.int32)
            got2 = arrays.trace(
                sid,
                np.asarray(
                    [graph.node_index[d] for d in dsts], np.int32
                ),
                rows, False, excls,
            )
            want2 = [
                ksp2_engine.trace_paths_from_row(
                    src, d, graph.node_index, rows[i].tolist(), excl,
                    cands_of, transit_blocked,
                )
                for i, (d, excl) in enumerate(zip(dsts, excls))
            ]
            assert got2 == want2, "per-dst excluded trace diverged"
