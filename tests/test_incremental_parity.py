"""Warm-started device reconvergence: the incremental churn path must
stay bit-identical to a cold rebuild.

The fused churn dispatch (EllState.reconverge) seeds the fixed point
with the previous solve's distance rows and resets only in rows whose
old shortest paths were TIGHT through an increase-affected edge
(spf_sparse._warm_seed; since PR 31 only the columns of such a row that
no in-edge supports any more, spf_sparse._cone_seed, whose seed
tests/test_cone_seed.py holds to its bounds); every other row keeps its
previous distances as valid upper bounds of the min-relaxation. These tests drive mixed
churn — metric increases, decreases, both at once, link down/restore,
overload flips, stacked patches — and require byte equality with a
from-scratch compile+solve at every step, plus counter assertions
proving the warm path actually ran (a silent fallback to cold solves
would pass parity while giving up the entire speedup)."""

from __future__ import annotations

from dataclasses import replace

import numpy as np
import pytest

from openr_tpu.graph.linkstate import LinkState
from openr_tpu.models import topologies
from openr_tpu.ops import spf_sparse
from tests.test_sp_route_reuse import (
    _drop_adj,
    _mutate_metric,
    _restore_adj,
    _set_overload,
)


def load(topo):
    ls = LinkState(area=topo.area)
    for name in sorted(topo.adj_dbs):
        ls.update_adjacency_database(topo.adj_dbs[name])
    return ls


def _adj_other(ls, node, i):
    return ls.get_adjacency_databases()[node].adjacencies[i].other_node_name


def _grow_in_degree(ls, node, peers):
    """Point ``peers`` at ``node`` (both directions, metric 2), pushing
    its row past its compiled slot class."""
    db = ls.get_adjacency_databases()[node]
    adjs = list(db.adjacencies)
    for peer in peers:
        pdb = ls.get_adjacency_databases()[peer]
        ls.update_adjacency_database(replace(
            pdb,
            adjacencies=tuple(pdb.adjacencies) + (replace(
                pdb.adjacencies[0],
                other_node_name=node,
                if_name=f"if_{peer}_{node}",
                other_if_name=f"if_{node}_{peer}",
                metric=2,
            ),),
        ))
        adjs.append(replace(
            db.adjacencies[0],
            other_node_name=peer,
            if_name=f"if_{node}_{peer}",
            other_if_name=f"if_{peer}_{node}",
            metric=2,
        ))
    ls.update_adjacency_database(replace(db, adjacencies=tuple(adjs)))


class TestEllStateWarmParity:
    """EllState.reconverge vs compile_ell + ell_view_batch_packed,
    byte-for-byte, across every churn class the warm seed models."""

    ROOT = "node-0"

    def _check(self, state, ls, affected):
        if affected:
            patched = spf_sparse.ell_patch(
                state.graph, ls, sorted(affected), widen=True
            )
            assert patched is not None
        else:
            patched = state.graph
        srcs = spf_sparse.ell_source_batch(patched, ls, self.ROOT)
        packed = np.asarray(state.reconverge(patched, srcs))
        ref = np.asarray(
            spf_sparse.ell_view_batch_packed(
                spf_sparse.compile_ell(ls), srcs
            )
        )
        np.testing.assert_array_equal(packed, ref)

    def test_mixed_churn_bit_identical(self):
        topo = topologies.random_mesh(18, degree=4, seed=4, max_metric=9)
        ls = load(topo)
        state = spf_sparse.EllState(spf_sparse.compile_ell(ls))
        c0 = dict(spf_sparse.ELL_COUNTERS)

        # cold first solve: fresh state takes the force-reset sentinel
        # path (same compiled executable as warm)
        self._check(state, ls, [])

        # pure metric increase -> warm solve with a real reset cone
        other = _adj_other(ls, "node-2", 0)
        _mutate_metric(ls, "node-2", 0, 15)
        self._check(state, ls, {"node-2", other})

        # pure decrease: no rows reset, previous distances are seeds
        _mutate_metric(ls, "node-2", 0, 1)
        self._check(state, ls, {"node-2", other})

        # mixed increase + decrease in ONE patch
        o3 = _adj_other(ls, "node-3", 0)
        o5 = _adj_other(ls, "node-5", 1)
        _mutate_metric(ls, "node-3", 0, 20)
        _mutate_metric(ls, "node-5", 1, 1)
        self._check(state, ls, {"node-3", o3, "node-5", o5})

        # link down (reads as w -> INF, an increase) then restore
        o7 = _adj_other(ls, "node-7", 0)
        dropped = _drop_adj(ls, "node-7", 0)
        self._check(state, ls, {"node-7", o7})
        _restore_adj(ls, "node-7", dropped)
        self._check(state, ls, {"node-7", o7})

        # overload flip on/off: journaled at effective weights (a
        # drain reads as an increase of the node's out-edges, an
        # undrain as a decrease), so these stay WARM — and still match
        # bit-for-bit
        c_ov0 = dict(spf_sparse.ELL_COUNTERS)
        _set_overload(ls, "node-9", True)
        self._check(state, ls, {"node-9"})
        _set_overload(ls, "node-9", False)
        self._check(state, ls, {"node-9"})
        c_ov1 = dict(spf_sparse.ELL_COUNTERS)
        assert (
            c_ov1["ell_structural_warm_solves"]
            - c_ov0["ell_structural_warm_solves"]
            >= 2
        )

        # back to pure metric churn: still warm after the flips
        _mutate_metric(ls, "node-4", 0, 7)
        self._check(state, ls, {"node-4", _adj_other(ls, "node-4", 0)})

        c1 = dict(spf_sparse.ELL_COUNTERS)
        assert c1["ell_incremental_syncs"] - c0["ell_incremental_syncs"] >= 7
        # every step after the initial cold solve must ride the warm
        # path, flips included
        assert c1["ell_warm_solves"] - c0["ell_warm_solves"] >= 6
        assert c1["ell_cold_solves"] - c0["ell_cold_solves"] == 1

    def test_stacked_patches_merge_warm_and_match(self):
        """Two patches landing before a solve MERGE in the journal:
        each edge keeps the weight snapshot from the LAST-SOLVED graph
        (first touch wins) while the current side advances, so the
        increase delta emitted at solve time is sound against the
        resident distances and the solve stays WARM — including the
        adversarial order (decrease then increase of the same edge)
        where chaining tight tests against the intermediate weight
        would under-seed. Bit-identity against the cold oracle is the
        proof; stacked patches used to force a cold seed here."""
        topo = topologies.random_mesh(14, degree=3, seed=9, max_metric=7)
        ls = load(topo)
        state = spf_sparse.EllState(spf_sparse.compile_ell(ls))
        self._check(state, ls, [])

        # patch 1 applied WITHOUT a solve (the prewarm flow)
        o2 = _adj_other(ls, "node-2", 0)
        _mutate_metric(ls, "node-2", 0, 2)
        p1 = spf_sparse.ell_patch(state.graph, ls, ["node-2", o2],
                                  widen=True)
        assert p1 is not None
        state.apply_patch(p1)

        # patch 2 stacked on the un-solved journal: decrease then
        # increase of the same edge — the merged entry must test
        # tightness against the ORIGINAL snapshot, not patch 1's value
        c0 = dict(spf_sparse.ELL_COUNTERS)
        _mutate_metric(ls, "node-2", 0, 30)
        self._check(state, ls, {"node-2", o2})
        c1 = dict(spf_sparse.ELL_COUNTERS)
        assert c1["ell_warm_solves"] > c0["ell_warm_solves"]
        assert c1["ell_patch_merges"] > c0["ell_patch_merges"]
        assert c1["ell_cold_solves"] == c0["ell_cold_solves"]

        # journal drained by the solve: next pure-metric event is warm
        c0 = c1
        _mutate_metric(ls, "node-3", 0, 11)
        self._check(state, ls, {"node-3", _adj_other(ls, "node-3", 0)})
        c1 = dict(spf_sparse.ELL_COUNTERS)
        assert c1["ell_warm_solves"] > c0["ell_warm_solves"]

    def test_prewarm_flow_stays_warm(self):
        """apply_patch (solve-free band sync) followed by reconverge at
        the SAME version must consume the journaled increase delta on
        the warm path — the publication-time prewarm must not demote
        the next rebuild to a cold solve."""
        topo = topologies.random_mesh(14, degree=3, seed=2, max_metric=7)
        ls = load(topo)
        state = spf_sparse.EllState(spf_sparse.compile_ell(ls))
        self._check(state, ls, [])

        o4 = _adj_other(ls, "node-4", 0)
        _mutate_metric(ls, "node-4", 0, 18)
        patched = spf_sparse.ell_patch(state.graph, ls, ["node-4", o4],
                                       widen=True)
        assert patched is not None
        state.apply_patch(patched)

        c0 = dict(spf_sparse.ELL_COUNTERS)
        srcs = spf_sparse.ell_source_batch(state.graph, ls, self.ROOT)
        packed = np.asarray(state.reconverge(state.graph, srcs))
        ref = np.asarray(
            spf_sparse.ell_view_batch_packed(
                spf_sparse.compile_ell(ls), srcs
            )
        )
        np.testing.assert_array_equal(packed, ref)
        c1 = dict(spf_sparse.ELL_COUNTERS)
        assert c1["ell_warm_solves"] > c0["ell_warm_solves"]
        assert c1["ell_cold_solves"] == c0["ell_cold_solves"]

    def test_widen_event_counted_and_exact(self):
        """A row outgrowing its slot class widens the band (wholesale
        re-upload, counted in ell_widen_events) — parity must hold
        through the shape change."""
        topo = topologies.grid(4)
        ls = load(topo)
        state = spf_sparse.EllState(spf_sparse.compile_ell(ls))
        self._check(state, ls, [])

        # grow node-5's in-degree past its compiled slot class by
        # pointing several new neighbors at it
        peers = ("node-0", "node-3", "node-10", "node-12",
                 "node-14", "node-15")
        _grow_in_degree(ls, "node-5", peers)
        affected = {"node-5", *peers}
        patched = spf_sparse.ell_patch(
            state.graph, ls, sorted(affected), widen=True
        )
        assert patched is not None and patched.widened
        c0 = dict(spf_sparse.ELL_COUNTERS)
        srcs = spf_sparse.ell_source_batch(patched, ls, self.ROOT)
        packed = np.asarray(state.reconverge(patched, srcs))
        c1 = dict(spf_sparse.ELL_COUNTERS)
        assert c1["ell_widen_events"] > c0["ell_widen_events"]
        # a widen changes node-5's degree CLASS, so a fresh compile_ell
        # RENUMBERS nodes (class-grouped ids) while the resident state
        # keeps ids stable by design — compare via the host oracle, not
        # via raw ids against a recompile
        b = len(srcs)
        d, fh = packed[:b], packed[b:].astype(bool)
        for i, sid in enumerate(srcs):
            src = patched.node_names[sid]
            oracle = ls.run_spf(src)
            for dst in patched.node_names:
                did = patched.node_index[dst]
                want = oracle[dst].metric if dst in oracle else None
                got = int(d[i, did])
                assert (got >= spf_sparse.INF) == (want is None)
                if want is not None:
                    assert got == want, (src, dst, got, want)
        # first hops for the root row (same check as assert_view_parity)
        oracle = ls.run_spf(self.ROOT)
        for dst in patched.node_names:
            did = patched.node_index[dst]
            got_nh = {
                patched.node_names[srcs[i]]
                for i in np.nonzero(fh[:, did])[0]
            }
            want_nh = (
                oracle[dst].next_hops
                if dst in oracle and dst != self.ROOT
                else set()
            )
            assert got_nh == want_nh, (dst, got_nh, want_nh)


class TestApplyPatchOneProgramPerBand:
    """EllState.apply_patch — the solve-free sync behind the decision
    module's publication-time prewarm and the KSP2 masked batches —
    lands a patch with ONE jitted scatter per band that has changed
    rows (spf_sparse._patch_band, the expression _ell_reconverge
    traces in-program), nothing for an unchanged or a widened band.
    Counts on the CPU backend; never times."""

    ROOT = "rsw-0-0"
    LEAF = "rsw-0-1"

    def _view_by_name(self, graph, ls):
        """dst -> (distance from ROOT, first-hop names): a widen
        renumbers a fresh compile_ell, so views compare by name."""
        srcs = spf_sparse.ell_source_batch(graph, ls, self.ROOT)
        return srcs, lambda packed: {
            dst: (
                int(packed[0, did]),
                frozenset(
                    graph.node_names[srcs[i]]
                    for i in np.nonzero(packed[len(srcs):, did])[0]
                ),
            )
            for dst, did in graph.node_index.items()
        }

    @pytest.mark.parametrize(
        "case",
        ["one_band", "both_bands", "widened_band", "overload_flip",
         "stacked_patches"],
    )
    def test_patch_parity_launches_and_compiles(self, case, monkeypatch):
        ls = load(topologies.fat_tree(2))
        state = spf_sparse.EllState(spf_sparse.compile_ell(ls))
        assert len(state.graph.bands) == 2
        srcs, _ = self._view_by_name(state.graph, ls)
        state.reconverge(state.graph, srcs)  # resident distances

        jitted = spf_sparse._patch_band
        launches = []  # the band tensor shape of every launch

        def counting(src, w, ids, rows_src, rows_w):
            # host row blocks go straight in: no staging of its own
            assert all(
                isinstance(a, np.ndarray) for a in (ids, rows_src, rows_w)
            )
            launches.append(src.shape)
            return jitted(src, w, ids, rows_src, rows_w)

        monkeypatch.setattr(spf_sparse, "_patch_band", counting)

        def patch(affected):
            patched = spf_sparse.ell_patch(
                state.graph, ls, sorted(affected), widen=True
            )
            assert patched is not None
            before = len(launches)
            state.apply_patch(patched)
            mine = launches[before:]
            assert len(mine) == len(set(mine))  # at most one per band
            assert len(mine) == sum(
                bi not in (patched.widened or ())
                for bi in patched.changed
            )
            for bi in range(len(patched.bands)):
                np.testing.assert_array_equal(
                    np.asarray(state.src[bi]), patched.src[bi])
                np.testing.assert_array_equal(
                    np.asarray(state.w[bi]), patched.w[bi])
            np.testing.assert_array_equal(
                np.asarray(state.overloaded), patched.overloaded)
            assert state.graph.changed is None
            return patched, mine

        spine = _adj_other(ls, self.LEAF, 0)
        c0 = dict(spf_sparse.ELL_COUNTERS)
        if case == "one_band":
            # LEAF's metric toward its spine sits in the spine's row
            _mutate_metric(ls, self.LEAF, 0, 7)
            _, mine = patch({spine})
            assert len(mine) == 1
        elif case == "both_bands":
            _mutate_metric(ls, self.LEAF, 0, 7)
            _, mine = patch({self.LEAF, spine})
            assert len(mine) == 2
        elif case == "widened_band":
            peers = [f"rsw-1-{i}" for i in range(5)]
            shape = state.src[0].shape
            _grow_in_degree(ls, self.LEAF, peers)
            patched, mine = patch({self.LEAF, *peers})
            # the widened band is re-uploaded wholesale, not scattered
            assert patched.widened == {0} and mine == []
            assert state.src[0].shape[1] > shape[1]
        elif case == "overload_flip":
            _set_overload(ls, spine, True)
            patch({spine})
        else:
            # one edge moved twice with no solve in between: the
            # journal keeps the solved-under snapshot (metric 1)
            _mutate_metric(ls, self.LEAF, 0, 30)
            patch({self.LEAF, spine})
            _mutate_metric(ls, self.LEAF, 0, 3)
            patch({self.LEAF, spine})

        # the rebuild that follows: bands current, warm, equal to cold
        srcs, by_name = self._view_by_name(state.graph, ls)
        got = by_name(np.asarray(state.reconverge(state.graph, srcs)))
        c1 = dict(spf_sparse.ELL_COUNTERS)
        assert c1["ell_warm_solves"] == c0["ell_warm_solves"] + 1
        assert c1["ell_cold_solves"] == c0["ell_cold_solves"]
        if case == "overload_flip":
            assert (c1["ell_structural_warm_solves"]
                    == c0["ell_structural_warm_solves"] + 1)
        if case == "stacked_patches":
            assert c1["ell_patch_merges"] == c0["ell_patch_merges"] + 1
        cold = spf_sparse.compile_ell(ls)
        cold_srcs, cold_by_name = self._view_by_name(cold, ls)
        want = cold_by_name(np.asarray(
            spf_sparse.ell_view_batch_packed(cold, cold_srcs)))
        assert got == want

        # a second patch of a bucket already seen compiles nothing
        _mutate_metric(ls, self.LEAF, 0, 11)
        patch({self.LEAF, spine})
        compiled = jitted._cache_size()
        _mutate_metric(ls, self.LEAF, 0, 12)
        _, mine = patch({self.LEAF, spine})
        assert len(mine) == 2
        assert jitted._cache_size() == compiled


class TestSolverIncrementalParity:
    """SpfSolver end to end: a persistent device solver riding the
    incremental path must produce a RouteDatabase bit-identical to a
    cold rebuild (fresh LinkState replay + fresh solver) after every
    churn event."""

    def _fresh_world(self, ls, topo, ps):
        from openr_tpu.decision.spf_solver import SpfSolver

        cold_ls = LinkState(area=ls.area)
        for name, db in sorted(ls.get_adjacency_databases().items()):
            cold_ls.update_adjacency_database(db)
        solver = SpfSolver(self.root, backend="device")
        return solver.build_route_db(
            self.root, {topo.area: cold_ls}, ps
        )

    def test_mixed_churn_route_db_parity(self, monkeypatch):
        from openr_tpu.decision import spf_solver as ss
        from openr_tpu.decision.prefix_state import PrefixState
        from openr_tpu.decision.spf_solver import SpfSolver

        monkeypatch.setattr(ss, "SPARSE_NODE_THRESHOLD", 4)
        topo = topologies.random_mesh(16, degree=4, seed=6, max_metric=9)
        ls = load(topo)
        ps = PrefixState()
        for pdb in topo.prefix_dbs.values():
            ps.update_prefix_database(pdb)
        self.root = "node-0"
        area_ls = {topo.area: ls}
        warm = SpfSolver(self.root, backend="device")

        def check():
            got = warm.build_route_db(self.root, area_ls, ps)
            want = self._fresh_world(ls, topo, ps)
            assert got.to_route_db(self.root) == want.to_route_db(
                self.root
            )

        check()  # cold
        check()  # steady state
        slot = {}
        muts = [
            lambda: _mutate_metric(ls, "node-3", 0, 12),   # increase
            lambda: _mutate_metric(ls, "node-3", 0, 2),    # decrease
            lambda: (                                      # mixed
                _mutate_metric(ls, "node-5", 0, 17),
                _mutate_metric(ls, "node-8", 1, 1),
            ),
            lambda: slot.__setitem__("adj", _drop_adj(ls, "node-7", 0)),
            lambda: _restore_adj(ls, "node-7", slot["adj"]),
            lambda: _set_overload(ls, "node-9", True),
            lambda: _set_overload(ls, "node-9", False),
            lambda: _mutate_metric(ls, "node-11", 0, 6),
        ]
        for mut in muts:
            mut()
            check()
