"""Sparse edge-list SPF kernels: parity with the dense kernels, the host
Dijkstra oracle, and the sharded mesh variant."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from openr_tpu.graph.linkstate import LinkState
from openr_tpu.graph.snapshot import INF, compile_snapshot
from openr_tpu.models import topologies
from openr_tpu.ops import spf, spf_sparse
from openr_tpu.types import AdjacencyDatabase


def load(topo, overloaded_nodes=()):
    ls = LinkState(area=topo.area)
    for name, db in sorted(topo.adj_dbs.items()):
        if name in overloaded_nodes:
            db = AdjacencyDatabase(
                this_node_name=db.this_node_name,
                is_overloaded=True,
                adjacencies=db.adjacencies,
                node_label=db.node_label,
                area=db.area,
            )
        ls.update_adjacency_database(db)
    return ls


class TestSparseParity:
    def assert_matches_oracle(self, ls, use_link_metric=True):
        graph = spf_sparse.compile_sparse(ls, use_link_metric)
        src_ids = np.arange(graph.n, dtype=np.int32)
        d = np.asarray(
            spf_sparse.sparse_distances_from_sources(graph, src_ids)
        )
        for src in graph.node_names:
            sid = graph.node_index[src]
            oracle = ls.run_spf(src, use_link_metric)
            for dst in graph.node_names:
                did = graph.node_index[dst]
                want = oracle[dst].metric if dst in oracle else None
                got = int(d[sid, did])
                assert (got >= INF) == (want is None), (src, dst)
                if want is not None:
                    assert got == want, (src, dst, got, want)

    def test_grid(self):
        self.assert_matches_oracle(load(topologies.grid(4)))

    def test_random_weighted(self):
        for seed in range(3):
            topo = topologies.random_mesh(
                24, degree=4, seed=seed, max_metric=20
            )
            self.assert_matches_oracle(load(topo))

    def test_overloaded_transit(self):
        topo = topologies.random_mesh(20, degree=4, seed=5, max_metric=9)
        self.assert_matches_oracle(
            load(topo, overloaded_nodes={"node-2", "node-9"})
        )

    def test_overloaded_source_still_originates(self):
        topo = topologies.grid(3)
        ls = load(topo, overloaded_nodes={"node-0"})
        graph = spf_sparse.compile_sparse(ls)
        d = np.asarray(
            spf_sparse.sparse_distances_from_sources(
                graph, [graph.node_index["node-0"]]
            )
        )
        for name in graph.node_names:
            assert d[0, graph.node_index[name]] < INF

    def test_hop_count_mode(self):
        topo = topologies.random_mesh(16, degree=3, seed=7, max_metric=40)
        self.assert_matches_oracle(load(topo), use_link_metric=False)

    def test_matches_dense_kernel(self):
        topo = topologies.fat_tree(
            pods=2, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=3
        )
        ls = load(topo, overloaded_nodes={"fsw-0-0"})
        snap = compile_snapshot(ls)
        graph = spf_sparse.compile_sparse(ls)
        assert snap.node_names == list(graph.node_names)
        src_ids = np.arange(graph.n, dtype=np.int32)
        d_sparse = np.asarray(
            spf_sparse.sparse_distances_from_sources(graph, src_ids)
        )
        d_dense = np.asarray(
            spf.distances_from_sources(
                jnp.asarray(snap.metric),
                jnp.asarray(snap.overloaded),
                jnp.asarray(src_ids),
            )
        )
        np.testing.assert_array_equal(
            d_sparse[:, : graph.n], d_dense[:, : graph.n]
        )


class TestEllFormat:
    """ELL fixed-slot graph: oracle parity + incremental row patching."""

    @staticmethod
    def batch_for(graph, ls, src):
        srcs = spf_sparse.ell_source_batch(graph, ls, src)
        sid = srcs[0]
        nbrs = [i for i in srcs[1:] if i != sid]
        return sid, nbrs, srcs

    def assert_view_parity(self, ls):
        graph = spf_sparse.compile_ell(ls)
        for src in graph.node_names:
            sid, nbrs, srcs = self.batch_for(graph, ls, src)
            packed = np.asarray(
                spf_sparse.ell_view_batch_packed(graph, srcs)
            )
            b = len(srcs)
            d, fh = packed[:b], packed[b:].astype(bool)
            oracle = ls.run_spf(src)
            for dst in graph.node_names:
                did = graph.node_index[dst]
                want = oracle[dst].metric if dst in oracle else None
                got = int(d[0, did])
                assert (got >= INF) == (want is None), (src, dst)
                if want is not None:
                    assert got == want, (src, dst)
                got_nh = {
                    graph.node_names[srcs[i]]
                    for i in np.nonzero(fh[:, did])[0]
                }
                want_nh = (
                    oracle[dst].next_hops
                    if dst in oracle and dst != src
                    else set()
                )
                assert got_nh == want_nh, (src, dst, got_nh, want_nh)

    def test_grid(self):
        self.assert_view_parity(load(topologies.grid(4)))

    def test_random_weighted(self):
        for seed in range(2):
            topo = topologies.random_mesh(
                18, degree=4, seed=seed, max_metric=12
            )
            self.assert_view_parity(load(topo))

    def test_overloaded_nodes(self):
        topo = topologies.random_mesh(16, degree=4, seed=3, max_metric=9)
        self.assert_view_parity(
            load(topo, overloaded_nodes={"node-1", "node-7"})
        )

    def test_patch_matches_full_recompile(self):
        topo = topologies.random_mesh(20, degree=4, seed=5, max_metric=9)
        ls = load(topo)
        graph = spf_sparse.compile_ell(ls)

        # churn one metric
        from dataclasses import replace

        db = ls.get_adjacency_databases()["node-4"]

        adjs = list(db.adjacencies)
        a0 = adjs[0]
        adjs[0] = replace(a0, metric=a0.metric + 3)
        ls.update_adjacency_database(replace(db, adjacencies=tuple(adjs)))
        affected = {"node-4", a0.other_node_name}
        patched = spf_sparse.ell_patch(graph, ls, sorted(affected))
        full = spf_sparse.compile_ell(ls)
        assert patched is not None
        assert patched.bands == full.bands
        for a, b in zip(patched.src, full.src):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(patched.w, full.w):
            np.testing.assert_array_equal(a, b)

    def test_fused_reconverge_matches_unfused(self):
        topo = topologies.random_mesh(14, degree=3, seed=8, max_metric=7)
        ls = load(topo)
        graph = spf_sparse.compile_ell(ls)
        sid, nbrs, srcs = self.batch_for(graph, ls, "node-0")
        state = spf_sparse.EllState(graph)

        # churn: bump one adjacency metric, patch incrementally
        from dataclasses import replace

        db = ls.get_adjacency_databases()["node-2"]

        adjs = list(db.adjacencies)
        a0 = adjs[0]
        adjs[0] = replace(a0, metric=a0.metric + 5)
        ls.update_adjacency_database(replace(db, adjacencies=tuple(adjs)))
        patched = spf_sparse.ell_patch(
            graph, ls, ["node-2", a0.other_node_name]
        )
        assert patched is not None
        packed = np.asarray(state.reconverge(patched, srcs))
        ref = np.asarray(
            spf_sparse.ell_view_batch_packed(
                spf_sparse.compile_ell(ls), srcs
            )
        )
        np.testing.assert_array_equal(packed, ref)
        # resident bands now equal the full recompile
        for a, b in zip(state.src, spf_sparse.compile_ell(ls).src):
            np.testing.assert_array_equal(np.asarray(a), b)


class TestSparseSolverBackend:
    def test_sparse_device_backend_matches_host(self, monkeypatch):
        """Past SPARSE_NODE_THRESHOLD the device backend switches to the
        edge-list kernel; the full RouteDatabase must stay identical."""
        from openr_tpu.decision import spf_solver as ss
        from openr_tpu.decision.prefix_state import PrefixState
        from openr_tpu.decision.spf_solver import SpfSolver

        monkeypatch.setattr(ss, "SPARSE_NODE_THRESHOLD", 4)
        topo = topologies.random_mesh(18, degree=4, seed=2, max_metric=9)
        ls = load(topo, overloaded_nodes={"node-3"})
        ps = PrefixState()
        for pdb in topo.prefix_dbs.values():
            ps.update_prefix_database(pdb)
        area_ls = {topo.area: ls}
        sparse_db = SpfSolver("node-0", backend="device").build_route_db(
            "node-0", area_ls, ps
        )
        host_db = SpfSolver("node-0", backend="host").build_route_db(
            "node-0", area_ls, ps
        )
        assert sparse_db.to_route_db("node-0") == host_db.to_route_db(
            "node-0"
        )

    def test_sparse_backend_with_lfa(self, monkeypatch):
        from openr_tpu.decision import spf_solver as ss
        from openr_tpu.decision.prefix_state import PrefixState
        from openr_tpu.decision.spf_solver import SpfSolver

        monkeypatch.setattr(ss, "SPARSE_NODE_THRESHOLD", 4)
        topo = topologies.random_mesh(14, degree=3, seed=6, max_metric=7)
        ls = load(topo)
        ps = PrefixState()
        for pdb in topo.prefix_dbs.values():
            ps.update_prefix_database(pdb)
        area_ls = {topo.area: ls}
        kw = dict(compute_lfa_paths=True)
        sparse_db = SpfSolver(
            "node-0", backend="device", **kw
        ).build_route_db("node-0", area_ls, ps)
        host_db = SpfSolver("node-0", backend="host", **kw).build_route_db(
            "node-0", area_ls, ps
        )
        assert sparse_db.to_route_db("node-0") == host_db.to_route_db(
            "node-0"
        )


class TestShardedSparse:
    @pytest.fixture(scope="class")
    def mesh8(self):
        from openr_tpu.parallel import mesh as pmesh

        assert len(jax.devices()) == 8
        return pmesh.make_mesh(axis_name=spf_sparse.SOURCES_AXIS)

    def test_sharded_matches_unsharded(self, mesh8):
        topo = topologies.random_mesh(48, degree=4, seed=3, max_metric=15)
        ls = load(topo, overloaded_nodes={"node-5"})
        # pad the node axis so rows divide across 8 devices
        graph = spf_sparse.compile_sparse(ls, align=8)
        d_sharded = np.asarray(
            spf_sparse.sharded_sparse_all_sources(graph, mesh8)
        )
        d_local = np.asarray(
            spf_sparse.sparse_distances_from_sources(
                graph, np.arange(graph.n_pad, dtype=np.int32)
            )
        )
        np.testing.assert_array_equal(d_sharded, d_local)

    def test_padding_rows_inert(self, mesh8):
        topo = topologies.grid(4)
        ls = load(topo)
        graph = spf_sparse.compile_sparse(ls, align=8)
        d = np.asarray(spf_sparse.sharded_sparse_all_sources(graph, mesh8))
        assert (d[graph.n :, : graph.n] >= INF).all()


class TestEllAllSources:
    """The ELL-band all-sources kernel (gather+reduce, no segment-min):
    oracle parity, block streaming, and the mesh-sharded variant."""

    def test_matches_edge_list_kernel_and_oracle(self):
        topo = topologies.random_mesh(30, degree=4, seed=11, max_metric=13)
        ls = load(topo, overloaded_nodes={"node-6"})
        ell = spf_sparse.compile_ell(ls)
        d = spf_sparse.ell_all_sources(ell, block=16)
        # node numbering differs between ELL (class-grouped) and the
        # flat kernels — compare via names against the host oracle
        for src in ell.node_names:
            oracle = ls.run_spf(src)
            sid = ell.node_index[src]
            for dst in ell.node_names:
                did = ell.node_index[dst]
                want = oracle[dst].metric if dst in oracle else None
                got = int(d[sid, did])
                assert (got >= INF) == (want is None), (src, dst)
                if want is not None:
                    assert got == want, (src, dst, got, want)

    def test_block_streaming_covers_all_rows(self):
        topo = topologies.grid(5)
        ls = load(topo)
        ell = spf_sparse.compile_ell(ls, align=8)
        full = spf_sparse.ell_all_sources(ell, block=ell.n_pad)
        seen = np.zeros(ell.n_pad, dtype=bool)
        for start, blk in spf_sparse.iter_ell_all_sources(ell, block=8):
            take = min(8, ell.n_pad - start)
            np.testing.assert_array_equal(
                blk[:take], full[start : start + take]
            )
            seen[start : start + take] = True
        assert seen.all()

    def test_overloaded_source_originates_padding_inert(self):
        topo = topologies.grid(4)
        ls = load(topo, overloaded_nodes={"node-0"})
        ell = spf_sparse.compile_ell(ls, align=8)
        d = spf_sparse.ell_all_sources(ell, block=8)
        oid = ell.node_index["node-0"]
        for name in ell.node_names:
            assert d[oid, ell.node_index[name]] < INF
        assert (d[ell.n :, : ell.n] >= INF).all()


class TestShardedEll:
    @pytest.fixture(scope="class")
    def mesh8(self):
        from openr_tpu.parallel import mesh as pmesh

        assert len(jax.devices()) == 8
        return pmesh.make_mesh(axis_name=spf_sparse.SOURCES_AXIS)

    def test_sharded_matches_unsharded(self, mesh8):
        topo = topologies.random_mesh(40, degree=4, seed=9, max_metric=11)
        ls = load(topo, overloaded_nodes={"node-4"})
        ell = spf_sparse.compile_ell(ls, align=8)
        d_sharded = np.asarray(
            spf_sparse.sharded_ell_all_sources(ell, mesh8)
        )
        d_local = spf_sparse.ell_all_sources(ell, block=ell.n_pad)
        np.testing.assert_array_equal(d_sharded, d_local)

    def test_per_shard_parity_vs_host(self, mesh8):
        """Distance parity for a sampled row in EVERY shard (a broken
        shard boundary cannot hide behind shard-0 sampling)."""
        topo = topologies.fat_tree(
            pods=2, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=5
        )
        ls = load(topo)
        ell = spf_sparse.compile_ell(ls, align=8)
        d = np.asarray(spf_sparse.sharded_ell_all_sources(ell, mesh8))
        per_shard = ell.n_pad // 8
        for shard in range(8):
            row = shard * per_shard  # first row owned by this shard
            if row >= ell.n:
                continue
            src = ell.node_names[row]
            oracle = ls.run_spf(src)
            for dst in ell.node_names:
                want = oracle[dst].metric if dst in oracle else None
                got = int(d[row, ell.node_index[dst]])
                assert (got >= INF) == (want is None), (shard, src, dst)
                if want is not None:
                    assert got == want, (shard, src, dst)


class TestMaskedSourceBatch:
    """ops.spf_sparse._ell_masked_source_batch: batched per-destination
    masked SPF (the KSP2 second-path device kernel)."""

    def test_masked_distances_match_host_dijkstra(self):
        import random

        from openr_tpu.graph.linkstate import LinkState
        from openr_tpu.models import topologies
        from openr_tpu.ops import spf_sparse
        from openr_tpu.ops.spf import INF

        topo = topologies.random_mesh(24, degree=3, seed=5, max_metric=9)
        ls = LinkState(area=topo.area)
        for name in sorted(topo.adj_dbs):
            ls.update_adjacency_database(topo.adj_dbs[name])
        graph = spf_sparse.compile_ell(ls)
        src = "node-0"
        sid = graph.node_index[src]

        rng = random.Random(3)
        all_links = sorted(ls.all_links())
        exclusion_sets = [
            set(rng.sample(all_links, k)) for k in (0, 1, 2, 3)
        ]
        masks, ok = spf_sparse.build_edge_masks(
            graph, exclusion_sets, ls.parallel_pairs()
        )
        assert ok.all()  # no parallel links in this mesh
        drows = spf_sparse.ell_masked_distances(graph, sid, masks)

        for i, excl in enumerate(exclusion_sets):
            want = ls.run_spf(src, True, excl)
            for name, nid in graph.node_index.items():
                got = int(drows[i][nid])
                if name in want:
                    assert got == want[name].metric, (i, name)
                else:
                    assert got >= INF, (i, name)

    def test_parallel_link_exclusion_first_class(self):
        """Masking ONE member of a parallel group must keep its
        sibling usable (per-link slots; reference LinkState.h:82 Link
        identity, LinkState.cpp:763 linksToIgnore)."""
        import numpy as np

        from openr_tpu.graph.linkstate import LinkState
        from openr_tpu.ops import spf_sparse
        from openr_tpu.ops.spf import INF
        from tests.test_linkstate import adj, db

        ls = LinkState(area="0")
        ls.update_adjacency_database(
            db("a", [adj("b", "if1_ab", "if1_ba", metric=1),
                     adj("b", "if2_ab", "if2_ba", metric=5)])
        )
        ls.update_adjacency_database(
            db("b", [adj("a", "if1_ba", "if1_ab", metric=1),
                     adj("a", "if2_ba", "if2_ab", metric=5)])
        )
        graph = spf_sparse.compile_ell(ls)
        assert graph.slot_of is not None
        links = sorted(ls.all_links())
        assert len(links) == 2  # the two LAG members
        cheap = min(links, key=lambda l: l.metric_from("a"))
        masks, ok = spf_sparse.build_edge_masks(
            graph, [{cheap}, set()], ls.parallel_pairs()
        )
        assert ok[0] and ok[1]  # both representable now
        sid = graph.node_index["a"]
        d = spf_sparse.ell_masked_distances(graph, sid, masks)
        bid = graph.node_index["b"]
        # cheap member (metric 1) excluded: the metric-5 sibling carries
        assert int(d[0, bid]) == 5
        # nothing excluded: the cheap member wins
        assert int(d[1, bid]) == 1
        # masking BOTH members disconnects the pair
        masks2, ok2 = spf_sparse.build_edge_masks(
            graph, [set(links)], ls.parallel_pairs()
        )
        assert ok2[0]
        d2 = spf_sparse.ell_masked_distances(graph, sid, masks2)
        assert int(d2[0, bid]) >= INF


class TestShardedMaskedBatch:
    def test_sharded_masked_matches_single_chip(self):
        """The mesh-sharded KSP2 masked batch (destinations sharded,
        bands replicated) equals the single-chip solve for every batch
        element — a broken shard boundary cannot hide."""
        import jax

        from openr_tpu.parallel import mesh as pmesh

        topo = topologies.fat_tree(
            pods=2, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=4
        )
        ls = LinkState(area=topo.area)
        for name in sorted(topo.adj_dbs):
            ls.update_adjacency_database(topo.adj_dbs[name])
        graph = spf_sparse.compile_ell(ls)
        src = graph.node_names[0]
        sid = graph.node_index[src]
        # one masked graph per destination: exclude that destination's
        # first-path links (the real KSP2 shape)
        dsts = [n for n in graph.node_names if n != src][:8]
        excl = []
        for dst in dsts:
            links = set()
            for path in ls.get_kth_paths(src, dst, 1):
                links.update(path)
            excl.append(links)
        masks, ok = spf_sparse.build_edge_masks(
            graph, excl, ls.parallel_pairs()
        )
        assert all(ok)
        single = spf_sparse.ell_masked_distances(graph, sid, masks)
        mesh = pmesh.make_mesh(
            jax.devices()[:8], axis_name=spf_sparse.SOURCES_AXIS
        )
        sharded = spf_sparse.sharded_ell_masked_distances(
            graph, sid, masks, mesh
        )
        assert (sharded == single).all()


# --- where the overload mask sits (PR 29) -----------------------------
#
# The three relaxes mask the DISTANCE columns of overloaded nodes
# (spf_sparse._mask_transit_cols). The plain reference below masks the
# EDGE slots instead, in numpy: where(overloaded[src], INF, w). Term by
# term the two are the same int32, so every pass and the fixed point
# must agree bit for bit.

_MASK_BANDS = (
    spf_sparse.EllBand(start=0, rows=24, k=4),
    spf_sparse.EllBand(start=24, rows=10, k=8),
)
_MASK_N_PAD = 40  # 34 real rows + 6 padding columns
_MASK_ISLAND = (30, 31, 32, 33)  # edges among themselves only
_MASK_MAIN = [v for v in range(34) if v not in _MASK_ISLAND]
_MASK_SOURCES = (0, 7, 25, 30)  # batch rows; 30 starts inside the island


def _mask_case(scenario: str, seed: int):
    """A seeded random banded ELL graph with the property the scenario
    names. Returns (src bands, w bands, overloaded or None, extras)."""
    rng = np.random.default_rng(seed)
    main = np.array(_MASK_MAIN, dtype=np.int32)
    src, w = [], []
    for band in _MASK_BANDS:
        ids = np.arange(band.start, band.start + band.rows, dtype=np.int32)
        s = rng.choice(main, size=(band.rows, band.k)).astype(np.int32)
        ww = rng.integers(1, 20, size=(band.rows, band.k)).astype(np.int32)
        for r, v in enumerate(ids):
            if v in _MASK_ISLAND:
                s[r] = rng.choice(_MASK_ISLAND, size=band.k)
        # padding slots: a self-loop at INF, at least one per row
        pad = rng.random((band.rows, band.k)) < 0.25
        pad[:, -1] = True
        s = np.where(pad, ids[:, None], s)
        ww = np.where(pad, INF, ww)
        src.append(s)
        w.append(ww)
    overloaded = np.zeros(_MASK_N_PAD, dtype=bool)
    extras = {}
    if scenario == "transit":
        # the node most edges leave from
        tails = np.concatenate([s.ravel() for s in src])
        hub = int(np.bincount(tails, minlength=34)[1:30].argmax()) + 1
        overloaded[hub] = True
        extras["hub"] = hub
    elif scenario == "source":
        overloaded[list(_MASK_SOURCES[:2])] = True
    elif scenario == "fed_by_overloaded":
        # node 12's only in-edges come from overloaded nodes 3 and 4,
        # and it is overloaded itself
        src[0][12, :3] = (3, 4, 3)
        w[0][12, :3] = (2, 5, 9)
        overloaded[[3, 4, 12]] = True
    elif scenario == "padding":
        # a row of nothing but padding, and overloaded nodes beside it
        src[0][5] = 5
        w[0][5] = INF
        overloaded[[2, 26]] = True
    elif scenario == "unreachable":
        overloaded[[1, 31]] = True
    else:
        assert scenario == "no_mask", scenario
        overloaded = None
    return src, w, overloaded, extras


def _edge_side_relax(d, src, w, overloaded, masks=None):
    """The plain reference: mask the edge slots, one band at a time."""
    parts, pos = [], 0
    for bi, (s_b, w_b) in enumerate(zip(src, w)):
        w_eff = w_b if overloaded is None else np.where(
            overloaded[s_b], INF, w_b
        )
        w_eff = w_eff[None, :, :]
        if masks is not None:
            w_eff = np.where(masks[bi], INF, w_eff)
        cand = np.minimum(d[:, s_b].astype(np.int64) + w_eff, INF)
        rows = s_b.shape[0]
        parts.append(np.minimum(d[:, pos : pos + rows], cand.min(axis=2)))
        pos += rows
    parts.append(d[:, pos:])
    return np.concatenate(parts, axis=1).astype(np.int32)


def _as_uniform(src, w):
    """The bands as one [n_pad, k_max] block, self-loop/INF padded."""
    k = max(s.shape[1] for s in src)
    u_src = np.tile(np.arange(_MASK_N_PAD, dtype=np.int32)[:, None], (1, k))
    u_w = np.full((_MASK_N_PAD, k), INF, dtype=np.int32)
    pos = 0
    for s_b, w_b in zip(src, w):
        rows, kb = s_b.shape
        u_src[pos : pos + rows, :kb] = s_b
        u_w[pos : pos + rows, :kb] = w_b
        pos += rows
    return u_src, u_w


class TestOverloadMaskSitsOnDistances:
    @pytest.mark.parametrize("seed", [11, 2900000029])
    @pytest.mark.parametrize("scenario", [
        "transit", "source", "fed_by_overloaded", "padding",
        "unreachable", "no_mask",
    ])
    @pytest.mark.parametrize("kind", ["ell", "masked", "uniform"])
    def test_bit_identical_to_edge_side_reference(self, kind, scenario, seed):
        src, w, overloaded, extras = _mask_case(scenario, seed)
        rng = np.random.default_rng(seed + 1)
        srcs_t = tuple(jnp.asarray(s) for s in src)
        ws_t = tuple(jnp.asarray(x) for x in w)
        ov_dev = None if overloaded is None else jnp.asarray(overloaded)
        b = len(_MASK_SOURCES)
        masks = None
        if kind == "masked":
            masks = [
                rng.random((b,) + s.shape) < 0.15 for s in src
            ]
            masks_t = tuple(jnp.asarray(m) for m in masks)

            def relax(d, ov):
                return spf_sparse._ell_relax_masked(
                    d, _MASK_BANDS, srcs_t, ws_t, masks_t, ov
                )
        elif kind == "uniform":
            u_src, u_w = (jnp.asarray(x) for x in _as_uniform(src, w))

            def relax(d, ov):
                return spf_sparse._uniform_relax(d, u_src, u_w, ov)
        else:
            def relax(d, ov):
                return spf_sparse._ell_relax(
                    d, _MASK_BANDS, srcs_t, ws_t, ov
                )

        # one pass from arbitrary rows (INF among them)
        d_any = rng.integers(0, 60, size=(b, _MASK_N_PAD)).astype(np.int32)
        d_any[rng.random(d_any.shape) < 0.3] = INF
        got = np.asarray(relax(jnp.asarray(d_any), ov_dev))
        want = _edge_side_relax(d_any, src, w, overloaded, masks)
        assert got.dtype == np.int32
        assert (got == want).all()

        # to the fixed point: the unmasked origination pass, then masked
        # passes until nothing moves — every pass compared
        unit = np.full((b, _MASK_N_PAD), INF, dtype=np.int32)
        unit[np.arange(b), list(_MASK_SOURCES)] = 0
        d_got = np.asarray(relax(jnp.asarray(unit), None))
        d_want = _edge_side_relax(unit, src, w, None, masks)
        assert (d_got == d_want).all()
        for _ in range(_MASK_N_PAD):
            nxt_got = np.asarray(relax(jnp.asarray(d_got), ov_dev))
            nxt_want = _edge_side_relax(d_want, src, w, overloaded, masks)
            assert (nxt_got == nxt_want).all()
            if (nxt_want == d_want).all():
                break
            d_got, d_want = nxt_got, nxt_want
        else:
            raise AssertionError("no fixed point in n passes")
        d = d_got

        # what each scenario is there for
        assert (d[:, 34:] == INF).all()  # padding columns never move
        assert (d[:3][:, list(_MASK_ISLAND)] == INF).all()
        assert (d[3, _MASK_MAIN] == INF).all()
        direct = _edge_side_relax(unit, src, w, None, masks)
        free = direct
        for _ in range(_MASK_N_PAD):  # the same graph, nobody overloaded
            free = _edge_side_relax(free, src, w, None, masks)
        assert (d >= free).all()
        if scenario == "no_mask":
            assert (d == free).all()
        if scenario == "transit":
            # paths through the hub are gone, paths to it are not
            assert (d > free).any()
            assert (d[:, extras["hub"]] == free[:, extras["hub"]]).all()
        if scenario == "source":
            # an overloaded source still originates
            assert (direct[:2] < INF).sum() > 2
            assert (d[:2] <= direct[:2]).all()
        if scenario == "fed_by_overloaded":
            # reached only as a direct neighbour of a source, and no
            # batch source is 3 or 4
            assert (d[:, 12] == INF).all()
            assert (free[:3, 12] < INF).any()
        if scenario == "padding":
            assert (d[:, 5] == INF).all()

    @pytest.mark.parametrize("seed", [5, 2900000031])
    def test_fixed_point_entry_points_agree_with_reference(self, seed):
        """The jitted loops (which pass no mask for the origination
        pass) reach the edge-side reference's fixed point."""
        src, w, overloaded, _ = _mask_case("source", seed)
        overloaded[[9, 27]] = True
        srcs_t = tuple(jnp.asarray(s) for s in src)
        ws_t = tuple(jnp.asarray(x) for x in w)
        ov = jnp.asarray(overloaded)
        b = len(_MASK_SOURCES)
        unit = np.full((b, _MASK_N_PAD), INF, dtype=np.int32)
        unit[np.arange(b), list(_MASK_SOURCES)] = 0

        def fixed_point(masks=None, rows=unit):
            d = _edge_side_relax(rows, src, w, None, masks)
            while True:
                nxt = _edge_side_relax(d, src, w, overloaded, masks)
                if (nxt == d).all():
                    return d
                d = nxt

        want = fixed_point()
        got = np.asarray(spf_sparse._ell_from_sources(
            srcs_t, ws_t, ov, jnp.asarray(_MASK_SOURCES, dtype=jnp.int32),
            _MASK_BANDS, _MASK_N_PAD,
        ))
        assert (got == want).all()

        rng = np.random.default_rng(seed)
        masks = [rng.random((b,) + s.shape) < 0.15 for s in src]
        one = np.full((b, _MASK_N_PAD), INF, dtype=np.int32)
        one[:, 0] = 0
        got_m, passes = spf_sparse._ell_masked_source_batch(
            srcs_t, ws_t, tuple(jnp.asarray(m) for m in masks), ov,
            jnp.int32(0), _MASK_BANDS, _MASK_N_PAD,
        )
        assert (np.asarray(got_m) == fixed_point(masks, one)).all()
        assert 1 <= int(passes) <= _MASK_N_PAD

        u_src, u_w = _as_uniform(src, w)
        n, k = u_src.shape
        reset = spf_sparse._FORCE_RESET_EDGE
        _, got_u, _, _ = spf_sparse._tenant_view_solve(
            jnp.asarray(u_src), jnp.asarray(u_w), ov,
            jnp.asarray(_MASK_SOURCES, dtype=jnp.int32),
            jnp.full((1,), n, dtype=jnp.int32),
            jnp.zeros((1, k), dtype=jnp.int32),
            jnp.zeros((1, k), dtype=jnp.int32),
            jnp.asarray([reset[0]], dtype=jnp.int32),
            jnp.asarray([reset[1]], dtype=jnp.int32),
            jnp.asarray([reset[2]], dtype=jnp.int32),
            jnp.zeros((b, n), dtype=jnp.int32),
        )
        assert (np.asarray(got_u) == want).all()
