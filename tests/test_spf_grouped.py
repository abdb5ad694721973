"""Block-bipartite grouped kernels: oracle parity, structure
detection, and bit-exact digest equality with the ELL route sweep.

The grouped backend must be a drop-in for the gather-based ELL kernels:
same distances (host Dijkstra oracle, reference LinkState.cpp:809
runSpf), same route product (canonical digests equal bit-for-bit
despite the two layouts numbering nodes differently)."""

import numpy as np
from dataclasses import replace

from openr_tpu.graph.linkstate import LinkState
from openr_tpu.models import topologies
from openr_tpu.ops import route_sweep, spf_grouped
from openr_tpu.ops.spf import INF
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


def assert_forward_parity(ls):
    graph = spf_grouped.compile_grouped(ls)
    src_ids = np.arange(graph.n, dtype=np.int32)
    state = spf_grouped.GroupedState(graph)
    d = np.asarray(
        spf_grouped.grouped_distances_from_sources(
            graph, src_ids, state=state
        )
    )
    for src in graph.node_names:
        sid = graph.node_index[src]
        oracle = ls.run_spf(src)
        for dst in graph.node_names:
            did = graph.node_index[dst]
            want = oracle[dst].metric if dst in oracle else None
            got = int(d[sid, did])
            assert (got >= INF) == (want is None), (src, dst)
            if want is not None:
                assert got == want, (src, dst, got, want)
    return graph


class TestGroupedForwardParity:
    def test_fat_tree_structured(self):
        topo = topologies.fat_tree(
            pods=3, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=4
        )
        graph = assert_forward_parity(load(topo))
        report = spf_grouped.structure_report(graph)
        # structure detection must actually fire on a fabric: the rack
        # band groups by pod, the fabric band forms a pod x plane grid
        assert report["gather_shrink"] > 1.5, report
        grids = {
            (b["g1"], b["g2"]) for b in report["bands"] if b["g2"] > 1
        }
        assert grids, report  # at least one true 2-D grid band

    def test_grid_topology_degrades_gracefully(self):
        graph = assert_forward_parity(load(topologies.grid(4)))
        report = spf_grouped.structure_report(graph)
        assert report["gather_shrink"] >= 1.0

    def test_random_mesh(self):
        for seed in range(2):
            topo = topologies.random_mesh(
                18, degree=4, seed=seed, max_metric=20
            )
            assert_forward_parity(load(topo))

    def test_ring(self):
        assert_forward_parity(load(topologies.ring(12, metric=3)))

    def test_overloaded_transit_and_source(self):
        topo = topologies.fat_tree(
            pods=2, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=3
        )
        assert_forward_parity(load(topo, overloaded_nodes={"fsw-0-0"}))
        assert_forward_parity(load(topo, overloaded_nodes={"rsw-0-0"}))

    def test_asymmetric_metrics(self):
        topo = topologies.ring(6, metric=1)
        ls = load(topo)
        db = ls.get_adjacency_databases()["node-0"]
        adjs = [replace(a, metric=7) for a in db.adjacencies]
        ls.update_adjacency_database(replace(db, adjacencies=tuple(adjs)))
        assert_forward_parity(ls)


def digest_by_name(result):
    return route_sweep.digests_by_name(result)


class TestGroupedRouteSweep:
    def digest_by_name(self, result):
        return digest_by_name(result)

    def test_digest_matches_ell_backend(self):
        """The cross-backend witness: grouped and ELL sweeps number
        nodes differently, but the canonical digest per DESTINATION
        NAME must agree bit-exactly."""
        topo = topologies.fat_tree(
            pods=3, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=4
        )
        ls = load(topo, overloaded_nodes={"fsw-1-0"})
        names = sorted(ls.get_adjacency_databases().keys())
        samples = [names[0]]

        ell = route_sweep.RouteSweeper(
            route_sweep.compile_out_ell(ls), samples
        ).sweep(block=16)
        grouped = spf_grouped.GroupedRouteSweeper(
            spf_grouped.compile_out_grouped(ls), samples
        ).sweep(block=16)

        d_ell = self.digest_by_name(ell)
        d_grp = self.digest_by_name(grouped)
        assert d_ell == d_grp

    def test_route_tables_match_oracle(self):
        topo = topologies.fat_tree(
            pods=2, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=3
        )
        ls = load(topo)
        names = sorted(ls.get_adjacency_databases().keys())
        sweeper = spf_grouped.GroupedRouteSweeper(
            spf_grouped.compile_out_grouped(ls), names
        )
        result = sweeper.sweep(block=16)
        for src in names:
            got = result.routes_from(src)
            oracle = ls.run_spf(src)
            for dst in names:
                if dst == src:
                    continue
                want = oracle.get(dst)
                if want is None:
                    assert dst not in got, (src, dst)
                    continue
                metric, nhs = got[dst]
                assert metric == want.metric, (src, dst)
                assert nhs == set(want.next_hops), (src, dst)

    def test_random_mesh_digest_parity(self):
        topo = topologies.random_mesh(20, degree=4, seed=3, max_metric=9)
        ls = load(topo)
        names = sorted(ls.get_adjacency_databases().keys())
        ell = route_sweep.RouteSweeper(
            route_sweep.compile_out_ell(ls), [names[0]]
        ).sweep(block=16)
        grouped = spf_grouped.GroupedRouteSweeper(
            spf_grouped.compile_out_grouped(ls), [names[0]]
        ).sweep(block=16)
        assert self.digest_by_name(ell) == self.digest_by_name(grouped)


class TestShardedGroupedSweep:
    def test_sharded_matches_single_chip(self):
        """One sharded grouped dispatch over the 8-device CPU mesh:
        identical route product (bit-exact digests) as the single-chip
        block sweep AND as the ELL backend."""
        from openr_tpu.parallel import mesh as pmesh
        from openr_tpu.ops import spf_grouped as sg

        topo = topologies.fat_tree(
            pods=2, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=3
        )
        ls = load(topo, overloaded_nodes={"fsw-0-0"})
        graph = sg.compile_out_grouped(ls)
        samples = [graph.node_names[0]]
        single = sg.GroupedRouteSweeper(graph, samples).sweep(block=32)
        mesh = pmesh.make_mesh()
        assert graph.n_pad % mesh.devices.size == 0
        sharded = sg.sharded_grouped_route_sweep(graph, samples, mesh)
        np.testing.assert_array_equal(sharded.digests, single.digests)
        np.testing.assert_array_equal(
            sharded.sample_metrics, single.sample_metrics
        )
        np.testing.assert_array_equal(
            sharded.sample_masks, single.sample_masks
        )
        # cross-backend: the ELL sweep's name-keyed digests agree
        ell = route_sweep.RouteSweeper(
            route_sweep.compile_out_ell(ls), samples
        ).sweep(block=32)
        assert digest_by_name(ell) == digest_by_name(sharded)
