"""SPF kernel parity: the algebraic device kernels vs the Dijkstra oracle.

Every test loads a topology into the host LinkState, compiles a snapshot,
and cross-checks distances and ECMP first-hop sets between
``openr_tpu.ops.spf`` and ``LinkState.run_spf`` (whose semantics match the
reference openr/decision/LinkState.cpp:809 runSpf).
"""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from openr_tpu.graph.linkstate import LinkState
from openr_tpu.graph.snapshot import INF, compile_snapshot
from openr_tpu.models import topologies
from openr_tpu.ops import spf
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


def assert_parity(ls, use_link_metric=True):
    snap = compile_snapshot(ls)
    w = jnp.asarray(snap.metric if use_link_metric else snap.hop)
    ov = jnp.asarray(snap.overloaded)
    d = np.asarray(spf.all_pairs_distances(w, ov))

    for src in snap.node_names:
        sid = snap.node_index[src]
        oracle = ls.run_spf(src, use_link_metric)
        # distances
        for dst in snap.node_names:
            did = snap.node_index[dst]
            if dst in oracle:
                assert d[sid, did] == oracle[dst].metric, (
                    f"dist {src}->{dst}: kernel={d[sid, did]} "
                    f"oracle={oracle[dst].metric}"
                )
            else:
                assert d[sid, did] >= INF, f"{src}->{dst} should be unreachable"
        # ECMP first hops
        fh = np.asarray(
            spf.first_hop_matrix(w, ov, jnp.int32(sid), jnp.asarray(d[sid]), jnp.asarray(d))
        )
        for dst in snap.node_names:
            if dst == src:
                continue
            did = snap.node_index[dst]
            kernel_nh = {
                snap.node_names[v] for v in np.nonzero(fh[:, did])[0] if v < snap.n
            }
            oracle_nh = oracle[dst].next_hops if dst in oracle else set()
            assert kernel_nh == oracle_nh, (
                f"first hops {src}->{dst}: kernel={sorted(kernel_nh)} "
                f"oracle={sorted(oracle_nh)}"
            )


class TestDistanceParity:
    def test_grid(self):
        assert_parity(load(topologies.grid(4)))

    def test_fat_tree(self):
        assert_parity(
            load(
                topologies.fat_tree(
                    pods=2, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=2
                )
            )
        )

    def test_ring_with_metrics(self):
        topo = topologies.random_mesh(12, degree=2, seed=3, max_metric=50)
        assert_parity(load(topo))

    def test_random_meshes_weighted(self):
        for seed in range(4):
            topo = topologies.random_mesh(24, degree=4, seed=seed, max_metric=20)
            assert_parity(load(topo))

    def test_hop_count_mode(self):
        topo = topologies.random_mesh(16, degree=3, seed=9, max_metric=40)
        assert_parity(load(topo), use_link_metric=False)

    def test_overloaded_transit_nodes(self):
        for seed in range(4):
            topo = topologies.random_mesh(20, degree=4, seed=seed, max_metric=9)
            rng = random.Random(seed)
            over = set(rng.sample(sorted(topo.adj_dbs), 3))
            assert_parity(load(topo, overloaded_nodes=over))

    def test_overloaded_source_still_originates(self):
        topo = topologies.grid(3)
        ls = load(topo, overloaded_nodes={"node-0"})
        snap = compile_snapshot(ls)
        d = np.asarray(
            spf.all_pairs_distances(
                jnp.asarray(snap.metric), jnp.asarray(snap.overloaded)
            )
        )
        sid = snap.node_index["node-0"]
        # overloaded source reaches everything
        for dst in snap.node_names:
            assert d[sid, snap.node_index[dst]] < INF

    def test_disconnected_components(self):
        edges = [("a", "b", 1), ("c", "d", 1)]
        ls = load(topologies.build_topology("disc", edges))
        assert_parity(ls)

    def test_parallel_links(self):
        # two links between a and b with different metrics: min wins
        from tests.test_linkstate import adj, db

        ls = LinkState()
        ls.update_adjacency_database(
            db(
                "a",
                [
                    adj("b", "if1_ab", "if1_ba", metric=5),
                    adj("b", "if2_ab", "if2_ba", metric=3),
                ],
            )
        )
        ls.update_adjacency_database(
            db(
                "b",
                [
                    adj("a", "if1_ba", "if1_ab", metric=5),
                    adj("a", "if2_ba", "if2_ab", metric=4),
                ],
            )
        )
        assert ls.num_links == 2
        assert_parity(ls)


class TestSourceBatch:
    def test_subset_sources_match_all_pairs(self):
        topo = topologies.random_mesh(18, degree=4, seed=5, max_metric=30)
        ls = load(topo, overloaded_nodes={"node-3"})
        snap = compile_snapshot(ls)
        w = jnp.asarray(snap.metric)
        ov = jnp.asarray(snap.overloaded)
        d_all = np.asarray(spf.all_pairs_distances(w, ov))
        src = jnp.asarray([0, 3, 7, 11], dtype=jnp.int32)
        d_sub = np.asarray(spf.distances_from_sources(w, ov, src))
        np.testing.assert_array_equal(d_sub, d_all[np.asarray(src)])

    def test_padding_rows_inert(self):
        topo = topologies.grid(3)  # 9 nodes -> padded to 128
        ls = load(topo)
        snap = compile_snapshot(ls)
        assert snap.n_pad == 128
        d = np.asarray(
            spf.all_pairs_distances(
                jnp.asarray(snap.metric), jnp.asarray(snap.overloaded)
            )
        )
        # padding rows: self-distance 0, everything else unreachable
        assert (d[snap.n :, : snap.n] >= INF).all()
        assert (d[: snap.n, snap.n :] >= INF).all()


class TestSpfViewBatch:
    """The fused daemon hot-path kernel: batched {src} + neighbors SPF
    with first-hop rows, vs the Dijkstra oracle."""

    @staticmethod
    def batch_for(snap, src):
        sid = snap.node_index[src]
        real_srcs, srcs_dev = spf.source_batch(snap, sid)
        return sid, real_srcs[1:], srcs_dev

    def assert_view_parity(self, ls, use_link_metric=True):
        snap = compile_snapshot(ls)
        w = jnp.asarray(snap.metric)
        ov = jnp.asarray(snap.overloaded)
        for src in snap.node_names:
            sid, nbrs, srcs = self.batch_for(snap, src)
            d, fh = spf.spf_view_batch(w, ov, srcs, use_link_metric)
            d, fh = np.asarray(d), np.asarray(fh)
            oracle = ls.run_spf(src, use_link_metric)
            # row 0 = source distances; rows 1..len(nbrs) = neighbor rows
            for dst in snap.node_names:
                did = snap.node_index[dst]
                want = oracle[dst].metric if dst in oracle else None
                got = int(d[0, did])
                assert (got >= INF) == (want is None)
                if want is not None:
                    assert got == want, (src, dst)
                kernel_nh = {
                    snap.node_names[int(srcs[i])]
                    for i in np.nonzero(fh[:, did])[0]
                }
                want_nh = (
                    oracle[dst].next_hops
                    if dst in oracle and dst != src
                    else set()
                )
                assert kernel_nh == want_nh, (src, dst, kernel_nh, want_nh)
            # neighbor rows match their own oracle runs
            for i, nid in enumerate(nbrs):
                nbr_oracle = ls.run_spf(snap.node_names[nid], use_link_metric)
                for dst in snap.node_names:
                    did = snap.node_index[dst]
                    want = (
                        nbr_oracle[dst].metric if dst in nbr_oracle else None
                    )
                    got = int(d[1 + i, did])
                    assert (got >= INF) == (want is None)
                    if want is not None:
                        assert got == want

    def test_grid(self):
        self.assert_view_parity(load(topologies.grid(4)))

    def test_random_weighted(self):
        for seed in range(3):
            topo = topologies.random_mesh(20, degree=4, seed=seed, max_metric=20)
            self.assert_view_parity(load(topo))

    def test_overloaded_nodes(self):
        topo = topologies.random_mesh(16, degree=4, seed=2, max_metric=9)
        self.assert_view_parity(load(topo, overloaded_nodes={"node-1", "node-5"}))

    def test_hop_count_mode(self):
        topo = topologies.random_mesh(14, degree=3, seed=7, max_metric=40)
        self.assert_view_parity(load(topo), use_link_metric=False)

    def test_reconverge_step_fused_patch(self):
        """Patch-then-solve in one dispatch == recompile-then-solve."""
        topo = topologies.random_mesh(16, degree=4, seed=4, max_metric=9)
        ls = load(topo)
        snap = compile_snapshot(ls)
        metric_dev = jnp.asarray(snap.metric)
        ov = jnp.asarray(snap.overloaded)
        sid, nbrs, srcs = self.batch_for(snap, "node-0")

        # mutate one row on the host, patch it on device
        new_metric = snap.metric.copy()
        victim = snap.node_index["node-3"]
        row = new_metric[victim].copy()
        edges = np.nonzero(row < INF)[0]
        row[edges[0]] = row[edges[0]] + 7
        new_metric[victim] = row
        patch_ids = jnp.asarray(np.asarray([victim], dtype=np.int32))
        patch_vals = jnp.asarray(row[None, :])

        m2, packed = spf.reconverge_step(
            metric_dev, patch_ids, patch_vals, ov, srcs
        )
        b = srcs.shape[0]
        d2, fh2 = np.asarray(packed[:b]), np.asarray(packed[b:]).astype(bool)
        d_ref, fh_ref = spf.spf_view_batch(jnp.asarray(new_metric), ov, srcs)
        np.testing.assert_array_equal(np.asarray(m2), new_metric)
        np.testing.assert_array_equal(d2, np.asarray(d_ref))
        np.testing.assert_array_equal(fh2, np.asarray(fh_ref))


class TestNativeBackend:
    def test_native_matches_oracle(self):
        from openr_tpu.graph import native_spf

        if not native_spf.is_available():
            pytest.skip("native toolchain unavailable")
        for seed in range(3):
            topo = topologies.random_mesh(22, degree=4, seed=seed, max_metric=15)
            over = {"node-2", "node-7"} if seed == 1 else set()
            ls = load(topo, overloaded_nodes=over)
            snap = compile_snapshot(ls)
            d = native_spf.all_pairs_distances(snap)
            for src in snap.node_names:
                sid = snap.node_index[src]
                oracle = ls.run_spf(src)
                for dst in snap.node_names:
                    did = snap.node_index[dst]
                    expected = (
                        oracle[dst].metric if dst in oracle else INF
                    )
                    assert d[sid, did] == expected, (src, dst)
                fh = native_spf.first_hop_matrix(snap, sid, d[sid], d)
                for dst in snap.node_names:
                    if dst == src:
                        continue
                    did = snap.node_index[dst]
                    got = {
                        snap.node_names[v]
                        for v in np.nonzero(fh[:, did])[0]
                    }
                    want = (
                        oracle[dst].next_hops if dst in oracle else set()
                    )
                    assert got == want, (src, dst, got, want)

    def test_native_solver_backend_matches_device(self):
        from openr_tpu.graph import native_spf

        if not native_spf.is_available():
            pytest.skip("native toolchain unavailable")
        from openr_tpu.decision.prefix_state import PrefixState
        from openr_tpu.decision.spf_solver import SpfSolver

        topo = topologies.random_mesh(18, degree=4, seed=3, max_metric=9)
        ls = load(topo)
        prefix_state = PrefixState()
        for pdb in topo.prefix_dbs.values():
            prefix_state.update_prefix_database(pdb)
        area_ls = {topo.area: ls}
        my = "node-0"
        db_native = SpfSolver(my, backend="native").build_route_db(
            my, area_ls, prefix_state
        )
        db_device = SpfSolver(my, backend="device").build_route_db(
            my, area_ls, prefix_state
        )
        assert db_native.to_route_db(my) == db_device.to_route_db(my)
