"""Pipelined event windows (PR 16): multi-event bursts whose committed
dispatches submit back to back under one ``pipeline_drain`` (window
N+1 on the stream before window N's reap lands), speculative dispatch
of the debounce backlog's most-likely composition, and their
interaction with the chaos seams.

Four claims, each with its own class:

- Burst parity: ``churn_burst`` leaves digests bit-identical to the
  same events applied one sequential ``churn()`` at a time, across
  the ELL, grouped, and mesh-sharded backends — with the pipelining
  witnessed (``ops.pipelined_dispatches``) and the whole burst costing
  at most 2 host touches per drain.
- Speculation parity: a matching speculation ADOPTS
  (``ops.spec_hits``) and a mismatched one CANCELS
  (``ops.spec_cancels``, never silent); both end bit-identical to the
  sequential oracle, and sample-band compositions refuse to speculate
  (``ops.spec_skips``).
- Chaos-seam interaction: a fault mid-burst or mid-speculation
  degrades WITHIN the ladder (burst cancel -> supervised replay;
  speculation abandoned -> committed path), never up it — and the
  decision-layer speculation stands down entirely while any fault is
  armed so chaos charges are consumed only by the committed path.
- Compile flatness: warm bursts at pipeline depths 1..3 cost zero AOT
  compiles and zero backend jit compiles; the world-batch pipelined
  entry point solves batches bit-identically to per-batch
  ``solve_views`` while overlapping disjoint-bucket launches.
"""

import numpy as np
import pytest
from dataclasses import replace

from openr_tpu.faults.injector import (
    FaultSchedule,
    get_injector,
)
from openr_tpu.graph.linkstate import LinkState
from openr_tpu.models import topologies
from openr_tpu.ops import dispatch_accounting as da
from openr_tpu.ops import route_engine, route_sweep
from openr_tpu.telemetry import get_registry


def load(topo):
    ls = LinkState(area=topo.area)
    for name, db in sorted(topo.adj_dbs.items()):
        ls.update_adjacency_database(db)
    return ls


def make_topo():
    return topologies.fat_tree(
        pods=3, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=4
    )


def mutate_metric(ls, node, i, metric):
    db = ls.get_adjacency_databases()[node]
    adjs = list(db.adjacencies)
    adjs[i] = replace(adjs[i], metric=metric)
    ls.update_adjacency_database(replace(db, adjacencies=tuple(adjs)))
    return {node, adjs[i].other_node_name}


def make_engine(kind, ls):
    names = sorted(ls.get_adjacency_databases().keys())
    if kind in ("ell_sharded", "grouped_sharded"):
        import jax

        from openr_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(jax.devices())
        cls = (
            route_engine.RouteSweepEngine
            if kind == "ell_sharded"
            else route_engine.GroupedRouteSweepEngine
        )
        return cls(ls, [names[0]], align=16, mesh=mesh)
    cls = (
        route_engine.RouteSweepEngine
        if kind == "ell"
        else route_engine.GroupedRouteSweepEngine
    )
    return cls(ls, [names[0]])


def digests(engine):
    return route_sweep.digests_by_name(engine.result)


def safe_edges(ls, sample_names, count):
    """(node, slot) churn pairs whose BOTH endpoints avoid the sample
    band — a window touching a sample node's adjacencies refuses to
    speculate/burst by design."""
    out = []
    sample = set(sample_names)
    for node in sorted(ls.get_adjacency_databases().keys()):
        if node in sample:
            continue
        for i, a in enumerate(
            ls.get_adjacency_databases()[node].adjacencies
        ):
            if a.other_node_name in sample:
                continue
            out.append((node, i))
            break
        if len(out) == count:
            return out
    raise RuntimeError("topology too small for sample-free churn set")


KINDS = ("ell", "grouped", "ell_sharded", "grouped_sharded")
EVENTS = ((0, 7), (1, 5), (2, 9))  # (edge index, metric)


@pytest.fixture(autouse=True)
def _clean_injector():
    get_injector().reset()
    yield
    get_injector().reset()


@pytest.mark.parametrize("kind", KINDS)
class TestBurstParity:
    def test_burst_matches_sequential(self, kind):
        """A 3-event burst leaves the same digests as the same events
        applied one supervised churn() at a time."""
        topo = make_topo()
        ls_a, ls_b = load(topo), load(topo)
        seq = make_engine(kind, ls_a)
        bst = make_engine(kind, ls_b)
        edges = safe_edges(ls_a, seq.sample_names, 3)
        for ei, metric in EVENTS:
            n, s = edges[ei]
            seq.churn(ls_a, mutate_metric(ls_a, n, s, metric))
        bst.churn_burst(ls_b, [
            (lambda n=edges[ei][0], s=edges[ei][1], m=metric:
             mutate_metric(ls_b, n, s, m))
            for ei, metric in EVENTS
        ])
        assert digests(seq) == digests(bst)

    def test_burst_submits_ahead_of_reap(self, kind):
        """The acceptance-criterion witness: a warm multi-event burst
        dispatches window N+1 before window N's reap lands
        (ops.pipelined_dispatches), folds every window into one drain
        (ops.windows_per_drain), and the whole drain costs at most 2
        host touches."""
        topo = make_topo()
        ls = load(topo)
        eng = make_engine(kind, ls)
        edges = safe_edges(ls, eng.sample_names, 3)
        # warm the chain and the burst bucket
        for ei, metric in EVENTS:
            n, s = edges[ei]
            eng.churn(ls, mutate_metric(ls, n, s, metric))
        reg = get_registry()
        piped0 = reg.counter_get("ops.pipelined_dispatches")
        cancels0 = reg.counter_get("ops.burst_cancels")
        with da.pipeline_drain("test_drain") as w:
            eng.churn_burst(ls, [
                (lambda n=edges[ei][0], s=edges[ei][1], m=metric + 1:
                 mutate_metric(ls, n, s, m))
                for ei, metric in EVENTS
            ])
        assert reg.counter_get("ops.burst_cancels") == cancels0
        assert reg.counter_get("ops.pipelined_dispatches") >= piped0 + 2
        assert w.windows == len(EVENTS)
        assert w.touches <= 2, (
            f"burst cost {w.touches} touches; the drain budget is 2"
        )
        assert w.blocking_syncs == 0


class TestSpeculationParity:
    def _warm_pair(self):
        topo = make_topo()
        ls_a, ls_b = load(topo), load(topo)
        seq = make_engine("ell", ls_a)
        spc = make_engine("ell", ls_b)
        edges = safe_edges(ls_a, seq.sample_names, 3)
        for ei, metric in EVENTS:
            n, s = edges[ei]
            seq.churn(ls_a, mutate_metric(ls_a, n, s, metric))
            spc.churn(ls_b, mutate_metric(ls_b, n, s, metric))
        return ls_a, ls_b, seq, spc, edges

    def test_spec_hit_adopts_bit_identical(self):
        ls_a, ls_b, seq, spc, edges = self._warm_pair()
        reg = get_registry()
        h0 = reg.counter_get("ops.spec_hits")
        n, s = edges[0]
        aff_b = mutate_metric(ls_b, n, s, 21)
        assert spc.speculate_churn(ls_b, [aff_b])
        spc.churn_window(ls_b, [aff_b])
        seq.churn(ls_a, mutate_metric(ls_a, n, s, 21))
        assert reg.counter_get("ops.spec_hits") == h0 + 1
        assert digests(seq) == digests(spc)

    def test_spec_mismatch_cancels_bit_identical(self):
        """Deliver a LARGER backlog than was speculated: the stale
        speculation cancels (counted, never silent) and the committed
        replay equals the sequential chain."""
        ls_a, ls_b, seq, spc, edges = self._warm_pair()
        reg = get_registry()
        c0 = reg.counter_get("ops.spec_cancels")
        (n0, s0), (n1, s1) = edges[0], edges[1]
        aff_b1 = mutate_metric(ls_b, n0, s0, 23)
        assert spc.speculate_churn(ls_b, [aff_b1])
        aff_b2 = mutate_metric(ls_b, n1, s1, 6)
        spc.churn_window(ls_b, [aff_b1, aff_b2])
        seq.churn_window(ls_a, [
            mutate_metric(ls_a, n0, s0, 23),
            mutate_metric(ls_a, n1, s1, 6),
        ])
        assert reg.counter_get("ops.spec_cancels") == c0 + 1
        assert digests(seq) == digests(spc)

    def test_sample_band_composition_refuses_to_speculate(self):
        """A backlog touching a sample node's adjacencies skips
        speculation (the sample-band refresh mutates sweeper state
        before dispatch — not cancellable) and the committed window
        still lands bit-identically."""
        ls_a, ls_b, seq, spc, edges = self._warm_pair()
        reg = get_registry()
        k0 = reg.counter_get("ops.spec_skips")
        sample = spc.sample_names[0]
        aff_b = mutate_metric(ls_b, sample, 0, 15)
        assert not spc.speculate_churn(ls_b, [aff_b])
        assert reg.counter_get("ops.spec_skips") == k0 + 1
        spc.churn_window(ls_b, [aff_b])
        seq.churn(ls_a, mutate_metric(ls_a, sample, 0, 15))
        assert digests(seq) == digests(spc)


class TestChaosSeamInteraction:
    def test_fault_mid_burst_cancels_and_replays_within_ladder(self):
        """A dispatch fault inside a burst window cancels the burst
        (ops.burst_cancels) and replays the coalesced union through
        the SUPERVISED path — the ladder degrades warm->...), never
        exhausting, and the result still matches the sequential
        oracle run without any fault."""
        topo = make_topo()
        ls_a, ls_b = load(topo), load(topo)
        seq = make_engine("ell", ls_a)
        bst = make_engine("ell", ls_b)
        edges = safe_edges(ls_a, seq.sample_names, 3)
        for ei, metric in EVENTS:
            n, s = edges[ei]
            seq.churn(ls_a, mutate_metric(ls_a, n, s, metric))
            bst.churn(ls_b, mutate_metric(ls_b, n, s, metric))
        reg = get_registry()
        c0 = reg.counter_get("ops.burst_cancels")
        lost0 = reg.counter_get("recovery.device_lost")
        get_injector().arm(
            "route_engine.dispatch", FaultSchedule.fail_once()
        )
        bst.churn_burst(ls_b, [
            (lambda n=edges[ei][0], s=edges[ei][1], m=metric + 2:
             mutate_metric(ls_b, n, s, m))
            for ei, metric in EVENTS
        ])
        for ei, metric in EVENTS:
            n, s = edges[ei]
            seq.churn(ls_a, mutate_metric(ls_a, n, s, metric + 2))
        assert reg.counter_get("ops.burst_cancels") == c0 + 1
        # degraded WITHIN the ladder: no device-loss escalation
        assert reg.counter_get("recovery.device_lost") == lost0
        assert digests(seq) == digests(bst)

    def test_fault_mid_speculation_abandons_not_escalates(self):
        """A fault during the speculative solve abandons the attempt
        (ops.spec_cancels) OUTSIDE the supervisor — the later
        committed window runs clean and bit-identical; the ladder
        never sees the speculative failure."""
        topo = make_topo()
        ls_a, ls_b = load(topo), load(topo)
        seq = make_engine("ell", ls_a)
        spc = make_engine("ell", ls_b)
        edges = safe_edges(ls_a, seq.sample_names, 2)
        for ei, metric in EVENTS[:2]:
            n, s = edges[ei]
            seq.churn(ls_a, mutate_metric(ls_a, n, s, metric))
            spc.churn(ls_b, mutate_metric(ls_b, n, s, metric))
        reg = get_registry()
        c0 = reg.counter_get("ops.spec_cancels")
        n, s = edges[0]
        aff_b = mutate_metric(ls_b, n, s, 31)
        get_injector().arm(
            "route_engine.dispatch", FaultSchedule.fail_once()
        )
        assert not spc.speculate_churn(ls_b, [aff_b])
        assert reg.counter_get("ops.spec_cancels") == c0 + 1
        spc.churn_window(ls_b, [aff_b])
        seq.churn(ls_a, mutate_metric(ls_a, n, s, 31))
        assert digests(seq) == digests(spc)

    def test_decision_speculation_stands_down_while_armed(self):
        """The decision-layer speculation gate: while ANY chaos charge
        is armed, speculate_views refuses (ops.spec_skips) WITHOUT
        consuming the charge — the committed rebuild owns every fault
        seam, so a chaos test's armed fault can never be eaten by a
        speculative solve outside the ladder."""
        from openr_tpu.decision.prefix_state import PrefixState
        from openr_tpu.decision.spf_solver import SpfSolver

        topo = topologies.grid(4)
        ls = load(topo)
        root = sorted(ls.get_adjacency_databases())[0]
        solver = SpfSolver(root, backend="device")
        area_ls = {topo.area: ls}
        reg = get_registry()
        k0 = reg.counter_get("ops.spec_skips")
        inj = get_injector()
        inj.arm("decision.spf_solve", FaultSchedule.fail_once())
        assert solver.speculate_views(root, area_ls, PrefixState()) == 0
        assert reg.counter_get("ops.spec_skips") == k0 + 1
        assert inj.any_armed, "stand-down must not consume the charge"

    def test_decision_speculation_stages_when_clear(self):
        """With no charge armed the same call stages warm views
        (ops.spec_dispatches) and the next build consumes them
        (ops.spec_hits)."""
        from openr_tpu.decision.prefix_state import PrefixState
        from openr_tpu.decision.spf_solver import SpfSolver

        topo = topologies.grid(4)
        ls = load(topo)
        root = sorted(ls.get_adjacency_databases())[0]
        solver = SpfSolver(root, backend="device")
        area_ls = {topo.area: ls}
        ps = PrefixState()
        reg = get_registry()
        d0 = reg.counter_get("ops.spec_dispatches")
        h0 = reg.counter_get("ops.spec_hits")
        assert solver.speculate_views(root, area_ls, ps) == 1
        assert reg.counter_get("ops.spec_dispatches") == d0 + 1
        solver.build_route_db(root, area_ls, ps)
        assert reg.counter_get("ops.spec_hits") == h0 + 1


class TestCompileFlatnessAndWorldBatch:
    def test_zero_retraces_across_pipeline_depths(self):
        """After warmup, bursts at depths 1, 2 and 3 compile NOTHING:
        pipelining reuses the eager path's per-(tag, bucket)
        executables."""
        topo = make_topo()
        ls = load(topo)
        eng = make_engine("ell", ls)
        edges = safe_edges(ls, eng.sample_names, 3)
        for ei, metric in EVENTS:
            n, s = edges[ei]
            eng.churn(ls, mutate_metric(ls, n, s, metric))
        eng.churn_burst(ls, [
            lambda: mutate_metric(ls, edges[0][0], edges[0][1], 4),
            lambda: mutate_metric(ls, edges[1][0], edges[1][1], 6),
        ])
        reg = get_registry()
        aot0 = reg.counter_get("ops.aot_compiles")
        jax0 = reg.counter_get("jax.compile_count")
        for metrics in ((8,), (9, 12), (13, 5, 7)):
            eng.churn_burst(ls, [
                (lambda n=edges[k][0], s=edges[k][1], m=m:
                 mutate_metric(ls, n, s, m))
                for k, m in enumerate(metrics)
            ])
        assert reg.counter_get("ops.aot_compiles") == aot0
        assert reg.counter_get("jax.compile_count") == jax0

    def test_world_batch_pipelined_matches_sequential(self):
        """solve_views_pipelined over disjoint-shape batches returns
        per-batch views bit-identical to per-batch solve_views, while
        overlapping the launches (ops.pipelined_dispatches) and
        folding the batches into one drain."""
        from openr_tpu.ops.world_batch import WorldManager

        topos_a = [topologies.grid(3), topologies.grid(4)]
        topos_b = [
            topologies.random_mesh(24, 3, seed=7),
            topologies.random_mesh(30, 4, seed=11),
        ]
        batch_a = [
            (f"a{i}", load(t), sorted(load(t).get_adjacency_databases())[0])
            for i, t in enumerate(topos_a)
        ]
        batch_b = [
            (f"b{i}", load(t), sorted(load(t).get_adjacency_databases())[0])
            for i, t in enumerate(topos_b)
        ]
        ref_mgr = WorldManager(slots_per_bucket=8)
        ref_views = [
            ref_mgr.solve_views(batch_a),
            ref_mgr.solve_views(batch_b),
        ]
        reg = get_registry()
        drains0 = reg.counter_get("ops.pipeline_drains")
        pip_mgr = WorldManager(slots_per_bucket=8)
        got_views = pip_mgr.solve_views_pipelined([batch_a, batch_b])
        assert reg.counter_get("ops.pipeline_drains") == drains0 + 1
        for ref_batch, got_batch in zip(ref_views, got_views):
            for (rg, rs, rp), (gg, gs, gp) in zip(ref_batch, got_batch):
                assert rs == gs
                np.testing.assert_array_equal(np.asarray(rp),
                                              np.asarray(gp))
