"""A fabric whose spines outgrow its switches' band, through the normal
path on the CPU: ``compile_ell`` bands rows by the power of two at or
above their degree, so the 50k fabric (degree 8 / 84 / 893) is the first
deployment with THREE bands, and a flap of an FSW-SSW link patches the
middle and the widest one in one window. Here that shape at 486 nodes
(40 pods of 2 FSW and 10 RSW, 3 SSW a plane: degree 2 / 13 / 40, bands
k=8 / 16 / 64), forced onto the ELL formulation, driven through
``Decision`` with the seeded ``metric`` and ``flap`` events of
``adj-churn``. After every window the device backend's ``RouteDatabase``
equals the plain per-source Dijkstra of ``chipbench/reference.py`` and
is bit-identical to ``solver_backend=host``; and the ``ops.ell_reconverge``
span says what every pass streams (``slots``) and what of it is an edge
(``edges``), on a warm and on a structural-warm solve. Counts, never
times: this is the CPU.
"""

from __future__ import annotations

import numpy as np
import pytest

from chipbench import reference, topology, traffic
from openr_tpu.decision import spf_solver
from openr_tpu.decision.decision import Decision
from openr_tpu.graph.snapshot import INF
from openr_tpu.messaging.queue import ReplicateQueue
from openr_tpu.ops import spf_sparse
from openr_tpu.telemetry import get_tracer
from openr_tpu.types import Publication
from openr_tpu.utils import wire
from tests.test_incremental_parity import load as _link_state

THREE_BAND = {"kind": "fat_tree", "pods": 40, "ssw_per_plane": 3,
              "fsw_per_pod": 2, "rsw_per_pod": 10}
BANDS = [(400, 8), (80, 16), (6, 64)]  # RSW, FSW, SSW: rows x k
SLOTS = sum(rows * k for rows, k in BANDS)
VANTAGE = "rsw-0-0"
SP_ECMP = {"algorithm": "SP_ECMP", "type": "IP"}
MIX = {"kinds": {"metric": 0.8, "flap": 0.2}}
# 36 events, in the bursts one rebuild window carries after a stall
BURSTS = (1, 1, 2, 1, 3, 1, 4, 2, 1, 6, 1, 5, 1, 2, 4, 1)
FALLBACKS = ("decision.fallbacks", "decision.spf_host_fallback",
             "decision.backend_switches", "decision.device_state_resets")


@pytest.fixture(scope="module")
def fabric():
    topo = topology.build(THREE_BAND, SP_ECMP)
    assert len(topo.adj_dbs) == 486 and topo.links() == 1040
    return topo


def _tiers(before, gen, ev) -> frozenset:
    """The tiers of the two ends of the link ``ev`` changed."""
    node = ev.value.originator_id
    was = {a.other_node_name: a.metric for a in before[node].adjacencies}
    now = {a.other_node_name: a.metric for a in gen.adj_dbs[node].adjacencies}
    (other,) = [o for o in set(was) | set(now) if was.get(o) != now.get(o)]
    return frozenset((node.split("-")[0], other.split("-")[0]))


def _filled(graph) -> int:
    """The bands' filled slots, counted the slow way."""
    return sum(int(np.count_nonzero(w < INF)) for w in graph.w)


def _directed_up(adj_dbs) -> int:
    """Directed edges of the links that are up both ways."""
    pairs = {(n, a.other_node_name)
             for n, db in adj_dbs.items() for a in db.adjacencies}
    return sum((b, a) in pairs for a, b in pairs)


def test_compile_ell_gives_three_bands_and_counts_their_edges(fabric):
    graph = spf_sparse.compile_ell(_link_state(fabric))
    assert [(b.rows, b.k) for b in graph.bands] == BANDS
    assert graph.n == 486 and graph.n_pad == 512
    assert graph.edges == 2 * fabric.links() == 2080
    assert graph.edges == _filled(graph)
    # the out-graph collapses parallel links; this fabric has none
    assert spf_sparse.compile_ell(
        _link_state(fabric), direction="out").edges == 2080


def _decision(backend: str):
    kv_q = ReplicateQueue(name=f"{backend}:kvstore")
    return kv_q, Decision(
        VANTAGE,
        kvstore_updates_queue=kv_q,
        route_updates_queue=ReplicateQueue(name=f"{backend}:routes"),
        solver_backend=backend,
    )


@pytest.mark.parametrize("prewarm", [False, True],
                         ids=["fused-patch", "prewarmed"])
@pytest.mark.parametrize("seed", [3, 10, 4294967318])
def test_routes_equal_reference_and_host_after_every_window(
        fabric, seed, prewarm, monkeypatch):
    """``prewarm``: whether each publication's rows are scattered as it
    lands (``SpfSolver.prewarm``, as ``Decision._on_publication`` does
    under the policy wait: one ``jit_patch`` a band) or ride the window's
    fused solve (``jit__ell_reconverge``'s own scatter, a triple a band)."""
    monkeypatch.setattr(spf_solver, "SPARSE_NODE_THRESHOLD", 32)
    patched_bands = []
    real_patch = spf_sparse.ell_patch

    def recording(graph, ls, affected, widen=False):
        patched = real_patch(graph, ls, affected, widen=widen)
        if patched is not None:
            patched_bands.append(frozenset(patched.changed))
        return patched

    monkeypatch.setattr(spf_sparse, "ell_patch", recording)
    gen = traffic.Generator(fabric, seed, MIX, VANTAGE)
    initial = gen.initial_key_vals()
    queues, sides = zip(*(_decision(b) for b in ("device", "host")))
    device = sides[0]
    tracer = get_tracer()

    def counters():
        return dict(spf_solver.get_spf_counters(), **device.counters)

    fsw_ssw, warm_spans, structural_spans = set(), 0, 0
    try:
        for d in sides:
            d.process_publication(Publication(key_vals=dict(initial), area="0"))
            d.rebuild_routes("LOAD")
        ls = device.area_link_states["0"]
        before = counters()
        for burst in BURSTS:
            events = []
            for _ in range(burst):
                was = dict(gen.adj_dbs)
                events.append(gen.draw())
                tiers = _tiers(was, gen, events[-1])
                if tiers == {"fsw", "ssw"}:
                    fsw_ssw.add(events[-1].kind)
            for d in sides:
                for ev in events:
                    d.process_publication(Publication(
                        key_vals={ev.key: ev.value}, area="0"))
                    if prewarm and d is device:
                        d.spf_solver.prewarm(d.area_link_states)
            structural0 = counters()["decision.ell_structural_warm_solves"]
            trace = tracer.start()
            device.pending.adopt_trace(trace)
            for d in sides:
                d.rebuild_routes("WINDOW")
            tracer.finish(trace)
            live, host = (d.route_db.to_route_db(VANTAGE) for d in sides)
            assert reference.routes_of(live) == reference.routes(
                gen.adj_dbs, gen.prefix_dbs, VANTAGE)
            assert wire.dumps(live) == wire.dumps(host)
            # the span says the resident bands' slots and filled slots:
            # the bands as the solve left them, counted the slow way,
            # and the LSDB's own directed edges
            (span,) = [s for s in trace.spans
                       if s.name == "ops.ell_reconverge"]
            state = spf_solver._ELL_RESIDENT._cache[ls][1]
            assert span.attrs["warm"] is True
            assert span.attrs["slots"] == SLOTS
            assert span.attrs["edges"] == state.graph.edges \
                == _filled(state.graph)
            assert span.attrs["edges"] == _directed_up(gen.adj_dbs)
            if counters()["decision.ell_structural_warm_solves"] \
                    > structural0:
                structural_spans += 1
            else:
                warm_spans += 1
    finally:
        for q in queues:
            q.close()
    after = counters()
    # the stream held a flap and a metric change of an FSW-SSW link ...
    assert fsw_ssw == {"metric", "flap"}
    # ... and one patch scattered rows of the middle AND the widest band
    # (a flap takes the link out of both ends' rows)
    assert any({1, 2} <= bands for bands in patched_bands), patched_bands
    assert {b for bands in patched_bands for b in bands} == {0, 1, 2}
    # every window solved warm on the resident bands, some across a
    # link that went or came (structural-warm), none fell back
    solved = after["decision.ell_warm_solves"] \
        - before["decision.ell_warm_solves"]
    assert solved == len(BURSTS) == warm_spans + structural_spans
    assert warm_spans >= 1 and structural_spans >= 1
    assert after["decision.ell_cold_solves"] \
        == before["decision.ell_cold_solves"]
    assert after.get("decision.ell_widen_events", 0) \
        == before.get("decision.ell_widen_events", 0)
    assert after["decision.ell_full_compiles"] \
        == before["decision.ell_full_compiles"]
    if prewarm:
        assert after["decision.ell_prewarms"] \
            - before["decision.ell_prewarms"] == sum(BURSTS)
    for name in FALLBACKS:
        assert after.get(name, 0) == before.get(name, 0), name


def test_ell_patch_keeps_the_edge_count_through_a_widened_band(fabric):
    """A row that outgrows its band widens it in place (``widen=True``):
    the count follows the rows ``ell_patch`` re-derives, whatever the
    band's new width."""
    from dataclasses import replace

    ls = _link_state(fabric)
    graph = spf_sparse.compile_ell(ls)
    # rsw-1-0 (2 links, band k=8) gains 9 parallel links to fsw-1-0
    a_db = ls.get_adjacency_databases()["rsw-1-0"]
    b_db = ls.get_adjacency_databases()["fsw-1-0"]
    (ab,) = [a for a in a_db.adjacencies if a.other_node_name == "fsw-1-0"]
    (ba,) = [a for a in b_db.adjacencies if a.other_node_name == "rsw-1-0"]
    extra_a = tuple(replace(ab, if_name=f"{ab.if_name}_{i}",
                            other_if_name=f"{ab.other_if_name}_{i}")
                    for i in range(9))
    extra_b = tuple(replace(ba, if_name=f"{ba.if_name}_{i}",
                            other_if_name=f"{ba.other_if_name}_{i}")
                    for i in range(9))
    ls.update_adjacency_database(
        replace(a_db, adjacencies=a_db.adjacencies + extra_a))
    ls.update_adjacency_database(
        replace(b_db, adjacencies=b_db.adjacencies + extra_b))
    patched = spf_sparse.ell_patch(
        graph, ls, ["fsw-1-0", "rsw-1-0"], widen=True)
    assert patched.widened == frozenset({0, 1})
    assert patched.edges == graph.edges + 18 == _filled(patched)
    assert spf_sparse.compile_ell(ls).edges == patched.edges
