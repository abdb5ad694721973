"""The long-diameter deployment through the normal path, on the CPU at a
size the ELL path takes: a 65 x 65 grid (4,225 nodes, above
``SPARSE_NODE_THRESHOLD``) solved from its corner, 128 hops from the far
one.

Two things are held here. The solve's RESULT is what it was: after every
burst of ``grid-10000.drain-churn``'s events (a node re-costs all its
links, a link flaps) the device backend's ``RouteDatabase`` equals the
plain per-source Dijkstra of ``chipbench/reference.py`` and is
bit-identical to ``solver_backend=host``. And the two scalars a solve now
carries out beside its packed view say what the solve did: the relax
passes its ``while_loop`` ran and the batch rows ``_warm_seed`` restarted
from the cold init. Counts, never times: this is the CPU.
"""

from __future__ import annotations

import os
from dataclasses import replace

import numpy as np
import pytest

from chipbench import reference, spec, topology, traffic
from openr_tpu.decision.decision import Decision
from openr_tpu.decision.spf_solver import SPARSE_NODE_THRESHOLD
from openr_tpu.graph.linkstate import LinkState
from openr_tpu.messaging.queue import ReplicateQueue
from openr_tpu.ops import spf_sparse
from openr_tpu.telemetry import get_registry, get_tracer
from openr_tpu.types import Publication
from openr_tpu.utils import wire
from tests.test_incremental_parity import load as _link_state

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIDE = 65
CORNER, FAR = "node-0", f"node-{SIDE * SIDE - 1}"
SP_ECMP = {"algorithm": "SP_ECMP", "type": "IP"}
MIX = {"kinds": {"node-metric": 0.8, "flap": 0.2}}
# 40 events, in the bursts one rebuild window carries after a stall
BURSTS = (1, 1, 2, 1, 3, 1, 4, 2, 1, 6, 1, 8, 1, 2, 5, 1)


@pytest.fixture(scope="module")
def grid():
    # the driver file registers the ``grid`` kind and ``node-metric``
    spec.load_driver(REPO, "pipeline_grid")
    topo = topology.build({"kind": "grid", "n": SIDE}, SP_ECMP)
    assert len(topo.adj_dbs) == SIDE * SIDE > SPARSE_NODE_THRESHOLD
    return topo


def _decision(backend: str):
    kv_q = ReplicateQueue(name=f"{backend}:kvstore")
    return kv_q, Decision(
        CORNER,
        kvstore_updates_queue=kv_q,
        route_updates_queue=ReplicateQueue(name=f"{backend}:routes"),
        solver_backend=backend,
    )


@pytest.mark.parametrize("seed", [7, 4294967311])
def test_routes_equal_reference_and_host_after_every_burst(grid, seed):
    assert sum(BURSTS) == 40
    gen = traffic.Generator(grid, seed, MIX, CORNER)
    initial = gen.initial_key_vals()
    queues, sides = zip(*(_decision(b) for b in ("device", "host")))
    registry, tracer = get_registry(), get_tracer()
    spans = []
    try:
        for d in sides:
            d.process_publication(Publication(key_vals=dict(initial), area="0"))
            d.rebuild_routes("LOAD")
        solves0 = registry.counter_get("decision.ell_warm_solves")
        observed0 = registry.histogram("ops.ell.relax_passes").count
        resets0 = registry.counter_get("decision.ell_reset_solves")
        kinds = set()
        for burst in BURSTS:
            events = [gen.draw() for _ in range(burst)]
            kinds |= {ev.kind for ev in events}
            for d in sides:
                for ev in events:
                    d.process_publication(Publication(
                        key_vals={ev.key: ev.value}, area="0"))
            # the trace a publication would carry out of KvStore
            trace = tracer.start()
            sides[0].pending.adopt_trace(trace)
            for d in sides:
                d.rebuild_routes("BURST")
            tracer.finish(trace)
            spans += [
                s for s in trace.spans if s.name == "ops.solve_readback"]
            live, host = (d.route_db.to_route_db(CORNER) for d in sides)
            assert reference.routes_of(live) == reference.routes(
                gen.adj_dbs, gen.prefix_dbs, CORNER)
            assert wire.dumps(live) == wire.dumps(host)
        assert kinds == {"node-metric", "flap"}
    finally:
        for q in queues:
            q.close()
    # every rebuild solved warm on the resident bands, each solve was
    # observed once, and the span of the readback carries what it read
    solves = registry.counter_get("decision.ell_warm_solves") - solves0
    assert solves == len(BURSTS) == len(spans)
    assert registry.histogram("ops.ell.relax_passes").count - observed0 \
        == solves
    resets = registry.counter_get("decision.ell_reset_solves") - resets0
    assert resets == sum(s.attrs["reset_rows"] > 0 for s in spans)
    # from a corner nearly every node-metric raises a tight edge
    assert resets >= len(BURSTS) // 2
    for s in spans:
        assert 1 <= s.attrs["passes"] < SIDE * SIDE
        assert 0 <= s.attrs["reset_rows"] <= 8
        if s.attrs["reset_rows"]:
            assert s.attrs["passes"] >= 2 * (SIDE - 1) - 1


# -- the two scalars, at the ops level ---------------------------------------


def _set_metrics(ls: LinkState, node: str, metric: int) -> None:
    db = ls.get_adjacency_databases()[node]
    ls.update_adjacency_database(replace(db, adjacencies=tuple(
        replace(a, metric=metric) for a in db.adjacencies)))


class _Resident:
    """``EllState`` over the grid, solved from the corner, the way
    ``_EllResidentCache.view_packed`` drives it."""

    def __init__(self, topo):
        self.ls = _link_state(topo)
        self.state = spf_sparse.EllState(spf_sparse.compile_ell(self.ls))
        self.cold = self.solve([])

    def solve(self, affected):
        graph = self.state.graph
        if affected:
            graph = spf_sparse.ell_patch(
                graph, self.ls, sorted(affected), widen=True)
        srcs = spf_sparse.ell_source_batch(graph, self.ls, CORNER)
        packed, passes, reset_rows = self.state.fetch_view(
            self.state.reconverge(graph, srcs))
        fresh, stats = spf_sparse._ell_view_batch(
            tuple(graph.src), tuple(graph.w), graph.overloaded,
            *spf_sparse._batch_args(graph, srcs), graph.bands, graph.n_pad)
        np.testing.assert_array_equal(packed, np.asarray(fresh))
        names = [graph.node_names[i] for i in srcs]
        ecc = reference.relax_passes(
            self.ls.get_adjacency_databases(), sorted(set(names)))
        return dict(passes=passes, reset_rows=reset_rows, batch=len(srcs),
                    ecc=ecc, cold_stats=tuple(int(x) for x in stats))

    def recost(self, node: str, metric: int):
        _set_metrics(self.ls, node, metric)
        return self.solve({node} | {
            a.other_node_name
            for a in self.ls.get_adjacency_databases()[node].adjacencies})


@pytest.fixture()
def resident(grid):
    return _Resident(grid)


def test_a_forced_reset_runs_the_corners_eccentricity_in_passes(resident):
    """A first solve has no previous rows: every batch row starts from
    the cold init, whose one pass (not counted: it is outside the loop)
    settles the 1-hop nodes. A node ``h`` hops away is final after
    ``h - 1`` loop passes and one more sees nothing change: the hop
    eccentricity, 2 * 64 from the corner of a 65 x 65 grid."""
    got = resident.cold
    assert got["ecc"] == 2 * (SIDE - 1) == 128
    assert got["passes"] == got["ecc"]
    assert got["reset_rows"] == got["batch"] == 8
    # the cold program says the same of itself
    assert got["cold_stats"] == (got["ecc"], got["batch"])


@pytest.mark.parametrize("node,tight", [
    ("node-1", True),               # the vantage's own neighbour
    (f"node-{32 * SIDE + 32}", True),   # the middle of the grid
    (f"node-{SIDE * SIDE - 2}", True),  # next to the far corner
    (FAR, False),    # the far corner: no link of it points away
])
def test_a_node_that_raises_its_links_restarts_rows_unless_it_is_the_far_corner(
        resident, node, tight):
    """Every link that points away from the corner lies on a shortest
    path, and every node but the far corner has one: raising all of a
    node's links is tight in ``_warm_seed`` and restarts whole rows,
    which then take the eccentricity in passes wherever the node is."""
    got = resident.recost(node, 2)
    if tight:
        assert got["reset_rows"] >= 1
        assert got["passes"] >= got["ecc"] - 1
    else:
        assert got["reset_rows"] == 0 and got["passes"] == 1


@pytest.mark.parametrize("node", [FAR, f"node-{SIDE * SIDE - 2}",
                                  f"node-{32 * SIDE + 32}"])
def test_a_pure_decrease_resets_no_row_and_a_far_one_takes_few_passes(
        resident, node):
    """Raised, then lowered back: the second patch only decreases, so
    the previous rows are valid upper bounds, no row restarts, and the
    passes are those the news needs to travel from the node onward:
    a handful next to the far corner, more from the middle, never the
    eccentricity."""
    resident.recost(node, 7)
    got = resident.recost(node, 1)
    assert got["reset_rows"] == 0
    if node == f"node-{32 * SIDE + 32}":
        assert got["passes"] < got["ecc"]
    else:
        assert got["passes"] <= 4


def test_fetch_view_books_each_solve_once(resident):
    registry = get_registry()
    hist = registry.histogram("ops.ell.relax_passes")
    count0 = hist.count
    sum0 = registry.snapshot()["ops.ell.relax_passes.sum"]
    resets0 = registry.counter_get("decision.ell_reset_solves")
    a = resident.recost("node-5", 3)      # tight: restarts rows
    b = resident.recost("node-5", 1)      # pure decrease
    assert hist.count - count0 == 2
    assert registry.snapshot()["ops.ell.relax_passes.sum"] - sum0 \
        == a["passes"] + b["passes"]
    assert registry.counter_get("decision.ell_reset_solves") - resets0 == 1
