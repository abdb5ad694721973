"""The long-diameter deployment through the normal path, on the CPU at a
size the ELL path takes: a 65 x 65 grid (4,225 nodes, above
``SPARSE_NODE_THRESHOLD``) solved from its corner, 128 hops from the far
one.

Two things are held here. The solve's RESULT is what it was: after every
burst of ``grid-10000.drain-churn``'s events (a node re-costs all its
links, a link flaps) the device backend's ``RouteDatabase`` equals the
plain per-source Dijkstra of ``chipbench/reference.py`` and is
bit-identical to ``solver_backend=host``. And the two scalars a solve now
carries out beside its packed view say what the solve did: the passes
over the bands its two ``while_loop``s ran (``_cone_seed``'s support
passes, then the relax passes) and the batch rows a tight increased edge
flagged. Since PR 31 a flagged row restarts only the columns no in-edge
supports any more, so what a solve pays is the depth of what changed and
not the corner's 128 hops. Counts, never times: this is the CPU.
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
    ecc = 2 * (SIDE - 1)
    for s in spans:
        # at worst a whole row is cone: support passes, then relax passes
        assert 1 <= s.attrs["passes"] <= 2 * ecc
        assert 0 <= s.attrs["reset_rows"] <= 8
        if s.attrs["reset_rows"]:
            # one support pass at least, and the relax pass that confirms
            assert s.attrs["passes"] >= 2
    # a flagged solve no longer costs the eccentricity: the typical one
    # finds every raised link's head a second parent and closes at once
    flagged = sorted(
        s.attrs["passes"] for s in spans if s.attrs["reset_rows"])
    assert flagged[len(flagged) // 2] <= 6, flagged


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

    def withdraw(self, node: str, other: str):
        """One side of a link stops announcing it, as a flap does."""
        self._before = self.ls.get_adjacency_databases()[node]
        self.ls.update_adjacency_database(replace(
            self._before, adjacencies=tuple(
                a for a in self._before.adjacencies
                if a.other_node_name != other)))
        return self.solve({node, other})

    def restore(self, node: str):
        self.ls.update_adjacency_database(self._before)
        return self.solve({node} | {
            a.other_node_name for a in self._before.adjacencies})


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


@pytest.mark.parametrize("node,flagged,passes", [
    # the vantage's own neighbour is a source of the batch: every
    # distance of its own row rises, that row is all cone, and the two
    # loops each walk it (127 + 1 support passes, 127 relax passes)
    ("node-1", True, 2 * 2 * (SIDE - 1) - 1),
    # the middle of the grid, and next to the far corner: each raised
    # link's head has a second parent at the same distance, so one
    # support pass marks nothing and one relax pass changes nothing
    (f"node-{32 * SIDE + 32}", True, 2),
    (f"node-{SIDE * SIDE - 2}", True, 2),
    (FAR, False, 1),    # the far corner: no link of it points away
])
def test_a_node_that_raises_its_links_restarts_rows_unless_it_is_the_far_corner(
        resident, node, flagged, passes):
    """Every link that points away from the corner lies on a shortest
    path, and every node but the far corner has one: raising all of a
    node's links is tight and flags rows. What the solve then pays is
    the depth of the cone behind the raised links, not the
    eccentricity: nothing for an interior node, the whole row only for
    a source of the batch itself."""
    got = resident.recost(node, 2)
    assert got["passes"] == passes
    if flagged:
        assert got["reset_rows"] >= 1
        assert got["passes"] <= 4 or node == "node-1"
    else:
        assert got["reset_rows"] == 0


def _cone_passes(depth: int) -> int:
    """A chain of ``depth`` columns behind a raised edge: the support
    loop marks one a pass and one more pass marks nothing; the relax
    loop settles one a pass and one more pass changes nothing."""
    return 2 * (depth + 1)


@pytest.mark.parametrize("node,depth", [
    # row 0 beyond column 2: on a line through the batch, not in it
    ("node-2", SIDE - 3),
    # column 0 below rows 5, 10, 60
    (f"node-{5 * SIDE}", SIDE - 6),
    (f"node-{10 * SIDE}", SIDE - 11),
    (f"node-{60 * SIDE}", SIDE - 61),
])
def test_a_raise_on_a_line_through_the_vantage_pays_the_line_behind_it(
        resident, node, depth):
    """On column 0 (and row 0) a node's only shortest parent is the one
    before it on the line, so a raise there moves the whole line behind
    it farther: the cone is that line, and the passes are its depth and
    the two confirming ones, twice over. Under the eccentricity, which
    is what any flagged row cost before."""
    got = resident.recost(node, 2)
    assert got["reset_rows"] >= 1
    assert got["passes"] == _cone_passes(depth) < got["ecc"]


@pytest.mark.parametrize("a,b,support,relax", [
    # an interior link: the head keeps its other parent
    (32 * SIDE + 32, 32 * SIDE + 33, 1, 1),
    # a link of row 0: the head's only parent was the tail, so row 0
    # beyond it is the cone, 54 columns that the support loop marks one
    # a pass; every one of them then takes its new distance from its
    # neighbour on row 1, which never moved, in ONE relax pass
    (10, 11, SIDE - 11 + 1, 2),
])
def test_a_withdrawn_link_pays_its_cone_and_nothing_when_a_second_parent_holds(
        resident, a, b, support, relax):
    """A flap's withdrawal reads as w -> INF on both directions of the
    link; the one that pointed away from the corner was tight."""
    got = resident.withdraw(f"node-{a}", f"node-{b}")
    assert got["reset_rows"] >= 1
    assert got["passes"] == support + relax < got["ecc"]
    # the restore only decreases: nothing is flagged, no support pass,
    # and the news travels down the row it shortens
    back = resident.restore(f"node-{a}")
    assert back["reset_rows"] == 0
    assert back["passes"] == (1 if support == 1 else support)


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
