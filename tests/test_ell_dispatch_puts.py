"""A warm ELL dispatch hands the device only what it does not hold
(PR 52): the no-op scatter triple of a band and the source batch stay
on the device from one ``EllState.reconverge`` to the next, and the
``ops.ell_reconverge`` span says how many host arrays each dispatch put
(``puts``). Held here, through ``Decision`` on the 486-node three-band
fabric and a 12 x 12 one-band grid and on ``EllState`` alone: the count
a prewarmed and a fused-patch window say, against the puts really made
(``jnp.asarray``, ``jax.device_put`` and the jitted call wrapped); that the resident no-op
is a no-op whatever the band holds (every band bit-identical to the
host's ``EllGraph`` after every dispatch, the window after a patch of
row 0 of each band and the one after a widen included); that the held
source batch goes when the vantage's neighbour set does; and the routes
against the plain Dijkstra of ``chipbench/reference.py`` throughout.
Counts, never times: this is the CPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference, topology, traffic
from chipbench.served_paths import pipeline_grid  # noqa: F401 - the grid
from openr_tpu.decision import spf_solver
from openr_tpu.decision.decision import Decision
from openr_tpu.messaging.queue import ReplicateQueue
from openr_tpu.ops import spf_sparse
from openr_tpu.telemetry import get_registry, get_tracer
from openr_tpu.types import Publication
from tests.test_fabric_three_band import MIX, SP_ECMP, THREE_BAND
from tests.test_incremental_parity import _grow_in_degree
from tests.test_incremental_parity import load as _link_state
from tests.test_sp_route_reuse import _drop_adj, _mutate_metric, _restore_adj

GRID = {"kind": "grid", "n": 12}
SHAPES = {
    # shape, traffic mix, vantage, bands (rows x k)
    "fabric": (THREE_BAND, MIX, "rsw-0-0", [(400, 8), (80, 16), (6, 64)]),
    "grid": (GRID, {"kinds": {"node-metric": 0.8, "flap": 0.2}}, "node-0",
             [(144, 8)]),
}
INCREASE_TRIPLE = 3  # what every dispatch hands over: inc_t, inc_h, inc_w


@pytest.fixture(scope="module", params=sorted(SHAPES))
def shape(request):
    group, mix, vantage, bands = SHAPES[request.param]
    return topology.build(group, SP_ECMP), mix, vantage, bands


class _PutCounter:
    """``jnp.asarray``, ``jax.device_put`` and the jitted program
    wrapped: the host (numpy) arrays handed to any of them while
    ``EllState.reconverge`` runs, a count a dispatch."""

    def __init__(self, monkeypatch):
        self.inside = False
        self.dispatches = []
        real_asarray, real_put = jnp.asarray, jax.device_put
        real_reconverge = spf_sparse.EllState.reconverge
        real_program = spf_sparse._ell_reconverge

        def asarray(a, *args, **kwargs):
            self._note(a)
            return real_asarray(a, *args, **kwargs)

        def device_put(x, *args, **kwargs):
            self._note(x)
            return real_put(x, *args, **kwargs)

        def program(*args):
            self._note(args)
            return real_program(*args)

        def reconverge(state, patched, srcs):
            self.inside = True
            self.dispatches.append(0)
            try:
                return real_reconverge(state, patched, srcs)
            finally:
                self.inside = False

        monkeypatch.setattr(jnp, "asarray", asarray)
        monkeypatch.setattr(jax, "device_put", device_put)
        monkeypatch.setattr(spf_sparse, "_ell_reconverge", program)
        monkeypatch.setattr(spf_sparse.EllState, "reconverge", reconverge)

    def _note(self, tree):
        if self.inside:
            self.dispatches[-1] += sum(
                isinstance(leaf, np.ndarray)
                for leaf in jax.tree_util.tree_leaves(tree))


def _assert_bands_are_the_hosts(state):
    """The resident bands, read back, against the host's EllGraph."""
    graph = state.graph
    assert [(s.shape, w.shape) for s, w in zip(state.src, state.w)] \
        == [((b.rows, b.k),) * 2 for b in graph.bands]
    for bi in range(len(graph.bands)):
        np.testing.assert_array_equal(np.asarray(state.src[bi]), graph.src[bi])
        np.testing.assert_array_equal(np.asarray(state.w[bi]), graph.w[bi])


def _resident(decision):
    ls = decision.area_link_states["0"]
    return spf_solver._ELL_RESIDENT._cache[ls][1]


def _windows(topo, mix, vantage, seed, n, prewarm, monkeypatch):
    """``n`` one-event windows through ``Decision``; yields each
    window's ``ops.ell_reconverge`` span, the resident state, the bands
    the window's patch named and whether the source batch changed,
    after holding the routes to the reference."""
    monkeypatch.setattr(spf_solver, "SPARSE_NODE_THRESHOLD", 32)
    patched_bands = []
    real_patch = spf_sparse.ell_patch

    def recording(graph, ls, affected, widen=False):
        patched = real_patch(graph, ls, affected, widen=widen)
        assert patched is not None and not patched.widened
        patched_bands.append(
            {bi for bi, rows in patched.changed.items() if len(rows)})
        return patched

    monkeypatch.setattr(spf_sparse, "ell_patch", recording)
    gen = traffic.Generator(topo, seed, mix, vantage)
    queue = ReplicateQueue(name="puts:kvstore")
    decision = Decision(
        vantage,
        kvstore_updates_queue=queue,
        route_updates_queue=ReplicateQueue(name="puts:routes"),
        solver_backend="device",
    )
    tracer = get_tracer()
    try:
        decision.process_publication(Publication(
            key_vals=dict(gen.initial_key_vals()), area="0"))
        decision.rebuild_routes("LOAD")
        state = _resident(decision)
        for _ in range(n):
            ev = gen.draw()
            held = state._srcs_dev
            del patched_bands[:]
            decision.process_publication(Publication(
                key_vals={ev.key: ev.value}, area="0"))
            if prewarm:
                decision.spf_solver.prewarm(decision.area_link_states)
            trace = tracer.start()
            decision.pending.adopt_trace(trace)
            decision.rebuild_routes("WINDOW")
            tracer.finish(trace)
            assert reference.routes_of(
                decision.route_db.to_route_db(vantage)
            ) == reference.routes(gen.adj_dbs, gen.prefix_dbs, vantage)
            assert _resident(decision) is state
            (span,) = [s for s in trace.spans
                       if s.name == "ops.ell_reconverge"]
            (bands,) = patched_bands
            yield span, state, bands, state._srcs_dev is not held
    finally:
        queue.close()


@pytest.mark.parametrize("prewarm", [True, False],
                         ids=["prewarmed", "fused-patch"])
def test_the_span_says_the_puts_a_window_made(shape, prewarm, monkeypatch):
    """A prewarmed window puts the increase triple and nothing else; a
    window whose rows ride the fused solve puts a triple more for every
    band its patch names. Either way the bands are the host's after the
    dispatch and the no-op triples are the arrays they were."""
    topo, mix, vantage, bands = shape
    histogram = get_registry().histogram
    observed0, triples, scattered = None, None, set()
    for n, (span, state, named, new_srcs) in enumerate(_windows(
            topo, mix, vantage, 23, 24, prewarm, monkeypatch), 1):
        assert [(b.rows, b.k) for b in state.graph.bands] == bands
        assert span.attrs["warm"] is not new_srcs
        fused = 0 if prewarm else len(named)
        assert span.attrs["puts"] \
            == INCREASE_TRIPLE + 3 * fused + int(new_srcs)
        assert 0 <= span.attrs["stage_ms"] <= span.attrs["host_overhead_ms"]
        _assert_bands_are_the_hosts(state)
        # one entry a band shape, put by the cold solve and kept
        assert sorted(state._noops) == sorted(bands)
        triples = triples or dict(state._noops)
        assert all(state._noops[k] is triples[k] for k in triples)
        scattered |= named
        # one observation a dispatch (the load's cold solve was one)
        observed = histogram("ops.ell.dispatch_puts").count
        observed0 = observed - 1 if observed0 is None else observed0
        assert observed - observed0 == n
    assert scattered == set(range(len(bands)))


def test_the_puts_counted_agree_with_the_span_over_20_prewarmed_windows(
        shape, monkeypatch):
    topo, mix, vantage, bands = shape
    counter = _PutCounter(monkeypatch)
    said = []
    for span, _state, _named, new_srcs in _windows(
            topo, mix, vantage, 4294967301, 20, True, monkeypatch):
        assert span.attrs["puts"] <= INCREASE_TRIPLE + int(new_srcs)
        said.append(span.attrs["puts"])
    assert len(said) == 20 and said.count(INCREASE_TRIPLE) >= 18
    # the load's cold solve put every band's no-op triple, the source
    # batch and its own increase triple
    assert counter.dispatches == [3 * len(bands) + 1 + INCREASE_TRIPLE] + said


# -- EllState alone ----------------------------------------------------------


def _by_name(graph, srcs, packed) -> dict:
    """(source, destination) -> (distance, is a first hop), by name: a
    widen renumbers a fresh ``compile_ell``."""
    names, b = graph.node_names, len(srcs)
    return {
        (names[sid], dst): (int(packed[i, did]), bool(packed[b + i, did]))
        for i, sid in enumerate(srcs)
        for dst, did in graph.node_index.items()
    }


class _World:
    """``EllState`` over the three-band fabric, solved from an RSW, each
    dispatch held to a cold solve of a fresh compile."""

    ROOT = "rsw-0-0"

    def __init__(self):
        self.ls = _link_state(topology.build(THREE_BAND, SP_ECMP))
        self.state = spf_sparse.EllState(spf_sparse.compile_ell(self.ls))
        self.tracer = get_tracer()
        self.solve(())  # cold: puts the no-op triples and the batch

    def patch(self, affected):
        patched = spf_sparse.ell_patch(
            self.state.graph, self.ls, sorted(affected), widen=True)
        assert patched is not None
        return patched

    def solve(self, affected, prewarm=False):
        """One window: the patch scattered ahead of the solve
        (``prewarm``) or riding it; returns the span's attributes."""
        patched = self.patch(affected) if affected else self.state.graph
        if prewarm:
            self.state.apply_patch(patched)
            patched = self.state.graph
        srcs = spf_sparse.ell_source_batch(patched, self.ls, self.ROOT)
        trace = self.tracer.start()
        self.tracer.activate(trace)
        try:
            packed = np.asarray(self.state.reconverge(patched, srcs))
        finally:
            self.tracer.deactivate()
        self.tracer.finish(trace)
        cold = spf_sparse.compile_ell(self.ls)
        cold_srcs = spf_sparse.ell_source_batch(cold, self.ls, self.ROOT)
        assert _by_name(self.state.graph, srcs, packed) == _by_name(
            cold, cold_srcs,
            np.asarray(spf_sparse.ell_view_batch_packed(cold, cold_srcs)))
        _assert_bands_are_the_hosts(self.state)
        (span,) = [s for s in trace.spans if s.name == "ops.ell_reconverge"]
        return span.attrs

    def other(self, node, i=0):
        db = self.ls.get_adjacency_databases()[node]
        return db.adjacencies[i].other_node_name

    def set_metric_into(self, node, metric):
        """Re-cost the first link of ``node`` at its far end: the edge
        INTO ``node``, which is a slot of ``node``'s own row. Returns
        the two nodes whose rows a patch re-derives."""
        peer = self.other(node)
        adjs = self.ls.get_adjacency_databases()[peer].adjacencies
        (i,) = [i for i, a in enumerate(adjs) if a.other_node_name == node]
        _mutate_metric(self.ls, peer, i, metric)
        return {node, peer}


@pytest.mark.parametrize("band", [0, 1, 2])
def test_the_window_after_a_patch_of_row_0_leaves_row_0_as_patched(band):
    """Today's no-op rewrote row 0 with itself, read from the host band
    each time; the resident one names no row, so a patch of row 0 ahead
    of the solve stands."""
    world = _World()
    graph = world.state.graph
    first = graph.node_names[graph.bands[band].start]
    affected = world.set_metric_into(first, 7)
    assert 0 in world.patch(affected).changed[band]
    row0 = np.array(graph.w[band][0], copy=True)
    attrs = world.solve(affected, prewarm=True)
    assert attrs["puts"] == INCREASE_TRIPLE and attrs["warm"] is True
    assert not np.array_equal(world.state.graph.w[band][0], row0)
    # and the next window, which patches nothing at all
    assert world.solve(())["puts"] == INCREASE_TRIPLE
    # a patch of row 0 that rides the solve is staged as ever
    affected = world.set_metric_into(first, 3)
    named = {bi for bi, rows in world.patch(affected).changed.items()
             if len(rows)}
    assert band in named
    assert world.solve(affected)["puts"] == INCREASE_TRIPLE + 3 * len(named)
    assert 3 in world.state.graph.w[band][0]


@pytest.mark.parametrize("prewarm", [True, False],
                         ids=["prewarmed", "fused-patch"])
def test_a_widened_band_gets_a_no_op_of_its_own_shape(prewarm):
    world = _World()
    shapes = sorted(world.state._noops)
    assert shapes == sorted([(400, 8), (80, 16), (6, 64)])
    widens0 = spf_sparse.ELL_COUNTERS["ell_widen_events"]
    # rsw-1-0 (band k=8) gains 9 links: its row outgrows the band
    peers = [f"rsw-2-{i}" for i in range(9)]
    _grow_in_degree(world.ls, "rsw-1-0", peers)
    affected = {"rsw-1-0", *peers}
    assert world.patch(affected).widened == frozenset({0})
    attrs = world.solve(affected, prewarm=prewarm)
    assert spf_sparse.ELL_COUNTERS["ell_widen_events"] == widens0 + 1
    assert world.state.graph.bands[0].k == 16
    # the new shape's triple, and when the widen rides the solve the
    # band's two tensors whole
    assert attrs["puts"] == INCREASE_TRIPLE + 3 + (0 if prewarm else 2)
    assert sorted(world.state._noops) == sorted(shapes + [(400, 16)])
    # the window after: nothing but the increase triple again
    assert world.solve(())["puts"] == INCREASE_TRIPLE
    other = world.other("rsw-1-0")
    _mutate_metric(world.ls, "rsw-1-0", 0, 5)
    assert world.solve({"rsw-1-0", other}, prewarm=True)["puts"] \
        == INCREASE_TRIPLE


def test_the_held_source_batch_goes_when_the_vantages_neighbours_do():
    world = _World()
    root = world.ROOT
    key0, dev0 = world.state._srcs_dev
    assert key0 == tuple(spf_sparse.ell_source_batch(
        world.state.graph, world.ls, root))
    # another node's link: the batch is the one held
    other = world.other("rsw-3-0")
    _mutate_metric(world.ls, "rsw-3-0", 0, 4)
    attrs = world.solve({"rsw-3-0", other}, prewarm=True)
    assert attrs["puts"] == INCREASE_TRIPLE and attrs["warm"] is True
    assert world.state._srcs_dev[1] is dev0
    # the vantage's own link goes: a neighbour fewer, a new batch, cold
    peer = world.other(root)
    dropped = _drop_adj(world.ls, root, 0)
    attrs = world.solve({root, peer}, prewarm=True)
    key1, dev1 = world.state._srcs_dev
    assert attrs["puts"] == INCREASE_TRIPLE + 1 and attrs["warm"] is False
    assert key1 != key0 and dev1 is not dev0
    assert world.state.graph.node_index[peer] not in key1
    np.testing.assert_array_equal(np.asarray(dev1), key1)
    # and comes back
    _restore_adj(world.ls, root, dropped)
    attrs = world.solve({root, peer}, prewarm=True)
    assert attrs["puts"] == INCREASE_TRIPLE + 1
    assert world.state._srcs_dev[0] == key0
    assert world.solve(())["puts"] == INCREASE_TRIPLE


def test_the_batch_is_keyed_by_its_own_ids_not_by_the_warm_key():
    """``state/snapshot.py`` writes ``_warm_key`` from outside: the held
    ids answer for themselves."""
    world = _World()
    graph = world.state.graph
    srcs = spf_sparse.ell_source_batch(graph, world.ls, "rsw-5-0")
    cold = np.asarray(spf_sparse.ell_view_batch_packed(graph, srcs))
    # another vantage's rows, restored as a snapshot restores them
    world.state._d_dev = jnp.asarray(cold[:len(srcs)])
    world.state._warm_key = tuple(srcs)
    warm0 = spf_sparse.ELL_COUNTERS["ell_warm_solves"]
    packed = np.asarray(world.state.reconverge(graph, srcs))
    assert spf_sparse.ELL_COUNTERS["ell_warm_solves"] == warm0 + 1
    np.testing.assert_array_equal(packed, cold)
    assert world.state._srcs_dev[0] == tuple(srcs)


@pytest.mark.parametrize("rows, k", [(5, 8), (400, 8), (6, 64)])
def test_the_no_op_triple_is_a_no_op_whatever_the_band_holds(rows, k):
    """An id no row has: the scatter drops it, in the fused program's
    expression and in ``_patch_band``'s."""
    rng = np.random.default_rng(rows * k)
    src = rng.integers(0, rows, (rows, k), dtype=np.int32)
    w = rng.integers(1, 1 << 20, (rows, k), dtype=np.int32)
    graph = spf_sparse.EllGraph(
        node_names=(), node_index={}, n=rows, n_pad=rows,
        bands=(spf_sparse.EllBand(0, rows, k),), src=(src,), w=(w,),
        overloaded=np.zeros(rows, dtype=bool))
    noops = {}
    _, _, (ids,), (ps,), (pw,), puts = spf_sparse.band_patch_inputs(
        (None,), (None,), graph, noops)
    assert puts == 3 and list(noops) == [(rows, k)]
    assert (ids.shape, ps.shape, pw.shape) == ((1,), (1, k), (1, k))
    assert ids.dtype == ps.dtype == pw.dtype == jnp.int32
    assert int(ids[0]) == rows
    for scatter in (jax.jit(spf_sparse._scatter_band_rows),
                    spf_sparse._patch_band):
        out_src, out_w = scatter(jnp.asarray(src), jnp.asarray(w), ids, ps, pw)
        np.testing.assert_array_equal(np.asarray(out_src), src)
        np.testing.assert_array_equal(np.asarray(out_w), w)
    # the second time the triple is the one held, and nothing is put
    again = spf_sparse.band_patch_inputs((None,), (None,), graph, noops)
    assert again[5] == 0 and again[2][0] is ids
    # a caller that keeps none (the route engine) puts afresh
    assert spf_sparse.band_patch_inputs((None,), (None,), graph)[5] == 3
