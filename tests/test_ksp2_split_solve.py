"""A one-chip KSP2 sync waits for the rows it reads and carries the
matrix it keeps forward behind the window (PR 47).

``Ksp2Engine._sync_window`` used to dispatch ONE fused program (the
all-pairs fixed point, the view, the endpoint rows) and block on it.
It now dispatches the rows solve (``spf_sparse._ell_view_ep_rows``: the
view batch and the changed-edge endpoints, 40-48 source rows) and waits
for that, and sends the matrix solve (``_ell_all_view_rows``, which
kept its name and its donation) behind the window's last masked batch,
blocking on nothing of it. These tests hold the split to the program it
replaced, kept here as a reference and nowhere in the package, over
streams of windows on a 12 x 12 grid solved from its corner and on a
small fabric solved from a rack switch:

(a) the rows solve's ``packed`` is, row for row, what the fused program
    returns for the same arguments;
(b) the matrix the engine holds after the sync is a cold all-sources
    solve of the current graph (and the fused program's);
(c) paths and routes are the host solver's;

and the order of the dispatches, the counter that says the matrix went
behind the window, and the cold build after a raise on either side of
the matrix dispatch. Counts and equalities only: a CPU run's times say
nothing.
"""

from __future__ import annotations

import functools
import random
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from openr_tpu.decision import ksp2_engine
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.spf_solver import SPF_COUNTERS, SpfSolver
from openr_tpu.graph.linkstate import LinkState
from openr_tpu.models import topologies
from openr_tpu.ops import spf_sparse
from openr_tpu.telemetry import get_registry, get_tracer
from openr_tpu.types.lsdb import (
    PrefixForwardingAlgorithm,
    PrefixForwardingType,
)

_KSP2 = dict(
    forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
    forwarding_type=PrefixForwardingType.SR_MPLS,
)

# kind -> (topology, vantage)
_NETWORKS = {
    "grid": lambda: (topologies.grid(12, **_KSP2), "node-0"),
    "fabric": lambda: (topologies.fat_tree_nodes(60, **_KSP2), "rsw-0-0"),
}


@pytest.fixture(autouse=True)
def _engine_everywhere(monkeypatch):
    from openr_tpu.decision import spf_solver as ss

    monkeypatch.setattr(ss, "KSP2_DEVICE_MIN_DSTS", 1)


# -- the program of the parent commit, as a reference ---------------------


@functools.partial(jax.jit, static_argnames=("bands", "n"))
def _fused_parent(
    srcs_t, ws_t, overloaded, view_srcs, w_sv, ep_ids, d_prev,
    inc_tail, inc_head, inc_w, bands, n,
):
    """``_ell_all_view_rows`` as PR 44 left it, less the donation: the
    all-sources fixed point warm-seeded from ``d_prev``, the view
    derived from its rows, and the endpoint rows gathered from it and
    from ``d_prev``."""
    d_all, passes = spf_sparse._ell_fixed_point(
        srcs_t, ws_t, overloaded,
        jnp.arange(n, dtype=jnp.int32), bands, n,
        warm=(d_prev, inc_tail, inc_head, inc_w),
    )
    d = d_all[view_srcs]
    fh = spf_sparse._first_hops_from_rows(d, view_srcs, w_sv, overloaded, n)
    packed = jnp.concatenate(
        [d, fh.astype(jnp.int32), d_all[ep_ids], d_prev[ep_ids]], axis=0
    )
    return d_all, packed, passes


class _Watch:
    """Wraps the engine's dispatches: logs their order, and beside
    every rows solve runs the fused reference on the same arguments
    (before the matrix solve consumes ``d_prev``)."""

    def __init__(self, monkeypatch):
        self.log = []
        self.rows = []  # (packed of the rows solve, fused packed, fused D)
        real_rows = spf_sparse.ell_view_ep_rows
        real_matrix = spf_sparse.ell_all_view_rows
        real_masked = spf_sparse.ell_masked_distances_resident
        real_prime = ksp2_engine.Ksp2Engine._prime_all

        def rows(state, view_srcs, w_sv, ep_ids, d_prev, inc=None,
                 inc_bucket=4):
            inc_dev = spf_sparse._inc_args(inc, inc_bucket)
            want_d, want_packed, _ = _fused_parent(
                state.src, state.w, state.overloaded,
                jnp.asarray(view_srcs), jnp.asarray(w_sv),
                jnp.asarray(np.asarray(ep_ids, dtype=np.int32)),
                d_prev, *inc_dev,
                bands=state.graph.bands, n=state.graph.n_pad,
            )
            want = (np.asarray(want_packed), np.asarray(want_d))
            self.log.append("rows")
            out = real_rows(
                state, view_srcs, w_sv, ep_ids, d_prev, inc=inc,
                inc_bucket=inc_bucket,
            )
            self.rows.append((out[0], *want))
            return out

        def matrix(state, d_prev, inc_dev):
            self.log.append("matrix")
            return real_matrix(state, d_prev, inc_dev)

        def masked(*args, **kwargs):
            self.log.append("masked")
            return real_masked(*args, **kwargs)

        def prime(engine, ls):
            self.log.append("prime")
            return real_prime(engine, ls)

        monkeypatch.setattr(spf_sparse, "ell_view_ep_rows", rows)
        monkeypatch.setattr(spf_sparse, "ell_all_view_rows", matrix)
        monkeypatch.setattr(
            spf_sparse, "ell_masked_distances_resident", masked
        )
        monkeypatch.setattr(ksp2_engine.Ksp2Engine, "_prime_all", prime)

    def take(self):
        log, rows = self.log, self.rows
        self.log, self.rows = [], []
        return log, rows


# -- twin networks and the events a window is made of ---------------------


def _network(kind):
    topo, root = _NETWORKS[kind]()
    ls = LinkState(area=topo.area)
    for name in sorted(topo.adj_dbs):
        ls.update_adjacency_database(topo.adj_dbs[name])
    ps = PrefixState()
    for pdb in topo.prefix_dbs.values():
        ps.update_prefix_database(pdb)
    return topo, root, ls, ps


def _adjs(ls, node):
    return list(ls.get_adjacency_databases()[node].adjacencies)


def _publish(ls, node, **changes):
    db = ls.get_adjacency_databases()[node]
    ls.update_adjacency_database(replace(db, **changes))


def _recost(node, metric):
    """The node re-costs all its links (the grid cell's event)."""
    def apply(ls):
        _publish(ls, node, adjacencies=tuple(
            replace(a, metric=metric) for a in _adjs(ls, node)))
    return apply


def _one_metric(node, i, metric):
    def apply(ls):
        adjs = _adjs(ls, node)
        adjs[i] = replace(adjs[i], metric=metric)
        _publish(ls, node, adjacencies=tuple(adjs))
    return apply


def _link_down(node, i, kept):
    def apply(ls):
        adjs = _adjs(ls, node)
        kept.append(adjs.pop(i))
        _publish(ls, node, adjacencies=tuple(adjs))
    return apply


def _link_up(node, kept):
    def apply(ls):
        _publish(ls, node,
                 adjacencies=tuple(_adjs(ls, node)) + (kept.pop(),))
    return apply


def _overload(node, flag):
    return lambda ls: _publish(ls, node, is_overloaded=flag)


def _relabel(node, by):
    def apply(ls):
        label = ls.get_adjacency_databases()[node].node_label
        _publish(ls, node, node_label=label + by)
    return apply


def _storm(nodes, metric):
    """More endpoints than ENGINE_MAX_ENDPOINTS in one window."""
    def apply(ls):
        for node in nodes:
            _recost(node, metric)(ls)
    return apply


def _windows(kind, nodes, root):
    """(what the window is, its events): >= 30 windows of every class
    the split has a branch for. One callable a window; each is applied
    to the device twin and to the host twin."""
    rng = random.Random(f"split/{kind}")
    others = [n for n in nodes if n != root]
    out = []
    for cycle in range(4):
        a, b, c = rng.sample(others, 3)
        out += [
            ("recost-up", _recost(a, 3 + cycle)),
            ("recost-up", _recost(b, 2 + cycle)),
            ("recost-down", _recost(a, 1)),
            ("metric", _one_metric(c, 0, 5 + cycle)),
        ]
        kept: list = []
        out += [
            ("flap-down", _link_down(b, 0, kept)),
            ("flap-up", _link_up(b, kept)),
            ("recost-down", _recost(b, 1)),
        ]
        if cycle % 2 == 0:
            out += [
                ("drain", _overload(c, True)),
                ("recost-up", _recost(a, 4)),  # with a drained node about
                ("undrain", _overload(c, False)),
            ]
        # no link changed: no endpoint, no destination named
        out.append(("relabel", _relabel(a, 5000)))
    storm = rng.sample(others, 14)
    out.append(("storm", _storm(storm, 6)))
    out += [
        ("recost-down", _recost(storm[0], 1)),
        ("recost-up", _recost(storm[1], 8)),
        ("relabel", _relabel(storm[2], 7000)),
    ]
    assert len(out) >= 30
    return out


def _traced(fn):
    """``fn()`` under an active trace: (its result, the spans)."""
    tracer = get_tracer()
    trace = tracer.start()
    tracer.activate(trace)
    try:
        got = fn()
    finally:
        tracer.deactivate()
        tracer.finish(trace)
    return got, trace.spans


def _names(paths):
    """Paths as comparable values across two LinkStates."""
    return [[(l.n1, l.if1, l.n2, l.if2) for l in p] for p in paths]


# -- (a) + (b) + (c) after every window -----------------------------------


@pytest.mark.parametrize("kind", sorted(_NETWORKS))
def test_every_window_reads_the_fused_programs_rows_and_keeps_its_matrix(
        kind, monkeypatch):
    topo, root, ls_d, ps_d = _network(kind)
    _, _, ls_h, ps_h = _network(kind)
    nodes = sorted(topo.adj_dbs)
    dev = SpfSolver(root, backend="device")
    host = SpfSolver(root, backend="host")
    watch = _Watch(monkeypatch)
    assert dev.build_route_db(root, {topo.area: ls_d}, ps_d).to_route_db(
        root) == host.build_route_db(
        root, {topo.area: ls_h}, ps_h).to_route_db(root)
    engine = dev._ksp2_engines[ls_d]
    assert engine.valid and engine._mesh is None
    # the cold build: the matrix solve first, the rows solve off it
    log, _ = watch.take()
    assert [x for x in log if x in ("rows", "matrix")] == ["matrix", "rows"]
    reg = get_registry()
    seen = set()
    for step, (what, event) in enumerate(_windows(kind, nodes, root)):
        before = dict(SPF_COUNTERS)
        rows_passes = reg.counter_get("ops.ksp2.all_pairs_passes")
        event(ls_d)
        event(ls_h)
        built, spans = _traced(
            lambda: dev.build_route_db(root, {topo.area: ls_d}, ps_d))
        want = host.build_route_db(root, {topo.area: ls_h}, ps_h)
        # (c) routes, then both ranks of every destination's paths
        assert built.to_route_db(root) == want.to_route_db(root), (
            step, what)
        assert engine.valid
        for dst in engine.dsts:
            for k in (1, 2):
                assert _names(ls_d.get_kth_paths(root, dst, k)) == _names(
                    ls_h.get_kth_paths(root, dst, k)), (step, what, dst, k)
        moved = {k: SPF_COUNTERS[k] - before[k] for k in SPF_COUNTERS}
        log, rows = watch.take()
        graph = engine.state.graph
        cold_all = spf_sparse.ell_all_sources(graph)
        # (b) the matrix behind the window is the current graph's
        held = np.asarray(engine.d_prev_dev)
        assert held.shape == (graph.n_pad, graph.n_pad)
        assert np.array_equal(held, cold_all), (step, what)
        assert engine._matrix_due is None
        (sync,) = [s for s in spans if s.name == "decision.ksp2_sync"]
        waited = [s for s in spans if s.name == "ops.ksp2_all_pairs"]
        if sync.attrs["cold"]:
            assert what == "storm"
            assert moved["decision.ksp2_cold_builds"] == 1
            assert moved["decision.ksp2_incremental_syncs"] == 0
            assert moved["decision.ksp2_matrix_deferred"] == 0
            seen.add("cold")
            continue
        assert what != "storm"
        # one incremental sync: one rows solve waited for, one matrix
        # solve sent behind it, counted once
        assert moved["decision.ksp2_incremental_syncs"] == 1
        assert moved["decision.ksp2_matrix_deferred"] == 1
        assert 0 <= moved["decision.ksp2_matrix_unready"] <= 1
        (waited,) = waited
        b = len(engine._view.srcs)
        assert waited.attrs["rows"] == b + ksp2_engine.ENGINE_MAX_ENDPOINTS
        assert waited.attrs["passes"] == reg.counter_get(
            "ops.ksp2.all_pairs_passes") - rows_passes
        # (a) the rows it waited for are the fused program's, and so is
        # the matrix it kept
        ((packed, want_packed, want_d),) = rows
        packed = np.asarray(packed)
        assert packed.shape == want_packed.shape == (
            2 * b + 2 * ksp2_engine.ENGINE_MAX_ENDPOINTS, graph.n_pad)
        assert np.array_equal(packed, want_packed), (step, what)
        assert np.array_equal(held, want_d), (step, what)
        # the order: rows, the masked batches, the matrix behind the
        # last of them, and only then the host work that ends the sync
        assert log[0] == "rows" and log.count("rows") == 1
        assert log.count("matrix") == 1 and log.count("prime") == 1
        at = log.index("matrix")
        assert all(x == "masked" for x in log[1:at])
        assert log[at + 1:] == ["prime"]
        masked = [s for s in spans if s.name == "ops.ksp2_masked_solve"]
        assert bool(masked) == (at > 1)
        if what in ("drain", "undrain"):
            # a drain flip forces the cold seed in both programs, and
            # its refresh sends the window's last masked batch
            assert moved["decision.ksp2_warm_dispatches"] == 0
            assert any(s.attrs.get("refresh") for s in masked)
        else:
            assert moved["decision.ksp2_warm_dispatches"] == 1
        if what == "relabel":
            # no link changed: nothing for the proof to answer for, so
            # a refresh, and the matrix behind it
            assert sync.attrs["changed_pairs"] == 0
            assert [s.attrs.get("refresh") for s in masked] == [True]
        seen.add(what if not masked else what + "+masked")
    # every branch was taken: a cold build; the matrix behind a
    # recompute's batch, behind a refresh, and at the sync's end in a
    # window that named no destination and sent no masked batch
    assert {"cold", "relabel+masked", "drain+masked",
            "undrain+masked"} <= seen
    assert any(s.startswith("recost-up+") for s in seen)
    assert any(s.startswith("flap-") for s in seen)
    assert any("+" not in s for s in seen - {"cold"})
    # every matrix solve's pass count was booked, none waited for: the
    # last one lands with the matrix the test just read
    engine._book_matrix_passes()
    assert engine._matrix_passes == []
    assert reg.counter_get("ops.ksp2.matrix_passes") > 0


# -- a raise on either side of the matrix dispatch ------------------------


@pytest.mark.parametrize("site", ["after-the-rows-reap", "in-the-dispatch"])
@pytest.mark.parametrize("kind", sorted(_NETWORKS))
def test_a_raise_around_the_matrix_dispatch_leaves_a_cold_build_that_works(
        kind, site, monkeypatch):
    topo, root, ls, _ps = _network(kind)
    nodes = sorted(topo.adj_dbs)
    dsts = [n for n in nodes if n != root]
    engine = ksp2_engine.Ksp2Engine(root)
    assert engine.sync(ls, dsts) is None
    far = dsts[-1]
    _recost(far, 3)(ls)
    assert engine.sync(ls, dsts) is not None
    live = engine.d_prev_dev

    class Boom(RuntimeError):
        pass

    def boom(*args, **kwargs):
        raise Boom(site)

    _recost(far, 5)(ls)
    with monkeypatch.context() as m:
        if site == "after-the-rows-reap":
            # the rows are on the host, the matrix solve is still owed
            m.setattr(ksp2_engine.Ksp2Engine, "_affected_dsts", boom)
        else:
            m.setattr(spf_sparse, "ell_all_view_rows", boom)
        with pytest.raises(Boom):
            engine.sync(ls, dsts)
    # torn: never a donated buffer for the cold build to reuse
    assert not engine.valid
    assert engine.d_prev_dev is None and engine._matrix_due is None
    if site == "after-the-rows-reap":
        # nothing consumed the previous epoch's matrix
        assert not live.is_deleted()
    cold = SPF_COUNTERS["decision.ksp2_cold_builds"]
    assert engine.sync(ls, dsts) is None
    assert engine.valid
    assert SPF_COUNTERS["decision.ksp2_cold_builds"] == cold + 1
    graph = engine.state.graph
    assert np.array_equal(
        np.asarray(engine.d_prev_dev), spf_sparse.ell_all_sources(graph))
    # and the engine steps on from it as from any cold build
    _recost(far, 2)(ls)
    deferred = SPF_COUNTERS["decision.ksp2_matrix_deferred"]
    assert engine.sync(ls, dsts) is not None
    assert SPF_COUNTERS["decision.ksp2_matrix_deferred"] == deferred + 1
    assert np.array_equal(
        np.asarray(engine.d_prev_dev),
        spf_sparse.ell_all_sources(engine.state.graph))
    host = LinkState(area=topo.area)
    for name in nodes:
        host.update_adjacency_database(ls.get_adjacency_databases()[name])
    for dst in dsts:
        for k in (1, 2):
            assert _names(ls.get_kth_paths(root, dst, k)) == _names(
                host.get_kth_paths(root, dst, k)), (dst, k)


# -- the counter a matrix still running moves -----------------------------


def test_a_sync_that_finds_the_matrix_still_running_says_so(monkeypatch):
    """``decision.ksp2_matrix_unready``: read once a sync off
    ``d_prev_dev.is_ready()``, before the rows dispatch."""
    topo, root, ls, _ps = _network("grid")
    dsts = [n for n in sorted(topo.adj_dbs) if n != root]
    engine = ksp2_engine.Ksp2Engine(root)
    assert engine.sync(ls, dsts) is None
    real = spf_sparse.ell_view_ep_rows

    class NotYet:
        """The held matrix, as a future that has not landed."""

        def __init__(self, arr):
            self.arr, self.asked = arr, 0

        def is_ready(self):
            self.asked += 1
            return False

    def rows(state, view_srcs, w_sv, ep_ids, d_prev, **kwargs):
        return real(state, view_srcs, w_sv, ep_ids,
                    getattr(d_prev, "arr", d_prev), **kwargs)

    monkeypatch.setattr(spf_sparse, "ell_view_ep_rows", rows)
    real_dispatch = ksp2_engine.Ksp2Engine._dispatch_matrix

    def dispatch(eng, span=None):
        if isinstance(eng.d_prev_dev, NotYet):
            eng.d_prev_dev = eng.d_prev_dev.arr
        return real_dispatch(eng, span)

    monkeypatch.setattr(ksp2_engine.Ksp2Engine, "_dispatch_matrix", dispatch)
    counts = []
    for metric, ready in ((3, False), (4, True), (5, False)):
        _recost(dsts[-1], metric)(ls)
        if ready:
            jax.block_until_ready(engine.d_prev_dev)
        else:
            engine.d_prev_dev = held = NotYet(engine.d_prev_dev)
        before = SPF_COUNTERS["decision.ksp2_matrix_unready"]
        assert engine.sync(ls, dsts) is not None
        counts.append(SPF_COUNTERS["decision.ksp2_matrix_unready"] - before)
        if not ready:
            assert held.asked == 1
    assert counts == [1, 0, 1]
    assert np.array_equal(
        np.asarray(engine.d_prev_dev),
        spf_sparse.ell_all_sources(engine.state.graph))
