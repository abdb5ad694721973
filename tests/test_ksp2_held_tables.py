"""The KSP2 engine's held tables: what a warm sync patches from the
window's change instead of deriving it from every node again.

``Ksp2Engine`` keeps, from sync to sync, the flat candidate CSR of the
native tracer (``_TraceArrays``), the map it primes LinkState's
kth-path cache from, the overload map with its transit-blocked set, the
vantage's view batch on the device and the destinations' id arrays. A
sync brings each to the new LinkState from ``affected_nodes`` /
``changed`` / ``ov_flips``; a cold build makes them whole. These tests
hold the patched tables to freshly built ones after every step of a
stream of events (equality), and the work of a warm sync to the
window's size (counts). Never a time: the CPU backend's says nothing.
"""

from __future__ import annotations

import random
from dataclasses import replace

import numpy as np
import pytest

from openr_tpu.decision import ksp2_engine
from openr_tpu.decision.spf_solver import SPF_COUNTERS
from openr_tpu.graph import native_spf
from openr_tpu.graph.linkstate import LinkState
from openr_tpu.models import topologies
from openr_tpu.ops import spf_sparse
from openr_tpu.telemetry import get_tracer
from openr_tpu.types.lsdb import (
    PrefixForwardingAlgorithm,
    PrefixForwardingType,
)

_KSP2 = dict(
    forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
    forwarding_type=PrefixForwardingType.SR_MPLS,
)


def _lag_edges():
    """Leaf/spine where every pair is a two-member LAG: parallel links,
    which the walk-reach proof does not answer for."""
    return [
        (f"leaf-{leaf}", f"spine-{spine}", metric)
        for leaf in range(4) for spine in range(3) for metric in (1, 2)
    ]


# kind -> (topology, vantage)
_NETWORKS = {
    "fabric": lambda: (topologies.fat_tree_nodes(60, **_KSP2), "rsw-0-0"),
    "grid": lambda: (topologies.grid(6, **_KSP2), "node-0"),
    "lag": lambda: (
        topologies.build_topology("lag-fabric", _lag_edges(), **_KSP2),
        "leaf-0",
    ),
}


def _engine(topo, root):
    """A cold-built engine at ``root`` over a LinkState of ``topo``."""
    ls = LinkState(area=topo.area)
    for name in sorted(topo.adj_dbs):
        ls.update_adjacency_database(topo.adj_dbs[name])
    dsts = [name for name in sorted(topo.adj_dbs) if name != root]
    engine = ksp2_engine.Ksp2Engine(root)
    assert engine.sync(ls, dsts) is None
    return engine, ls, dsts


def _sync(engine, ls, dsts):
    """One sync under an active trace: (what it returned, the
    ``decision.ksp2_sync`` spans it opened)."""
    tracer = get_tracer()
    trace = tracer.start()
    tracer.activate(trace)
    try:
        affected = engine.sync(ls, dsts)
    finally:
        tracer.deactivate()
        tracer.finish(trace)
    return affected, [
        s for s in trace.spans if s.name == "decision.ksp2_sync"
    ]


# -- events: each returns the steps (thunks) it is made of ----------------


def _adjs(ls, node):
    return list(ls.get_adjacency_databases()[node].adjacencies)


def _publish(ls, node, **changes):
    db = ls.get_adjacency_databases()[node]
    ls.update_adjacency_database(replace(db, **changes))


def _metric(ls, rng, nodes, root):
    node = rng.choice(nodes)
    adjs = _adjs(ls, node)
    i = rng.randrange(len(adjs))
    adjs[i] = replace(adjs[i], metric=1 + adjs[i].metric % 9)
    return [lambda: _publish(ls, node, adjacencies=tuple(adjs))]


def _flap(ls, rng, nodes, root):
    """Down, then up again: a row that gets shorter, then longer (and
    the link back under a fresh, equal object)."""
    node = rng.choice([
        n for n in nodes if n != root and len(_adjs(ls, n)) >= 2
    ])
    adjs = _adjs(ls, node)
    kept = [
        a for a in adjs if a.other_node_name != root
    ]
    gone = rng.choice(kept)
    without = tuple(a for a in adjs if a is not gone)
    return [
        lambda: _publish(ls, node, adjacencies=without),
        lambda: _publish(ls, node, adjacencies=without + (gone,)),
    ]


def _drain(ls, rng, nodes, root):
    node = rng.choice([n for n in nodes if n != root])
    return [
        lambda: _publish(ls, node, is_overloaded=True),
        lambda: _publish(ls, node, is_overloaded=False),
    ]


def _label(ls, rng, nodes, root):
    node = rng.choice(nodes)
    label = ls.get_adjacency_databases()[node].node_label
    return [lambda: _publish(ls, node, node_label=label + 5000)]


def _node_metric(ls, rng, nodes, root):
    """A node re-costs every one of its links (the grid cell's event):
    several rows of unchanged length in one window."""
    node = rng.choice(nodes)
    adjs = tuple(
        replace(a, metric=1 + a.metric % 9) for a in _adjs(ls, node)
    )
    return [lambda: _publish(ls, node, adjacencies=adjs)]


_EVENTS = {
    "metric": _metric,
    "flap": _flap,
    "drain": _drain,
    "label": _label,
    "node-metric": _node_metric,
}


def _stream(ls, rng, nodes, root, event):
    """Steps of ``event`` (or, "mixed", of all of them interleaved so
    that a flap's or a drain's second half lands among other events)."""
    if event != "mixed":
        while True:
            yield from _EVENTS[event](ls, rng, nodes, root)
    pending = []
    while True:
        kind = rng.choice(sorted(_EVENTS))
        steps = _EVENTS[kind](ls, rng, nodes, root)
        yield steps[0]
        pending.extend(steps[1:])
        if pending and rng.random() < 0.5:
            yield pending.pop(0)


# -- the references --------------------------------------------------------


def _fresh_trace_arrays(engine, ls):
    graph = engine.state.graph
    return ksp2_engine._TraceArrays(
        graph, ksp2_engine.make_cands_of(ls, graph.node_index),
        {
            name for name in graph.node_names
            if ls.is_node_overloaded(name) and name != engine.src_name
        },
    )


def _assert_trace_arrays_fresh(engine, ls):
    """(a): the engine's arrays, brought to ``ls`` the way a trace
    brings them (patched from the journals), are what a fresh build
    over ``ls`` gives: offsets, origins, weights and the blocked bitmap
    byte for byte, and place for place the same live Link (link ids
    are handed out in the order links were first seen, so they are
    compared through the link table)."""
    graph = engine.state.graph
    held = engine._trace_arrays(
        ls, graph, ksp2_engine.make_cands_of(ls, graph.node_index),
        engine._blocked,
    )
    fresh = _fresh_trace_arrays(engine, ls)
    for name in ("off", "uid", "w", "blocked", "link"):
        got, want = getattr(held, name), getattr(fresh, name)
        assert got.dtype == want.dtype, name
        assert got.flags["C_CONTIGUOUS"], name
        if name != "link":
            assert got.tobytes() == want.tobytes(), name
    assert len(held.link) == len(fresh.link)
    for got, want in zip(held.link.tolist(), fresh.link.tolist()):
        assert held.links[got] is fresh.links[want]
    for i in range(len(graph.node_names)):
        for got, want in zip(held.rows_of(i), fresh.rows_of(i)):
            assert len(got) == len(want)
        lo, hi = int(held.off[i]), int(held.off[i + 1])
        assert held.uid[lo:hi].tolist() == held.rows_of(i)[1].tolist()
        assert held.w[lo:hi].tolist() == held.rows_of(i)[2].tolist()
        assert held.link[lo:hi].tolist() == held.rows_of(i)[0].tolist()


def _primed_by_the_loop(engine):
    """(b)'s reference: what the per-destination ``_prime_all`` wrote
    into LinkState's kth-path cache, a call a destination and rank."""
    want = {}
    for dst in engine.dsts:
        if dst in engine.host_dsts:
            continue  # LinkState computes these lazily (host SPF)
        want[(engine.src_name, dst, 1)] = engine.first_paths[dst]
        want[(engine.src_name, dst, 2)] = engine.second_paths.get(dst, [])
    return want


def _assert_primed(engine, ls, topology_moved):
    want = _primed_by_the_loop(engine)
    cache = ls._kth_path_cache
    assert {key: cache.get(key) for key in want} == want
    for (src, dst, k), paths in want.items():
        if k == 1 or dst in engine.second_paths:
            assert cache[(src, dst, k)] is paths, (dst, k)
    assert engine._primed == want
    if topology_moved:
        # the change emptied the cache and nobody primes these
        for dst in engine.host_dsts:
            assert (engine.src_name, dst, 1) not in cache
            assert (engine.src_name, dst, 2) not in cache


# -- (a) + (b): equality after every step of a stream ---------------------


@pytest.mark.parametrize(
    "event", ["metric", "flap", "drain", "label", "node-metric", "mixed"]
)
@pytest.mark.parametrize("kind", ["fabric", "grid", "lag"])
def test_patched_tables_equal_fresh_ones_after_every_step(kind, event):
    if not native_spf.is_available():
        pytest.skip("native core unavailable")
    topo, root = _NETWORKS[kind]()
    engine, ls, dsts = _engine(topo, root)
    _assert_trace_arrays_fresh(engine, ls)
    _assert_primed(engine, ls, True)
    nodes = sorted(topo.adj_dbs)
    rng = random.Random(f"{kind}/{event}")
    reflattens = SPF_COUNTERS["decision.ksp2_trace_reflattens"]
    cold = SPF_COUNTERS["decision.ksp2_cold_builds"]
    steps = _stream(ls, rng, nodes, root, event)
    patched = spliced = 0
    for _ in range(24 if event == "mixed" else 12):
        version = ls.topology_version
        next(steps)()
        affected, spans = _sync(engine, ls, dsts)
        assert engine.valid
        moved = ls.topology_version != version
        _assert_primed(engine, ls, moved)
        _assert_trace_arrays_fresh(engine, ls)
        for span in spans:
            if not span.attrs["cold"]:
                patched += span.attrs["trace_rows_patched"]
                spliced += span.attrs["trace_rows_spliced"]
                assert span.attrs["view_reused"] in (0, 1)
    if SPF_COUNTERS["decision.ksp2_cold_builds"] == cold:
        # no cold build: every row came into the flat arrays in place
        # or by a splice, none by flattening them anew
        assert (
            SPF_COUNTERS["decision.ksp2_trace_reflattens"] == reflattens
        )
        if event in ("metric", "node-metric"):
            assert patched > 0 and spliced == 0
        if event == "flap":
            assert spliced > 0


# -- (c): a warm sync's work is the window's ------------------------------


@pytest.fixture(scope="module")
def fabric_1016():
    """The 1,016-node fabric of ``fabric-1000-ksp2`` (13 pods of 8 FSW
    and 48 RSW, 36 SSW a plane) with an engine at its first RSW."""
    topo = topologies.fat_tree(
        13, ssw_per_plane=36, fsw_per_pod=8, rsw_per_pod=48, **_KSP2
    )
    assert len(topo.adj_dbs) == 1016
    return _engine(topo, "rsw-0-0")


class _Calls:
    """Counts calls of LinkState methods (patched on the class)."""

    def __init__(self, monkeypatch, *names):
        self.count = {name: 0 for name in names}
        for name in names:
            monkeypatch.setattr(
                LinkState, name, self._counting(name, getattr(LinkState, name))
            )

    def _counting(self, name, real):
        def counted(ls, *args, **kwargs):
            self.count[name] += 1
            return real(ls, *args, **kwargs)

        return counted


def test_a_warm_sync_of_one_metric_change_does_the_windows_work(
        fabric_1016, monkeypatch):
    engine, ls, dsts = fabric_1016
    if not native_spf.is_available():
        pytest.skip("native core unavailable")
    far = "rsw-7-3"  # another pod: not the vantage's neighbourhood
    for metric in (2, 3):  # warm: every shape and bucket exists
        adjs = _adjs(ls, far)
        adjs[0] = replace(adjs[0], metric=metric)
        _publish(ls, far, adjacencies=tuple(adjs))
        assert engine.sync(ls, dsts) is not None
    adjs = _adjs(ls, far)
    adjs[0] = replace(adjs[0], metric=4)
    _publish(ls, far, adjacencies=tuple(adjs))
    affected_nodes = ls.affected_since(engine.version)
    assert affected_nodes == {far, adjs[0].other_node_name}
    before = dict(SPF_COUNTERS)
    view = engine._view
    with monkeypatch.context() as m:
        calls = _Calls(
            m, "is_node_overloaded", "prime_kth_paths",
            "prime_kth_paths_bulk", "affected_since",
            "attr_affected_since",
        )
        affected, (span,) = _sync(engine, ls, dsts)
    assert affected is not None and not span.attrs["cold"]
    # the resident bands' patch and the engine's flip test ask once a
    # node each; nothing asks every node
    assert calls.count["is_node_overloaded"] <= 4 * len(affected_nodes)
    assert calls.count["prime_kth_paths"] == 0
    assert calls.count["prime_kth_paths_bulk"] == 1
    # the engine reads each journal once (the trace arrays take its
    # reading); the resident bands read the topology journal for theirs
    assert calls.count["affected_since"] <= 2
    assert calls.count["attr_affected_since"] == 1
    assert span.attrs["trace_rows_patched"] == len(affected_nodes)
    assert span.attrs["trace_rows_spliced"] == 0
    assert span.attrs["view_reused"] == 1
    assert engine._view is view
    for name in ("decision.ksp2_trace_reflattens",
                 "decision.ksp2_cold_builds"):
        assert SPF_COUNTERS[name] == before[name], name
    assert SPF_COUNTERS["decision.ksp2_incremental_syncs"] == (
        before["decision.ksp2_incremental_syncs"] + 1
    )
    _assert_primed(engine, ls, True)
    _assert_trace_arrays_fresh(engine, ls)


# -- (d): what does re-derive the blocked set and the view batch ----------


def _view_is_fresh(engine, ls):
    graph = engine.state.graph
    srcs = spf_sparse.ell_source_batch(graph, ls, engine.src_name)
    srcs_dev, w_sv = spf_sparse._batch_args(graph, srcs)
    held = engine._view
    assert held.index is graph.node_index and held.srcs == srcs
    assert np.array_equal(np.asarray(held.srcs_dev), np.asarray(srcs_dev))
    assert np.array_equal(np.asarray(held.w_sv_dev), np.asarray(w_sv))
    assert held.near == {graph.node_names[i] for i in srcs}


@pytest.mark.parametrize("kind", ["fabric", "grid"])
def test_a_drain_flip_rederives_the_blocked_set(kind):
    if not native_spf.is_available():
        pytest.skip("native core unavailable")
    topo, root = _NETWORKS[kind]()
    engine, ls, dsts = _engine(topo, root)
    assert engine._blocked == set()
    arrays = engine._tarrays[1]
    assert not arrays.blocked.any()
    near = engine._view.near
    node = next(n for n in sorted(topo.adj_dbs) if n not in near)
    index = engine.state.graph.node_index
    _publish(ls, node, is_overloaded=True)
    _, (span,) = _sync(engine, ls, dsts)
    assert not span.attrs["cold"] and span.attrs["view_reused"] == 1
    assert engine._blocked == {node} and engine.ov[node] is True
    assert engine._tarrays[1] is arrays
    assert np.flatnonzero(arrays.blocked).tolist() == [index[node]]
    was = arrays.blocked
    # a window with no flip leaves the bitmap the object it was
    adjs = _adjs(ls, node)
    adjs[0] = replace(adjs[0], metric=7)
    _publish(ls, node, adjacencies=tuple(adjs))
    _sync(engine, ls, dsts)
    assert arrays.blocked is was and engine._blocked == {node}
    _publish(ls, node, is_overloaded=False)
    _, (span,) = _sync(engine, ls, dsts)
    assert not span.attrs["cold"]
    assert engine._blocked == set() and engine.ov[node] is False
    assert not arrays.blocked.any()
    _assert_trace_arrays_fresh(engine, ls)
    _assert_primed(engine, ls, True)


@pytest.mark.parametrize("kind", ["fabric", "grid"])
def test_an_event_at_the_vantage_rederives_the_view_batch(kind):
    topo, root = _NETWORKS[kind]()
    engine, ls, dsts = _engine(topo, root)
    _view_is_fresh(engine, ls)
    held = engine._view
    # far from the vantage: the held device arrays go as they are
    near = held.near
    far = next(n for n in reversed(sorted(topo.adj_dbs)) if n not in near)
    adjs = _adjs(ls, far)
    i = next(
        i for i, a in enumerate(adjs) if a.other_node_name not in near
    )
    adjs[i] = replace(adjs[i], metric=6)
    _publish(ls, far, adjacencies=tuple(adjs))
    _, (span,) = _sync(engine, ls, dsts)
    assert span.attrs["view_reused"] == 1 and engine._view is held
    # one of the vantage's own links: its direct metric is in the batch
    adjs = _adjs(ls, root)
    adjs[0] = replace(adjs[0], metric=5)
    _publish(ls, root, adjacencies=tuple(adjs))
    _, (span,) = _sync(engine, ls, dsts)
    assert not span.attrs["cold"] and span.attrs["view_reused"] == 0
    assert engine._view is not held
    _view_is_fresh(engine, ls)
    assert 5 in np.asarray(engine._view.w_sv_dev).tolist()
    # a neighbour's other link: re-derived too (the journals name the
    # neighbour), and equal to what it was
    held = engine._view
    nbr = adjs[0].other_node_name
    adjs = _adjs(ls, nbr)
    i = next(i for i, a in enumerate(adjs) if a.other_node_name != root)
    adjs[i] = replace(adjs[i], metric=3)
    _publish(ls, nbr, adjacencies=tuple(adjs))
    _, (span,) = _sync(engine, ls, dsts)
    assert span.attrs["view_reused"] == 0
    _view_is_fresh(engine, ls)
    assert engine._view.srcs == held.srcs


def test_a_sync_that_raises_drops_the_held_tables(monkeypatch):
    topo, root = _NETWORKS["fabric"]()
    engine, ls, dsts = _engine(topo, root)
    assert engine._primed and engine._view and engine._blocked is not None
    adjs = _adjs(ls, dsts[0])
    adjs[0] = replace(adjs[0], metric=9)
    _publish(ls, dsts[0], adjacencies=tuple(adjs))

    def torn(self, ls_):
        raise RuntimeError("torn")

    with monkeypatch.context() as m:
        m.setattr(ksp2_engine.Ksp2Engine, "_prime_all", torn)
        with pytest.raises(RuntimeError):
            engine.sync(ls, dsts)
    assert not engine.valid
    assert engine._primed == {} and engine._view is None
    assert engine._blocked is None
    assert engine.sync(ls, dsts) is None and engine.valid
    _assert_primed(engine, ls, True)
    _view_is_fresh(engine, ls)
    if native_spf.is_available():
        _assert_trace_arrays_fresh(engine, ls)


def test_bulk_priming_is_one_update_of_the_cache():
    ls = LinkState(area="0")
    ls.prime_kth_paths("a", "b", 1, ["kept"])
    paths = {("a", "b", 2): [], ("a", "c", 1): ["p"]}
    ls.prime_kth_paths_bulk(paths)
    assert ls._kth_path_cache == {("a", "b", 1): ["kept"], **paths}
    assert ls.get_kth_paths("a", "c", 1) is paths[("a", "c", 1)]


def _affected_since_by_the_whole_walk(journal, current, version):
    """The reference: every entry of the journal visited, oldest
    first, as ``LinkState._affected_since`` did."""
    if version == current:
        return set()
    if not journal or journal[0][0] > version + 1:
        return None
    affected = set()
    for v, nodes in journal:
        if v <= version:
            continue
        if not nodes:
            return None
        affected |= nodes
    return affected


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_journal_walk_stops_where_the_version_is(seed):
    """Newest first and only as far back as the version asked for:
    the same answer as the whole walk, evicted history and a change
    of unrecorded blast radius included."""
    from collections import deque

    rng = random.Random(seed)
    journal = deque(maxlen=16)
    for v in range(1, 41):
        nodes = frozenset(
            rng.sample("abcdefgh", rng.randrange(1, 4))
            if rng.random() < 0.9 else ()
        )
        journal.append((v, nodes))
        for version in range(0, v + 1):
            assert LinkState._affected_since(journal, v, version) == (
                _affected_since_by_the_whole_walk(journal, v, version)
            ), (v, version)
    assert LinkState._affected_since(deque(), 3, 1) is None
