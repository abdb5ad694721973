"""``KSP2_ED_ECMP`` over ``SR_MPLS`` through the normal path, on the CPU
at a size the KSP2 engine takes: a 3-pod fabric of 56 nodes, every
prefix KSP2, solved from ``rsw-0-0`` (55 destinations,
``KSP2_DEVICE_MIN_DSTS`` = 32).

After every burst of ``fabric-1000-ksp2.adj-churn``'s events (a metric
change, a link flap) the device backend's ``RouteDatabase`` — unicast
next hops with their label stacks, and the node-label MPLS routes —
equals the plain reference of ``chipbench/reference_ksp2.py`` and is
bit-identical to ``solver_backend=host``. Both of the engine's branches
are taken (an incremental sync, and the cold build a window of many
events overflows into), and a rebuild's trace holds the spans the
per-layer metrics read, nested and closed. Counts, never times: this is
the CPU.
"""

from __future__ import annotations

import pytest

from chipbench import reference_ksp2, topology, traffic
from openr_tpu.decision import ksp2_engine
from openr_tpu.decision.decision import Decision
from openr_tpu.decision.spf_solver import KSP2_DEVICE_MIN_DSTS, SPF_COUNTERS
from openr_tpu.messaging.queue import ReplicateQueue
from openr_tpu.telemetry import get_tracer
from openr_tpu.types import Publication
from openr_tpu.utils import wire

VANTAGE = "rsw-0-0"
KSP2 = {"algorithm": "KSP2_ED_ECMP", "type": "SR_MPLS"}
MIX = {"kinds": {"metric": 0.8, "flap": 0.2}}
# 60 events in the bursts one rebuild window carries; the window of 40
# touches more endpoints than ENGINE_MAX_ENDPOINTS and goes cold
BURSTS = (1, 1, 2, 1, 3, 1, 40, 1, 2, 1, 4, 1, 2)
# (a sync whose tests name no destination solves and traces nothing)
ENGINE_SPANS = (
    "decision.ksp2_sync", "ops.ksp2_all_pairs", "decision.ksp2_routes",
)


@pytest.fixture(scope="module")
def fabric():
    topo = topology.build({
        "kind": "fat_tree", "pods": 3, "ssw_per_plane": 2,
        "fsw_per_pod": 4, "rsw_per_pod": 12}, KSP2)
    assert len(topo.adj_dbs) - 1 >= KSP2_DEVICE_MIN_DSTS + 1
    return topo


def _decision(backend: str, vantage: str = VANTAGE):
    kv_q = ReplicateQueue(name=f"{backend}:kvstore")
    return kv_q, Decision(
        vantage,
        kvstore_updates_queue=kv_q,
        route_updates_queue=ReplicateQueue(name=f"{backend}:routes"),
        solver_backend=backend,
    )


def _inside(inner, outer) -> bool:
    return (outer.ts_ms <= inner.ts_ms
            and inner.ts_ms + inner.dur_ms <= outer.ts_ms + outer.dur_ms + 0.5)


@pytest.mark.parametrize("seed", [7, 4294967311])
def test_routes_equal_reference_and_host_after_every_burst(fabric, seed):
    assert sum(BURSTS) == 60
    gen = traffic.Generator(fabric, seed, MIX, VANTAGE)
    initial = gen.initial_key_vals()
    queues, sides = zip(*(_decision(b) for b in ("device", "host")))
    tracer = get_tracer()
    traces, kinds = [], set()
    try:
        for d in sides:
            d.process_publication(Publication(key_vals=dict(initial), area="0"))
            d.rebuild_routes("LOAD")
        before = dict(SPF_COUNTERS)
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
            traces.append(trace)
            live, host = (d.route_db.to_route_db(VANTAGE) for d in sides)
            assert reference_ksp2.routes_of(live) == reference_ksp2.routes(
                gen.adj_dbs, gen.prefix_dbs, VANTAGE)
            assert reference_ksp2.mpls_routes_of(live) \
                == reference_ksp2.mpls_routes(gen.adj_dbs, VANTAGE)
            assert wire.dumps(live) == wire.dumps(host)
        assert kinds == {"metric", "flap"}
    finally:
        for q in queues:
            q.close()

    moved = {k: SPF_COUNTERS[k] - before.get(k, 0) for k in SPF_COUNTERS}
    # both branches: a window syncs incrementally unless it touches more
    # endpoints than ENGINE_MAX_ENDPOINTS (the window of 40), and then
    # rebuilds cold; no destination went to the host
    assert moved["decision.ksp2_incremental_syncs"] >= len(BURSTS) - 3
    assert moved["decision.ksp2_cold_builds"] >= 1
    assert moved["decision.ksp2_incremental_syncs"] \
        + moved["decision.ksp2_cold_builds"] == len(BURSTS)
    assert moved["decision.ksp2_host_fallbacks"] == 0
    assert moved["decision.ksp2_device_batches"] >= 1
    assert 0 < moved["decision.ksp2_affected_dsts"] \
        < 55 * moved["decision.ksp2_incremental_syncs"]

    cold = 0
    for trace, burst in zip(traces, BURSTS):
        assert trace.well_formed()
        by_name = {}
        for s in trace.spans:
            assert s.closed
            by_name.setdefault(s.name, []).append(s)
        for name in ENGINE_SPANS:
            assert name in by_name, (name, burst)
        (build,) = by_name["decision.route_build"]
        (sync,) = by_name["decision.ksp2_sync"]
        (routes,) = by_name["decision.ksp2_routes"]
        assert _inside(sync, build) and _inside(routes, build)
        assert routes.ts_ms >= sync.ts_ms + sync.dur_ms - 0.5
        assert set(sync.attrs) >= {"changed_pairs", "affected", "cold"}
        assert 0 <= sync.attrs["affected"] <= 55
        if sync.attrs["cold"]:
            cold += 1
            assert sync.attrs["affected"] == 55
        else:
            assert 1 <= sync.attrs["changed_pairs"] \
                <= ksp2_engine.ENGINE_MAX_CHANGED_PAIRS
        # one dispatch the sync waits for; a sync that names too many
        # destinations only after it falls to the cold build, which
        # dispatches again
        assert len(by_name["ops.ksp2_all_pairs"]) <= 1 + sync.attrs["cold"]
        for s in by_name["ops.ksp2_all_pairs"]:
            assert _inside(s, sync)
            # a cold build waits for the matrix (56 nodes, padded), an
            # incremental sync for the rows it reads: the view batch
            # (the root and its 4 uplinks, padded) and the endpoints
            assert s.attrs["rows"] == (
                128 if sync.attrs["cold"]
                else 8 + ksp2_engine.ENGINE_MAX_ENDPOINTS)
            assert s.attrs["batches"] == 1
        for s in by_name.get("decision.ksp2_trace", ()):
            assert _inside(s, sync)
            assert s.attrs["rank"] in (1, 2) and s.attrs["dsts"] >= 0
        for s in by_name.get("ops.ksp2_masked_solve", ()):
            assert _inside(s, sync)
            assert 1 <= s.attrs["rows"] <= 55 and s.attrs["batches"] >= 1
            # the second-rank traces of the batch nest in it, unless it
            # only keeps the rows exact (a window of several links)
            assert s.attrs.get("refresh") or any(
                _inside(t, s) and t.attrs["rank"] == 2
                for t in by_name["decision.ksp2_trace"])
        assert routes.attrs["prefixes"] >= 55
        assert 0 <= routes.attrs["reused"] <= routes.attrs["prefixes"]
        if not sync.attrs["cold"]:
            # what the engine did not name is served from the cache
            assert routes.attrs["reused"] >= 55 - sync.attrs["affected"]
    assert cold == moved["decision.ksp2_cold_builds"]
    # an event that moves first paths re-solves them with fresh masks
    # and re-traces both ranks
    assert any(
        s.name == "ops.ksp2_masked_solve" for t in traces for s in t.spans)
    assert {s.attrs["rank"] for t in traces for s in t.spans
            if s.name == "decision.ksp2_trace"} == {1, 2}


@pytest.mark.parametrize("seed", [39, 2390000039])
def test_the_engine_serves_a_grid_22_hops_deep_event_by_event(seed):
    """Past the hop gate the solver had until PR 39 (16): a 12 x 12
    grid solved from its corner, 22 hops to the far one, under
    ``grid-1000-ksp2.drain-churn``'s events, one a window. The engine
    is built at the load and stays; after every event the routes are
    the plain reference's and ``solver_backend=host``'s to the byte;
    and the spans say what ran: a masked batch, which starts cold, at
    least the corner's eccentricity in passes; a window of several
    links (a node re-costs two to four) proven edge by edge, so that
    no row is solved again only to keep it exact; and a drained node,
    which the proof does not answer for, the rows the sync refreshed."""
    from dataclasses import replace

    from chipbench.served_paths import pipeline_grid  # noqa: F401 - grid

    corner, events = "node-0", 24
    grid = topology.build({"kind": "grid", "n": 12}, KSP2)
    gen = traffic.Generator(
        grid, seed, {"kinds": {"node-metric": 0.8, "flap": 0.2}}, corner)
    tracer = get_tracer()
    queues, sides = zip(*(_decision(b, corner) for b in ("device", "host")))
    spans, kinds = [], set()
    try:
        for d in sides:
            d.process_publication(Publication(
                key_vals=dict(gen.initial_key_vals()), area="0"))
            d.rebuild_routes("LOAD")
        ls = sides[0].area_link_states["0"]
        depth = ls.get_max_hops_to_node(corner)
        assert depth == 22
        (engine,) = sides[0].spf_solver._ksp2_engines.values()
        assert engine.valid and engine.src_name == corner
        before = dict(SPF_COUNTERS)
        for _ in range(events):
            ev = gen.draw()
            kinds.add(ev.kind)
            for d in sides:
                d.process_publication(Publication(
                    key_vals={ev.key: ev.value}, area="0"))
            trace = tracer.start()
            sides[0].pending.adopt_trace(trace)
            for d in sides:
                d.rebuild_routes("EVENT")
            tracer.finish(trace)
            assert trace.well_formed()
            spans.append({})
            for s in trace.spans:
                spans[-1].setdefault(s.name, []).append(s)
            live, host = (d.route_db.to_route_db(corner) for d in sides)
            assert reference_ksp2.routes_of(live) == reference_ksp2.routes(
                gen.adj_dbs, gen.prefix_dbs, corner)
            assert reference_ksp2.mpls_routes_of(live) \
                == reference_ksp2.mpls_routes(gen.adj_dbs, corner)
            assert wire.dumps(live) == wire.dumps(host)
            # the one engine, resident
            assert list(sides[0].spf_solver._ksp2_engines.values()) \
                == [engine] and engine.valid
        # a node in the middle of the grid is drained: an overload flip
        # moves effective weights the raw metrics do not show, the
        # window is not proven and every row it did not name is solved
        # again (the plain reference does not cover a drained node:
        # held to the host backend alone)
        drained = replace(gen.adj_dbs["node-66"], is_overloaded=True)
        value = replace(
            gen.initial_key_vals()["adj:node-66"], version=10 ** 6,
            value=wire.dumps(drained))
        for d in sides:
            d.process_publication(Publication(
                key_vals={"adj:node-66": value}, area="0"))
        trace = tracer.start()
        sides[0].pending.adopt_trace(trace)
        for d in sides:
            d.rebuild_routes("DRAIN")
        tracer.finish(trace)
        live, host = (d.route_db.to_route_db(corner) for d in sides)
        assert wire.dumps(live) == wire.dumps(host)
        (drain_sync,) = [
            s for s in trace.spans if s.name == "decision.ksp2_sync"]
        assert not drain_sync.attrs["cold"]
        assert 0 < drain_sync.attrs["refreshed_rows"] <= 143
        assert any(s.name == "ops.ksp2_masked_solve" and s.attrs.get("refresh")
                   and s.attrs["passes"] >= depth for s in trace.spans)
    finally:
        for q in queues:
            q.close()
    assert kinds == {"node-metric", "flap"}
    moved = {k: SPF_COUNTERS[k] - before.get(k, 0) for k in SPF_COUNTERS}
    # every event, and the drain behind them
    assert moved["decision.ksp2_incremental_syncs"] == events + 1
    assert moved["decision.ksp2_cold_builds"] == 0
    assert moved["decision.ksp2_host_fallbacks"] == 0
    several = 0
    for by_name in spans:
        (sync,) = by_name["decision.ksp2_sync"]
        (fused,) = by_name["ops.ksp2_all_pairs"]
        # warm: from the pass that sees nothing change to, where a tight
        # edge got dearer and rows restart, the graph's diameter
        assert 1 <= fused.attrs["passes"] <= 144
        for s in by_name.get("ops.ksp2_masked_solve", ()):
            assert s.attrs["passes"] >= depth
        for s in by_name.get("decision.ksp2_trace", ()):
            assert 0 <= s.attrs["hops"] <= 143
        # proven, however many links the window changed
        assert sync.attrs["refreshed_rows"] == 0
        assert not any(s.attrs.get("refresh")
                       for s in by_name.get("ops.ksp2_masked_solve", ()))
        several += sync.attrs["changed_pairs"] > 2
    # a node of the grid has two to four links: most windows are not
    # one link's
    assert several >= events // 2
    assert max(s.attrs["hops"] for by_name in spans
               for s in by_name.get("decision.ksp2_trace", ())) >= depth


@pytest.mark.parametrize("seed, pods, rsws, windows", [
    (51, 3, 12, 240), (53, 3, 12, 240), (72, 4, 8, 400)])
def test_one_event_at_a_time_the_routes_stay_the_references(
        seed, pods, rsws, windows):
    """The engine's narrowest path: one link changes, the DAG tests name
    some destinations, ``_second_paths_may_move`` keeps those whose
    walks read the changed place of a candidate list, and only what
    moved is re-derived. Windows of 1 to 4 publications through
    Decision's own trigger, so the first is staged under the window and
    the rest join it; after each window the routes are the plain
    reference's. (Seed 51 caught places going
    stale when a link joined a list ahead of them; seed 72, on four
    pods, a masked row left stale at a node that mattered to no route
    until, 300 events on, it did.)"""
    from tools.soak_ksp2 import fabric_world, soak_cell

    out = soak_cell(seed, fabric_world(pods, rsws), windows)
    assert out["parity"] == "ok", out
    moved, dsts = out["moved"], out["dsts"]
    syncs = moved["decision.ksp2_incremental_syncs"]
    assert syncs >= windows - 10
    assert moved["decision.ksp2_host_fallbacks"] == 0
    # most windows move a handful of the destinations or none
    assert moved["decision.ksp2_affected_dsts"] < dsts * syncs // 6
    # ... and a good share of them solve nothing on the device again
    assert moved["decision.ksp2_device_batches"] < syncs
    # every window's first publication staged the engine's sync; the
    # rebuild found it at its version unless a second publication had
    # joined (15% of the windows), and then stepped on from it
    assert moved["ops.spec_dispatches"] == windows
    assert moved["ops.spec_hits"] + moved["ops.spec_cancels"] == windows
    assert windows // 12 < moved["ops.spec_cancels"] < windows // 4
    assert syncs + moved["decision.ksp2_cold_builds"] \
        == windows + moved["ops.spec_cancels"]


def test_a_soak_seed_of_the_grid_cell_22_hops_from_the_corner():
    """``tools/soak_ksp2.py --cell``'s third world: the events of
    ``grid-1000-ksp2.drain-churn`` on a 12 x 12 grid solved from its
    corner, past the hop gate the solver had until PR 39. A node
    re-costs two to four links at once, so nearly every window is one
    the walk-reach proof answers edge by edge (until PR 39 it answered
    for one link only and such a window re-solved every row it did not
    name): after every window the routes are the reference's and no
    masked row the engine keeps, re-solved or left, is stale."""
    from tools.soak_ksp2 import grid_world, soak_cell

    windows = 120
    out = soak_cell(57, grid_world(12), windows)
    assert out["parity"] == "ok", out
    moved = out["moved"]
    assert out["dsts"] == 143
    syncs = moved["decision.ksp2_incremental_syncs"]
    assert syncs >= windows - 10
    assert moved["decision.ksp2_host_fallbacks"] == 0
    assert moved["ops.spec_dispatches"] == windows
    assert moved["ops.spec_hits"] + moved["ops.spec_cancels"] == windows
    assert syncs + moved["decision.ksp2_cold_builds"] \
        == windows + moved["ops.spec_cancels"]
    # every masked batch starts cold, 22 hops from the corner; the warm
    # all-pairs fixed point runs at least the pass that sees no change
    assert moved["ops.ksp2.masked_passes"] \
        >= 22 * moved["decision.ksp2_device_batches"]
    assert moved["ops.ksp2.all_pairs_passes"] >= syncs
    # what is solved again is what the proof names, a handful, in one
    # batch a sync: no second batch that refreshes the rest
    assert moved["decision.ksp2_device_batches"] <= syncs
    assert moved["decision.ksp2_affected_dsts"] < out["dsts"] * syncs // 3


def test_settle_heap_reclaims_what_an_earlier_call_froze():
    """``gc.freeze`` alone keeps cyclic garbage for ever; thawing first
    is what lets the next call collect what has died since."""
    import gc
    import weakref

    from openr_tpu.telemetry import settle_heap

    class Node:
        pass

    a, b = Node(), Node()
    a.other, b.other = b, a  # a cycle: only the collector frees it
    gone = weakref.ref(a)
    try:
        settle_heap()
        assert gc.get_freeze_count() > 0 and gone() is not None
        del a, b
        gc.collect()
        assert gone() is not None, "frozen: out of the collector's reach"
        settle_heap()
        assert gone() is None
    finally:
        gc.unfreeze()


def test_repeated_cold_builds_do_not_pile_up_in_the_permanent_generation(
        fabric):
    """Decision settles the heap after a rebuild in which the engine
    built cold, and only then. Window after window of 80 events (more
    endpoints than ENGINE_MAX_ENDPOINTS: every one a cold build), what
    is frozen stays what is alive: each build's path lists replace the
    last build's, which the next settling collects."""
    import gc

    gen = traffic.Generator(fabric, 11, MIX, VANTAGE)
    kv_q, decision = _decision("device")
    frozen = []
    try:
        decision.process_publication(Publication(
            key_vals=dict(gen.initial_key_vals()), area="0"))
        decision.rebuild_routes("LOAD")
        assert gc.get_freeze_count() > 0
        before = dict(SPF_COUNTERS)
        # an incremental sync leaves the collector's policy alone
        gc.unfreeze()
        ev = gen.draw()
        decision.process_publication(Publication(
            key_vals={ev.key: ev.value}, area="0"))
        decision.rebuild_routes("EVENT")
        assert gc.get_freeze_count() == 0
        for _ in range(6):
            for _ in range(80):
                ev = gen.draw()
                decision.process_publication(Publication(
                    key_vals={ev.key: ev.value}, area="0"))
            decision.rebuild_routes("BURST")
            frozen.append(gc.get_freeze_count())
        assert SPF_COUNTERS["decision.ksp2_cold_builds"] \
            - before["decision.ksp2_cold_builds"] == 6
        assert SPF_COUNTERS["decision.ksp2_incremental_syncs"] \
            - before["decision.ksp2_incremental_syncs"] == 1
    finally:
        kv_q.close()
        gc.unfreeze()
    # what is frozen follows what is alive (the LSDB's own churn moves
    # it by a percent), not the number of builds; that a later call
    # does collect what an earlier one froze is the test above's
    assert max(frozen[1:]) - frozen[0] < frozen[0] // 20, frozen


def _publish(decision, *events, trace=None):
    """Each event through Decision's own trigger, as KvStore's queue
    would hand it over; the first carries the window's trace."""
    for ev in events:
        decision._on_publication(Publication(
            key_vals={ev.key: ev.value}, area="0", trace=trace))
        trace = None


def _loaded(fabric, seed, *backends):
    """A traffic generator and one Decision a backend, the initial LSDB
    built into routes (the engine's cold build, on the device side)."""
    gen = traffic.Generator(fabric, seed, MIX, VANTAGE)
    queues, sides = zip(*(_decision(b) for b in backends))
    for d in sides:
        d.process_publication(Publication(
            key_vals=dict(gen.initial_key_vals()), area="0"))
        d.rebuild_routes("LOAD")
    return gen, queues, sides


def _spec_counters():
    from openr_tpu.telemetry import get_registry

    reg = get_registry()
    out = {n: reg.counter_get(n) for n in (
        "ops.spec_dispatches", "ops.spec_hits", "ops.spec_cancels",
        "ops.spec_skips")}
    out.update(SPF_COUNTERS)
    return out


def _delta(before, *names):
    now = _spec_counters()
    return tuple(now[n] - before.get(n, 0) for n in names)


def _assert_reference(gen, live, host=None):
    assert reference_ksp2.routes_of(live) == reference_ksp2.routes(
        gen.adj_dbs, gen.prefix_dbs, VANTAGE)
    assert reference_ksp2.mpls_routes_of(live) \
        == reference_ksp2.mpls_routes(gen.adj_dbs, VANTAGE)
    if host is not None:
        assert wire.dumps(live) == wire.dumps(host)


def test_the_engine_is_staged_and_still_no_view_is_solved_on_the_view_path(
        fabric):
    """The publication that opens a debounce window stages the rebuild's
    device step (``Decision._on_publication`` -> ``speculate_views``).
    Where a KSP2 engine is live the rebuild takes its view from the
    engine's fused dispatch, so what is staged there is the engine's
    sync: counted as a dispatch, consumed by the window's rebuild as a
    hit, and no view is solved for nobody on the view path that area
    never runs otherwise."""
    gen, queues, (decision,) = _loaded(fabric, 13, "device")
    try:
        solver = decision.spf_solver
        (engine,) = solver._ksp2_engines.values()
        assert engine.valid and engine.src_name == VANTAGE
        for _ in range(3):
            before = _spec_counters()
            # the window's opener, through Decision's own trigger
            _publish(decision, gen.draw())
            assert decision._rebuild_debounced.is_scheduled()
            assert engine.staged
            assert _delta(
                before, "ops.spec_dispatches", "ops.spec_skips",
                "ops.spec_hits", "decision.device_solves",
            ) == (1, 0, 0, 0)
            decision._on_debounce_fire()
            assert not engine.staged
            assert _delta(
                before, "ops.spec_dispatches", "ops.spec_hits",
                "ops.spec_cancels", "ops.spec_skips",
                "decision.device_solves", "decision.ksp2_incremental_syncs",
            ) == (1, 1, 0, 0, 0, 1)
            _assert_reference(gen, decision.route_db.to_route_db(VANTAGE))
        # another root's view is nobody's to serve but the view path's
        other = "rsw-1-0"
        assert solver.speculate_views(
            other, decision.area_link_states, decision.prefix_state) == 1
    finally:
        queues[0].close()


def test_a_staged_sync_is_the_windows_one_sync_under_the_stage(fabric):
    """Opener through ``_on_publication``, then ``_on_debounce_fire``:
    one dispatch, one hit, no view solve, and the window's trace holds
    exactly one ``decision.ksp2_sync``, with its children, inside
    ``decision.speculate`` inside ``decision.debounce``; the rebuild's
    ``decision.route_build`` holds ``decision.ksp2_routes`` and no
    sync. Routes equal the host replay's and the reference's."""
    gen, queues, (decision, host) = _loaded(fabric, 17, "device", "host")
    tracer = get_tracer()
    try:
        for _ in range(4):
            ev = gen.draw()
            before = _spec_counters()
            trace = tracer.start()
            _publish(decision, ev, trace=trace)
            decision._on_debounce_fire()
            tracer.finish(trace)
            host.process_publication(Publication(
                key_vals={ev.key: ev.value}, area="0"))
            host.rebuild_routes("EVENT")
            assert _delta(
                before, "ops.spec_dispatches", "ops.spec_hits",
                "ops.spec_cancels", "decision.device_solves",
                "decision.ksp2_incremental_syncs",
            ) == (1, 1, 0, 0, 1)
            _assert_reference(
                gen, decision.route_db.to_route_db(VANTAGE),
                host.route_db.to_route_db(VANTAGE))
            assert trace.well_formed()
            by_name = {}
            for s in trace.spans:
                assert s.closed
                by_name.setdefault(s.name, []).append(s)
            (window,) = by_name["decision.debounce"]
            (stage,) = by_name["decision.speculate"]
            (sync,) = by_name["decision.ksp2_sync"]
            (build,) = by_name["decision.route_build"]
            (routes,) = by_name["decision.ksp2_routes"]
            assert stage.attrs["staged"] == 1
            assert _inside(stage, window) and _inside(sync, stage)
            assert not sync.attrs["cold"]
            for name in ("ops.ksp2_all_pairs", "ops.ksp2_masked_solve",
                         "decision.ksp2_trace"):
                for s in by_name.get(name, ()):
                    assert _inside(s, sync), name
            assert len(by_name["ops.ksp2_all_pairs"]) == 1
            assert build.ts_ms >= window.ts_ms + window.dur_ms - 0.5
            assert _inside(routes, build)
            assert routes.attrs["reused"] >= 55 - sync.attrs["affected"]
            for name in ("graph.view_sync", "ops.spf_view_batch",
                         "ops.ell_reconverge"):
                assert name not in by_name
    finally:
        for q in queues:
            q.close()


@pytest.mark.parametrize("seed", [19, 2147483693])
def test_a_publication_that_joins_after_the_stage_takes_the_union(
        fabric, seed):
    """Two adjacency publications in one window: the first is staged,
    the second moves the version past the stage. The rebuild's own sync
    steps on from the staged version (``ops.spec_cancels``: only the
    overlap is lost), and the build re-derives the routes of the
    destinations EITHER publication moved: the carry is the union, and
    the route cache swallowed none of them."""
    gen, queues, (decision, host) = _loaded(fabric, seed, "device", "host")
    solver = decision.spf_solver
    (engine,) = solver._ksp2_engines.values()
    both_moved = 0
    try:
        for _ in range(12):
            evs = [gen.draw(), gen.draw()]
            before = _spec_counters()
            old = dict(solver._route_table.unicast)
            _publish(decision, evs[0])
            assert engine.staged
            first = set(engine._carried)
            _publish(decision, evs[1])
            assert _delta(before, "ops.spec_dispatches") == (1,)  # the latch
            decision._on_debounce_fire()
            for ev in evs:
                host.process_publication(Publication(
                    key_vals={ev.key: ev.value}, area="0"))
            host.rebuild_routes("EVENT")
            assert _delta(
                before, "ops.spec_dispatches", "ops.spec_hits",
                "ops.spec_cancels", "decision.device_solves",
                "decision.ksp2_incremental_syncs",
            ) == (1, 0, 1, 0, 2)
            _assert_reference(
                gen, decision.route_db.to_route_db(VANTAGE),
                host.route_db.to_route_db(VANTAGE))
            # what the stage found moved was re-derived by the build
            # although the build's own sync did not name it: the route
            # object the solver's table held was replaced, not kept
            table = solver._route_table
            new = table.unicast
            for prefix, entry in old.items():
                advertisers = {
                    node for node, _area
                    in decision.prefix_state.entries_for(prefix)}
                if advertisers & first:
                    assert new.get(prefix) is not entry, prefix
                    both_moved += 1
            assert _delta(before, "decision.ksp2_route_reuses")[0] \
                <= table.n_prefixes - len(first)
            assert not engine._carried and not engine.staged
    finally:
        for q in queues:
            q.close()
    assert both_moved > 0, "no window's stage moved a destination"


def test_a_staged_sync_that_raises_leaves_the_rebuild_correct(
        fabric, monkeypatch):
    """An abandoned speculation (``ops.spec_cancels``), never an
    escalation: the torn engine is invalid, the window's rebuild builds
    it cold on its own ladder's first rung, and the routes are the
    reference's."""
    gen, queues, (decision, host) = _loaded(fabric, 23, "device", "host")
    (engine,) = decision.spf_solver._ksp2_engines.values()
    real = ksp2_engine.Ksp2Engine._recompute
    armed = []

    def torn(self, *args, **kwargs):
        if armed:
            armed.pop()
            real(self, *args, **kwargs)  # paths moved, nothing committed
            raise RuntimeError("staged sync torn")
        return real(self, *args, **kwargs)

    monkeypatch.setattr(ksp2_engine.Ksp2Engine, "_recompute", torn)
    try:
        for _ in range(40):
            ev = gen.draw()
            before = _spec_counters()
            armed.append(True)
            _publish(decision, ev)
            tore = not armed
            armed.clear()
            host.process_publication(Publication(
                key_vals={ev.key: ev.value}, area="0"))
            host.rebuild_routes("EVENT")
            if tore:
                assert not engine.valid and not engine.staged
                assert _delta(
                    before, "ops.spec_dispatches", "ops.spec_cancels",
                ) == (0, 1)
            decision._on_debounce_fire()
            if tore:
                assert engine.valid
                assert _delta(
                    before, "ops.spec_hits", "ops.spec_cancels",
                    "decision.ksp2_cold_builds",
                    "decision.device_state_resets",
                ) == (0, 1, 1, 0)
            _assert_reference(
                gen, decision.route_db.to_route_db(VANTAGE),
                host.route_db.to_route_db(VANTAGE))
            if tore:
                break
        else:
            pytest.fail("no event reached _recompute")
        # and the window after it stages and hits again
        before = _spec_counters()
        _publish(decision, gen.draw())
        decision._on_debounce_fire()
        assert _delta(before, "ops.spec_dispatches", "ops.spec_hits") \
            == (1, 1)
        _assert_reference(gen, decision.route_db.to_route_db(VANTAGE))
    finally:
        for q in queues:
            q.close()


def test_a_cold_build_inside_a_stage_still_reaches_settle_heap(
        fabric, monkeypatch):
    """A staged sync that overflows into a cold build runs outside the
    bracket ``rebuild_routes`` keeps around its own work; the window's
    rebuild settles the heap for it all the same, once, and the build
    takes "all" (no destination's route reused)."""
    from openr_tpu.decision import decision as decision_mod

    settled = []
    monkeypatch.setattr(
        decision_mod, "settle_heap", lambda: settled.append(True))
    # every incremental sync overflows into a cold build
    monkeypatch.setattr(
        ksp2_engine.Ksp2Engine, "_diff_pairs", lambda *a, **k: None)
    gen, queues, (decision,) = _loaded(fabric, 29, "device")
    try:
        assert settled == [True]  # the load's own cold build
        before = _spec_counters()
        _publish(decision, gen.draw())
        assert _delta(
            before, "decision.ksp2_cold_builds", "ops.spec_dispatches",
        ) == (1, 1)
        assert settled == [True], "not under the policy wait"
        decision._on_debounce_fire()
        assert settled == [True, True]
        # (the one route reused is the vantage's own prefix, which no
        # destination's paths reach)
        assert _delta(
            before, "decision.ksp2_cold_builds", "ops.spec_hits",
            "decision.ksp2_route_reuses",
        ) == (1, 1, 1)
        _assert_reference(gen, decision.route_db.to_route_db(VANTAGE))
        # a window with no cold build anywhere leaves the heap alone
        monkeypatch.undo()
        monkeypatch.setattr(
            decision_mod, "settle_heap", lambda: settled.append(True))
        _publish(decision, gen.draw())
        decision._on_debounce_fire()
        assert settled == [True, True]
    finally:
        queues[0].close()
