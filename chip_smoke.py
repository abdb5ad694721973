#!/usr/bin/env python3
"""Start the two served paths on the chip and check what comes out.

One process owns the accelerator and drives, through the entry points a
deployment uses and with the options ``OpenrConfig`` ships
(``solver_backend="device"``, no ``OPENR_*`` variable set):

- ``pipeline_fabric_1008`` / ``pipeline_fabric_10k``: KvStore -> Decision
  -> Fib wired as ``daemon.py`` wires them (``SustainedLoadHarness``):
  bulk LSDB load to the first ``RouteDatabase`` in Fib, a short run of
  the seeded default ``EventMix``, drain, and ``check_parity()`` against
  the unshedded host-Dijkstra replay. 1008 nodes is the dense
  formulation, 10,000 the resident sliced-ELL one. A sealed machine
  cannot hold 10k daemons; the harness is the repo's stand-in for the
  LSDB they would flood.
- ``ksp2_fabric_1008``: every prefix ``KSP2_ED_ECMP`` through
  ``SpfSolver(backend="device")`` over a few churn events,
  ``RouteDatabase`` equal to ``backend="host"``.
- ``ksp2_grid_961``: every prefix ``KSP2_ED_ECMP`` on upstream's own
  KSP2 graph, the 31 x 31 grid, through ``SpfSolver`` from the corner
  (60 hops deep): four nodes re-cost all their links, the engine the
  load built serves every one, device against host.
- ``multiarea_2x1000``: the border router of ``multi-area-2x1000``
  (two areas of the 1k fabric, KvStore -> Decision -> PrefixManager as
  ``daemon.py`` builds a border): cold build of both areas, one
  adjacency event an area, one prefix toggled on and off; after each,
  routes and the re-originated keys in each area's KvStore equal to
  ``chipbench/reference_multiarea.py``.
- ``serve``: ``SolverService`` behind ``CtrlServer`` in this process,
  JAX-free client processes over the ctrl wire, every FIB digest equal
  to one built by ``SpfSolver(backend="host")``.
- ``mesh4`` (>= 4 devices only): the KSP2 leg and a 10k route-engine
  churn sharded over four devices.

The run fails — exit code != 0, no result line — without a TPU, on any
exception or parity miss, on any fallback counter above zero, and when
a mechanism the legs exist to exercise never ran. On a pass stdout ends
with a ``summary: {...}`` line (per-leg verdicts, counters, compile
counts, peak bytes; also written to ``chiprun_out/chip_smoke.json``) and
then, as its last line, the verdict and nothing else:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Wall seconds in the summary are for budgeting the run, not a metric.
Nothing here is a benchmark.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from dataclasses import replace

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
# what the chip tool copies back after a run
ARTEFACT_DIR = os.path.join(REPO, "chiprun_out")

# any of these above zero means a leg ended on a path that hides the
# device (a degradation rung, a host Dijkstra, a plain-jit retry)
FALLBACK_COUNTERS = (
    "decision.backend_switches",
    "decision.fallbacks",
    "decision.degradations",
    "decision.device_state_resets",
    "decision.spf_host_fallback",
    "decision.ksp2_host_fallbacks",
    "route_engine.fallbacks",
    "ops.aot_fallbacks",
    "serve.errors",
)

# "the mechanism ran": a pass with any of these at zero took some other
# path than the one the leg names
MECHANISM_COUNTERS = (
    "decision.ell_cold_solves",
    "decision.ell_warm_solves",
    "decision.ell_incremental_syncs",
    "decision.ksp2_device_batches",
    "decision.ksp2_incremental_syncs",
    "tenancy.dispatches",
    "tenancy.warm_solves",
    "tenancy.wave_joins",
    "serve.waves",
)


class SmokeFailure(Exception):
    """A leg produced a wrong or unproven result."""


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- compile accounting ------------------------------------------------------


def _compile_summary() -> dict:
    """Backend compiles and persistent-cache traffic of this process, as
    ``telemetry.jax_hooks`` counted them off ``jax.monitoring``. A cache
    hit still reports a backend-compile event (its duration is the
    retrieval), and jax counts a miss only for an executable it then
    stores (one that took over a second to build) — so on a warm cache
    ``persistent_cache_misses`` counts only compiles that straddle that
    threshold and ``compile_max_s`` is about a second, not a cold
    build."""
    from openr_tpu.telemetry import get_registry

    reg = get_registry()
    name = "jax.duration_ms.jax.core.compile.backend_compile_duration"
    hist = reg.histogram_if_exists(name)
    stats = hist.stats() if hist is not None else {}
    count = int(stats.get(name + ".count", 0))
    return {
        "compiles": count,
        "compile_s": round(stats.get(name + ".avg", 0.0) * count / 1e3, 3),
        "compile_max_s": round(stats.get(name + ".max", 0.0) / 1e3, 3),
        "persistent_cache_hits": int(reg.counter_get(
            "jax.events.jax.compilation_cache.cache_hits"
        )),
        "persistent_cache_misses": int(reg.counter_get(
            "jax.events.jax.compilation_cache.cache_misses"
        )),
    }


# -- shared fixtures ---------------------------------------------------------


def _link_state(topo):
    from openr_tpu.graph.linkstate import LinkState

    ls = LinkState(area=topo.area)
    for name in sorted(topo.adj_dbs):
        ls.update_adjacency_database(topo.adj_dbs[name])
    return ls


def _fabric(nodes: int, **topo_kwargs):
    from openr_tpu.models import topologies

    topo = topologies.fat_tree_nodes(nodes, **topo_kwargs)
    return topo, _link_state(topo)


def _bump_metric(ls, node: str, step: int) -> str:
    """One adjacency metric change on ``node``; returns the peer."""
    db = ls.get_adjacency_databases()[node]
    adjs = list(db.adjacencies)
    adjs[0] = replace(adjs[0], metric=2 + step % 5)
    ls.update_adjacency_database(replace(db, adjacencies=tuple(adjs)))
    return adjs[0].other_node_name


def _counter_delta(before: dict, names) -> dict:
    from openr_tpu.telemetry import get_registry

    reg = get_registry()
    return {k: reg.counter_get(k) - before.get(k, 0) for k in names}


def _counter_snapshot(names) -> dict:
    from openr_tpu.telemetry import get_registry

    reg = get_registry()
    return {k: reg.counter_get(k) for k in names}


# -- legs --------------------------------------------------------------------


def leg_pipeline(nodes: int, events: int = 20, rate: int = 4,
                 timeout_s: float = 600.0) -> dict:
    """KvStore -> Decision -> Fib at ``nodes`` on the device backend."""
    from openr_tpu.load.harness import SustainedLoadHarness

    watched = (
        "decision.ell_cold_solves", "decision.ell_warm_solves",
        "decision.ell_structural_warm_solves",
        "decision.ell_incremental_syncs", "decision.ell_full_compiles",
        "decision.ladder_walks",
    )
    before = _counter_snapshot(watched)
    # the debounce window OpenrConfig ships
    harness = SustainedLoadHarness(
        nodes=nodes,
        solver_backend="device",
        debounce_min_s=0.010,
        debounce_max_s=0.250,
    )
    harness.start(initial_timeout_s=timeout_s)
    try:
        report = harness.run_fixed_rate(
            rate, events / float(rate), drain_grace_s=timeout_s
        )
        _require(report.drained, f"pipeline {nodes}: backlog never drained")
        _require(
            report.published > 0, f"pipeline {nodes}: nothing published"
        )
        routes = len(harness.fib.get_route_db().unicast_routes)
        parity = harness.check_parity()
    finally:
        harness.stop()
    _require(parity, f"pipeline {nodes}: RouteDatabase != host replay")
    _require(routes > 0, f"pipeline {nodes}: Fib holds no routes")
    return {
        "nodes": len(harness.topo.adj_dbs),
        "published": report.published,
        "fib_unicast_routes": routes,
        "parity": parity,
        "counts": _counter_delta(before, watched),
    }


def _shard_holders(array) -> list:
    return sorted({s.device.id for s in array.addressable_shards})


def _ksp2_forwarding() -> dict:
    from openr_tpu.types.lsdb import (
        PrefixForwardingAlgorithm,
        PrefixForwardingType,
    )

    return dict(
        forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
        forwarding_type=PrefixForwardingType.SR_MPLS,
    )


def _ksp2_parity(what: str, topo, ls, root: str, event, events: int):
    """Every prefix KSP2 through ``SpfSolver`` from ``root``: the load,
    then ``events`` times ``event(step)`` on ``ls``, device == host to
    the byte each time. Returns (the device solver, the leg's result)."""
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.utils import wire

    watched = (
        "decision.ksp2_device_batches", "decision.ksp2_cold_builds",
        "decision.ksp2_incremental_syncs",
        "decision.ksp2_warm_dispatches",
        "decision.ksp2_matrix_deferred",
    )
    before = _counter_snapshot(watched)
    ps = PrefixState()
    for pdb in topo.prefix_dbs.values():
        ps.update_prefix_database(pdb)
    area_ls = {topo.area: ls}
    device = SpfSolver(root, backend="device")
    host = SpfSolver(root, backend="host")
    routes = 0
    for step in range(events + 1):
        if step:
            event(step)
        got = device.build_route_db(root, area_ls, ps).to_route_db(root)
        want = host.build_route_db(root, area_ls, ps).to_route_db(root)
        _require(
            wire.dumps(got) == wire.dumps(want),
            f"{what}: device RouteDatabase != host at event {step}",
        )
        routes = len(got.unicast_routes)
    _require(routes > 0, f"{what}: empty RouteDatabase")
    return device, {
        "nodes": ls.num_nodes,
        "events": events,
        "unicast_routes": routes,
        "parity": True,
        "counts": _counter_delta(before, watched),
    }


def leg_ksp2(nodes: int, events: int = 4, shard_devices=None) -> dict:
    """All-KSP2 fabric through ``SpfSolver``: device == host. With
    ``shard_devices`` (the engine mesh's device ids) the engine's
    resident all-pairs matrix must hold a shard on each of them."""
    topo, ls = _fabric(nodes, **_ksp2_forwarding())
    names = sorted(topo.adj_dbs)
    rsw = next(k for k in names if k.startswith("rsw"))
    fsw = next(k for k in names if k.startswith("fsw"))
    device, out = _ksp2_parity(
        f"ksp2 {nodes}", topo, ls, rsw,
        lambda step: _bump_metric(ls, fsw, step), events,
    )
    if shard_devices is not None:
        engine = device._ksp2_engines.get(ls)
        _require(engine is not None, f"ksp2 {nodes}: no resident engine")
        out["shard_devices"] = _shard_holders(engine.d_prev_dev)
        _require(
            out["shard_devices"] == sorted(shard_devices),
            f"ksp2 {nodes}: all-pairs matrix lives on devices "
            f"{out['shard_devices']}, mesh is {sorted(shard_devices)}",
        )
    return out


def leg_ksp2_grid(side: int = 31, events: int = 4) -> dict:
    """All-KSP2 ``side`` x ``side`` grid (31: upstream's own KSP2 graph,
    ``BM_DecisionGrid`` N=1000) through ``SpfSolver`` from the corner,
    60 hops from the far one: device == host after the load and after
    each of ``events`` nodes re-costing every one of their links, and
    the engine that the load built is the one that served them all."""
    from openr_tpu.models import topologies

    topo = topologies.grid(side, **_ksp2_forwarding())
    ls = _link_state(topo)
    root = "node-0"

    def recost(step: int) -> None:
        # nodes spread over the grid, never the root
        node = f"node-{1 + (step * 389) % (side * side - 1)}"
        db = ls.get_adjacency_databases()[node]
        ls.update_adjacency_database(replace(db, adjacencies=tuple(
            replace(a, metric=1 + (a.metric % 10)) for a in db.adjacencies
        )))

    what = f"ksp2 grid {side}x{side}"
    device, out = _ksp2_parity(what, topo, ls, root, recost, events)
    engine = device._ksp2_engines.get(ls)
    _require(
        engine is not None and engine.valid and engine.src_name == root,
        f"{what}: no resident engine for {root}",
    )
    counts = out["counts"]
    _require(
        counts["decision.ksp2_cold_builds"] == 1
        and counts["decision.ksp2_incremental_syncs"] == events
        # one chip: every sync waited for its rows alone and sent the
        # all-pairs matrix solve behind the window
        and counts["decision.ksp2_matrix_deferred"] == events,
        f"{what}: not one cold build and {events} incremental syncs, "
        f"each with its matrix solve behind the window: {counts}",
    )
    out["hops_from_root"] = ls.get_max_hops_to_node(root)
    return out


def leg_multiarea(topology=None, timeout_s: float = 300.0) -> dict:
    """``multi-area-2x1000``'s border: both areas built cold, one metric
    change in each, one prefix toggled on and then off. After each step
    the routes Decision holds and the vantage's live re-originated keys
    in each area's KvStore equal the plain two-area reference.
    ``topology`` overrides sizes of the configuration's (tests)."""
    from chipbench import reference_multiarea, spec
    from openr_tpu.decision.decision import Decision
    from openr_tpu.kvstore.client import KvStoreClient
    from openr_tpu.kvstore.store import KvStore
    from openr_tpu.messaging.queue import ReplicateQueue
    from openr_tpu.prefixmgr.prefix_manager import PrefixManager
    from openr_tpu.types import TTL_INFINITY, KeySetParams, Value
    from openr_tpu.utils import keys as keyutil
    from openr_tpu.utils import wire
    from openr_tpu.utils.eventbase import OpenrEventBase

    with open(os.path.join(
        REPO, "chipbench", "configs", "multi-area-2x1000.json"
    )) as f:
        config = json.load(f)
    config["topology"].update(topology or {})
    bench = spec.load_driver(REPO, config["served_path"]).set_up.__globals__
    areas, borders, name = config["areas"], config["borders"], config["vantage"]
    (peer,) = [b for b in borders if b != name]
    topos = bench["build"](config)
    gen = bench["TwoAreaTraffic"](
        topos, 1, {"kinds": {"metric": 1.0}}, name, borders)
    peer_keys = bench["peer_reoriginations"](topos, peer, borders)
    initial = gen.initial_key_vals()
    for area, dbs in peer_keys.items():
        for key, db in dbs.items():
            initial[area][key] = Value(
                version=1, originator_id=peer, value=wire.dumps(db),
                ttl=TTL_INFINITY)

    store = KvStore(node_id=name, areas=areas)
    route_updates = ReplicateQueue(name=f"{name}:routeUpdates")
    client_evb = OpenrEventBase(name=f"kvclient:{name}")
    client = KvStoreClient(client_evb, name, store)
    decision = Decision(
        name,
        kvstore_updates_queue=store.updates_queue,
        route_updates_queue=route_updates,
        static_routes_queue=ReplicateQueue(name=f"{name}:staticRoutes"),
        debounce_min_s=0.01, debounce_max_s=0.25, solver_backend="device",
    )
    prefix_manager = PrefixManager(
        name, client, decision_route_updates_queue=route_updates,
        areas=areas,
    )
    own = f"{keyutil.PREFIX_DB_MARKER}{name}:"
    counted = ("decision.device_solves", "prefixmgr.redistributed_keys",
               "prefixmgr.withdrawn_keys")
    before = _counter_snapshot(counted)

    def held() -> dict:
        return reference_multiarea.owed_of(
            bench["live_entries"](store, areas, own))

    def settle(what: str) -> tuple:
        lsdb = gen.lsdb(peer_keys)
        want = reference_multiarea.routes(lsdb, name)
        owed = reference_multiarea.reoriginations(lsdb, name, areas)
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            got = reference_multiarea.routes_of(decision.evb.call_and_wait(
                lambda: decision.route_db.to_route_db(name)))
            if got == want and held() == owed:
                return len(want), len(owed)
            time.sleep(0.05)
        raise SmokeFailure(
            f"multiarea: {what}: routes or re-originated keys never "
            f"equalled the reference ({len(want)} routes, {len(owed)} keys)")

    def publish(ev) -> None:
        store.set_key_vals(ev.area, KeySetParams(
            key_vals={ev.key: ev.value},
            originator_id=ev.value.originator_id))

    store.start()
    client_evb.run_in_thread()
    decision.start()
    prefix_manager.start()
    try:
        for area in areas:
            store.set_key_vals(
                area, KeySetParams(key_vals=dict(initial[area])))
        routes, keys = settle("cold build of both areas")
        for area in areas:
            publish(bench["AreaEvent"](gen.gens[area].event("metric"), area))
            settle(f"a metric change in area {area}")
        # a non-border node's extra /128: on, then off
        node = next(n for n in gen.gens[areas[0]]._nodes if n not in borders)
        gen.gens[areas[0]]._pick = lambda: node
        for what, more in (("re-originated", 1), ("withdrawn", 0)):
            publish(bench["AreaEvent"](
                gen.gens[areas[0]].event("prefix"), areas[0]))
            _require(
                settle(f"a toggled prefix {what}") == (routes + more,
                                                       keys + more),
                f"multiarea: a toggled prefix {what}: wrong counts")
    finally:
        prefix_manager.stop()
        decision.stop()
        client.stop()
        client_evb.stop()
        client_evb.join()
        store.stop()
    counts = _counter_delta(before, counted)
    _require(counts["decision.device_solves"] >= len(areas) + 2,
             f"multiarea: the device did not solve every step: {counts}")
    _require(counts["prefixmgr.redistributed_keys"] == keys + 1
             and counts["prefixmgr.withdrawn_keys"] == 1,
             f"multiarea: not one key a prefix: {counts}")
    return {"parity": True, "routes": routes, "reoriginated_keys": keys,
            "nodes_per_area": len(topos[areas[0]].adj_dbs), "counts": counts}


def leg_serve(clients: int = 2, tenant_sizes=(("grid", 32), ("mesh", 1000)),
              tenants_per_client: int = 4, rounds: int = 3) -> dict:
    """``SolverService`` behind the ctrl wire, driven by JAX-free client
    processes; every FIB digest checked against the host solver."""
    from openr_tpu.ctrl.server import CtrlServer
    from openr_tpu.ctrl.solver import SolverCtrlHandler
    from openr_tpu.load import multi_client
    from openr_tpu.serve.service import SolverService

    watched = (
        "tenancy.dispatches", "tenancy.warm_solves", "tenancy.cold_solves",
        "tenancy.wave_joins", "tenancy.admissions", "serve.waves",
        "serve.requests", "serve.errors",
    )
    before = _counter_snapshot(watched)
    specs = {}
    for c in range(clients):
        ids = range(c * tenants_per_client, (c + 1) * tenants_per_client)
        specs[f"c{c}"] = [
            multi_client.TenantSpec(
                f"c{c}t{n}", *tenant_sizes[n % len(tenant_sizes)], seed=n + 1
            )
            for n in ids
        ]
    all_specs = [s for lst in specs.values() for s in lst]

    svc = SolverService().start()
    srv = CtrlServer(SolverCtrlHandler(svc))
    srv.start()
    procs = []
    try:
        with tempfile.TemporaryDirectory() as out_dir:
            procs = multi_client.spawn_clients(
                "127.0.0.1", srv.port, specs, rounds, out_dir,
                fib_every=1,
            )
            # the host-Dijkstra replay (no device kernel) runs while the
            # children drive the wire
            want = multi_client.oracle_fib_digests(
                all_specs, rounds, every=1, backend="host"
            )
            results = multi_client.harvest(procs)
    finally:
        for p, _path in procs:
            if p.is_alive():
                p.kill()
            p.join(timeout=10)
        srv.stop()
        svc.stop()

    errors = [e for r in results for e in r.get("errors", [])]
    _require(not errors, f"serve: client errors {errors[:4]}")
    short = [r["client_id"] for r in results if r.get("rounds") != rounds]
    _require(not short, f"serve: clients short of {rounds} rounds: {short}")
    got = {}
    for r in results:
        got.update(r.get("fib", {}))
    diverged = sorted(t for t in want if got.get(t) != want[t])
    _require(
        not diverged, f"serve: FIB digests differ from host for {diverged}"
    )
    return {
        "clients": clients,
        "tenants": len(all_specs),
        "rounds": rounds,
        "fib_digests_checked": sum(len(v) for v in want.values()),
        "parity": True,
        "counts": _counter_delta(before, watched),
    }


def leg_mesh4(nodes_ksp2: int = 1008, nodes_engine: int = 10000,
              events: int = 4) -> dict:
    """Four devices: the KSP2 leg under the engine mesh ``main.py``
    installs, and a 10k route-engine churn sharded over the same mesh,
    equal to its one-device twin, with no reshard and every device
    holding shards of the resident state."""
    import jax

    from openr_tpu.decision import ksp2_engine
    from openr_tpu.ops import route_engine, route_sweep
    from openr_tpu.parallel.mesh import make_mesh

    watched = ("ops.reshard_events", "ops.shard_readback_bytes")
    before = _counter_snapshot(watched)
    devices = jax.devices()[:4]
    ids = sorted(d.id for d in devices)
    mesh = make_mesh(devices)
    ksp2_engine.set_engine_mesh(mesh)
    try:
        ksp2 = leg_ksp2(nodes_ksp2, events=events, shard_devices=ids)
    finally:
        ksp2_engine.set_engine_mesh(None)

    topo, ls = _fabric(nodes_engine)
    names = sorted(topo.adj_dbs)
    rsw = next(k for k in names if k.startswith("rsw"))
    fsw = next(k for k in names if k.startswith("fsw"))
    sharded = route_engine.RouteSweepEngine(ls, [rsw], mesh=mesh)
    single = route_engine.RouteSweepEngine(ls, [rsw])
    for step in range(events):
        affected = {fsw, _bump_metric(ls, fsw, step)}
        sharded.churn(ls, affected)
        single.churn(ls, affected)
    _require(
        route_sweep.digests_by_name(sharded.result)
        == route_sweep.digests_by_name(single.result),
        "mesh4: sharded route-engine digests != one-device engine",
    )
    holders = _shard_holders(sharded._packed_dev)
    _require(
        holders == ids,
        f"mesh4: route product lives on devices {holders}, mesh is {ids}",
    )
    counts = _counter_delta(before, watched)
    _require(
        counts["ops.reshard_events"] == 0,
        f"mesh4: {counts['ops.reshard_events']} reshard event(s)",
    )
    _require(
        counts["ops.shard_readback_bytes"] > 0,
        "mesh4: no per-shard readback ran",
    )
    return {
        "devices": len(devices),
        "ksp2": ksp2,
        "engine_nodes": sharded.graph.n,
        "engine_events": events,
        "engine_incremental_events": sharded.incremental_events,
        "shard_devices": holders,
        "counts": counts,
    }


# -- driver ------------------------------------------------------------------


def _versions() -> dict:
    import jax
    import jaxlib

    out = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        import libtpu

        out["libtpu"] = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        out["libtpu"] = None
    return out


def run(legs, device: dict, setup: dict) -> int:
    """Drive ``legs`` ([(name, thunk)]) in order, then judge the
    counters. An exception in a leg is not caught: it ends the process
    with its traceback and a non-zero code."""
    import jax

    dev0 = jax.devices()[0]
    summary = {
        "ok": False, "device": device, **setup,
        "legs": {}, "wall_s": {}, "peak_bytes_in_use": {},
    }
    before = _counter_snapshot(FALLBACK_COUNTERS + MECHANISM_COUNTERS)
    for name, thunk in legs:
        t0 = time.monotonic()
        print(f"[{name}] start", flush=True)
        result = thunk()
        summary["wall_s"][name] = round(time.monotonic() - t0, 1)
        summary["legs"][name] = result
        # the process-wide high-water mark once this leg is done (the
        # CPU backend reports none)
        summary["peak_bytes_in_use"][name] = (
            dev0.memory_stats() or {}
        ).get("peak_bytes_in_use")
        print(f"[{name}] ok {json.dumps(result)}", flush=True)

    fallbacks = _counter_delta(before, FALLBACK_COUNTERS)
    mechanisms = _counter_delta(before, MECHANISM_COUNTERS)
    failures = [f"{k} = {v}" for k, v in fallbacks.items() if v]
    failures += [f"{k} never ran" for k, v in mechanisms.items() if not v]
    summary.update(
        fallback_counters=fallbacks,
        mechanism_counters=mechanisms,
        compile=_compile_summary(),
        failures=failures,
    )
    summary["ok"] = not failures
    summary["claim"] = None
    os.makedirs(ARTEFACT_DIR, exist_ok=True)
    with open(os.path.join(ARTEFACT_DIR, "chip_smoke.json"), "w") as f:
        json.dump(summary, f, indent=1)
    if failures:
        print("chip_smoke FAILED:", file=sys.stderr)
        for failure in failures:
            print(f"  - {failure}", file=sys.stderr)
        return 1
    print(f"summary: {json.dumps(summary)}", flush=True)
    # the verdict line holds these two keys and no other; everything
    # else is in the summary line above and in chip_smoke.json
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def main() -> int:
    import jax

    dev0 = jax.devices()[0]
    device = {
        "platform": dev0.platform,
        "kind": dev0.device_kind,
        "count": len(jax.devices()),
    }
    if dev0.platform != "tpu":
        # without a chip the installed jax does not raise, it carries on
        # to the CPU backend — so the refusal has to be ours
        print(
            f"chip_smoke: no TPU (jax found {device}); this check only "
            "means something on the chip", file=sys.stderr,
        )
        return 2

    from openr_tpu.graph import native_spf
    from openr_tpu.telemetry import jax_hooks
    from openr_tpu.utils import compile_cache

    setup = {
        "versions": _versions(),
        "compile_cache_dir": compile_cache.enable(),
        "compile_cache_from_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")
        ),
        # built now, from the tracked source, before anything loads it;
        # a missing or failing compiler raises here
        "native_core": os.path.relpath(native_spf.build(), REPO),
    }
    print(f"device: {device}", flush=True)
    print(f"setup: {setup}", flush=True)
    jax_hooks.install()

    legs = [
        ("pipeline_fabric_1008", lambda: leg_pipeline(1008)),
        ("pipeline_fabric_10k", lambda: leg_pipeline(10000)),
        ("ksp2_fabric_1008", lambda: leg_ksp2(1008)),
        ("ksp2_grid_961", leg_ksp2_grid),
        ("multiarea_2x1000", leg_multiarea),
        ("serve", leg_serve),
    ]
    if device["count"] >= 4:
        legs.append(("mesh4", leg_mesh4))
    return run(legs, device, setup)


if __name__ == "__main__":
    sys.exit(main())
