"""Soak the incremental KSP2 engine: long randomized mutation streams,
checked after every step.

All prefixes are KSP2_ED_ECMP, so every event exercises the engine's
invalidation algebra (first/second path membership tests, masked
re-solve, the walk-reach proof) plus the label/overload
materialization extras. Two streams:

- ``soak_one``: metric wiggles, overload flips, node-label changes,
  link drop/restore through ``SpfSolver``, device (engine) against a
  fresh host solver, byte-exact RouteDatabase parity at every step;
- ``soak_cell``: the events of the cell fabric-1000-ksp2.adj-churn
  (chipbench's generator: 80% metric change, 20% link flap) on small
  fabrics, and those of grid-1000-ksp2.drain-churn (80% a node re-costs
  all its links, 20% link flap) on a 12 x 12 grid solved from its
  corner, 22 hops deep, where nearly every window changes several
  links and the walk-reach proof answers for each of them, through
  ``Decision`` as KvStore's queue hands them over (``_on_publication``
  then ``_on_debounce_fire``: the window's first publication stages the
  engine's sync, the rest join and move the version past the stage),
  one to four a rebuild window, and after every window the
  RouteDatabase (unicast next hops with label stacks, node-label MPLS
  routes) against the plain reference chipbench/reference_ksp2.py.
  This is the stream that found the three holes in
  ``Ksp2Engine._second_paths_may_move`` (PERF.md section 6, PR 32);
  tests/test_ksp2_pipeline.py runs its seeds.

Run:  python -m tools.soak_ksp2 [--seeds 12] [--steps 40]
      python -m tools.soak_ksp2 --cell [--seeds 12] [--steps 500]
Prints one JSON line per seed; exits non-zero on the first break.
"""

from __future__ import annotations

import argparse
import json
import random
import sys
import time
from dataclasses import replace

from openr_tpu.decision import spf_solver as _ss
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.spf_solver import SPF_COUNTERS, SpfSolver
from openr_tpu.graph.linkstate import LinkState
from openr_tpu.models import topologies
from openr_tpu.types.lsdb import (
    PrefixForwardingAlgorithm,
    PrefixForwardingType,
)


def _build(kind: str, n: int):
    kwargs = dict(
        forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
        forwarding_type=PrefixForwardingType.SR_MPLS,
    )
    topo = (
        topologies.grid(n, **kwargs)
        if kind == "grid"
        else topologies.fat_tree_nodes(n, **kwargs)
    )
    ls = LinkState(area=topo.area)
    for name in sorted(topo.adj_dbs):
        ls.update_adjacency_database(topo.adj_dbs[name])
    ps = PrefixState()
    for pdb in topo.prefix_dbs.values():
        ps.update_prefix_database(pdb)
    return topo, ls, ps


def soak_one(seed: int, kind: str, n: int, steps: int) -> dict:
    rng = random.Random(seed)
    topo, ls_d, ps_d = _build(kind, n)
    _t, ls_h, ps_h = _build(kind, n)
    names = sorted(topo.adj_dbs)
    root = next(
        (k for k in names if k.startswith("rsw")), names[0]
    )
    dev = SpfSolver(root, backend="device")
    host = SpfSolver(root, backend="host")
    pulled: dict = {}

    def mutate(ls):
        node = rng.choice(names)
        db = ls.get_adjacency_databases()[node]
        r = rng.random()
        if r < 0.5 and db.adjacencies:
            i = rng.randrange(len(db.adjacencies))
            adjs = list(db.adjacencies)
            adjs[i] = replace(adjs[i], metric=1 + rng.randrange(9))
            ls.update_adjacency_database(
                replace(db, adjacencies=tuple(adjs))
            )
        elif r < 0.7:
            ls.update_adjacency_database(
                replace(db, is_overloaded=not db.is_overloaded)
            )
        elif r < 0.85 and db.adjacencies:
            key = (id(ls), node)
            if key in pulled:
                adj = pulled.pop(key)
                db = ls.get_adjacency_databases()[node]
                ls.update_adjacency_database(
                    replace(
                        db,
                        adjacencies=tuple(
                            list(db.adjacencies) + [adj]
                        ),
                    )
                )
            else:
                i = rng.randrange(len(db.adjacencies))
                adjs = list(db.adjacencies)
                pulled[key] = adjs.pop(i)
                ls.update_adjacency_database(
                    replace(db, adjacencies=tuple(adjs))
                )
        else:
            ls.update_adjacency_database(
                replace(
                    db, node_label=51000 + rng.randrange(500)
                )
            )

    t0 = time.time()
    syncs0 = SPF_COUNTERS["decision.ksp2_incremental_syncs"]
    for step in range(steps):
        st = rng.getstate()
        mutate(ls_d)
        rng.setstate(st)
        mutate(ls_h)
        d = dev.build_route_db(root, {topo.area: ls_d}, ps_d)
        h = host.build_route_db(root, {topo.area: ls_h}, ps_h)
        if d.to_route_db(root) != h.to_route_db(root):
            return {
                "seed": seed, "kind": kind, "n": n,
                "step": step, "parity": "BROKEN",
            }
    return {
        "seed": seed, "kind": kind, "n": n, "steps": steps,
        "parity": "ok",
        "incremental_syncs": SPF_COUNTERS[
            "decision.ksp2_incremental_syncs"
        ] - syncs0,
        "wall_s": round(time.time() - t0, 1),
    }


def fabric_world(pods: int, rsws: int) -> dict:
    """``pods`` pods of 4 FSW and ``rsws`` RSW, 2 SSW a plane, under
    adj-churn's mix, solved from the first RSW."""
    return {
        "topology": {"kind": "fat_tree", "pods": pods, "ssw_per_plane": 2,
                     "fsw_per_pod": 4, "rsw_per_pod": rsws},
        "kinds": {"metric": 0.8, "flap": 0.2}, "vantage": "rsw-0-0",
    }


def grid_world(n: int) -> dict:
    """``n`` x ``n`` grid under drain-churn's mix, solved from the
    corner: 2 (n - 1) hops deep."""
    return {
        "topology": {"kind": "grid", "n": n},
        "kinds": {"node-metric": 0.8, "flap": 0.2}, "vantage": "node-0",
    }


# 56 and 50 nodes and 144, all above KSP2_DEVICE_MIN_DSTS
CELL_WORLDS = (fabric_world(3, 12), fabric_world(4, 8), grid_world(12))


def _stale_rows(engine) -> list:
    """Destinations whose masked row, as the engine holds it, is not
    what a solve of its masked graph gives now. The engine's proofs
    read the rows as exact, so a stale one is a fault the day it is
    made, routes right or not."""
    from openr_tpu.ops import spf_sparse

    dsts = [d for d in engine.dsts if d not in engine.host_dsts]
    masks, ok = spf_sparse.build_edge_masks(
        engine.state.graph, [engine.excl[d] for d in dsts]
    )
    rows, _passes = spf_sparse.ell_masked_distances_resident(
        engine.state, engine.sid, masks
    )
    return [
        d for i, d in enumerate(dsts)
        if ok[i] and (rows[i] != engine.dm[engine.dst_pos[d]]).any()
    ]


def soak_cell(seed: int, world: dict, windows: int) -> dict:
    """``windows`` rebuild windows of a cell's events on ``world``
    (``fabric_world`` / ``grid_world``: the network, the mix's kinds,
    the vantage), 85%
    of them one publication (its staged sync is the window's: a hit)
    and the rest two to four (the stage is stepped on from: a cancel,
    and the build takes the union). Returns the counters the stream
    moved, or where the routes left the reference or a masked row of
    the engine's went stale."""
    from chipbench import reference_ksp2, topology, traffic
    from chipbench.served_paths import pipeline_grid  # noqa: F401 - grid
    from openr_tpu.decision.decision import Decision
    from openr_tpu.messaging.queue import ReplicateQueue
    from openr_tpu.telemetry import get_registry
    from openr_tpu.types import Publication

    vantage = world["vantage"]
    fabric = topology.build(
        world["topology"], {"algorithm": "KSP2_ED_ECMP", "type": "SR_MPLS"},
    )
    gen = traffic.Generator(fabric, seed, {"kinds": world["kinds"]}, vantage)
    kv_q = ReplicateQueue(name="soak:kvstore")
    decision = Decision(
        vantage,
        kvstore_updates_queue=kv_q,
        route_updates_queue=ReplicateQueue(name="soak:routes"),
        solver_backend="device",
    )
    rng = random.Random(seed)
    reg = get_registry()
    spec = ("ops.spec_dispatches", "ops.spec_hits", "ops.spec_cancels",
            "ops.ksp2.masked_passes", "ops.ksp2.all_pairs_passes")

    def counters() -> dict:
        return {**SPF_COUNTERS, **{k: reg.counter_get(k) for k in spec}}

    out = {"seed": seed, "world": world["topology"], "windows": windows,
           "dsts": len(fabric.adj_dbs) - 1}
    t0 = time.time()
    try:
        decision.process_publication(Publication(
            key_vals=dict(gen.initial_key_vals()), area="0"))
        decision.rebuild_routes("LOAD")
        before = counters()
        for window in range(windows):
            for _ in range(1 if rng.random() < 0.85 else rng.randint(2, 4)):
                ev = gen.draw()
                decision._on_publication(Publication(
                    key_vals={ev.key: ev.value}, area="0"))
            decision._on_debounce_fire()
            live = decision.route_db.to_route_db(vantage)
            if reference_ksp2.routes_of(live) != reference_ksp2.routes(
                gen.adj_dbs, gen.prefix_dbs, vantage
            ) or reference_ksp2.mpls_routes_of(
                live
            ) != reference_ksp2.mpls_routes(gen.adj_dbs, vantage):
                return {**out, "window": window, "parity": "BROKEN"}
            (engine,) = decision.spf_solver._ksp2_engines.values()
            stale = _stale_rows(engine)
            if stale:
                return {**out, "window": window, "parity": "BROKEN",
                        "stale_rows": stale[:8]}
    finally:
        kv_q.close()
    out["parity"] = "ok"
    out["moved"] = {
        k: v - before.get(k, 0)
        for k, v in counters().items()
        if k.startswith("decision.ksp2_") or k in spec
    }
    out["wall_s"] = round(time.time() - t0, 1)
    return out


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--steps", type=int, default=40)
    p.add_argument("--cell", action="store_true",
                   help="the cell's events against the plain reference")
    args = p.parse_args()
    # engine active regardless of destination count
    _ss.KSP2_DEVICE_MIN_DSTS = 1
    worlds = [("grid", 5), ("fabric", 120)]
    rc = 0
    for seed in range(args.seeds):
        if args.cell:
            out = soak_cell(
                seed, CELL_WORLDS[seed % len(CELL_WORLDS)], args.steps
            )
        else:
            kind, n = worlds[seed % len(worlds)]
            out = soak_one(seed, kind, n, args.steps)
        print(json.dumps(out), flush=True)
        if out.get("parity") != "ok":
            rc = 1
            break
    return rc


if __name__ == "__main__":
    sys.exit(main())
