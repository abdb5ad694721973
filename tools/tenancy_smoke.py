#!/usr/bin/env python
"""Tenant-plane gate (``make tenancy-smoke``) and report artifact.

Exercises the multi-tenant batched-worlds subsystem
(``openr_tpu.ops.world_batch``) end to end with B=8 mixed-size tenants
spanning two shape buckets, then fails loudly if the tenancy contract
regressed:

- per-tenant BIT PARITY: every batched view (cold build, metric churn,
  link flap, overload flip) must equal the sequential single-graph
  engine's ``ell_view_batch_packed`` output byte for byte,
- COMPILE FLATNESS: once the shape buckets are warm, new tenants
  joining them (and warm churn re-solves) must cost ZERO jit compiles
  (``jax.compile_count`` ceiling == 0 after warmup),
- EVICTION ROUND TRIP: overcommitting a 2-slot bucket must evict to
  host snapshots and REHYDRATE WARM on re-admission (rehydrations and
  warm_solves counted, zero cold solves, bits still identical),
- the batched-vs-sequential per-tenant dispatch timing ratio is
  reported as an artifact field (a CPU ratio, gated nowhere; on the
  chip it is not measured — ROADMAP R3).

Writes a JSON artifact (``--out``, default
``/tmp/openr_tpu_tenancy_smoke.json``); exit 0 on pass, 1 with a
reason list on fail. Runs CPU-pinned — this gates the tenant plane's
bookkeeping and kernels, not device throughput.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# allow direct invocation (python tools/tenancy_smoke.py) in addition
# to module mode (python -m tools.tenancy_smoke)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_tenants():
    import numpy as np  # noqa: F401

    from openr_tpu.graph.linkstate import LinkState
    from openr_tpu.models import topologies

    topos = [
        topologies.grid(3),
        topologies.grid(4),
        topologies.grid(5),
        topologies.random_mesh(20, 3, seed=7),
        topologies.random_mesh(30, 4, seed=11),
        topologies.random_mesh(48, 4, seed=13),
        topologies.random_mesh(64, 3, seed=17),
        topologies.random_mesh(150, 3, seed=19),
    ]
    lss = []
    for topo in topos:
        ls = LinkState(area=topo.area)
        for _name, db in sorted(topo.adj_dbs.items()):
            ls.update_adjacency_database(db)
        lss.append(ls)
    return [
        (f"t{i}", ls, sorted(ls.get_adjacency_databases())[0])
        for i, ls in enumerate(lss)
    ]


def _mutate_metric(ls, node, i, metric):
    from dataclasses import replace

    db = ls.get_adjacency_databases()[node]
    adjs = list(db.adjacencies)
    adjs[i] = replace(adjs[i], metric=metric)
    ls.update_adjacency_database(replace(db, adjacencies=tuple(adjs)))


def _flap_link(ls, node):
    from dataclasses import replace

    db = ls.get_adjacency_databases()[node]
    adjs = list(db.adjacencies)
    dropped = adjs.pop(0)
    ls.update_adjacency_database(replace(db, adjacencies=tuple(adjs)))
    return dropped


def _restore_link(ls, node, adj):
    from dataclasses import replace

    db = ls.get_adjacency_databases()[node]
    ls.update_adjacency_database(
        replace(db, adjacencies=tuple(list(db.adjacencies) + [adj]))
    )


def _check_parity(mgr, items, tag, failures):
    import numpy as np

    from openr_tpu.ops.spf_sparse import (
        compile_ell,
        ell_source_batch,
        ell_view_batch_packed,
    )

    views = mgr.solve_views(items)
    bad = 0
    for (tid, ls, root), (_g, srcs, packed) in zip(items, views):
        graph = compile_ell(ls)
        ref_srcs = ell_source_batch(graph, ls, root)
        ref = np.asarray(ell_view_batch_packed(graph, ref_srcs))
        if srcs != ref_srcs or not np.array_equal(packed, ref):
            bad += 1
    if bad:
        failures.append(f"{tag}: {bad}/{len(items)} tenants diverged")
    return bad == 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--out", default="/tmp/openr_tpu_tenancy_smoke.json"
    )
    parser.add_argument(
        "--timing-rounds",
        type=int,
        default=5,
        help="rounds for the informational batched-vs-seq timing",
    )
    args = parser.parse_args(argv)

    from openr_tpu.ops.spf_sparse import (
        compile_ell,
        ell_source_batch,
        ell_view_batch_packed,
    )
    from openr_tpu.ops.world_batch import TENANCY_COUNTERS, WorldManager
    from openr_tpu.telemetry import get_registry, jax_hooks

    hooks_live = jax_hooks.install()
    reg = get_registry()
    failures: list = []
    report: dict = {"gates": {}}

    # -- gate 1: B=8 mixed-size parity across cold + churn ----------------
    items = _build_tenants()
    mgr = WorldManager(slots_per_bucket=8)
    _check_parity(mgr, items, "cold", failures)
    report["gates"]["cold_parity"] = not failures
    for _tid, ls, root in items[::2]:
        _mutate_metric(ls, root, 0, 55)
    _check_parity(mgr, items, "metric-churn", failures)
    ls3 = items[3][1]
    node3 = sorted(ls3.get_adjacency_databases())[1]
    dropped = _flap_link(ls3, node3)
    _check_parity(mgr, items, "link-down", failures)
    _restore_link(ls3, node3, dropped)
    _check_parity(mgr, items, "link-up", failures)
    report["gates"]["churn_parity"] = not failures
    report["buckets"] = mgr.bucket_count()
    if mgr.bucket_count() < 2:
        failures.append(
            "expected mixed-size tenants to span >=2 shape buckets"
        )

    # -- gate 2: compile-count ceiling ------------------------------------
    if hooks_live:
        compiles0 = reg.counter_get("jax.compile_count")
        join = [
            (f"j{i}", ls, root)
            for i, (_t, ls, root) in enumerate(_build_tenants())
        ]
        for _tid, ls, root in join:
            _mutate_metric(ls, root, 0, 33)
        mgr.solve_views(join)
        for _tid, ls, root in items[::2]:
            _mutate_metric(ls, root, 0, 66)
        mgr.solve_views(items)
        compile_delta = reg.counter_get("jax.compile_count") - compiles0
        report["gates"]["compile_delta_after_warmup"] = compile_delta
        if compile_delta > 0:
            failures.append(
                f"jit retraced {compile_delta}x after bucket warmup "
                "(bucket join / warm churn must be retrace-free)"
            )
    else:
        report["gates"]["compile_delta_after_warmup"] = None

    # -- gate 3: eviction round trip --------------------------------------
    ev_items = [
        (f"e{i}", ls, root)
        for i, (_t, ls, root) in enumerate(_build_tenants()[:3])
    ]
    small = WorldManager(slots_per_bucket=2)
    ev0 = TENANCY_COUNTERS["evictions"]
    _check_parity(small, ev_items, "evict-wave", failures)
    if TENANCY_COUNTERS["evictions"] - ev0 < 1:
        failures.append("overcommitted bucket produced no evictions")
    evicted = [
        t
        for t in (small._tenants[tid] for tid, _ls, _r in ev_items)
        if t.slot is None and t.solved
    ]
    if not evicted:
        failures.append("no solved tenant was evicted to host snapshot")
    else:
        tid = evicted[0].tenant_id
        idx = [t for t, _ls, _r in ev_items].index(tid)
        ls = ev_items[idx][1]
        _mutate_metric(
            ls, sorted(ls.get_adjacency_databases())[0], 0, 123
        )
        r0 = TENANCY_COUNTERS["rehydrations"]
        w0 = TENANCY_COUNTERS["warm_solves"]
        c0 = TENANCY_COUNTERS["cold_solves"]
        _check_parity(small, ev_items, "rehydrate", failures)
        if TENANCY_COUNTERS["rehydrations"] - r0 < 1:
            failures.append("re-admission did not count a rehydration")
        if TENANCY_COUNTERS["warm_solves"] - w0 < 1:
            failures.append("rehydrated tenant did not solve WARM")
        if TENANCY_COUNTERS["cold_solves"] - c0 > 0:
            failures.append(
                "rehydration paid a cold solve (journal replay broken)"
            )
    report["gates"]["eviction_round_trip"] = not any(
        "rehydrat" in f or "evict" in f for f in failures
    )

    # -- informational timing: batched vs sequential ----------------------
    t_batched = t_seq = 0.0
    for round_i in range(max(1, args.timing_rounds)):
        for _tid, ls, root in items:
            _mutate_metric(ls, root, 0, 40 + round_i)
        t0 = time.perf_counter()
        mgr.solve_views(items)
        t_batched += time.perf_counter() - t0
        t0 = time.perf_counter()
        for _tid, ls, root in items:
            graph = compile_ell(ls)
            ell_view_batch_packed(
                graph, ell_source_batch(graph, ls, root)
            )
        t_seq += time.perf_counter() - t0
    report["timing"] = {
        "rounds": args.timing_rounds,
        "batched_ms_per_round": 1000.0 * t_batched / args.timing_rounds,
        "sequential_cold_ms_per_round": (
            1000.0 * t_seq / args.timing_rounds
        ),
        "ratio": (t_batched / t_seq) if t_seq else None,
    }

    report["counters"] = {
        f"tenancy.{k}": TENANCY_COUNTERS[k] for k in TENANCY_COUNTERS
    }
    report["failures"] = failures
    report["passed"] = not failures
    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=2, sort_keys=True)
    print(json.dumps(report["gates"], indent=2, sort_keys=True))
    if failures:
        print("TENANCY SMOKE FAILED:", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
        return 1
    print(f"tenancy smoke passed; report at {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
