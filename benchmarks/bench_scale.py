"""Scale benchmark: sparse all-sources SPF on large fat-trees.

The BASELINE.json scale configs ("Incremental SPF under link-flap churn
... 10k-node", "100k-node ... all-sources SPF sharded") need the sparse
edge-list kernel — the dense N x N matrix stops being feasible past a
few thousand nodes. This harness times all-sources distances on a
10k-node (default; --nodes for other sizes) 3-tier fat-tree, blocked
over source chunks so the [S, E] relaxation temporary stays bounded.

On one chip the source blocks run sequentially; on a mesh each device
owns a block slice (openr_tpu.ops.spf_sparse.sharded_sparse_all_sources)
— same kernel, sharded source axis.

Run:  python -m benchmarks.bench_scale [--nodes 10000] [--block 1024]
Prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from openr_tpu.graph.linkstate import LinkState
from openr_tpu.models import topologies
from openr_tpu.ops import spf_sparse
from openr_tpu.utils import compile_cache

compile_cache.enable()


def _dispatch_readback_rtt_ms() -> float:
    """Median of five MINIMAL dispatch+readback round trips — the fixed
    cost every readback pays on this host and device. Recorded in churn
    artifacts so an event median decomposes into host work + k round
    trips."""
    import statistics

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda v: v + 1)
    x = jnp.zeros((8,), jnp.int32)
    np.asarray(f(x))  # warm the compile
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(f(x))
        ts.append((time.perf_counter() - t0) * 1000)
    return round(statistics.median(ts), 2)


def _host_touches_by_tag() -> dict:
    """Per-tag ``ops.host_touches.<tag>`` p50s from the live registry:
    which event-window tags ran, and how many host turnarounds each
    cost per window (2 == the warm committed-dispatch contract)."""
    from openr_tpu.telemetry import get_registry

    out = {}
    for name, h in get_registry().histograms().items():
        if name.startswith("ops.host_touches.") and h.count:
            out[name[len("ops.host_touches."):]] = {
                "p50": round(h.percentile(0.50), 1),
                "count": h.count,
            }
    return out


def _get_profiler():
    from openr_tpu.telemetry import get_profiler

    return get_profiler()


def _chained_device_only_ms(step, readback, k: int = 4,
                            reps: int = 5) -> float:
    """Per-dispatch device time via K data-dependent chained dispatches
    against ONE readback: the fixed dispatch-and-readback cost
    cancels in (T_K - T_1) / (K - 1). ``step(prev)`` issues
    the next dispatch (prev is None on the first); ``readback(result)``
    forces one device->host sync. Shared by every bench in this module
    — the methodology must stay identical across benches."""
    import statistics

    def time_chain(kk: int) -> float:
        t0 = time.perf_counter()
        out = None
        for _ in range(kk):
            out = step(out)
        readback(out)
        return (time.perf_counter() - t0) * 1000.0

    time_chain(1)  # warm any K=1 cache path
    t1 = statistics.median(time_chain(1) for _ in range(reps))
    tk = statistics.median(time_chain(k) for _ in range(reps))
    return round(max(0.0, (tk - t1) / (k - 1)), 3)


def _latency_percentiles(samples) -> dict:
    """Nearest-rank p50/p95/p99 for a per-event latency sample list —
    the DeltaPath-style distribution account every churn leg reports
    alongside its median (means hide the warm/cold split)."""
    if not samples:
        return {"p50_ms": None, "p95_ms": None, "p99_ms": None}
    import math

    ordered = sorted(samples)
    n = len(ordered)

    def rank(q: float) -> float:
        # nearest-rank: ceil(q*n)-th smallest, 1-indexed
        return round(
            ordered[min(n - 1, max(0, math.ceil(q * n) - 1))], 3
        )

    return {
        "p50_ms": rank(0.50),
        "p95_ms": rank(0.95),
        "p99_ms": rank(0.99),
    }


def churn_bench(nodes: int, churn_events: int) -> dict:
    """Incremental reconvergence under link-flap churn at ``nodes`` scale
    (BASELINE.json config 4) over the resident ELL graph: per event the
    host patches O(degree) edge rows, one fused dispatch re-solves the
    {src} + neighbors view, one readback returns it. Returns the result
    dict (shared by ``--churn`` here and the official ``bench.py``)."""
    import statistics

    from openr_tpu.ops import spf_sparse
    from dataclasses import replace

    topo = topologies.fat_tree_nodes(nodes)
    ls = LinkState(area=topo.area)
    for name in sorted(topo.adj_dbs):
        ls.update_adjacency_database(topo.adj_dbs[name])
    graph = spf_sparse.compile_ell(ls)

    my_node = next(k for k in sorted(topo.adj_dbs) if k.startswith("rsw"))
    churn_node = next(
        k for k in sorted(topo.adj_dbs) if k.startswith("fsw")
    )
    srcs = spf_sparse.ell_source_batch(graph, ls, my_node)

    state = spf_sparse.EllState(graph)

    def churn(step):
        db = ls.get_adjacency_databases()[churn_node]
        adjs = list(db.adjacencies)
        a0 = adjs[0]
        adjs[0] = replace(a0, metric=2 + step % 5)
        ls.update_adjacency_database(
            replace(db, adjacencies=tuple(adjs))
        )
        return {churn_node, a0.other_node_name}

    def reconverge(affected):
        nonlocal srcs
        patched = spf_sparse.ell_patch(state.graph, ls, sorted(affected))
        if patched is None:
            # node set changed / row outgrew its class: full recompile
            # (renumbers node ids, so the source batch must be rebuilt)
            state.__init__(spf_sparse.compile_ell(ls))
            patched = state.graph
            srcs = spf_sparse.ell_source_batch(patched, ls, my_node)
        return np.asarray(state.reconverge(patched, srcs))

    packed = reconverge({my_node})  # warm-up compile
    # oracle gate on the warm result
    oracle = ls.run_spf(my_node)
    from openr_tpu.ops.spf import INF

    d0 = packed[: len(srcs)][0]
    for dst in list(graph.node_names)[:: max(1, graph.n // 50)]:
        did = graph.node_index[dst]
        want = oracle[dst].metric if dst in oracle else None
        assert (int(d0[did]) >= INF) == (want is None), dst
        if want is not None:
            assert int(d0[did]) == want, dst

    reconverge(churn(99))  # compile the patch-bucket program
    c0 = dict(spf_sparse.ELL_COUNTERS)
    samples = []
    for step in range(churn_events):
        affected = churn(step)
        t0 = time.perf_counter()
        reconverge(affected)
        samples.append((time.perf_counter() - t0) * 1000)
    c1 = dict(spf_sparse.ELL_COUNTERS)
    # post-churn oracle gate: the WARM-started path must still match
    # the host Dijkstra bit-for-bit after the whole mixed sequence
    packed = reconverge(churn(churn_events))
    oracle = ls.run_spf(my_node)
    d_after = packed[: len(srcs)][0]
    for dst in list(graph.node_names)[:: max(1, graph.n // 50)]:
        did = graph.node_index[dst]
        want = oracle[dst].metric if dst in oracle else None
        assert (int(d_after[did]) >= INF) == (want is None), dst
        if want is not None:
            assert int(d_after[did]) == want, dst
    import jax

    platform = jax.devices()[0].platform
    device_only = _chained_device_only_ms(
        lambda _prev: state.reconverge(state.graph, srcs),
        np.asarray,
        k=8,
    )
    median = round(statistics.median(samples), 1)
    return {
        "bench": f"scale.ell_churn_reconverge_{graph.n}_nodes",
        "events": churn_events,
        "median_ms": median,
        # nearest-rank p90 (index 8 of 10, not the max)
        "p90_ms": round(
            sorted(samples)[max(0, -(-len(samples) * 9 // 10) - 1)], 1
        ),
        **_latency_percentiles(samples),
        "device_only_ms": device_only,
        "host_overhead_ms": round(max(0.0, median - device_only), 3),
        "incremental_syncs": c1["ell_incremental_syncs"]
        - c0["ell_incremental_syncs"],
        "warm_solves": c1["ell_warm_solves"] - c0["ell_warm_solves"],
        "cold_solves": c1["ell_cold_solves"] - c0["ell_cold_solves"],
        "widen_events": c1["ell_widen_events"] - c0["ell_widen_events"],
        "platform": platform,
        "oracle_spot_check": "passed",
    }


def run_churn(args):
    print(
        json.dumps(churn_bench(args.nodes, args.churn_events)),
        flush=True,
    )


def convergence_trace_bench(
    nodes: int,
    churn_events: int = 6,
    trace_path: str = "",
    solver_backend: str = "device",
) -> dict:
    """Per-event convergence latency through the REAL module pipeline —
    KvStore publication -> Decision debounce + solve -> Fib program —
    with the telemetry tracer accounting every stage. Unlike the
    solver-only churn legs this measures the daemon path the north-star
    claim is actually about, and emits the trace artifact the claim can
    be audited against (``trace_path``: JSONL, one trace per line,
    loadable span-by-span; plus ``<trace_path>.chrome.json`` for
    chrome://tracing / Perfetto)."""
    import os
    from dataclasses import replace

    import jax

    from openr_tpu.decision.decision import Decision
    from openr_tpu.fib.fib import Fib
    from openr_tpu.kvstore.wrapper import KvStoreWrapper
    from openr_tpu.messaging.queue import ReplicateQueue
    from openr_tpu.platform.fib_service import MockFibAgent
    from openr_tpu.telemetry import get_registry, get_tracer
    from openr_tpu.types import (
        DEFAULT_AREA,
        TTL_INFINITY,
        KeySetParams,
        Value,
    )
    from openr_tpu.utils import keys as keyutil
    from openr_tpu.utils import wire

    topo = topologies.fat_tree_nodes(nodes)
    rsw = next(k for k in sorted(topo.adj_dbs) if k.startswith("rsw"))
    fsw = next(k for k in sorted(topo.adj_dbs) if k.startswith("fsw"))

    store = KvStoreWrapper(f"bench:{rsw}")
    route_q = ReplicateQueue(name="routeUpdates")
    decision = Decision(
        rsw,
        kvstore_updates_queue=store.store.updates_queue,
        route_updates_queue=route_q,
        debounce_min_s=0.01,
        debounce_max_s=0.25,
        solver_backend=solver_backend,
    )
    fib = Fib(rsw, MockFibAgent(), route_q, keepalive_interval_s=30.0)
    tracer = get_tracer()
    n_ring0 = len(tracer.traces())

    versions: dict = {}

    def publish(key: str, payload: bytes, originator: str) -> None:
        v = versions[key] = versions.get(key, 0) + 1
        store.set_key(key, payload, version=v, originator=originator)

    def wait_until(pred, timeout: float) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if pred():
                return True
            time.sleep(0.005)
        return pred()

    store.start()
    decision.start()
    fib.start()
    try:
        # BULK initial load: one set_key_vals publication for the whole
        # topology so Decision sees ONE debounce window and does ONE
        # full cold build — per-key publishing at 10k+ nodes streams for
        # minutes, each debounce firing a partial-topology rebuild (and
        # a fresh jit compile at that partial shape)
        initial: dict = {}
        for name in sorted(topo.adj_dbs):
            key = keyutil.adj_key(name)
            payload = wire.dumps(topo.adj_dbs[name])
            versions[key] = 1
            initial[key] = Value(
                version=1,
                originator_id=name,
                value=payload,
                ttl=TTL_INFINITY,
                hash=wire.generate_hash(1, name, payload),
            )
        for name in sorted(topo.prefix_dbs):
            key = keyutil.prefix_db_key(name)
            payload = wire.dumps(topo.prefix_dbs[name])
            versions[key] = 1
            initial[key] = Value(
                version=1,
                originator_id=name,
                value=payload,
                ttl=TTL_INFINITY,
                hash=wire.generate_hash(1, name, payload),
            )
        store.store.set_key_vals(
            DEFAULT_AREA, KeySetParams(key_vals=initial)
        )
        # initial convergence (includes the solver's first compiles)
        assert wait_until(
            lambda: len(fib.get_route_db().unicast_routes) > 0, 1800.0
        ), "initial convergence timed out"
        # settle any still-debouncing startup publications
        wait_until(lambda: False, 0.6)

        n_before = len(tracer.traces())
        for step in range(churn_events):
            db = topo.adj_dbs[fsw]
            adjs = list(db.adjacencies)
            adjs[0] = replace(adjs[0], metric=2 + step % 5)
            db = replace(db, adjacencies=tuple(adjs))
            topo.adj_dbs[fsw] = db
            want = len(tracer.traces())
            publish(keyutil.adj_key(fsw), wire.dumps(db), fsw)
            # one traced publication -> FIB cycle per event: wait for
            # the trace to retire before the next churn so debounce
            # merges never collapse the sample count
            assert wait_until(
                lambda: len(tracer.traces()) > want, 120.0
            ), f"churn event {step} never completed a trace"
    finally:
        fib.stop()
        decision.stop()
        store.stop()

    churn_traces = tracer.traces()[n_before:]
    complete = [t for t in churn_traces if t.complete and t.well_formed()]
    e2e = [t.e2e_ms for t in complete if t.e2e_ms is not None]

    artifact = None
    if trace_path:
        os.makedirs(
            os.path.dirname(os.path.abspath(trace_path)), exist_ok=True
        )
        with open(trace_path, "w") as f:
            f.write(
                "\n".join(
                    json.dumps(t.to_dict()) for t in churn_traces
                )
                + "\n"
            )
        with open(trace_path + ".chrome.json", "w") as f:
            json.dump(tracer.chrome_trace(), f)
        artifact = trace_path

    span_ms = {}
    for span_name in ("decision.debounce", "decision.rebuild", "fib.program"):
        durs = [
            s.dur_ms
            for t in complete
            for s in t.spans
            if s.name == span_name and s.dur_ms is not None
        ]
        if durs:
            span_ms[span_name] = _latency_percentiles(durs)

    snap = get_registry().snapshot()
    return {
        "bench": f"scale.convergence_trace_{nodes}_nodes",
        "events": churn_events,
        "traces_complete": len(complete),
        "traces_incomplete": len(churn_traces) - len(complete),
        "unclosed_spans": snap.get("telemetry.traces_unclosed_spans", 0),
        "median_ms": (
            round(sorted(e2e)[len(e2e) // 2], 3) if e2e else None
        ),
        **_latency_percentiles(e2e),
        "span_ms": span_ms,
        "trace_artifact": artifact,
        "platform": jax.devices()[0].platform,
        "solver_backend": solver_backend,
        "ring_total": len(tracer.traces()) - n_ring0,
    }


def ksp2_churn_bench(nodes: int, churn_events: int,
                     ksp2_dst_count: int = 0,
                     sp_only: bool = False) -> dict:
    """Fabric churn rebuild through the full SpfSolver — the
    incremental-KSP2-engine path (BASELINE.json config 2 axis;
    reference semantics: Decision.cpp:908 selectBestPathsKsp2).
    Shared by the scale harness and the official bench.py artifact.

    ``ksp2_dst_count`` > 0 marks only that many (evenly sampled)
    prefixes as KSP2_ED_ECMP and leaves the rest SP_ECMP — the
    realistic large-fabric shape (KSP2 is a per-prefix opt-in) and the
    one that scales the ENGINE to 10k+ nodes: the all-pairs event
    dispatch covers the whole graph while host path tracing stays
    bounded by the KSP2 destination count.

    ``sp_only=True`` keeps every prefix SP_ECMP — the north-star
    framing (BASELINE.json: full-SPF reconvergence of one node's
    RouteDb at 100k): per event the device re-solves the
    {source}+neighbors view in one fused dispatch and the SP route
    reuse dirty test bounds the host rebuild to O(changed) prefixes;
    no all-pairs state exists at all."""
    import statistics
    from dataclasses import replace

    import jax

    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.spf_solver import (
        SPF_COUNTERS,
        SpfSolver,
    )
    from openr_tpu.types.lsdb import (
        PrefixForwardingAlgorithm,
        PrefixForwardingType,
    )

    if sp_only and ksp2_dst_count > 0:
        raise ValueError(
            "sp_only excludes ksp2_dst_count: pick one shape"
        )
    all_ksp2 = ksp2_dst_count <= 0 and not sp_only
    topo = topologies.fat_tree_nodes(
        nodes,
        forwarding_algorithm=(
            PrefixForwardingAlgorithm.KSP2_ED_ECMP
            if all_ksp2
            else PrefixForwardingAlgorithm.SP_ECMP
        ),
        forwarding_type=PrefixForwardingType.SR_MPLS,
    )
    ls = LinkState(area=topo.area)
    for name in sorted(topo.adj_dbs):
        ls.update_adjacency_database(topo.adj_dbs[name])
    ps = PrefixState()
    if ksp2_dst_count > 0:
        names = sorted(topo.prefix_dbs)
        stride = max(1, len(names) // ksp2_dst_count)
        chosen = set(names[::stride][:ksp2_dst_count])
        for name in names:
            pdb = topo.prefix_dbs[name]
            if name in chosen:
                pdb = replace(
                    pdb,
                    prefix_entries=tuple(
                        replace(
                            e,
                            forwarding_algorithm=(
                                PrefixForwardingAlgorithm.KSP2_ED_ECMP
                            ),
                        )
                        for e in pdb.prefix_entries
                    ),
                )
            topo.prefix_dbs[name] = pdb
    for pdb in topo.prefix_dbs.values():
        ps.update_prefix_database(pdb)
    area_ls = {topo.area: ls}
    rsw = next(k for k in sorted(topo.adj_dbs) if k.startswith("rsw"))
    fsw = next(k for k in sorted(topo.adj_dbs) if k.startswith("fsw"))
    solver = SpfSolver(rsw, backend="device")
    t0 = time.perf_counter()
    solver.build_route_db(rsw, area_ls, ps)
    cold_ms = (time.perf_counter() - t0) * 1000

    def churn(step):
        db = ls.get_adjacency_databases()[fsw]
        adjs = list(db.adjacencies)
        adjs[0] = replace(adjs[0], metric=2 + step % 5)
        ls.update_adjacency_database(replace(db, adjacencies=tuple(adjs)))

    # one full metric cycle warms every jit shape (engine cold build +
    # each masked-batch bucket) before the timed window
    for step in range(5):
        churn(step)
        solver.build_route_db(rsw, area_ls, ps)

    from openr_tpu.decision.spf_solver import get_spf_counters

    from openr_tpu.telemetry import get_registry

    _reg = get_registry()
    pd0 = _reg.counter_get("ops.pipelined_dispatches")
    or0 = _reg.counter_get("ops.overlapped_reaps")
    before = get_spf_counters()
    samples = []
    for step in range(churn_events):
        churn(step)
        t0 = time.perf_counter()
        solver.build_route_db(rsw, area_ls, ps)
        samples.append((time.perf_counter() - t0) * 1000)
    after = get_spf_counters()
    pipelined = _reg.counter_get("ops.pipelined_dispatches") - pd0
    overlapped = _reg.counter_get("ops.overlapped_reaps") - or0

    # SPECULATED leg: stage the warm view solve while a debounce
    # timer would have idled (the decision terminal's move), then
    # rebuild — the staged SpfView adopts (ops.spec_hits) and the
    # rebuild's solve window starts already warm
    spec_d0 = _reg.counter_get("ops.spec_dispatches")
    spec_h0 = _reg.counter_get("ops.spec_hits")
    spec_samples = []
    for step in range(3):
        churn(churn_events + step)
        solver.speculate_views(rsw, area_ls)
        t0 = time.perf_counter()
        solver.build_route_db(rsw, area_ls, ps)
        spec_samples.append((time.perf_counter() - t0) * 1000)
    spec_dispatches = _reg.counter_get("ops.spec_dispatches") - spec_d0
    spec_hits = _reg.counter_get("ops.spec_hits") - spec_h0

    _pd_hist = _reg.histograms().get("ops.pipeline_depth")
    _occ_hist = _reg.histograms().get("ops.host_touches.ksp2_window")
    rtt_ms = _dispatch_readback_rtt_ms()
    batches_per_event = round(
        (SPF_COUNTERS["decision.ksp2_device_batches"]
         - before["decision.ksp2_device_batches"])
        / max(1, churn_events),
        2,
    )
    overlapped_per_event = overlapped / max(1, churn_events)
    return {
        "bench": (
            f"scale.fabric_{ls.num_nodes}_sp_churn_rebuild"
            if sp_only
            else f"scale.fabric_{ls.num_nodes}_ksp2_churn_rebuild"
        ),
        "ksp2_dsts": (
            0
            if sp_only
            else ksp2_dst_count if not all_ksp2 else ls.num_nodes
        ),
        "sp_route_reuses_per_event": round(
            (SPF_COUNTERS["decision.sp_route_reuses"]
             - before["decision.sp_route_reuses"])
            / max(1, churn_events),
            1,
        ),
        "events": churn_events,
        "median_ms": round(statistics.median(samples), 1),
        "p90_ms": round(
            sorted(samples)[max(0, -(-len(samples) * 9 // 10) - 1)], 1
        ),
        **_latency_percentiles(samples),
        "cold_build_ms": round(cold_ms, 1),
        "platform": jax.devices()[0].platform,
        "ksp2_host_fallbacks": SPF_COUNTERS[
            "decision.ksp2_host_fallbacks"
        ] - before["decision.ksp2_host_fallbacks"],
        # incremental device syncs per kind: the engine's fused
        # all-pairs dispatch (KSP2 shapes), plus the resident ELL band
        # deltas (the SpfView path) reported separately — they cover
        # the SAME events, so summing would double-count
        "incremental_syncs": after["decision.ksp2_incremental_syncs"]
        - before["decision.ksp2_incremental_syncs"],
        "ell_incremental_syncs": (
            after.get("decision.ell_incremental_syncs", 0)
            - before.get("decision.ell_incremental_syncs", 0)
        ),
        "warm_solves": after.get("decision.ell_warm_solves", 0)
        - before.get("decision.ell_warm_solves", 0),
        "warm_dispatches": after.get("decision.ksp2_warm_dispatches", 0)
        - before.get("decision.ksp2_warm_dispatches", 0),
        "ell_full_compiles": after["decision.ell_full_compiles"]
        - before["decision.ell_full_compiles"],
        "prewarms": after["decision.ell_prewarms"]
        - before["decision.ell_prewarms"],
        # device ROUND TRIPS per event: each dispatch+readback pays
        # the fixed round trip measured above, so this is the
        # fixed-cost multiplier of the e2e median (the speculative
        # one-round-trip fast path exists to drive it to 1)
        "device_batches_per_event": batches_per_event,
        "dispatch_readback_rtt_ms": rtt_ms,
        # pipelined-window fields (PR 16): the KSP2 chunk pipeline runs
        # one chunk deep — chunk i+1's masked solve is on the stream
        # before chunk i's reap lands — so of the k chunk round trips
        # per event, ``overlapped`` hid their host turnaround behind
        # device work; the amortized RTT is what each chunk
        # EFFECTIVELY pays once the overlap is netted out
        "pipelined_dispatches_per_event": round(
            pipelined / max(1, churn_events), 2
        ),
        "overlapped_reaps_per_event": round(overlapped_per_event, 2),
        "pipeline_depth_median": (
            round(_pd_hist.percentile(0.50), 1)
            if _pd_hist is not None and _pd_hist.count else None
        ),
        "window_occupancy_touches_p50": (
            round(_occ_hist.percentile(0.50), 1)
            if _occ_hist is not None and _occ_hist.count else None
        ),
        "dispatch_readback_rtt_amortized_ms": round(
            rtt_ms
            * max(0.0, batches_per_event - overlapped_per_event)
            / max(1.0, batches_per_event),
            2,
        ) if batches_per_event else rtt_ms,
        # speculated-rebuild economics: hit rate and the per-event
        # median when the view solve was staged during the debounce
        "spec_dispatches": int(spec_dispatches),
        "spec_hit_rate": (
            round(spec_hits / spec_dispatches, 2)
            if spec_dispatches else None
        ),
        "spec_median_ms": round(statistics.median(spec_samples), 1),
    }


def all_sources_bench(
    nodes: int, block: int, kernel: str = "ell",
    max_blocks: int = 0,
) -> dict:
    """All-sources SPF at ``nodes`` scale (BASELINE.json config 5 axis).
    kernel="ell": sliced-ELL gather+reduce blocks (the TPU-fast path);
    kernel="edges": the flat dst-sorted edge list + segment-min (kept
    for comparison — segment-min lowers to serialized scatters on TPU).
    Device-only per-block time is isolated by chaining K block solves
    against one readback, same as bench.py (the fixed readback cost
    cancels)."""
    import statistics

    import jax

    topo = topologies.fat_tree_nodes(nodes)
    ls = LinkState(area=topo.area)
    for name in sorted(topo.adj_dbs):
        ls.update_adjacency_database(topo.adj_dbs[name])
    platform = jax.devices()[0].platform

    t0 = time.perf_counter()
    if kernel == "ell":
        graph = spf_sparse.compile_ell(ls)
        state = spf_sparse.EllState(graph)
        edges = int(
            sum((w < 2 ** 30 - 1).sum() for w in graph.w)
        )
        import jax.numpy as jnp

        def solve_block(ids):
            if not isinstance(ids, jax.Array):
                ids = jnp.asarray(np.asarray(ids, dtype=np.int32))
            return spf_sparse.ell_distances_from_sources(
                graph, ids, state=state
            )

    else:
        graph = spf_sparse.compile_sparse(ls)
        edges = int(np.sum(graph.full_w < 2 ** 30 - 1))

        def solve_block(ids):
            return spf_sparse.sparse_distances_from_sources(graph, ids)

    compile_ms = (time.perf_counter() - t0) * 1000

    n = graph.n_pad
    # warm-up one block (jit compile)
    np.asarray(solve_block(np.arange(block, dtype=np.int32)))

    # device-only per-block FIRST (chain K data-dependent solves, one
    # readback — the fixed readback cost cancels in the K-vs-1
    # difference): the full sweep below queues the whole [N, N] product
    # for transfer to the host, and that backlog would otherwise
    # inflate the chained timing
    device_only_block_ms = None
    if platform != "cpu":
        ids0 = np.arange(block, dtype=np.int32)
        device_only_block_ms = _chained_device_only_ms(
            # data dependence: seed block i from block i-1's result
            lambda d: solve_block(
                ids0 if d is None else (ids0 + d[0, 0] % n) % n
            ),
            lambda d: np.asarray(d[0, 0]),
        )

    # e2e streaming sweep: solve + read back every block ([N, N] int32
    # product on the host at the end — transfer-dominated)
    import jax.numpy as jnp

    id_blocks = [
        jnp.asarray(np.arange(s, s + block, dtype=np.int32) % n)
        for s in range(0, n, block)
    ]
    if max_blocks > 0:
        # at 100k the full [N, N] readback is ~40 GB — measure a
        # representative slice and extrapolate (device_only_* already
        # covers the compute claim; the sweep is transfer-bound)
        id_blocks = id_blocks[:max_blocks]
    t0 = time.perf_counter()
    sample_row0 = None
    for i, ids in enumerate(id_blocks):
        d_blk = np.asarray(solve_block(ids))
        if i == 0:
            sample_row0 = d_blk[0]
    all_sources_ms = (time.perf_counter() - t0) * 1000

    # oracle spot checks: row 0 vs host Dijkstra
    oracle = ls.run_spf(graph.node_names[0])
    for dst in list(graph.node_names)[:: max(1, graph.n // 50)]:
        did = graph.node_index[dst]
        want = oracle[dst].metric if dst in oracle else None
        got = int(sample_row0[did])
        from openr_tpu.ops.spf import INF

        assert (got >= INF) == (want is None), dst
        if want is not None:
            assert got == want, (dst, got, want)

    n_blocks = -(-n // block)
    out = {
        "bench": f"scale.{kernel}_all_sources_{graph.n}_nodes",
        "kernel": kernel,
        "edges": edges,
        "edge_compile_ms": round(compile_ms, 1),
        "all_sources_ms": round(all_sources_ms, 1),
        "source_block": block,
        "swept_blocks": len(id_blocks),
        "total_blocks": n_blocks,
        "platform": platform,
        "oracle_spot_check": "passed",
    }
    if device_only_block_ms is not None:
        out["device_only_block_ms"] = device_only_block_ms
        out["device_only_all_sources_ms"] = round(
            device_only_block_ms * n_blocks, 1
        )
        # the remainder of the e2e sweep is host<->device transfer: the
        # [N, N] int32 product read back block-by-block
        out["readback_mb"] = round(n * block * len(id_blocks) * 4 / 1e6, 1)
        out["transfer_ms"] = round(
            max(
                0.0,
                all_sources_ms - device_only_block_ms * len(id_blocks),
            ),
            1,
        )
    return out


def route_sweep_bench(
    nodes: int, block: int, max_blocks: int = 0,
    backend: str = "ell",
) -> dict:
    """All-sources sweep with route selection CONSUMED ON-DEVICE
    (ops.route_sweep): per destination block the device computes every
    source's per-destination metric + ECMP next-hop mask, reads back
    only digests + sampled route rows. This is the transfer-fixed
    version of the config-5 axis — e2e tracks device compute instead of
    the [N, N] readback (414 MB at 10k, 40 GB at 100k).

    Oracle: sampled nodes' full route tables vs the host Dijkstra
    (reference runSpf / getNextHopsWithMetric semantics)."""
    import statistics

    import jax
    import jax.numpy as jnp

    from openr_tpu.ops import route_sweep
    from openr_tpu.ops.spf import INF

    topo = topologies.fat_tree_nodes(nodes)
    ls = LinkState(area=topo.area)
    for name in sorted(topo.adj_dbs):
        ls.update_adjacency_database(topo.adj_dbs[name])
    platform = jax.devices()[0].platform

    t0 = time.perf_counter()
    if backend == "grouped":
        from openr_tpu.ops import spf_grouped

        graph = spf_grouped.compile_out_grouped(ls)
    else:
        graph = route_sweep.compile_out_ell(ls)
    # one sample per tier: a rack, a fabric and a spine switch see
    # different band shapes and ECMP fanouts
    samples = []
    for prefix in ("rsw", "fsw", "ssw"):
        nm = next(
            (k for k in graph.node_names if k.startswith(prefix)), None
        )
        if nm is not None:
            samples.append(nm)
    if backend == "grouped":
        sweeper = spf_grouped.GroupedRouteSweeper(graph, samples)
        edges = int(sum(
            (seg.w < INF).sum()
            for band in graph.bands for seg in band.segments
        ))
    else:
        sweeper = route_sweep.RouteSweeper(graph, samples)
        edges = int(sum((w < INF).sum() for w in graph.w))
    compile_ms = (time.perf_counter() - t0) * 1000

    n = graph.n_pad
    ids0 = np.arange(block, dtype=np.int32)
    np.asarray(sweeper.solve_block(ids0))  # jit warm-up

    # device-only per-block via K data-dependent chained dispatches
    # against one readback (the fixed readback cost cancels)
    device_only_block_ms = None
    impl_ms = None
    if platform != "cpu":
        ids0_dev = jnp.asarray(ids0)

        def chain_ms():
            return _chained_device_only_ms(
                lambda p: sweeper.solve_block(
                    ids0_dev if p is None else (ids0 + p[0, 1] % n) % n
                ),
                lambda p: np.asarray(p[0, 0]),
            )

        if backend == "grouped":
            # contraction impl CHOSEN BY MEASUREMENT on real hardware
            # (same contract as the dense min-plus path): time jnp and
            # pallas at the bench shapes, run the winner, keep both
            # numbers in the artifact
            from openr_tpu.ops import spf_grouped

            impl_ms = {}
            ref = None
            for impl in ("jnp", "pallas", "pallas_t"):
                spf_grouped.set_grouped_impl(impl)
                try:
                    got = np.asarray(
                        sweeper.solve_block(ids0_dev)
                    )  # compile + parity gate vs the jnp product
                    if impl == "jnp":
                        # the gate's reference MUST be the jnp product:
                        # seeding it from a surviving pallas variant
                        # would let a shared pallas lowering bug
                        # parity-check against itself
                        ref = got
                    elif ref is None:
                        impl_ms["parity_unverified"] = impl
                    elif not np.array_equal(ref, got):
                        # parity failure is a CORRECTNESS signal, not an
                        # ordinary probe error: record it distinctly so a
                        # pallas/jnp divergence on real hardware is
                        # front-and-center in the artifact rather than
                        # buried in an _error string
                        impl_ms["parity_failed"] = impl
                        raise RuntimeError("pallas/jnp divergence")
                    impl_ms[impl] = chain_ms()
                except Exception as e:  # pallas probe must not kill jnp
                    impl_ms[impl] = None
                    impl_ms[f"{impl}_error"] = (
                        f"{type(e).__name__}: {e}"
                    )
            timed = [
                (v, k) for k, v in impl_ms.items()
                if isinstance(v, (int, float))
            ]
            if not timed:
                raise RuntimeError(
                    f"both contraction impls failed: {impl_ms}"
                )
            winner = min(timed)[1]
            spf_grouped.set_grouped_impl(winner)
            device_only_block_ms = impl_ms[winner]
        else:
            device_only_block_ms = chain_ms()

    # e2e sweep: every destination block solved AND route-selected on
    # device; the host receives digests + sampled route rows only
    n_sweep = min(n, max_blocks * block) if max_blocks > 0 else n
    t0 = time.perf_counter()
    if max_blocks > 0:
        # partial sweep: first K blocks through the same path, id
        # uploads up front in one async burst (same discipline as
        # sweep(); a per-block upload would serialize a host round
        # trip between blocks)
        blocks = [
            jnp.asarray(
                np.arange(start, start + block, dtype=np.int32) % n
            )
            for start in range(0, n_sweep, block)
        ]
        total = 0
        for ids in blocks:
            packed = np.asarray(sweeper.solve_block(ids))
            total += int(packed[:, 1].sum())
        result = None
    else:
        result = sweeper.sweep(block=block)
    e2e_ms = (time.perf_counter() - t0) * 1000

    out = {
        "bench": f"scale.route_sweep_{graph.n}_nodes",
        "kernel": f"{backend}_route_sweep",
        "edges": edges,
        "edge_compile_ms": round(compile_ms, 1),
        "e2e_ms": round(e2e_ms, 1),
        "source_block": block,
        "swept_blocks": -(-n_sweep // block),
        "total_blocks": -(-n // block),
        "samples": samples,
        "platform": platform,
        # readback per block: digest + nh_total + S metrics + S masks
        "readback_kb": round(
            n_sweep * (2 + len(samples) * (1 + sweeper.samp_v.shape[1] // 32))
            * 4 / 1024, 1
        ),
    }
    if device_only_block_ms is not None:
        out["device_only_block_ms"] = device_only_block_ms
        out["device_only_all_sources_ms"] = round(
            device_only_block_ms * (-(-n // block)), 1
        )
    if impl_ms is not None:
        out["impl_ms"] = impl_ms
        from openr_tpu.ops import spf_grouped

        out["impl"] = spf_grouped.get_grouped_impl().name
    if result is not None:
        # oracle gate: every sample node's complete route table
        for nm in samples:
            got = result.routes_from(nm)
            oracle = ls.run_spf(nm)
            for dst in list(graph.node_names)[:: max(1, graph.n // 50)]:
                if dst == nm:
                    continue
                want = oracle.get(dst)
                if want is None:
                    assert dst not in got, (nm, dst)
                    continue
                g_metric, g_nhs = got[dst]
                assert g_metric == want.metric, (nm, dst)
                assert g_nhs == set(want.next_hops), (nm, dst)
        out["oracle_spot_check"] = "passed"
        out["route_rows_total"] = int(result.nh_totals[: graph.n].sum())
    return out


def route_engine_churn_bench(
    nodes: int, churn_events: int, churn_kind: str = "metric",
    sharded: bool = False, backend: str = "ell",
) -> dict:
    """Incremental NETWORK-WIDE route reconvergence (ops.route_engine):
    per churn event, ONE fused dispatch re-solves only the affected
    destination rows of the resident route product and reads back
    their digests + sample route rows — the route-server analogue of
    the reference's incremental Decision rebuild, at all-destinations
    scope. Parity gate: engine digests vs a from-scratch full sweep.

    ``churn_kind="metric"`` wiggles one adjacency's metric per event;
    ``"link"`` alternates REMOVING and RESTORING a leaf adjacency —
    real topology churn (LinkState.cpp:565-719 semantics), proving
    structure events ride the same incremental dispatch."""
    import statistics
    from dataclasses import replace

    import jax

    from openr_tpu.ops import dispatch_accounting as da
    from openr_tpu.ops import route_engine, route_sweep
    from openr_tpu.telemetry import get_registry

    topo = topologies.fat_tree_nodes(nodes)
    ls = LinkState(area=topo.area)
    for name in sorted(topo.adj_dbs):
        ls.update_adjacency_database(topo.adj_dbs[name])
    names = sorted(topo.adj_dbs)
    rsw = next(k for k in names if k.startswith("rsw"))
    fsw = next(k for k in names if k.startswith("fsw"))

    mesh = None
    if sharded:
        from openr_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(jax.devices())
    cls = (
        route_engine.GroupedRouteSweepEngine
        if backend == "grouped"
        else route_engine.RouteSweepEngine
    )
    t0 = time.perf_counter()
    engine = cls(ls, [rsw], mesh=mesh)
    cold_ms = (time.perf_counter() - t0) * 1000

    # link-churn state: the adjacency pair currently removed
    churn_rsw = next(
        k for k in names if k.startswith("rsw") and k != rsw
    )
    pulled: dict = {}

    def drop_link(u, v):
        for x, y in ((u, v), (v, u)):
            db = ls.get_adjacency_databases()[x]
            keep, gone = [], []
            for a in db.adjacencies:
                (gone if a.other_node_name == y else keep).append(a)
            pulled[(x, y)] = tuple(gone)
            ls.update_adjacency_database(
                replace(db, adjacencies=tuple(keep))
            )

    def restore_link(u, v):
        for x, y in ((u, v), (v, u)):
            db = ls.get_adjacency_databases()[x]
            ls.update_adjacency_database(
                replace(
                    db,
                    adjacencies=tuple(
                        list(db.adjacencies) + list(pulled.pop((x, y)))
                    ),
                )
            )

    def churn(step):
        if churn_kind == "link":
            peer = ls.get_adjacency_databases()[churn_rsw].adjacencies[
                0
            ].other_node_name if not pulled else next(
                v for (u, v) in pulled if u == churn_rsw
            )
            if pulled:
                restore_link(churn_rsw, peer)
            else:
                drop_link(churn_rsw, peer)
            return {churn_rsw, peer}
        db = ls.get_adjacency_databases()[fsw]
        adjs = list(db.adjacencies)
        a0 = adjs[0]
        adjs[0] = replace(a0, metric=2 + step % 5)
        ls.update_adjacency_database(
            replace(db, adjacencies=tuple(adjs))
        )
        return {fsw, a0.other_node_name}

    # warm every bucket shape outside the timed window
    for step in range(4):
        engine.churn(ls, churn(step))

    # PIPELINED timed loop: every event defers its host-side delta
    # apply, which then rides the NEXT event's dispatch window (the
    # double-buffer overlap) — per-event wall time is dispatch + the
    # overlapped consume of the previous delta, never a dedicated
    # host readback stall
    samples = []
    records = []  # PendingDelta | (moved, bytes, rows, overlap_ms)
    # per-event frontier probe stats (only events that hit the
    # overflow policy contribute; engine.last_* is per-probe state)
    frontier_rows, frontier_cells, frontier_jumps = [], [], []
    # committed-dispatch accounting: per-event host touches (submit
    # phases + reap phases, 2 = the contract) and the window's
    # blocking-sync total (0 on the warm path — every readback was
    # kicked at submit time)
    touches = []
    _reg = get_registry()
    sync0 = _reg.counter_get("ops.blocking_syncs")
    disp0 = _reg.counter_get("ops.host_dispatches")
    for step in range(churn_events):
        affected = churn(step)
        probe0 = engine.frontier_resolves + engine.frontier_fallbacks
        t0 = time.perf_counter()
        with da.event_window("bench_churn") as win:
            out = engine.churn(ls, affected, defer_consume=True)
        samples.append((time.perf_counter() - t0) * 1000)
        touches.append(win.touches)
        if (
            engine.frontier_resolves + engine.frontier_fallbacks
            > probe0
            and engine.last_frontier_rows >= 0
        ):
            frontier_rows.append(engine.last_frontier_rows)
            frontier_cells.append(engine.last_frontier_cells)
            frontier_jumps.append(engine.last_frontier_jumps)
        if isinstance(out, route_engine.PendingDelta):
            records.append(out)
        elif out is not None and out != []:
            # full-width refresh: its delta was consumed inline
            records.append((
                out, engine.last_readback_bytes,
                engine.last_delta_rows, 0.0,
            ))
        else:
            # cold rebuild (None) or detection no-op ([])
            records.append((out, 0, 0, 0.0))
    t0 = time.perf_counter()
    engine.flush()  # drain the tail event's delta
    drain_ms = (time.perf_counter() - t0) * 1000
    blocking_syncs = _reg.counter_get("ops.blocking_syncs") - sync0
    host_dispatches = _reg.counter_get("ops.host_dispatches") - disp0

    # device-only per-event cost with the fixed transport cancelled:
    # K data-dependent deferred churn dispatches against ONE drain,
    # (T_K - T_1)/(K - 1) — the denominator of host_overhead_ratio
    _extra = [churn_events]

    def _chain_step(_prev):
        _extra[0] += 1
        return engine.churn(ls, churn(_extra[0]), defer_consume=True)

    device_only_ms = _chained_device_only_ms(
        _chain_step, lambda _out: engine.flush(), k=4, reps=3
    )

    # PIPELINED BURST + SPECULATION leg: the same churn stream
    # delivered the way the debounce terminal hands it over — multi
    # -event bursts whose windows submit back to back under ONE
    # pipeline drain (window N+1 on the stream before window N's reap
    # lands), then single windows whose composition was speculatively
    # dispatched while a debounce timer would have idled. Harvested
    # from the committed-dispatch registry: touches per DRAIN (~2 for
    # a whole burst vs 2 per window), window occupancy per drain,
    # pipeline depth, and the speculation hit rate.
    spec_d0 = _reg.counter_get("ops.spec_dispatches")
    spec_h0 = _reg.counter_get("ops.spec_hits")
    _step = [churn_events + 100]
    burst_samples = []
    for _ in range(3):
        evs = []
        for _k in range(3):
            _step[0] += 1
            evs.append(lambda s=_step[0]: churn(s))
        t0 = time.perf_counter()
        engine.churn_burst(ls, evs)
        burst_samples.append((time.perf_counter() - t0) * 1000)
    for _ in range(3):
        _step[0] += 1
        affected = churn(_step[0])
        engine.speculate_churn(ls, [affected])
        engine.churn_window(ls, [affected])
    spec_dispatches = _reg.counter_get("ops.spec_dispatches") - spec_d0
    spec_hits = _reg.counter_get("ops.spec_hits") - spec_h0
    _hists = _reg.histograms()

    def _drain_p50(name):
        h = _hists.get(name)
        if h is None or not h.count:
            return None
        return round(h.percentile(0.50), 1)

    windows_per_drain = _drain_p50("ops.windows_per_drain")
    rtt_ms = _dispatch_readback_rtt_ms()

    affected_counts = []
    rb_bytes, delta_rows, overlap_ms = [], [], []
    for rec in records:
        if isinstance(rec, route_engine.PendingDelta):
            affected_counts.append(len(rec.names))
            rb_bytes.append(rec.readback_bytes)
            delta_rows.append(rec.delta_rows)
            overlap_ms.append(rec.overlap_ms)
        else:
            moved, b, r, o = rec
            affected_counts.append(
                len(moved) if moved is not None else -1
            )
            rb_bytes.append(b)
            delta_rows.append(r)
            overlap_ms.append(o)
    full_product_bytes = (
        engine._packed_dev.shape[0] * engine._packed_dev.shape[1] * 4
    )

    # parity gate on the final (fully drained) state
    full = route_sweep.digests_by_name(
        route_sweep.all_sources_route_sweep(ls, [rsw], block=1024)
    )
    assert route_sweep.digests_by_name(engine.result) == full

    return {
        "bench": f"scale.route_engine_churn_{engine.graph.n}_nodes",
        "churn_kind": churn_kind,
        "engine_backend": backend,
        "sharded_devices": (
            mesh.devices.size if mesh is not None else 0
        ),
        "events": churn_events,
        "median_ms": round(statistics.median(samples), 1),
        "p90_ms": round(
            sorted(samples)[max(0, -(-len(samples) * 9 // 10) - 1)], 1
        ),
        **_latency_percentiles(samples),
        "cold_build_ms": round(cold_ms, 1),
        "affected_dsts_median": (
            int(statistics.median(incr))
            if (incr := [c for c in affected_counts if c >= 0])
            else None
        ),
        "cold_rebuilds_in_window": sum(
            1 for c in affected_counts if c < 0
        ),
        "incremental_events": engine.incremental_events,
        "full_refreshes": engine.full_refreshes,
        # structural-churn / frontier re-solve accounting: how many
        # events were link-level (weight to/from INF), how many of the
        # overflow events rode the frontier path vs fell back to the
        # full-width refresh, and how big the cones were
        "structural_events": engine.structural_events,
        "frontier_resolves": engine.frontier_resolves,
        "frontier_fallbacks": engine.frontier_fallbacks,
        "frontier_rows_median": (
            int(statistics.median(frontier_rows))
            if frontier_rows else None
        ),
        "frontier_cells_median": (
            round(statistics.median(frontier_cells), 1)
            if frontier_cells else None
        ),
        "frontier_jumps_median": (
            int(statistics.median(frontier_jumps))
            if frontier_jumps else None
        ),
        # delta-compacted readback accounting: bytes per event scale
        # with CHANGED rows, not the [n_pad, W] product width
        "readback_bytes_median": int(statistics.median(rb_bytes)),
        "readback_bytes_max": max(rb_bytes),
        "full_product_bytes": full_product_bytes,
        "delta_rows_median": int(statistics.median(delta_rows)),
        "delta_rows_max": max(delta_rows),
        "overlap_ms_median": round(statistics.median(overlap_ms), 3),
        "pipeline_drain_ms": round(drain_ms, 3),
        # committed-dispatch contract fields: 2 touches/event on the
        # warm path (one submit run + one reap run), 0 blocking syncs
        # (every readback kicked at submit), and the e2e-vs-device
        # ratio the host-overhead runbook recipe triages from
        "host_touches_per_event": round(
            statistics.median(touches), 1
        ),
        "host_touches_max": max(touches),
        "blocking_syncs_per_event": round(
            blocking_syncs / max(1, churn_events), 3
        ),
        "host_dispatches_per_event": round(
            host_dispatches / max(1, churn_events), 2
        ),
        "device_only_ms": device_only_ms,
        "host_overhead_ratio": round(
            statistics.median(samples) / max(device_only_ms, 1e-3), 2
        ),
        # MEASURED ratio (telemetry.profiler): window wall over sampled
        # block-for-ready device time — the headline number; the
        # derived chained-dispatch ratio above stays for comparison
        "host_overhead_ratio_measured": (
            _get_profiler().host_overhead_ratio() or None
        ),
        "host_touches_by_tag": _host_touches_by_tag(),
        # pipelined-window fields (PR 16): burst wall time, drains and
        # their occupancy/touch budget, speculation economics, and the
        # dispatch-and-readback round trip amortized over the windows
        # sharing one drain —
        # the number that shows ~2 touches per DRAIN, not per window
        "pipeline_burst_median_ms": round(
            statistics.median(burst_samples), 1
        ),
        "pipeline_drains": int(
            _reg.counter_get("ops.pipeline_drains")
        ),
        "pipeline_depth_median": _drain_p50("ops.pipeline_depth"),
        "touches_per_drain_p50": _drain_p50("ops.touches_per_drain"),
        "windows_per_drain_p50": windows_per_drain,
        "spec_dispatches": int(spec_dispatches),
        "spec_hit_rate": (
            round(spec_hits / spec_dispatches, 2)
            if spec_dispatches else None
        ),
        "dispatch_readback_rtt_ms": rtt_ms,
        "dispatch_readback_rtt_amortized_ms": round(
            rtt_ms / max(1.0, windows_per_drain or 1.0), 2
        ),
        "platform": jax.devices()[0].platform,
        "oracle_spot_check": "passed",
    }


def link_churn_bench(
    nodes: int, churn_events: int = 10,
    sharded: bool = False, backend: str = "ell",
) -> dict:
    """Paired structural-vs-metric churn legs through the resident
    route engine: the SAME topology and event count, once as metric
    wiggles (the bucketed baseline) and once as alternating link
    remove/restore (overflow events that ride the frontier re-solve).
    Reports the link-vs-metric median ratio — the PR 6 target is the
    link leg landing within ~2x of the metric leg — plus the
    frontier-vs-full split and cone-size medians for the link leg."""
    metric = route_engine_churn_bench(
        nodes, churn_events, churn_kind="metric",
        sharded=sharded, backend=backend,
    )
    link = route_engine_churn_bench(
        nodes, churn_events, churn_kind="link",
        sharded=sharded, backend=backend,
    )
    out = dict(link)
    out["bench"] = link["bench"].replace(
        "route_engine_churn", "link_churn"
    )
    out["metric_churn_median_ms"] = metric["median_ms"]
    out["metric_churn_p90_ms"] = metric["p90_ms"]
    out["link_vs_metric_ratio"] = round(
        link["median_ms"] / max(metric["median_ms"], 1e-9), 3
    )
    overflowed = link["frontier_resolves"] + link["full_refreshes"]
    out["frontier_fraction"] = (
        round(link["frontier_resolves"] / overflowed, 3)
        if overflowed else None
    )
    out["meets_2x_target"] = bool(
        link["median_ms"] <= 2.0 * metric["median_ms"]
    )
    return out


def sharded_churn_bench(
    nodes: int, churn_events: int = 10, backend: str = "ell",
) -> dict:
    """Paired sharded-vs-single churn legs plus the resharding-free
    contract accounting (issue 7): the SAME metric-churn scenario once
    over all visible devices and once single-chip, with the registry
    deltas that prove the sharded leg never paid an implicit XLA copy —
    ``ops.reshard_events`` must stay 0 across the sharded run — and the
    per-shard overlapped-readback volume (``ops.shard_readback_bytes``,
    ``ops.shard_consume_overlap_ms``). On one real chip the 8-way
    virtual mesh measures sharded dispatch overhead; on a real slice
    the ratio is the scale-out win."""
    from openr_tpu.telemetry import get_registry

    reg = get_registry()

    def contract():
        return (
            reg.counter_get("ops.reshard_events"),
            reg.counter_get("ops.shard_readback_bytes"),
        )

    r0, b0 = contract()
    sharded = route_engine_churn_bench(
        nodes, churn_events, churn_kind="metric",
        sharded=True, backend=backend,
    )
    r1, b1 = contract()
    single = route_engine_churn_bench(
        nodes, churn_events, churn_kind="metric",
        sharded=False, backend=backend,
    )

    # lazily registered: only a mesh engine's deferred consume
    # observes it, so a missing histogram means the sharded leg never
    # overlapped a readback (that would be a bug worth seeing here)
    hist = reg.histograms().get("ops.shard_consume_overlap_ms")
    out = dict(sharded)
    out["bench"] = sharded["bench"].replace(
        "route_engine_churn", "sharded_churn"
    )
    out["reshard_events"] = r1 - r0
    out["resharding_free"] = bool(r1 - r0 == 0)
    out["shard_readback_bytes"] = b1 - b0
    out["shard_consume_overlap_ms"] = (
        hist.stats() if hist is not None else None
    )
    out["single_chip_median_ms"] = single["median_ms"]
    out["single_chip_p90_ms"] = single["p90_ms"]
    out["sharded_vs_single_ratio"] = round(
        sharded["median_ms"] / max(single["median_ms"], 1e-9), 3
    )
    return out


def sustained_load_bench(
    nodes: int = 1000, rate: int = 240, duration_s: float = 4.0,
    p99_slo_ms: float = 5000.0, seed: int = 20260805,
) -> dict:
    """Sustained-load leg through the REAL KvStore→Decision→Fib
    pipeline (openr_tpu.load): a seeded open-loop publication stream at
    a fixed target rate with admission control (shed-by-coalescing +
    rate-adaptive debounce) and the pipelined Decision emit stage on,
    followed by a short binary-search max-sustainable-rate estimate
    against the p99 convergence SLO. Reports the e2e latency
    distribution, shed/coalesce counters, queue high-watermark, and the
    oracle-parity verdict (shedded live RouteDatabase vs unshedded
    replay)."""
    from openr_tpu.load import AdmissionConfig
    from openr_tpu.load.harness import SustainedLoadHarness

    harness = SustainedLoadHarness(
        nodes=nodes,
        seed=seed,
        solver_backend="host",
        debounce_max_s=0.05,
        admission=AdmissionConfig(shed_depth=4, cap_s=0.5),
        pipelined_emit=True,
    )
    t0 = time.perf_counter()
    harness.start(initial_timeout_s=600.0)
    start_s = time.perf_counter() - t0
    try:
        rep = harness.run_fixed_rate(
            rate, duration_s, p99_slo_ms=p99_slo_ms
        )
        search = harness.find_max_sustainable_rate(
            p99_slo_ms=p99_slo_ms,
            lo=max(25, rate // 2),
            hi=rate * 2,
            duration_s=max(1.5, duration_s / 2),
            max_probes=3,
        )
        parity = harness.check_parity()
    finally:
        harness.stop()
    out = rep.to_dict()
    out["bench"] = f"scale.sustained_load_{nodes}_e2e_ms"
    out["start_s"] = round(start_s, 3)
    out["median_ms"] = out["e2e_ms"]["p50"]
    out["p99_ms"] = out["e2e_ms"]["p99"]
    out["max_sustainable"] = search
    out["oracle_parity"] = bool(parity)
    return out


def multi_tenant_bench(
    tenants: int = 8, rounds: int = 20, seed: int = 20260805,
) -> dict:
    """Multi-tenant batched-worlds leg (ops.world_batch): B mixed-size
    tenant graphs under per-round metric churn, solved two ways —

    - SEQUENTIAL: one warm ``EllState.reconverge`` fused dispatch per
      tenant per round (the pre-tenancy status quo: N engine calls),
    - BATCHED: one ``WorldManager.solve_views`` round (one dispatch
      per shape bucket + delta-compacted readback).

    Reports per-tenant dispatch cost both ways, the batched/sequential
    ratio (the ISSUE 9 acceptance gate is <= 0.5x at B=8), bucket
    compile counts, and the tenancy counter deltas. Parity is asserted
    on the final round — a fast bench must still be a correct one.

    The fleet is mixed-size (grids + meshes, 9..126 nodes, varying
    degree) but sized to COALESCE under the arbiter's shape rounding:
    a dispatch amortizes per-call overhead across exactly the tenants
    that share a bucket, so the bench measures the design's target
    regime — many similar-scale worlds, one executable. A fleet
    spanning many buckets degrades toward the sequential cost by
    construction (each extra bucket is one more dispatch per round);
    the parity gates in tests/tools cover that shape, the throughput
    gate lives here."""
    from openr_tpu.graph.linkstate import LinkState
    from openr_tpu.models import topologies
    from openr_tpu.ops.spf_sparse import (
        EllState,
        compile_ell,
        ell_patch,
        ell_source_batch,
    )
    from openr_tpu.ops.world_batch import TENANCY_COUNTERS, WorldManager

    def mk_topos():
        base = [
            topologies.grid(3),
            topologies.grid(5),
            topologies.grid(7),
            topologies.random_mesh(24, 3, seed=seed % 1000 + 7),
            topologies.random_mesh(48, 4, seed=seed % 1000 + 11),
            topologies.random_mesh(80, 4, seed=seed % 1000 + 13),
            topologies.random_mesh(104, 3, seed=seed % 1000 + 17),
            topologies.random_mesh(126, 3, seed=seed % 1000 + 19),
        ]
        while len(base) < tenants:
            base.append(
                topologies.random_mesh(
                    40, 3, seed=seed % 1000 + 23 + len(base)
                )
            )
        return base[:tenants]

    def mk_ls(topo):
        ls = LinkState(area=topo.area)
        for _name, adj_db in sorted(topo.adj_dbs.items()):
            ls.update_adjacency_database(adj_db)
        return ls

    def wiggle(ls, root, metric):
        from dataclasses import replace

        adj_db = ls.get_adjacency_databases()[root]
        adjs = list(adj_db.adjacencies)
        adjs[0] = replace(adjs[0], metric=metric)
        ls.update_adjacency_database(
            replace(adj_db, adjacencies=tuple(adjs))
        )

    # -- sequential: one warm EllState per tenant --------------------------
    seq_ls = [mk_ls(t) for t in mk_topos()]
    seq_roots = [
        sorted(ls.get_adjacency_databases())[0] for ls in seq_ls
    ]
    states = [EllState(compile_ell(ls)) for ls in seq_ls]
    versions = [ls.topology_version for ls in seq_ls]
    for i, (ls, st) in enumerate(zip(seq_ls, states)):
        np.asarray(
            st.reconverge(
                st.graph, ell_source_batch(st.graph, ls, seq_roots[i])
            )
        )
    seq_round_ms = []
    for r in range(rounds):
        for i, ls in enumerate(seq_ls):
            wiggle(ls, seq_roots[i], 40 + r)
        t0 = time.perf_counter()
        for i, (ls, st) in enumerate(zip(seq_ls, states)):
            affected = ls.affected_since(versions[i])
            versions[i] = ls.topology_version
            patched = ell_patch(
                st.graph, ls, sorted(affected), widen=True
            )
            np.asarray(
                st.reconverge(
                    patched, ell_source_batch(patched, ls, seq_roots[i])
                )
            )
        seq_round_ms.append(1000.0 * (time.perf_counter() - t0))

    # -- batched: one WorldManager over the same churn ---------------------
    bat_ls = [mk_ls(t) for t in mk_topos()]
    bat_roots = [
        sorted(ls.get_adjacency_databases())[0] for ls in bat_ls
    ]
    items = [
        (f"bt{i}", ls, root)
        for i, (ls, root) in enumerate(zip(bat_ls, bat_roots))
    ]
    compiles0 = TENANCY_COUNTERS["bucket_compiles"]
    counters0 = {k: TENANCY_COUNTERS[k] for k in TENANCY_COUNTERS}
    mgr = WorldManager(slots_per_bucket=max(8, tenants))
    mgr.solve_views(items)  # warmup (bucket compiles land here)
    bat_round_ms = []
    views = None
    for r in range(rounds):
        for i, ls in enumerate(bat_ls):
            wiggle(ls, bat_roots[i], 40 + r)
        t0 = time.perf_counter()
        views = mgr.solve_views(items)
        bat_round_ms.append(1000.0 * (time.perf_counter() - t0))

    # final-round parity: the batched rows must match a cold oracle
    from openr_tpu.ops.spf_sparse import ell_view_batch_packed

    parity = True
    for (tid, ls, root), (_g, srcs, packed) in zip(items, views):
        graph = compile_ell(ls)
        ref = np.asarray(
            ell_view_batch_packed(
                graph, ell_source_batch(graph, ls, root)
            )
        )
        parity = parity and np.array_equal(packed, ref)

    seq_med = sorted(seq_round_ms)[len(seq_round_ms) // 2]
    bat_med = sorted(bat_round_ms)[len(bat_round_ms) // 2]
    return {
        "bench": f"scale.multi_tenant_{tenants}_dispatch_ms",
        "tenants": tenants,
        "rounds": rounds,
        "sequential_round_ms": round(seq_med, 3),
        "batched_round_ms": round(bat_med, 3),
        "sequential_per_tenant_ms": round(seq_med / tenants, 4),
        "batched_per_tenant_ms": round(bat_med / tenants, 4),
        "batched_vs_sequential_ratio": round(bat_med / seq_med, 4),
        "bucket_compiles": TENANCY_COUNTERS["bucket_compiles"]
        - compiles0,
        "buckets": mgr.bucket_count(),
        "parity": bool(parity),
        "tenancy_counters": {
            k: TENANCY_COUNTERS[k] - counters0[k]
            for k in counters0
        },
    }


def recovery_bench(
    nodes: int = 200, boots: int = 3, seed: int = 20260805,
) -> dict:
    """Crash-recovery leg (openr_tpu.state): cold boot vs warm boot.

    A Decision journals a fat-tree LSDB plus a short churn tail through
    ``StatePlane`` (checkpoint + WAL + engine snapshot), then the
    process "crashes" (device caches dropped). Two boot paths race from
    the same crash point, ``boots`` times each:

    - COLD: a fresh Decision replays every publication from scratch and
      pays the cold ELL build + first solve,
    - WARM: open the backing store, ``recover()`` (journal over
      checkpoint), ``warm_boot()`` — the resident ELL state is seeded
      from the persisted snapshot and the rebuild reconverges warm.

    Reports both boot medians, the warm/cold ratio (the recovery
    design's payoff: warm << cold), the journal/checkpoint shape the
    recovery replayed, and route parity between the two boots — a fast
    warm boot that diverges is a failed one."""
    import os
    import shutil
    import tempfile
    from dataclasses import replace

    from openr_tpu.config_store.persistent_store import PersistentStore
    from openr_tpu.decision import spf_solver
    from openr_tpu.decision.decision import Decision
    from openr_tpu.decision.spf_solver import reset_device_caches
    from openr_tpu.messaging.queue import ReplicateQueue
    from openr_tpu.state import StatePlane
    from openr_tpu.telemetry import get_registry
    from openr_tpu.types import Publication, Value
    from openr_tpu.utils import keys as keyutil
    from openr_tpu.utils import wire

    reg = get_registry()
    # route the bench area through the resident sliced-ELL path (the
    # one the state plane snapshots)
    spf_solver.SPARSE_NODE_THRESHOLD = 4
    topo = topologies.fat_tree_nodes(nodes)
    n = len(topo.adj_dbs)
    node = next(m for m in sorted(topo.adj_dbs) if m.startswith("rsw"))
    area = topo.area
    workdir = tempfile.mkdtemp(prefix="openr_tpu_bench_recovery_")
    path = os.path.join(workdir, "state.bin")
    versions: dict = {}
    published: list = []

    def make_decision(name, plane=None):
        return Decision(
            node,
            kvstore_updates_queue=ReplicateQueue(name=f"bkv-{name}"),
            route_updates_queue=ReplicateQueue(name=f"brt-{name}"),
            state_plane=plane,
        )

    def kv_value(key, originator, payload):
        versions[key] = versions.get(key, 0) + 1
        return Value(
            version=versions[key],
            originator_id=originator,
            value=payload,
        )

    try:
        store = PersistentStore(path)
        plane = StatePlane(store, checkpoint_every=4)
        live = make_decision("live", plane)
        initial = {}
        for adj_db in topo.adj_dbs.values():
            initial[keyutil.adj_key(adj_db.this_node_name)] = kv_value(
                keyutil.adj_key(adj_db.this_node_name),
                adj_db.this_node_name,
                wire.dumps(adj_db),
            )
        for pdb in topo.prefix_dbs.values():
            initial[keyutil.prefix_db_key(pdb.this_node_name)] = kv_value(
                keyutil.prefix_db_key(pdb.this_node_name),
                pdb.this_node_name,
                wire.dumps(pdb),
            )
        published.append(initial)
        plane.on_kvstore_merge(area, initial)
        live.process_publication(
            Publication(key_vals=dict(initial), area=area)
        )
        live.rebuild_routes("BENCH")
        live.checkpoint_state()
        # short churn tail so recovery replays a real WAL, not just the
        # checkpoint
        mutated = dict(topo.adj_dbs)
        for i, name in enumerate(sorted(mutated)[:4]):
            adj_db = mutated[name]
            adjs = list(adj_db.adjacencies)
            adjs[0] = replace(adjs[0], metric=10 + i)
            mutated[name] = replace(adj_db, adjacencies=tuple(adjs))
            kv = {
                keyutil.adj_key(name): kv_value(
                    keyutil.adj_key(name), name,
                    wire.dumps(mutated[name]),
                )
            }
            published.append(kv)
            plane.on_kvstore_merge(area, kv)
            live.process_publication(
                Publication(key_vals=dict(kv), area=area)
            )
            live.rebuild_routes("BENCH")
        live.checkpoint_state()
        routes_live = wire.dumps(live.route_db.to_route_db(node))
        store.stop()

        warm_ms, cold_ms = [], []
        warm_seeds0 = reg.counter_get("state.warm_seeds")
        rec = None
        routes_warm = routes_cold = None
        for _ in range(boots):
            # warm: store open + recover + warm_boot, from a crashed
            # process (resident device state gone)
            reset_device_caches()
            t0 = time.perf_counter()
            store2 = PersistentStore(path)
            plane2 = StatePlane(store2)
            rec = plane2.recover()
            warm = make_decision("warm", plane2)
            warm.warm_boot(rec)
            warm_ms.append(1000.0 * (time.perf_counter() - t0))
            routes_warm = wire.dumps(warm.route_db.to_route_db(node))
            store2.stop()

            # cold: replay every publication from scratch
            reset_device_caches()
            t0 = time.perf_counter()
            cold = make_decision("cold")
            for kv in published:
                cold.process_publication(
                    Publication(key_vals=dict(kv), area=area)
                )
            cold.rebuild_routes("BENCH")
            cold_ms.append(1000.0 * (time.perf_counter() - t0))
            routes_cold = wire.dumps(cold.route_db.to_route_db(node))

        warm_med = sorted(warm_ms)[len(warm_ms) // 2]
        cold_med = sorted(cold_ms)[len(cold_ms) // 2]
        return {
            "bench": f"scale.recovery_{n}_warm_boot_ms",
            "nodes": n,
            "boots": boots,
            "warm_boot_ms": round(warm_med, 3),
            "cold_boot_ms": round(cold_med, 3),
            "warm_vs_cold_ratio": round(
                warm_med / max(cold_med, 1e-9), 4
            ),
            "journal_replayed": rec.journal_replayed,
            "had_checkpoint": rec.had_checkpoint,
            "warm_seeds": reg.counter_get("state.warm_seeds")
            - warm_seeds0,
            "parity": bool(
                routes_warm == routes_cold == routes_live
            ),
        }
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def integrity_audit_bench(
    nodes: int = 1000, churn_events: int = 24, seed: int = 0,
) -> dict:
    """Integrity-plane overhead leg (openr_tpu.integrity): the same
    metric-churn loop timed twice on one warm resident engine —
    auditing DISARMED (nothing registered; Decision's hook is one
    registry check) vs ARMED as shipped (production defaults: the
    wall-clock ``min_interval_s`` rate limit gates the hook, so a
    churn storm pays at most one audit pass per second and the MEDIAN
    event pays only the early-return check). Acceptance gate: armed
    e2e median within 5% of disarmed, zero violations on healthy
    state, and the audited product bit-identical to the from-scratch
    host sweep. The full forced audit pass (tiers 1+2 + row oracle)
    is timed separately — that is the cost one event per rate-limit
    window absorbs, reported for sizing, not gated on the median."""
    import statistics
    from dataclasses import replace

    import jax

    from openr_tpu.integrity.auditor import IntegrityAuditor
    from openr_tpu.ops import route_engine, route_sweep
    from openr_tpu.telemetry import get_registry

    reg = get_registry()
    topo = topologies.fat_tree_nodes(nodes)
    ls = LinkState(area=topo.area)
    for name in sorted(topo.adj_dbs):
        ls.update_adjacency_database(topo.adj_dbs[name])
    names = sorted(topo.adj_dbs)
    rsw = next(k for k in names if k.startswith("rsw"))
    fsw = next(k for k in names if k.startswith("fsw"))
    engine = route_engine.RouteSweepEngine(ls, [rsw])

    def churn(step):
        db = ls.get_adjacency_databases()[fsw]
        adjs = list(db.adjacencies)
        a0 = adjs[0]
        adjs[0] = replace(a0, metric=2 + step % 5)
        ls.update_adjacency_database(
            replace(db, adjacencies=tuple(adjs))
        )
        return {fsw, a0.other_node_name}

    # warm the dispatch shapes AND the audit kernels outside both
    # timed windows — the jit compiles must not land in either median
    aud = IntegrityAuditor(seed=seed)
    aud.register(engine)
    for step in range(8):
        engine.churn(ls, churn(step))
    assert aud.audit_now()[-1]["verdict"] == "clean"
    # the cost one event per rate-limit window absorbs: a full forced
    # pass, oracle included (steady-state passes skip the oracle
    # ``oracle_every - 1`` times out of ``oracle_every``)
    t0 = time.perf_counter()
    assert aud.audit_now()[-1]["verdict"] == "clean"
    audit_pass_ms = (time.perf_counter() - t0) * 1000
    aud.unregister(engine)

    def timed_loop(step0, audit):
        samples = []
        for step in range(step0, step0 + churn_events):
            affected = churn(step)
            t0 = time.perf_counter()
            engine.churn(ls, affected)
            if audit:
                aud.on_converge()
            samples.append((time.perf_counter() - t0) * 1000)
        return samples

    disarmed = timed_loop(8, audit=False)
    v0 = sum(
        reg.counter_get(f"integrity.violations.{t}")
        for t in ("residual", "digest", "oracle")
    )
    a0 = reg.counter_get("integrity.audits")
    aud.register(engine)
    armed = timed_loop(8 + churn_events, audit=True)
    aud.unregister(engine)

    audits = reg.counter_get("integrity.audits") - a0
    violations = (
        sum(
            reg.counter_get(f"integrity.violations.{t}")
            for t in ("residual", "digest", "oracle")
        )
        - v0
    )
    # parity gate: the audited resident product vs a from-scratch
    # full sweep — an audit plane that perturbs routes is a bug
    full = route_sweep.digests_by_name(
        route_sweep.all_sources_route_sweep(ls, [rsw], block=1024)
    )
    assert route_sweep.digests_by_name(engine.result) == full
    dis_med = statistics.median(disarmed)
    arm_med = statistics.median(armed)
    overhead = (arm_med - dis_med) / max(dis_med, 1e-9)
    return {
        "bench": f"scale.integrity_audit_{engine.graph.n}_churn_ms",
        "nodes": engine.graph.n,
        "events": churn_events,
        "disarmed_median_ms": round(dis_med, 3),
        "armed_median_ms": round(arm_med, 3),
        "audit_overhead_pct": round(100.0 * overhead, 2),
        "overhead_within_5pct": bool(overhead < 0.05),
        "audit_pass_ms": round(audit_pass_ms, 3),
        "audits": audits,
        "violations": violations,
        "platform": jax.devices()[0].platform,
        "oracle_spot_check": "passed",
    }


def fleet_twin_bench(
    nodes: int = 16, events: int = 10, seed: int = 20260805,
) -> dict:
    """Digital-twin leg (openr_tpu.twin): per-event fleet
    reconvergence solved two ways over the SAME LSDB stream —

    - BATCHED: the twin's one ``solve_views`` wave (all N vantages in
      one dispatch, vantage-view packing sharing one compiled graph),
    - SEQUENTIAL: N single-tenant ``solve_view`` calls per event (the
      pre-twin status quo: each vantage its own dispatch).

    Both sides measure device-view production only (route-db builds
    are identical host work either way); the final event's packed
    views are compared bit for bit — a fast bench must still be a
    correct one. ``make twin-smoke`` is the hard CI gate; this leg
    folds the fleet-throughput numbers into the official artifact."""
    import time as _time

    import jax
    import numpy as np

    from openr_tpu.graph.linkstate import LinkState
    from openr_tpu.load.generator import LoadGenerator
    from openr_tpu.models import topologies
    from openr_tpu.ops.world_batch import TENANCY_COUNTERS, WorldManager
    from openr_tpu.twin import FabricTwin
    from openr_tpu.types import AdjacencyDatabase
    from openr_tpu.utils import keys as keyutil
    from openr_tpu.utils import wire

    topo = topologies.ring(nodes)
    roots = sorted(topo.adj_dbs)
    twin = FabricTwin(topo)
    twin.converge()  # warm the fleet bucket
    items = [(twin._tid(n), twin.ls, n) for n in roots]

    seq_mgr = WorldManager(slots_per_bucket=1, max_resident=nodes)
    ls_seq = LinkState(topo.area)
    for n in roots:
        ls_seq.update_adjacency_database(topo.adj_dbs[n])
    for r in roots:
        seq_mgr.solve_view(f"seq/{r}", ls_seq, r)  # warm each world

    gen = LoadGenerator(topo, seed=seed % 1000)
    gen.initial_key_vals()
    batched_s = seq_s = 0.0
    applied = 0
    twin_dispatches = 0
    while applied < events:
        ev = gen.next_event()
        if not keyutil.is_adj_key(ev.key):
            continue  # prefix events cost no SPF wave on either side
        applied += 1
        db = wire.loads(ev.payload, AdjacencyDatabase)
        twin.ls.update_adjacency_database(db)
        d0 = TENANCY_COUNTERS["dispatches"]
        t0 = _time.perf_counter()
        views_b = twin.manager.solve_views(items)
        batched_s += _time.perf_counter() - t0
        twin_dispatches += TENANCY_COUNTERS["dispatches"] - d0
        ls_seq.update_adjacency_database(db)
        t0 = _time.perf_counter()
        views_s = [
            seq_mgr.solve_view(f"seq/{r}", ls_seq, r) for r in roots
        ]
        seq_s += _time.perf_counter() - t0
    parity = all(
        sb == ss
        and np.array_equal(np.asarray(pb), np.asarray(ps))
        for (_gb, sb, pb), (_gs, ss, ps) in zip(views_b, views_s)
    )
    assert parity, "fleet twin bench diverged from sequential oracle"
    twin.close()
    return {
        "vantages": nodes,
        "events": applied,
        "batched_ms_per_event": round(1000.0 * batched_s / applied, 3),
        "sequential_ms_per_event": round(1000.0 * seq_s / applied, 3),
        "ratio": round(batched_s / seq_s, 4) if seq_s else None,
        "dispatches_per_event": twin_dispatches / float(applied),
        "parity": parity,
        "platform": jax.devices()[0].platform,
    }


def solver_service_bench(
    tenants: int = 64, rounds: int = 10, submitters: int = 8,
    seed: int = 20260806,
) -> dict:
    """Solver-as-a-service leg (openr_tpu.serve): B tenants of mixed
    SLO class driven through a live ``SolverService`` wave loop by
    ``submitters`` concurrent threads (the in-process stand-in for
    client daemons — the TCP wire is the smoke gate's job, the
    scheduler is this leg's). Each round every submitter churns one
    metric per tenant and solicits a solve; concurrent submission is
    what makes requests pile into shared waves.

    Reports per-class latency percentiles (enqueue -> delivery),
    aggregate solves/s, waves and mean requests-per-wave, the wave
    join / preemption counter deltas, and the service-overhead ratio:
    served mean per-solve cost vs the same fleet solved as one direct
    ``WorldManager.solve_views`` batch per round (the scheduler-free
    floor). Parity is asserted on the final round — a fast server must
    still be a correct one."""
    import threading as _threading

    import jax

    from openr_tpu.graph.linkstate import LinkState
    from openr_tpu.models import topologies
    from openr_tpu.ops.spf_sparse import (
        compile_ell,
        ell_source_batch,
        ell_view_batch_packed,
    )
    from openr_tpu.ops.world_batch import TENANCY_COUNTERS, WorldManager
    from openr_tpu.serve.service import SolverService
    from openr_tpu.serve.slo import SLO_TABLE

    def mk_ls(i):
        kind = i % 3
        if kind == 0:
            topo = topologies.grid(3 + i % 3)
        elif kind == 1:
            topo = topologies.ring(8 + 2 * (i % 4))
        else:
            topo = topologies.random_mesh(
                20 + i % 16, 3, seed=seed % 1000 + i
            )
        ls = LinkState(area=topo.area)
        for _name, adj_db in sorted(topo.adj_dbs.items()):
            ls.update_adjacency_database(adj_db)
        return ls

    def wiggle(ls, root, metric):
        from dataclasses import replace

        adj_db = ls.get_adjacency_databases()[root]
        adjs = list(adj_db.adjacencies)
        adjs[0] = replace(adjs[0], metric=metric)
        ls.update_adjacency_database(
            replace(adj_db, adjacencies=tuple(adjs))
        )

    classes = sorted(SLO_TABLE)
    fleet = []
    for i in range(tenants):
        ls = mk_ls(i)
        fleet.append((
            f"b{i}", ls, sorted(ls.get_adjacency_databases())[0],
            classes[i % len(classes)],
        ))

    svc = SolverService(
        manager=WorldManager(
            slots_per_bucket=max(64, tenants), max_resident=2 * tenants
        )
    ).start()
    lat_ms: dict = {cls: [] for cls in classes}
    lat_lock = _threading.Lock()
    try:
        for tid, _ls, _root, slo in fleet:
            svc.register(tid, slo)
        # warmup: cold placements + one churn round, so both the cold
        # and the warm-incremental dispatch executables (and the delta
        # readback) are compiled before the measured rounds
        for tid, ls, root, _slo in fleet:
            svc.solve(tid, ls, root)
        for tid, ls, root, _slo in fleet:
            wiggle(ls, root, 39)
            svc.solve(tid, ls, root)
        joins0 = TENANCY_COUNTERS["wave_joins"]
        pre0 = TENANCY_COUNTERS["wave_preemptions"]
        waves0 = svc.waves()

        shard = max(1, -(-len(fleet) // submitters))
        shards = [
            fleet[i : i + shard] for i in range(0, len(fleet), shard)
        ]

        def drive(mine, r):
            for tid, ls, root, slo in mine:
                wiggle(ls, root, 40 + r)
                t0 = time.perf_counter()
                svc.solve(tid, ls, root)
                ms = 1000.0 * (time.perf_counter() - t0)
                with lat_lock:
                    lat_ms[slo].append(ms)

        t_serve0 = time.perf_counter()
        for r in range(rounds):
            threads = [
                _threading.Thread(target=drive, args=(mine, r))
                for mine in shards
            ]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
        serve_s = time.perf_counter() - t_serve0
        waves = svc.waves() - waves0
        joins = TENANCY_COUNTERS["wave_joins"] - joins0
        preemptions = TENANCY_COUNTERS["wave_preemptions"] - pre0

        # parity on the final round's state, tenant-by-tenant
        parity = True
        for tid, ls, root, _slo in fleet[:: max(1, tenants // 8)]:
            graph = compile_ell(ls)
            ref = np.asarray(ell_view_batch_packed(
                graph, ell_source_batch(graph, ls, root)
            ))
            _g, _srcs, packed = svc.solve(tid, ls, root)
            if not np.array_equal(packed, ref):
                parity = False
    finally:
        svc.stop()

    # scheduler-free floor: the same fleet, one direct batched
    # solve_views per round on a private manager
    mgr = WorldManager(
        slots_per_bucket=max(64, tenants), max_resident=2 * tenants
    )
    direct_ls = [mk_ls(i) for i in range(tenants)]
    direct = [
        (f"d{i}", ls, sorted(ls.get_adjacency_databases())[0])
        for i, ls in enumerate(direct_ls)
    ]
    mgr.solve_views(direct)  # warmup
    t0 = time.perf_counter()
    for r in range(rounds):
        for _tid, ls, root in direct:
            wiggle(ls, root, 40 + r)
        mgr.solve_views(direct)
    direct_s = time.perf_counter() - t0

    def pct(samples, q):
        if not samples:
            return None
        w = sorted(samples)
        return round(
            w[min(len(w) - 1, max(0, int(round(q * (len(w) - 1)))))], 3
        )

    total = rounds * tenants
    return {
        "tenants": tenants,
        "rounds": rounds,
        "submitters": submitters,
        "solves_per_s": round(total / serve_s, 1) if serve_s else None,
        "latency_ms": {
            cls: {"p50": pct(s, 0.5), "p99": pct(s, 0.99)}
            for cls, s in sorted(lat_ms.items())
        },
        "waves": waves,
        "requests_per_wave": round(total / waves, 2) if waves else None,
        "wave_joins": joins,
        "wave_preemptions": preemptions,
        "served_ms_per_solve": round(1000.0 * serve_s / total, 3),
        "direct_ms_per_solve": round(1000.0 * direct_s / total, 3),
        "service_overhead_ratio": (
            round(serve_s / direct_s, 3) if direct_s else None
        ),
        "parity": parity,
        "platform": jax.devices()[0].platform,
    }


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--nodes", type=int, default=10000)
    p.add_argument("--block", type=int, default=1024)
    p.add_argument("--max-blocks", type=int, default=0,
                   help="sweep only the first K source blocks (0 = all); "
                        "the 100k full-product readback is ~40 GB")
    p.add_argument("--kernel", choices=("ell", "edges"), default="ell")
    p.add_argument("--churn", action="store_true",
                   help="run the incremental ELL churn scenario instead "
                        "of all-sources")
    p.add_argument("--churn-events", type=int, default=10)
    p.add_argument("--routes-churn", action="store_true",
                   help="incremental network-wide route reconvergence "
                        "via the resident route engine")
    p.add_argument("--churn-kind", choices=("metric", "link"),
                   default="metric",
                   help="routes-churn event type: metric wiggle, or "
                        "alternating link remove/restore (topology "
                        "churn on the incremental path)")
    p.add_argument("--link-churn", action="store_true",
                   help="paired metric+link churn legs through the "
                        "resident route engine: link-vs-metric median "
                        "ratio, frontier-vs-full split, cone medians")
    p.add_argument("--sharded-churn", action="store_true",
                   help="paired sharded-vs-single metric-churn legs "
                        "with the resharding-free contract deltas "
                        "(ops.reshard_events, shard readback bytes, "
                        "consume-overlap histogram)")
    p.add_argument("--sharded", action="store_true",
                   help="routes-churn: shard the resident engine over "
                        "all visible devices (the past-12k design; on "
                        "one chip this measures the sharded dispatch "
                        "overhead)")
    p.add_argument("--routes", action="store_true",
                   help="all-sources sweep with on-device route "
                        "selection (digest + sample readback only)")
    p.add_argument("--traces", action="store_true",
                   help="convergence-trace leg: churn through the real "
                        "KvStore->Decision->Fib pipeline with the "
                        "telemetry tracer on, emitting a per-event "
                        "trace artifact + latency percentiles")
    p.add_argument("--trace-path", default="churn_traces.jsonl",
                   help="traces leg: JSONL artifact path (a "
                        ".chrome.json twin is written next to it)")
    p.add_argument("--solver-churn", action="store_true",
                   help="full SpfSolver churn rebuild of one node's "
                        "RouteDb (the north-star framing)")
    p.add_argument("--ksp2-dsts", type=int, default=0,
                   help="solver-churn: mark this many prefixes "
                        "KSP2_ED_ECMP (0 = every prefix KSP2)")
    p.add_argument("--sp-only", action="store_true",
                   help="solver-churn: keep every prefix SP_ECMP "
                        "(no KSP2 engine state at all)")
    p.add_argument("--backend", choices=("ell", "grouped"),
                   default="ell",
                   help="route-sweep relaxation backend: per-edge ELL "
                        "gather, or block-bipartite grouped (dense)")
    p.add_argument("--multi-tenant", action="store_true",
                   help="batched-worlds leg: B mixed-size tenant "
                        "graphs under churn, one batched dispatch vs "
                        "N sequential warm engine calls")
    p.add_argument("--tenants", type=int, default=8)
    p.add_argument("--integrity-audit", action="store_true",
                   help="integrity-plane overhead leg: the same warm "
                        "metric-churn loop audited every event vs "
                        "disarmed (gate: armed median within 5%)")
    args = p.parse_args(argv)
    if args.integrity_audit:
        print(
            json.dumps(
                integrity_audit_bench(
                    args.nodes, max(12, args.churn_events)
                )
            ),
            flush=True,
        )
        return
    if args.multi_tenant:
        print(
            json.dumps(
                multi_tenant_bench(
                    args.tenants, rounds=max(20, args.churn_events)
                )
            ),
            flush=True,
        )
        return
    if args.churn:
        run_churn(args)
        return
    if args.traces:
        print(
            json.dumps(
                convergence_trace_bench(
                    args.nodes, args.churn_events,
                    trace_path=args.trace_path,
                )
            ),
            flush=True,
        )
        return
    if args.solver_churn:
        print(
            json.dumps(
                ksp2_churn_bench(
                    args.nodes, args.churn_events,
                    ksp2_dst_count=args.ksp2_dsts,
                    sp_only=args.sp_only,
                )
            ),
            flush=True,
        )
        return
    if args.link_churn:
        print(
            json.dumps(
                link_churn_bench(
                    args.nodes, args.churn_events,
                    sharded=args.sharded,
                    backend=args.backend,
                )
            ),
            flush=True,
        )
        return
    if args.sharded_churn:
        print(
            json.dumps(
                sharded_churn_bench(
                    args.nodes, args.churn_events,
                    backend=args.backend,
                )
            ),
            flush=True,
        )
        return
    if args.routes_churn:
        print(
            json.dumps(
                route_engine_churn_bench(
                    args.nodes, args.churn_events,
                    churn_kind=args.churn_kind,
                    sharded=args.sharded,
                    backend=args.backend,
                )
            ),
            flush=True,
        )
        return
    if args.routes:
        print(
            json.dumps(
                route_sweep_bench(
                    args.nodes, args.block, max_blocks=args.max_blocks,
                    backend=args.backend,
                )
            ),
            flush=True,
        )
        return
    print(
        json.dumps(
            all_sources_bench(
                args.nodes, args.block, args.kernel,
                max_blocks=args.max_blocks,
            )
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
