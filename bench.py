"""Reconvergence benchmark: route-rebuild SPF after a topology change.

Scenario (mirrors the reference Decision benchmarks,
openr/decision/tests/DecisionBenchmark.cpp: BM_DecisionFabric, and its
<100 ms convergence design goal, openr/docs/Introduction/Overview.md:28):

  A ~1000-node 3-tier fat-tree is resident as a compiled snapshot on the
  device. One adjacency metric changes (link churn). Measured latency =
  incremental LinkState merge + ONE fused device dispatch (scatter the
  changed metric rows into the resident matrix + batched SPF from this
  node and every neighbor — exactly the rows a route rebuild consumes for
  best-path selection, ECMP first hops, and LFA; reference
  Decision.cpp:1124 getNextHopsWithMetric, :1192) + distance/first-hop
  readback to the host.

Prints exactly ONE JSON line:
  {"metric": ..., "value": ms, "unit": "ms", "vs_baseline": x,
   "device_only_ms": ms, "platform": "...", "error": null}
where vs_baseline is the speedup vs the reference's 100 ms convergence
design goal (>1.0 means faster than the goal). `value` is end-to-end
(dispatch + readback); `device_only_ms` isolates on-device compute by
timing K data-dependent chained dispatches against one (the fixed
dispatch-and-readback cost cancels in the difference).

One process: the benchmark runs in the invoking process, which owns the
accelerator, and fails (exit 2, no JSON line) when JAX finds none. A
time from the CPU backend is not a time.

Secondary legs folded into the same artifact:
- "bench_10k_churn": the 10k-node resident-ELL churn reconvergence
  (BASELINE.json config 4 axis), via benchmarks.bench_scale.churn_bench.
- "bench_link_churn": paired metric-vs-link churn at 10k through the
  resident route engine — link (structural) events overflow the bucket
  ladder and ride the frontier re-solve; reports the link-vs-metric
  median ratio (target: within ~2x) and the frontier-vs-full split.
- "minplus_ms": pallas-vs-jnp min-plus timing at the bench shape on real
  TPU; the main loop runs whichever measured faster (the losing number
  is kept in the artifact).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

BASELINE_MS = 100.0  # reference convergence design goal
NORTHSTAR_MS = 10.0  # this repo's own target (BASELINE.json)


def _run() -> dict:
    run_t0 = time.monotonic()

    from openr_tpu.utils import compile_cache

    compile_cache.enable()
    # jit compile count/time listeners: a compile-cache regression in
    # any leg shows up as jax.compile_count / jax.compile_ms in the
    # artifact instead of a silent latency cliff
    from openr_tpu.telemetry import jax_hooks as _jax_hooks

    _jax_hooks.install()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from openr_tpu.graph.linkstate import LinkState
    from openr_tpu.graph.snapshot import INF, SnapshotCache, pad_patch_rows
    from openr_tpu.models import topologies
    from openr_tpu.ops import spf as spf_ops
    from openr_tpu.types import Adjacency, AdjacencyDatabase

    platform = jax.devices()[0].platform
    snapshots = SnapshotCache()

    topo = topologies.fat_tree_nodes(1000)
    ls = LinkState(area=topo.area)
    for name in sorted(topo.adj_dbs):
        ls.update_adjacency_database(topo.adj_dbs[name])

    churn_node = "fsw-0-0"
    my_node = "rsw-0-0"

    def churn(step: int) -> None:
        """Bump one adjacency metric on churn_node (incremental update)."""
        db = ls.get_adjacency_databases()[churn_node]
        adjs = list(db.adjacencies)
        a0 = adjs[0]
        adjs[0] = Adjacency(
            other_node_name=a0.other_node_name,
            if_name=a0.if_name,
            metric=2 + (step % 5),
            next_hop_v6=a0.next_hop_v6,
            next_hop_v4=a0.next_hop_v4,
            adj_label=a0.adj_label,
            is_overloaded=a0.is_overloaded,
            rtt=a0.rtt,
            timestamp=a0.timestamp,
            weight=a0.weight,
            other_if_name=a0.other_if_name,
        )
        ls.update_adjacency_database(
            AdjacencyDatabase(
                this_node_name=db.this_node_name,
                is_overloaded=db.is_overloaded,
                adjacencies=tuple(adjs),
                node_label=db.node_label,
                area=db.area,
            )
        )

    # resident device state, owned by the bench loop
    snap0 = snapshots.get(ls)
    sid = snap0.node_index[my_node]
    batch, srcs_dev = spf_ops.source_batch(snap0, sid)
    bucket = srcs_dev.shape[0]
    state = {"metric_dev": jnp.asarray(snap0.metric)}
    noop_ids = np.asarray([sid] * 8, dtype=np.int32)

    def reconverge():
        snap = snapshots.get(ls)
        plan = snap.patch_plan()
        ids = pad_patch_rows(plan[0]) if plan is not None else None
        if ids is None:
            # full (re)compile or oversized change: upload the whole matrix
            state["metric_dev"] = jnp.asarray(snap.metric)
            ids = noop_ids
        vals = snap.metric[ids, :]
        # one fused dispatch: scatter + batched SPF + first hops. The
        # overloaded mask rides along on every step (patch_plan covers
        # metric rows only; this is an O(N) async upload).
        m2, packed = spf_ops.reconverge_step(
            state["metric_dev"],
            jnp.asarray(ids),
            jnp.asarray(vals),
            jnp.asarray(snap.overloaded),
            srcs_dev,
        )
        state["metric_dev"] = m2
        # Completion signal: read back the packed distance + first-hop
        # rows route selection consumes — the transfer is part of what
        # a rebuild waits for. One device->host sync per reconvergence.
        packed_host = np.asarray(packed)
        d_host = packed_host[:bucket]
        fh_host = packed_host[bucket:].astype(bool)
        return d_host, fh_host

    def oracle_gate(d_host, fh_host) -> bool:
        """Device distances + ECMP first hops vs the host Dijkstra oracle
        (reference runSpf semantics), exact."""
        oracle = ls.run_spf(my_node)
        names = snap0.node_names
        for dst, res in oracle.items():
            did = snap0.node_index[dst]
            if d_host[0, did] != res.metric:
                return False
            if dst != my_node:
                got_nh = {
                    names[batch[i]]
                    for i in np.nonzero(fh_host[: len(batch), did])[0]
                }
                if got_nh != res.next_hops:
                    return False
        for dst in set(names) - set(oracle):
            if d_host[0, snap0.node_index[dst]] < INF:
                return False
        return True

    # warm-up (jit compile + first snapshot) on the always-available jnp
    # formulation, oracle-gated
    spf_ops.set_minplus_impl("jnp")
    d_host, fh_host = reconverge()
    assert oracle_gate(d_host, fh_host), "device SPF failed oracle gate"

    # one churn+reconverge outside the timed loop: the first patched
    # snapshot compiles the fused scatter+SPF program (one-time cost)
    churn(99)
    reconverge()

    # Device-only compute time for the CURRENT min-plus impl. A single
    # e2e sample includes the fixed dispatch-and-readback cost; chain K
    # data-dependent dispatches (metric feeds back into the next step)
    # with ONE readback at the end, subtract the 1-dispatch+readback
    # time, and the fixed cost cancels:
    # per-dispatch device time = (T_K - T_1) / (K - 1).
    ov_dev = jnp.asarray(snap0.overloaded)
    ids_dev = jnp.asarray(noop_ids)
    # slice the 8 noop rows on-device instead of reading the whole
    # N x N matrix back to re-upload 8 rows
    vals_dev = state["metric_dev"][ids_dev, :]

    def chain_device_only() -> float:
        def time_chain(k: int) -> float:
            m = state["metric_dev"]
            t0 = time.perf_counter()
            packed = None
            for _ in range(k):
                m, packed = spf_ops.reconverge_step(
                    m, ids_dev, vals_dev, ov_dev, srcs_dev
                )
            np.asarray(packed)
            return (time.perf_counter() - t0) * 1000.0

        time_chain(1)  # warm any K=1 cache path
        t1 = statistics.median(time_chain(1) for _ in range(5))
        tk = statistics.median(time_chain(8) for _ in range(5))
        return round(max(0.0, (tk - t1) / 7.0), 3)

    # Min-plus impl CHOSEN BY MEASUREMENT on the accelerator: time the
    # jnp (XLA-fused) and pallas (hand-tiled VMEM) kernels at the bench
    # shape, run the main loop on the winner, keep the loser's number in
    # the artifact. Both lower on the v5e (chip_smoke.py compiles them
    # at this shape), so a pallas failure here ends the run.
    minplus_ms = {"jnp": chain_device_only()}
    spf_ops.set_minplus_impl("pallas")
    d_host, fh_host = reconverge()  # compile the pallas programs
    assert oracle_gate(d_host, fh_host), "pallas min-plus failed oracle gate"
    minplus_ms["pallas"] = chain_device_only()
    if minplus_ms["pallas"] >= minplus_ms["jnp"]:
        spf_ops.set_minplus_impl("jnp")
    minplus_winner = spf_ops.get_minplus_impl().name
    device_only = minplus_ms[minplus_winner]
    # record the oracle-gated winner under the autotuner's (platform,
    # kernel, shape) key and arm "auto" for every later leg, so the
    # optional legs below resolve to it instead of re-timing a
    # synthetic contraction
    from openr_tpu.ops.autotune import get_autotuner

    get_autotuner().record(
        "minplus",
        f"{bucket}x{state['metric_dev'].shape[-1]}",
        minplus_winner,
    )
    spf_ops.set_minplus_impl("auto")

    samples = []
    for step in range(10):
        churn(step)
        t0 = time.perf_counter()
        reconverge()
        samples.append((time.perf_counter() - t0) * 1000.0)
    value = statistics.median(samples)

    # Optional legs, each gated on the run's REMAINING time budget so
    # a cold compile cache cannot cost the headline number. A skipped
    # leg records why.
    def leg_elapsed() -> float:
        return time.monotonic() - run_t0

    def annotate_ratios(leg: dict) -> dict:
        """Shared vs_baseline / vs_northstar / scale-note annotation
        for per-leg dicts (the north-star note keeps a CPU-fallback
        artifact from reading as 'north star met' at the wrong scale).
        The leg's node count is parsed from its bench name
        (scale.<shape>_<N>_<metric>) so the note stays honest at any
        scale."""
        v = max(leg["median_ms"], 1e-9)
        leg["vs_baseline"] = round(BASELINE_MS / v, 3)
        leg["vs_northstar"] = round(NORTHSTAR_MS / v, 3)
        digits = [
            p for p in leg.get("bench", "").split("_") if p.isdigit()
        ]
        n_desc = f"{digits[0]} nodes" if digits else "this scale"
        leg["northstar_scale_note"] = (
            "north-star target is 100k nodes / v4-32 mesh; this leg "
            f"is {n_desc} on one {leg.get('platform', '?')} device"
        )
        dev = leg.get("device_only_ms")
        if dev and "host_overhead_ratio" not in leg:
            # e2e-vs-device ratio (the committed-dispatch target is
            # this trending to ~1 as host turnarounds leave the path)
            leg["host_overhead_ratio"] = round(v / max(dev, 1e-3), 2)
        measured = _measured_overhead_ratio()
        if measured is not None:
            # the profiler's own wall-vs-device account over recent
            # dispatch windows — this is the headline; the derived
            # ratio above stays for comparison against old artifacts
            leg["host_overhead_ratio_measured"] = measured
        if "pipeline_depth_median" not in leg:
            # windows concurrently in flight when this leg's dispatches
            # pipelined (>= 2 means window N+1 submitted before window
            # N's reap landed); None for a leg that never pipelined
            leg["pipeline_depth_median"] = _pipeline_depth_median()
        return leg

    # second leg: 10k-node resident-ELL churn (the north-star scale
    # axis, BASELINE.json config 4) folded into the same artifact
    bench_10k = None
    if os.environ.get("OPENR_BENCH_10K") == "1":
        if leg_elapsed() > 240:
            bench_10k = {
                "skipped": f"run budget ({leg_elapsed():.0f}s elapsed)"
            }
        else:
            try:
                from benchmarks.bench_scale import churn_bench

                bench_10k = annotate_ratios(churn_bench(10000, 10))
            except Exception as e:
                bench_10k = {"error": f"{type(e).__name__}: {e}"}

    # link-churn leg: structural (link up/down) events at 10k through
    # the frontier re-solve path, paired with a metric-churn control
    # run on the same topology — the PR 6 perf target is the link
    # median landing within ~2x of the metric median
    bench_link = None
    if os.environ.get("OPENR_BENCH_10K") == "1":
        if leg_elapsed() > 330:
            bench_link = {
                "skipped": f"run budget ({leg_elapsed():.0f}s elapsed)"
            }
        else:
            try:
                from benchmarks.bench_scale import link_churn_bench

                bench_link = link_churn_bench(10000, 8)
            except Exception as e:
                bench_link = {"error": f"{type(e).__name__}: {e}"}

    # third leg: fabric-1008 KSP2 churn through the full SpfSolver —
    # the incremental KSP2 engine (BASELINE.json config 2)
    bench_ksp2 = None
    if os.environ.get("OPENR_BENCH_KSP2") == "1":
        if leg_elapsed() > 390:
            bench_ksp2 = {
                "skipped": f"run budget ({leg_elapsed():.0f}s elapsed)"
            }
        else:
            try:
                from benchmarks.bench_scale import ksp2_churn_bench

                bench_ksp2 = annotate_ratios(
                    ksp2_churn_bench(1000, 10)
                )
            except Exception as e:
                bench_ksp2 = {"error": f"{type(e).__name__}: {e}"}

    # fourth leg: the destination-major route sweep with ON-DEVICE
    # route selection (config 5 axis, transfer-fixed): all-sources
    # product consumed on device, digests + sampled route rows read
    # back. Runs the grouped (block-bipartite) backend with on-chip
    # jnp-vs-pallas impl probing; 1008 keeps the CPU fallback cheap
    # while the per-block device time is the scale-relevant number.
    bench_routes = None
    if os.environ.get("OPENR_BENCH_ROUTES") == "1":
        if leg_elapsed() > 420:
            bench_routes = {
                "skipped": f"run budget ({leg_elapsed():.0f}s elapsed)"
            }
        else:
            try:
                from benchmarks.bench_scale import route_sweep_bench

                bench_routes = route_sweep_bench(
                    1000, 256, backend="grouped"
                )
            except Exception as e:
                bench_routes = {"error": f"{type(e).__name__}: {e}"}

    # fifth leg: the incremental route engine on the GROUPED backend —
    # per churn event ONE fused dispatch re-solves only affected
    # destination rows of the resident network-wide route product
    bench_rchurn = None
    if os.environ.get("OPENR_BENCH_ROUTES") == "1":
        if leg_elapsed() > 480:
            bench_rchurn = {
                "skipped": f"run budget ({leg_elapsed():.0f}s elapsed)"
            }
        else:
            try:
                from benchmarks.bench_scale import (
                    route_engine_churn_bench,
                )

                bench_rchurn = route_engine_churn_bench(
                    1000, 8, backend="grouped"
                )
            except Exception as e:
                bench_rchurn = {"error": f"{type(e).__name__}: {e}"}

    # sixth leg: full-SPF RouteDb reconvergence at 10k with every
    # prefix SP_ECMP — the north star AS DEFINED (BASELINE.json: one
    # node's RouteDatabase, full solver) at the largest scale that
    # fits the run budget; SP route reuse bounds the host rebuild
    # to O(changed) prefixes (the 100k variant is the watcher's
    # solver_churn_100k_sp leg)
    bench_spsolver = None
    if os.environ.get("OPENR_BENCH_ROUTES") == "1":
        if leg_elapsed() > 540:
            bench_spsolver = {
                "skipped": f"run budget ({leg_elapsed():.0f}s elapsed)"
            }
        else:
            try:
                from benchmarks.bench_scale import ksp2_churn_bench

                bench_spsolver = annotate_ratios(
                    ksp2_churn_bench(10000, 6, sp_only=True)
                )
            except Exception as e:
                bench_spsolver = {"error": f"{type(e).__name__}: {e}"}

    # seventh leg: convergence tracing through the REAL module pipeline
    # (KvStore -> Decision -> Fib) with the telemetry spine on — the
    # per-event publication->FIB latency distribution plus the trace
    # artifact the north-star claim is audited against. Scale rides the
    # same env gate as the 10k churn leg; the artifact lands next to
    # this file so the watcher can collect it.
    bench_traces = None
    if os.environ.get("OPENR_BENCH_TRACES") == "1":
        if leg_elapsed() > 420:
            bench_traces = {
                "skipped": f"run budget ({leg_elapsed():.0f}s elapsed)"
            }
        else:
            try:
                from benchmarks.bench_scale import (
                    convergence_trace_bench,
                )

                trace_nodes = int(
                    os.environ.get("OPENR_BENCH_TRACE_NODES", "1000")
                )
                bench_traces = convergence_trace_bench(
                    trace_nodes,
                    6,
                    trace_path=os.path.join(
                        os.path.dirname(os.path.abspath(__file__)),
                        "churn_traces.jsonl",
                    ),
                )
            except Exception as e:
                bench_traces = {"error": f"{type(e).__name__}: {e}"}

    # eighth leg: the resharding-free sharded dispatch contract —
    # sharded-vs-single resident churn with the registry deltas that
    # prove the sharded leg paid zero implicit XLA copies
    # (ops.reshard_events == 0) plus the per-shard overlapped-readback
    # account. On one chip the mesh is virtual and the ratio measures
    # sharded dispatch overhead, not scale-out.
    bench_shchurn = None
    if os.environ.get("OPENR_BENCH_SHARDED") == "1":
        if leg_elapsed() > 480:
            bench_shchurn = {
                "skipped": f"run budget ({leg_elapsed():.0f}s elapsed)"
            }
        else:
            try:
                from benchmarks.bench_scale import sharded_churn_bench

                bench_shchurn = sharded_churn_bench(1000, 8)
            except Exception as e:
                bench_shchurn = {"error": f"{type(e).__name__}: {e}"}

    # ninth leg: sustained-load service-plane run — the seeded
    # open-loop generator driving the REAL KvStore -> Decision -> Fib
    # pipeline at a fixed rate with admission control + pipelined emit,
    # plus a max-sustainable-rate estimate and the shed-by-coalescing
    # oracle-parity verdict (tools/load_report.py is the CI gate; this
    # leg folds the same numbers into the official bench artifact)
    bench_load = None
    if os.environ.get("OPENR_BENCH_LOAD") == "1":
        if leg_elapsed() > 540:
            bench_load = {
                "skipped": f"run budget ({leg_elapsed():.0f}s elapsed)"
            }
        else:
            try:
                from benchmarks.bench_scale import sustained_load_bench

                bench_load = sustained_load_bench(
                    int(os.environ.get("OPENR_BENCH_LOAD_NODES", "1000")),
                    rate=240,
                    duration_s=4.0,
                )
            except Exception as e:
                bench_load = {"error": f"{type(e).__name__}: {e}"}

    # tenth leg: multi-tenant batched worlds — B mixed-size tenant
    # graphs under per-round churn, solved as one bucket dispatch vs
    # one warm EllState reconverge per tenant; reports the
    # batched/sequential per-tenant cost ratio (the tenancy acceptance
    # gate is <= 0.5x at B=8), bucket compile counts, and the
    # tenancy.* counter deltas (make tenancy-smoke is the hard CI
    # gate; this leg folds the throughput numbers into the artifact)
    bench_tenancy = None
    if os.environ.get("OPENR_BENCH_TENANCY") == "1":
        if leg_elapsed() > 540:
            bench_tenancy = {
                "skipped": f"run budget ({leg_elapsed():.0f}s elapsed)"
            }
        else:
            try:
                from benchmarks.bench_scale import multi_tenant_bench

                bench_tenancy = multi_tenant_bench(
                    int(os.environ.get("OPENR_BENCH_TENANTS", "8"))
                )
            except Exception as e:
                bench_tenancy = {"error": f"{type(e).__name__}: {e}"}

    # eleventh leg: crash-recovery boot race — the state plane's cold
    # boot (replay every publication) vs warm boot (recover the
    # journaled checkpoint + rehydrate the resident engine from its
    # snapshot), parity-gated; the warm/cold ratio is the recovery
    # design's payoff number (make recovery-smoke is the hard CI gate;
    # this leg folds the timing into the official bench artifact)
    bench_recovery = None
    if os.environ.get("OPENR_BENCH_RECOVERY") == "1":
        if leg_elapsed() > 540:
            bench_recovery = {
                "skipped": f"run budget ({leg_elapsed():.0f}s elapsed)"
            }
        else:
            try:
                from benchmarks.bench_scale import recovery_bench

                bench_recovery = recovery_bench(
                    int(os.environ.get(
                        "OPENR_BENCH_RECOVERY_NODES", "200"
                    ))
                )
            except Exception as e:
                bench_recovery = {"error": f"{type(e).__name__}: {e}"}

    # twelfth leg: integrity-audit overhead — the same warm churn
    # loop with the audit plane armed every event (rate limit off,
    # the worst case) vs disarmed; the acceptance gate is an armed
    # e2e median within 5% of disarmed with zero violations on
    # healthy state (make integrity-smoke is the hard CI gate; this
    # leg folds the overhead number into the official artifact)
    bench_integrity = None
    if os.environ.get("OPENR_BENCH_INTEGRITY") == "1":
        if leg_elapsed() > 540:
            bench_integrity = {
                "skipped": f"run budget ({leg_elapsed():.0f}s elapsed)"
            }
        else:
            try:
                from benchmarks.bench_scale import integrity_audit_bench

                bench_integrity = integrity_audit_bench(
                    int(os.environ.get(
                        "OPENR_BENCH_INTEGRITY_NODES", "1000"
                    ))
                )
            except Exception as e:
                bench_integrity = {"error": f"{type(e).__name__}: {e}"}

    # thirteenth leg: digital-twin fleet reconvergence — N vantages
    # re-solved per topology event as ONE batched wave (the twin) vs
    # N sequential single-tenant dispatches (the pre-twin status quo),
    # parity-asserted on the final event; reports the per-event cost
    # ratio and dispatches/event (make twin-smoke is the hard CI
    # gate; this leg folds the fleet numbers into the artifact)
    bench_twin = None
    if os.environ.get("OPENR_BENCH_TWIN") == "1":
        if leg_elapsed() > 540:
            bench_twin = {
                "skipped": f"run budget ({leg_elapsed():.0f}s elapsed)"
            }
        else:
            try:
                from benchmarks.bench_scale import fleet_twin_bench

                bench_twin = fleet_twin_bench(
                    int(os.environ.get("OPENR_BENCH_TWIN_NODES", "16"))
                )
            except Exception as e:
                bench_twin = {"error": f"{type(e).__name__}: {e}"}

    # fourteenth leg: solver-as-a-service — B mixed-class tenants
    # driven through the live SolverService wave loop by concurrent
    # submitters; reports per-class latency percentiles, solves/s,
    # requests-per-wave, join/preemption deltas, and the scheduler
    # overhead vs a direct batched solve_views floor (make serve-smoke
    # is the hard CI gate; this leg folds the serving-throughput
    # numbers into the official artifact)
    bench_serve = None
    if os.environ.get("OPENR_BENCH_SERVE") == "1":
        if leg_elapsed() > 540:
            bench_serve = {
                "skipped": f"run budget ({leg_elapsed():.0f}s elapsed)"
            }
        else:
            try:
                from benchmarks.bench_scale import solver_service_bench

                bench_serve = solver_service_bench(
                    int(os.environ.get("OPENR_BENCH_SERVE_TENANTS", "64"))
                )
            except Exception as e:
                bench_serve = {"error": f"{type(e).__name__}: {e}"}

    # measured head-to-head: the committed same-host single-thread
    # solver runs (BASELINE_MEASURED.json — native C++ oracle + pure
    # Python host solver over the reference's DecisionBenchmark grid).
    # Unlike the 100 ms design-goal ratio, these divide by a MEASURED
    # number, so "matching-or-beating" is falsifiable.
    try:
        with open(
            os.path.join(os.path.dirname(__file__),
                         "BASELINE_MEASURED.json")
        ) as f:
            _measured_cases = json.load(f)["cases"]
    except (OSError, KeyError, ValueError):
        _measured_cases = {}

    def vs_measured_for(bench_name: str, v: float) -> dict:
        out = {}
        for backend, cases in _measured_cases.items():
            for case in cases:
                # rows marked with a non-default workload are not a
                # like-for-like single-node route build (e.g. the
                # native backend's all-sources sweep at 10k) and must
                # not feed a head-to-head ratio
                if case.get("workload") is not None:
                    continue
                if case.get("bench") == bench_name:
                    out[f"vs_measured_{backend}_solver"] = round(
                        case["churn_rebuild_ms"] / v, 3
                    )
        return out

    vs_measured = vs_measured_for(
        f"decision.fabric_{snap0.n}_sp_ecmp", value
    )
    if bench_spsolver is not None and "median_ms" in bench_spsolver:
        # baseline name derives from the leg's own node count so the
        # two cannot silently drift apart
        digits = [
            p
            for p in bench_spsolver.get("bench", "").split("_")
            if p.isdigit()
        ]
        if digits:
            bench_spsolver.update(
                vs_measured_for(
                    f"decision.fabric_{digits[0]}_sp_ecmp",
                    max(bench_spsolver["median_ms"], 1e-9),
                )
            )

    return {
        "metric": f"spf_reconvergence_ms_fattree_{snap0.n}",
        "value": round(value, 3),
        "unit": "ms",
        # two ratios, deliberately both: vs the reference's 100 ms
        # convergence goal AND vs this repo's own 10 ms north star
        "vs_baseline": round(BASELINE_MS / value, 3),
        "vs_northstar": round(NORTHSTAR_MS / value, 3),
        **vs_measured,
        "northstar_scale_note": (
            "north-star target is 100k nodes / v4-32 mesh; this metric "
            f"is {snap0.n} nodes on one {platform} device"
        ),
        "device_only_ms": device_only,
        "host_overhead_ratio": (
            round(value / max(device_only, 1e-3), 2)
            if device_only else None
        ),
        # headline measured ratio from the always-on profiling plane
        # (paired host/device timing per dispatch window) plus per-tag
        # host-touch distributions — the per-stage account that the
        # derived e2e/device ratio above can only approximate
        "host_overhead_ratio_measured": _measured_overhead_ratio(),
        "host_touches_by_tag": _host_touches_by_tag(),
        "pipeline_depth_median": _pipeline_depth_median(),
        "n_nodes": snap0.n,
        "platform": platform,
        # the oracle-gated measured winner (the session finishes with
        # impl="auto" armed so later legs resolve through the
        # autotuner; this field keeps the concrete winner readable)
        "minplus_impl": minplus_winner,
        "minplus_impl_armed": spf_ops.get_minplus_impl().name,
        "minplus_ms": minplus_ms,
        "bench_10k_churn": bench_10k,
        "bench_link_churn": bench_link,
        "bench_ksp2_churn": bench_ksp2,
        "bench_route_sweep": bench_routes,
        "bench_route_engine_churn": bench_rchurn,
        "bench_sp_solver_churn": bench_spsolver,
        "bench_sharded_churn": bench_shchurn,
        "bench_convergence_trace": bench_traces,
        "bench_sustained_load": bench_load,
        "bench_multi_tenant": bench_tenancy,
        "bench_recovery": bench_recovery,
        "bench_integrity_audit": bench_integrity,
        "bench_fleet_twin": bench_twin,
        "bench_solver_service": bench_serve,
        # per-event convergence-latency distribution from the telemetry
        # registry (convergence.e2e_ms feeds from every finished trace;
        # the solver-leg histograms ride along) — the artifact's
        # DeltaPath-style account next to the aggregate medians
        "latency_histograms": _histogram_snapshot(),
        # merged solver + resident-band counters accumulated across
        # every leg above — the churn-path health record (incremental
        # syncs, warm/cold solve split, widen and prewarm events)
        "spf_counters": _spf_counter_snapshot(),
        "error": None,
    }


def _pipeline_depth_median() -> "float | None":
    """Median ``ops.pipeline_depth`` observation — how many event
    windows were concurrently in flight at each pipelined submit —
    or None before any window pipelined."""
    try:
        from openr_tpu.telemetry import get_registry

        h = get_registry().histograms().get("ops.pipeline_depth")
        if h is None or not h.count:
            return None
        return round(h.percentile(0.50), 1)
    except Exception:
        return None


def _measured_overhead_ratio() -> "float | None":
    """Live ``ops.host_overhead_ratio`` from the profiling plane:
    sum(window wall) / sum(attributed device time) over the recent
    dispatch windows, or None before any sampled window landed."""
    try:
        from openr_tpu.telemetry import get_profiler

        ratio = get_profiler().host_overhead_ratio()
        return round(ratio, 3) if ratio is not None else None
    except Exception:
        return None


def _host_touches_by_tag() -> dict:
    """Per-tag ``ops.host_touches.<tag>`` snapshots (p50 + count) —
    which dispatch stages pay host turnarounds, and how often."""
    try:
        from openr_tpu.telemetry import get_registry

        reg = get_registry()
        out = {}
        for name, h in sorted(reg.histograms().items()):
            if not name.startswith("ops.host_touches.") or not h.count:
                continue
            tag = name[len("ops.host_touches."):]
            out[tag] = {
                "p50": round(h.percentile(0.50), 3),
                "count": h.count,
            }
        return out
    except Exception:
        return {}


def _histogram_snapshot() -> dict:
    """Every non-empty registry histogram, expanded to percentiles."""
    try:
        from openr_tpu.telemetry import get_registry

        out = {}
        for h in get_registry().histograms().values():
            if h.count:
                out.update(h.stats())
        return out
    except Exception:
        return {}


def _spf_counter_snapshot() -> dict:
    try:
        from openr_tpu.decision.spf_solver import get_spf_counters

        return {
            k: v for k, v in sorted(get_spf_counters().items()) if v
        }
    except Exception:
        return {}


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        # without the chip the installed jax carries on to the CPU
        # backend instead of failing; a number from there is not one
        print(
            "bench.py: no accelerator (jax.devices()[0].platform == "
            "'cpu'); refusing to time the CPU backend",
            file=sys.stderr,
        )
        return 2
    result = _run()
    result["device_kind"] = dev.device_kind
    result["device_count"] = len(jax.devices())
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
