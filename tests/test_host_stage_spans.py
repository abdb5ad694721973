"""The spans and attributes inside the host stages that outlast the
policy wait (PR 51), through ``Decision`` on the small twins of the
KSP2 and the ELL cells: where each nests, that it is closed and the
trace well formed, how many a window may open, that none is a direct
child of a span whose self time is a metric, and that the profiler's
sampler makes no wait of its own inside a window. Counts and shapes,
never times: this is the CPU.
"""

from __future__ import annotations

import jax
import pytest

from chipbench import spec, topology, traffic
from chipbench.record import RunRecord, Span
from chipbench.served_paths import pipeline_grid  # noqa: F401 - the grid
from openr_tpu.decision import spf_solver
from openr_tpu.telemetry import get_registry, get_tracer, reset_profiler
from openr_tpu.types import Publication
from tests.chipbench.benchdef import REPO
from tests.test_fabric_three_band import SP_ECMP, THREE_BAND
from tests.test_ksp2_pipeline import KSP2, MIX, VANTAGE, _decision, _inside

ELL_SPANS = {"ops.ell_patch", "ops.ell_scatter"}
KSP2_SPANS = {"decision.ksp2_diff", "decision.ksp2_walk_proof",
              "decision.ksp2_affected", "decision.ksp2_recompute"}
NEW_SPANS = ELL_SPANS | KSP2_SPANS
# spans whose self time is a per-layer metric (ksp2_masked_solve_ms,
# route_build_ms, rebuild_unattributed_ms)
SELF_TIMED = ("ops.ksp2_masked_solve", "decision.route_build",
              "decision.rebuild")
KSP2_TWIN = {"kind": "fat_tree", "pods": 3, "ssw_per_plane": 2,
             "fsw_per_pod": 4, "rsw_per_pod": 12}
# upstream's KSP2 graph at 12 x 12, solved from its corner: a node
# re-costs all its links, so most windows recompute
GRID_TWIN = {"kind": "grid", "n": 12}
GRID_MIX = {"kinds": {"node-metric": 1.0}}


def _parent_of(trace, span):
    """The innermost span of ``trace`` that holds ``span``."""
    at = trace.spans.index(span)
    for other in reversed(trace.spans[:at]):
        if other.depth == span.depth - 1:
            return other
    return None


def _by_name(trace) -> dict:
    out = {}
    for s in trace.spans:
        assert s.closed, s.name
        out.setdefault(s.name, []).append(s)
    return out


def _window(decision, tracer, *events):
    """One debounce window as KvStore's queue would hand it over: the
    first publication carries the trace, the timer's callback closes
    it."""
    trace = tracer.start()
    carried = trace
    for ev in events:
        decision._on_publication(Publication(
            key_vals={ev.key: ev.value}, area="0", trace=carried))
        carried = None
    decision._on_debounce_fire()
    tracer.finish(trace)
    assert trace.well_formed()
    return trace


def _loaded(topo, seed, mix, vantage=VANTAGE):
    gen = traffic.Generator(topo, seed, mix, vantage)
    queue, decision = _decision("device", vantage)
    decision.process_publication(Publication(
        key_vals=dict(gen.initial_key_vals()), area="0"))
    decision.rebuild_routes("LOAD")
    return gen, queue, decision


def _record(traces) -> RunRecord:
    return RunRecord(spans=[
        Span(t.trace_id, s.name, s.ts_ms, s.dur_ms, dict(s.attrs))
        for t in traces for s in t.spans])


def _read(name, record):
    return spec.load_reader(REPO, "per_layer", name)(record)


@pytest.fixture(scope="module")
def ksp2_windows():
    """40 single-event windows of the 56-node KSP2 fabric and 30 of the
    12 x 12 KSP2 grid, each through ``Decision``."""
    tracer = get_tracer()
    traces = []
    for shape, mix, vantage, seed, n in (
            (KSP2_TWIN, MIX, VANTAGE, 29, 40),
            (GRID_TWIN, GRID_MIX, "node-0", 31, 30)):
        topo = topology.build(shape, KSP2)
        gen, queue, decision = _loaded(topo, seed, mix, vantage)
        try:
            traces += [_window(decision, tracer, gen.draw())
                       for _ in range(n)]
        finally:
            queue.close()
    return traces


def test_the_ksp2_spans_nest_where_the_issue_says(ksp2_windows):
    recomputed = 0
    for trace in ksp2_windows:
        spans = _by_name(trace)
        (sync,) = spans["decision.ksp2_sync"]
        if sync.attrs["cold"]:
            continue
        (diff,) = spans["decision.ksp2_diff"]
        (proof,) = spans.get("decision.ksp2_walk_proof", [None])
        (aff,) = spans["decision.ksp2_affected"]
        (rows,) = spans["ops.ksp2_all_pairs"]
        assert _parent_of(trace, diff) is sync
        assert _parent_of(trace, aff) is sync
        assert set(diff.attrs) >= {"nodes", "pairs"}
        assert diff.attrs["pairs"] == sync.attrs["changed_pairs"] >= 1
        assert set(aff.attrs) >= {"first", "second"}
        if proof is not None:
            # the host work that runs while the rows are in flight
            assert _parent_of(trace, proof) is rows
            assert proof.attrs["candidates"] + proof.attrs["proven"] in (
                0, 55, 143)
        assert diff.ts_ms <= rows.ts_ms <= aff.ts_ms
        for rc in spans.get("decision.ksp2_recompute", ()):
            recomputed += 1
            assert _parent_of(trace, rc) is sync
            assert rc.attrs["first"] == aff.attrs["first"]
            assert rc.attrs["second"] == aff.attrs["second"]
            assert 0 <= rc.attrs["moved"] <= rc.attrs["first"] \
                + rc.attrs["second"]
        for name in ("ops.ksp2_masked_solve", "decision.ksp2_trace"):
            for s in spans.get(name, ()):
                assert _inside(s, sync)
        for masked in spans.get("ops.ksp2_masked_solve", ()):
            assert masked.attrs["masks_ms"] >= 0.0
            assert masked.attrs["mask_bytes"] > 0
            if not masked.attrs.get("refresh"):
                assert _parent_of(trace, masked).name \
                    == "decision.ksp2_recompute"
        # one matrix dispatch a window, booked where it was sent: on the
        # masked span it went behind, or on the sync
        booked = [s for s in spans.get("ops.ksp2_masked_solve", []) + [sync]
                  if "matrix_dispatch_ms" in s.attrs]
        assert len(booked) == 1
        (routes,) = spans["decision.ksp2_routes"]
        assert routes.attrs["selected"] <= routes.attrs["visited"]
        assert routes.attrs["select_ms"] >= 0.0
        # the budget: at most 8 new spans in a KSP2 window
        assert sum(len(spans.get(n, ())) for n in NEW_SPANS) <= 8
    assert recomputed >= 10


def test_no_new_span_is_a_direct_child_of_a_self_timed_span(ksp2_windows):
    seen = set()
    for trace in ksp2_windows:
        for s in trace.spans:
            if s.name in NEW_SPANS:
                seen.add(s.name)
                assert _parent_of(trace, s).name not in SELF_TIMED, s.name
    assert seen == NEW_SPANS


@pytest.mark.parametrize("name", [
    "ksp2_masked_solve_ms", "route_build_ms", "rebuild_unattributed_ms",
    "ksp2_all_pairs_ms", "prewarm_ms", "ksp2_sync_ms", "ksp2_trace_ms"])
def test_a_reader_that_was_there_reads_the_same_without_the_new_spans(
        ksp2_windows, name):
    record = _record(ksp2_windows)
    stripped = RunRecord(
        spans=[s for s in record.spans if s.name not in NEW_SPANS])
    assert len(stripped.spans) < len(record.spans)
    assert _read(name, record) == pytest.approx(_read(name, stripped))
    assert _read(name, record) is not None


def test_the_new_ksp2_readers_find_the_spans_of_a_real_window(ksp2_windows):
    record = _record(ksp2_windows)
    for name in ("ksp2_diff_ms", "ksp2_walk_proof_ms", "ksp2_recompute_ms",
                 "ksp2_masks_ms", "ksp2_mask_mb", "ksp2_select_ms",
                 "ksp2_sync_unattributed_ms", "ell_patch_host_ms",
                 "ell_patch_scatter_ms"):
        assert _read(name, record) is not None, name
    # under 200 traces there is no tail
    assert _read("tail_ksp2_sync_excess_ms", record) is None
    # the sync's own residue is what its children leave of it
    assert 0.0 <= _read("ksp2_sync_unattributed_ms", record) \
        <= _read("ksp2_sync_ms", record)


@pytest.fixture(scope="module")
def three_band():
    return topology.build(THREE_BAND, SP_ECMP)


def test_the_ell_spans_nest_under_the_prewarm_and_the_solve_says_its_parts(
        three_band, monkeypatch):
    monkeypatch.setattr(spf_solver, "SPARSE_NODE_THRESHOLD", 32)
    tracer = get_tracer()
    gen, queue, decision = _loaded(three_band, 37, MIX)
    try:
        for _ in range(12):
            trace = _window(decision, tracer, gen.draw())
            spans = _by_name(trace)
            (window,) = spans["decision.debounce"]
            (prewarm,) = spans["decision.prewarm"]
            (patch,) = spans["ops.ell_patch"]
            (scatter,) = spans["ops.ell_scatter"]
            assert _inside(prewarm, window)
            assert _parent_of(trace, patch) is prewarm
            assert _parent_of(trace, scatter) is prewarm
            assert patch.ts_ms + patch.dur_ms <= scatter.ts_ms + 0.5
            assert patch.attrs["rows"] >= 1 and patch.attrs["widened"] == 0
            assert 1 <= scatter.attrs["bands"] <= 3
            assert scatter.attrs["rows"] >= patch.attrs["rows"]
            # ids, sources and weights of the padded rows, int32
            assert scatter.attrs["bytes"] >= 4 * 3 * scatter.attrs["rows"]
            (solve,) = spans["ops.ell_reconverge"]
            (stage,) = spans["decision.speculate"]
            assert _inside(solve, stage)
            a = solve.attrs
            assert a["warm"] is True
            assert a["put_ms"] >= 0.0 and a["launch_ms"] > 0.0
            assert a["put_ms"] + a["launch_ms"] \
                == pytest.approx(a["dispatch_ms"], abs=1e-3)
            assert a["host_overhead_ms"] + a["dispatch_ms"] \
                <= solve.dur_ms + 1e-3
            (wait,) = spans["ops.solve_readback"]
            assert wait.ts_ms >= solve.ts_ms + solve.dur_ms - 0.5
            # the budget: at most 2 new spans a window in an ELL cell
            assert sum(len(spans.get(n, ())) for n in NEW_SPANS) == 2
        # a window of two publications patches twice, solves once
        trace = _window(decision, tracer, gen.draw(), gen.draw())
        spans = _by_name(trace)
        assert len(spans["decision.prewarm"]) == 2
        assert len(spans["ops.ell_patch"]) == len(spans["ops.ell_scatter"]) == 2
    finally:
        queue.close()


def test_without_a_prewarm_the_patch_is_the_view_syncs_and_rides_the_solve(
        three_band, monkeypatch):
    """The rows then go to the device inside the fused solve
    (``band_patch_inputs``): a patch span under ``graph.view_sync``, no
    scatter span, and a span that closes on a raise."""
    monkeypatch.setattr(spf_solver, "SPARSE_NODE_THRESHOLD", 32)
    tracer = get_tracer()
    gen, queue, decision = _loaded(three_band, 41, MIX)

    def build(event):
        decision.process_publication(Publication(
            key_vals={event.key: event.value}, area="0"))
        trace = tracer.start()
        decision.pending.adopt_trace(trace)
        decision.rebuild_routes("WINDOW")
        tracer.finish(trace)
        return trace

    try:
        for _ in range(3):
            trace = build(gen.draw())
            assert trace.well_formed()
            spans = _by_name(trace)
            (patch,) = spans["ops.ell_patch"]
            assert _parent_of(trace, patch).name == "graph.view_sync"
            assert "ops.ell_scatter" not in spans
            assert "decision.prewarm" not in spans
            (solve,) = spans["ops.ell_reconverge"]
            assert _parent_of(trace, solve).name == "decision.route_build"
        # the scoped span closes when the dispatch raises, and the
        # ladder's next rung opens its own
        real = spf_solver._ELL_RESIDENT.view_packed.__func__
        from openr_tpu.ops import spf_sparse

        def boom(*_a, **_k):
            raise RuntimeError("dispatch refused")

        monkeypatch.setattr(spf_sparse, "_ell_reconverge", boom)
        trace = build(gen.draw())
        assert real is spf_solver._ELL_RESIDENT.view_packed.__func__
        assert all(s.closed for s in trace.spans) and trace.well_formed()
        assert any(s.name == "ops.ell_reconverge" for s in trace.spans)
    finally:
        queue.close()


@pytest.mark.parametrize("shape, policy, threshold", [
    (KSP2_TWIN, KSP2, None), (THREE_BAND, SP_ECMP, 32),
    (KSP2_TWIN, SP_ECMP, None)],
    ids=["ksp2", "ell", "dense"])
def test_a_prefix_only_window_and_the_dense_solve_open_no_new_span(
        shape, policy, threshold, monkeypatch):
    if threshold is not None:
        monkeypatch.setattr(spf_solver, "SPARSE_NODE_THRESHOLD", threshold)
    tracer = get_tracer()
    topo = topology.build(shape, policy)
    gen, queue, decision = _loaded(
        topo, 43, {"kinds": {"prefix": 1.0}})
    try:
        for _ in range(4):
            trace = _window(decision, tracer, gen.draw())
            names = {s.name for s in trace.spans}
            assert "decision.rebuild" in names
            assert not names & NEW_SPANS, names
    finally:
        queue.close()
    if policy is SP_ECMP and threshold is None:
        # the dense formulation, under adjacency churn
        gen, queue, decision = _loaded(topo, 47, MIX)
        try:
            for _ in range(4):
                trace = _window(decision, tracer, gen.draw())
                names = {s.name for s in trace.spans}
                assert "ops.spf_view_batch" in names
                assert not names & NEW_SPANS, names
        finally:
            queue.close()


def test_the_sampler_never_waits_inside_a_window(monkeypatch):
    """24+ incremental syncs through ``Decision`` with
    ``jax.block_until_ready`` wrapped: the profiler calls it not once,
    its samples ride the syncs' own reaps (``ops.profile_samples``),
    the rows solve gets a device time and the matrix solve, which
    nobody reads, gets none."""
    import traceback

    reset_profiler(sample_every=4)
    reg = get_registry()
    callers = []
    real = jax.block_until_ready

    def watched(x):
        callers.append("".join(traceback.format_stack(limit=6)))
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", watched)

    def count(name):
        h = reg.histogram_if_exists(name)
        return h.count if h is not None else 0

    tracer = get_tracer()
    gen, queue, decision = _loaded(
        topology.build(GRID_TWIN, KSP2), 53, GRID_MIX, "node-0")
    names = ("ops.device_ms.ksp2_rows", "ops.device_ms.ksp2_masked_resident",
             "ops.device_ms.ksp2_view_rows", "ops.host_ms.ksp2_view_rows",
             "ops.host_ms.ksp2_rows")
    before = {n: count(n) for n in names}
    samples = reg.counter_get("ops.profile_samples")
    syncs = spf_solver.SPF_COUNTERS["decision.ksp2_incremental_syncs"]
    try:
        for _ in range(30):
            _window(decision, tracer, gen.draw())
    finally:
        queue.close()
        reset_profiler()
    syncs = spf_solver.SPF_COUNTERS["decision.ksp2_incremental_syncs"] - syncs
    assert syncs >= 24
    assert not [c for c in callers if "openr_tpu/telemetry" in c]
    moved = {n: count(n) - before[n] for n in names}
    assert moved["ops.host_ms.ksp2_rows"] == syncs
    assert moved["ops.host_ms.ksp2_view_rows"] == syncs
    # one in four of each read program (the cold build's was the
    # first), closed by the reap that read it
    assert moved["ops.device_ms.ksp2_rows"] in (syncs // 4, -(-syncs // 4))
    assert moved["ops.device_ms.ksp2_masked_resident"] >= 1
    assert moved["ops.device_ms.ksp2_view_rows"] == 0
    assert reg.counter_get("ops.profile_samples") - samples \
        == moved["ops.device_ms.ksp2_rows"] \
        + moved["ops.device_ms.ksp2_masked_resident"]
