"""The readers of the spans and attributes PR 51 put inside the host
stages that outlast the policy wait (``chipbench/hoststage.py``): each
on a hand-made record (its value, and nothing from a record whose
program has no such span or attribute, as the parent of that PR has
not), its entry in ``BENCHMARK.json``, the self-time readers that were
there held to what they read before, and a count of the new spans in
the cells that must run none. Counts and hand-made numbers only;
nothing here is a time."""

from __future__ import annotations

import json

import pytest

from benchdef import REPO, append_config, copy_checkout, entry, in_order
from benchdef import load, reported
from chipbench import run, spec
from chipbench.record import RunRecord, Span
from chipbench.xplane import DeviceTrace
from openr_tpu.telemetry import get_tracer

# the spans PR 51 added, and the attributes it added to spans that were
# there
NEW_SPANS = {
    "ops.ell_patch", "ops.ell_scatter", "decision.ksp2_diff",
    "decision.ksp2_walk_proof", "decision.ksp2_affected",
    "decision.ksp2_recompute",
}
NEW_ATTRS = {
    "ops.ell_reconverge": {"put_ms", "launch_ms"},
    "ops.ksp2_masked_solve": {"masks_ms", "mask_bytes",
                              "matrix_dispatch_ms"},
    "decision.ksp2_sync": {"matrix_dispatch_ms"},
    "decision.ksp2_routes": {"select_ms", "selected"},
}
KSP2_SPANS = {
    "decision.ksp2_sync", "ops.ksp2_all_pairs", "ops.ksp2_masked_solve",
    "decision.ksp2_trace", "decision.ksp2_routes",
}
PREWARM_CELLS = ["fabric-5000.adj-churn", "grid-10000.drain-churn",
                 "fabric-1000-ksp2.adj-churn", "grid-1000-ksp2.drain-churn",
                 "fabric-50k.adj-churn"]
ELL_CELLS = ["fabric-5000.adj-churn", "grid-10000.drain-churn",
             "fabric-50k.adj-churn"]
KSP2_CELLS = ["fabric-1000-ksp2.adj-churn", "grid-1000-ksp2.drain-churn"]


def _window(i: int, slow: bool) -> list:
    """One KSP2 window's trace, staged under the policy wait, in ms from
    its publication. A slow one spends 4 more in the sync: 2 in
    ``_recompute``'s own bookkeeping, 2 in its masked solve; and 1 more
    in the per-prefix pass."""
    t = 1000.0 * i
    more = 4.0 if slow else 0.0

    def span(name, start, dur, **attrs):
        return Span(i, name, t + start, dur, attrs)

    return [
        span("kvstore.publish", 0.0, 0.0),
        span("decision.debounce", 0.5, 12.0 + more, slack_ms=-1.0),
        span("decision.prewarm", 0.6, 2.0, rows=2),
        span("ops.ell_patch", 0.7, 0.8, rows=2, widened=0),
        span("ops.ell_scatter", 1.6, 0.9, bands=1, rows=2, bytes=200),
        span("decision.speculate", 3.0, 8.0 + more),
        span("decision.ksp2_sync", 3.1, 7.0 + more, matrix_dispatch_ms=0.1),
        span("decision.ksp2_diff", 3.2, 0.5, nodes=2, pairs=1),
        span("ops.ksp2_all_pairs", 3.8, 2.0, rows=40),
        span("decision.ksp2_walk_proof", 4.0, 1.0, candidates=3, proven=52),
        span("decision.ksp2_affected", 5.9, 0.3, first=1, second=3),
        span("decision.ksp2_recompute", 6.3, 3.0 + more, first=1, second=3,
             moved=2),
        span("ops.ksp2_masked_solve", 6.5, 2.0 + more / 2, rows=4,
             masks_ms=0.4 + more / 8, mask_bytes=2_000_000 + 250_000 * more),
        span("decision.ksp2_trace", 7.5, 0.5, hops=9),
        span("decision.rebuild", 12.5 + more, 1.5 + more / 4),
        span("decision.route_build", 12.6 + more, 1.0 + more / 4),
        span("decision.ksp2_routes", 12.7 + more, 0.5 + more / 4,
             reused=50, select_ms=0.3 + more / 8, selected=4),
        span("fib.program", 14.4 + 2 * more, 0.3),
    ]


def _ksp2_record(windows: int = 200, slow: int = 20) -> RunRecord:
    return RunRecord(spans=[
        s for i in range(windows) for s in _window(i, i >= windows - slow)])


def _ell_record() -> RunRecord:
    """Three ELL windows, and the traced tail of them on the profiler's
    clock (ns): each readback waits for a ``jit__ell_reconverge`` launch
    that ends 0.5, 1.5 and 4.0 ms before it does; the third launch was
    done before the host asked (a wait of 3.0 ms: all of it is after the
    device)."""
    spans, host, modules = [], [], []
    for i, (prep, put, launch) in enumerate(
            [(1.0, 0.25, 2.0), (1.5, 0.5, 2.5), (2.0, 0.75, 3.0)]):
        t = 100.0 * i
        spans += [
            Span(i, "decision.debounce", t, 12.0, {}),
            Span(i, "decision.prewarm", t + 0.1, 3.0, {}),
            Span(i, "ops.ell_patch", t + 0.2, 1.0 + i, {}),
            Span(i, "ops.ell_scatter", t + 1.3 + i, 0.5, {}),
            Span(i, "ops.ell_reconverge", t + 4.0, prep + put + launch, dict(
                warm=True, host_overhead_ms=prep, put_ms=put,
                launch_ms=launch, dispatch_ms=put + launch)),
            Span(i, "ops.solve_readback", t + 10.0, 3.0, {"bytes": 64}),
        ]
    for i, (launch_end, wait) in enumerate(
            [(12.5, (10.0, 13.0)), (111.5, (110.0, 113.0)),
             (209.0, (210.0, 213.0))]):
        modules.append((f"jit__ell_reconverge({i})", (launch_end - 2.0) * 1e6,
                        launch_end * 1e6))
        modules.append(("jit_patch(7)", wait[0] * 1e6, wait[0] * 1e6 + 10.0))
        host.append(("ops.solve_readback", wait[0] * 1e6, wait[1] * 1e6))
    host.append(("ops.solve_readback", 900e6, 901e6))  # the closing probe's
    device = DeviceTrace(window=(0.0, 1000e6), steady=(0.0, 500e6),
                         modules=[modules], host=host)
    return RunRecord(spans=spans, device=device)


def _stripped(record: RunRecord, also=frozenset()) -> RunRecord:
    """``record`` as the parent of PR 51 would have written it: none of
    the new spans, none of the new attributes (nor the spans ``also``
    names)."""
    spans = [
        Span(s.trace_id, s.name, s.ts_ms, s.dur_ms, {
            k: v for k, v in s.attrs.items()
            if k not in NEW_ATTRS.get(s.name, ())})
        for s in record.spans if s.name not in NEW_SPANS | set(also)]
    device = record.device
    if device is not None and "ops.solve_readback" in also:
        device = DeviceTrace(window=device.window, steady=device.steady,
                             modules=device.modules, host=[])
    return RunRecord(spans=spans, device=device)


def _read(name: str, record: RunRecord):
    return spec.load_reader(REPO, "per_layer", name)(record)


# reader -> (the record it reads, its value there, what else to strip
# for a record on which it has nothing to read)
READERS = {
    "ell_patch_host_ms": (_ell_record, 2.0, ()),
    "ell_patch_scatter_ms": (_ell_record, 0.5, ()),
    "solve_prep_ms": (_ell_record, 1.5, ("ops.ell_reconverge",)),
    "solve_put_ms": (_ell_record, 0.5, ()),
    "solve_launch_ms": (_ell_record, 2.5, ()),
    # 0.5 and 1.5 after the launch ended; 4.0 clipped to the wait's 3.0
    "readback_after_device_ms": (_ell_record, 1.5, ("ops.solve_readback",)),
    "ksp2_diff_ms": (_ksp2_record, 0.5, ()),
    "ksp2_walk_proof_ms": (_ksp2_record, 1.0, ()),
    # 3.0 less the masked solve's 2.0 (the trace inside that is not
    # subtracted twice)
    "ksp2_recompute_ms": (_ksp2_record, 1.0, ()),
    "ksp2_masks_ms": (_ksp2_record, 0.4, ()),
    "ksp2_mask_mb": (_ksp2_record, 2.0, ()),
    "ksp2_select_ms": (_ksp2_record, 0.3, ()),
    # 7.0 less diff 0.5, all-pairs 2.0, affected 0.3, recompute 3.0
    "ksp2_sync_unattributed_ms": (_ksp2_record, 1.2, ()),
    "tail_ksp2_sync_excess_ms": (_ksp2_record, 4.0, KSP2_SPANS),
    "tail_ksp2_recompute_excess_ms": (_ksp2_record, 2.0, ()),
    "tail_ksp2_masked_excess_ms": (_ksp2_record, 2.0, KSP2_SPANS),
    "tail_ksp2_routes_excess_ms": (_ksp2_record, 1.0, KSP2_SPANS),
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_gives_its_value(name):
    make, want, _ = READERS[name]
    assert _read(name, make()) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_gives_nothing_without_its_span(name):
    make, _, also = READERS[name]
    assert _read(name, _stripped(make(), also)) is None
    assert _read(name, RunRecord()) is None


@pytest.mark.parametrize("name", [
    "tail_ksp2_sync_excess_ms", "tail_ksp2_recompute_excess_ms",
    "tail_ksp2_masked_excess_ms", "tail_ksp2_routes_excess_ms"])
def test_a_tail_needs_two_hundred_traces_that_reached_fib(name):
    assert _read(name, _ksp2_record(windows=199, slow=19)) is None
    short = _ksp2_record()
    short.spans = [s for s in short.spans
                   if not (s.name == "fib.program" and s.trace_id == 0)]
    assert _read(name, short) is None


def test_a_trace_without_the_span_counts_as_zero_in_the_tail():
    """The ranking is ``spantail``'s, over every trace that reached
    ``fib.program``: windows that never recomputed pull the medians to
    0, they do not leave the ranking."""
    record = _ksp2_record()
    record.spans = [
        s for s in record.spans
        if s.trace_id >= 150 or s.name not in
        ("decision.ksp2_recompute", "ops.ksp2_masked_solve",
         "decision.ksp2_trace")]
    # the decile's 20 recompute (self 3.0); of all 200 only 50 do
    assert _read("tail_ksp2_recompute_excess_ms", record) \
        == pytest.approx(3.0)
    assert _read("tail_ksp2_masked_excess_ms", record) == pytest.approx(4.0)
    # a median over the windows that have one, as it always was
    assert _read("ksp2_recompute_ms", record) == pytest.approx(1.0)


@pytest.mark.parametrize("name", [
    "ksp2_masked_solve_ms", "route_build_ms", "rebuild_unattributed_ms",
    "ksp2_all_pairs_ms", "prewarm_ms", "solve_span_ms", "solve_wait_ms",
    "ksp2_sync_ms", "ksp2_trace_ms", "ksp2_routes_ms", "speculate_ms",
    "debounce_ms", "tail_debounce_excess_ms", "tail_rebuild_excess_ms"])
def test_the_readers_that_were_there_read_what_they_read(name):
    """No new span is a direct child of a span whose self time is a
    metric, and none moves an edge of a span that was there: on a record
    with the new spans and attributes taken out every reader that was
    there gives the number it gives with them in."""
    for make in (_ksp2_record, _ell_record):
        record = make()
        got, without = _read(name, record), _read(name, _stripped(record))
        assert got == (without if without is None
                       else pytest.approx(without)), name


def test_the_self_times_on_the_hand_made_window():
    record = _ksp2_record()
    # the masked solve less the trace inside it; the build less the
    # per-prefix pass; the rebuild less the build
    assert _read("ksp2_masked_solve_ms", record) == pytest.approx(1.5)
    assert _read("route_build_ms", record) == pytest.approx(0.5)
    assert _read("rebuild_unattributed_ms", record) == pytest.approx(0.5)


LAYER_OF = {"debounce": "debounce_ms", "solve": "solve_span_ms",
            "ksp2": "ksp2_sync_ms", "rebuild": "ksp2_routes_ms"}
ENTRIES = [
    ("ell_patch_host_ms", "ms", "program_span", "debounce", "conv_p50_ms",
     PREWARM_CELLS, "prewarm_ms"),
    ("ell_patch_scatter_ms", "ms", "program_span", "debounce", "conv_p50_ms",
     PREWARM_CELLS, "prewarm_ms"),
    ("solve_prep_ms", "ms", "program_span", "solve", "conv_p50_ms",
     ELL_CELLS, "solve_span_ms"),
    ("solve_put_ms", "ms", "program_span", "solve", "conv_p50_ms",
     ELL_CELLS, "solve_span_ms"),
    ("solve_launch_ms", "ms", "program_span", "solve", "conv_p50_ms",
     ELL_CELLS, "solve_span_ms"),
    ("readback_after_device_ms", "ms", "device_trace", "solve",
     "conv_p50_ms", ELL_CELLS, "solve_span_ms"),
    ("ksp2_diff_ms", "ms", "program_span", "ksp2", "conv_p50_ms",
     KSP2_CELLS, "ksp2_sync_ms"),
    ("ksp2_walk_proof_ms", "ms", "program_span", "ksp2", "conv_p50_ms",
     KSP2_CELLS, "ksp2_all_pairs_ms"),
    ("ksp2_recompute_ms", "ms", "program_span", "ksp2", "conv_p95_ms",
     KSP2_CELLS, "ksp2_sync_ms"),
    ("ksp2_masks_ms", "ms", "program_span", "ksp2", "conv_p50_ms",
     KSP2_CELLS, "ksp2_masked_solve_ms"),
    ("ksp2_mask_mb", "MB", "program_span", "ksp2", "conv_p50_ms",
     KSP2_CELLS, "ksp2_masked_solve_ms"),
    ("ksp2_select_ms", "ms", "program_span", "rebuild", "conv_p95_ms",
     KSP2_CELLS, "ksp2_routes_ms"),
    ("ksp2_sync_unattributed_ms", "ms", "program_span", "ksp2",
     "conv_p50_ms", KSP2_CELLS, "ksp2_sync_ms"),
    ("tail_ksp2_sync_excess_ms", "ms", "program_span", "ksp2",
     "conv_p95_ms", KSP2_CELLS, "tail_debounce_excess_ms"),
    ("tail_ksp2_recompute_excess_ms", "ms", "program_span", "ksp2",
     "conv_p95_ms", KSP2_CELLS, "tail_debounce_excess_ms"),
    ("tail_ksp2_masked_excess_ms", "ms", "program_span", "ksp2",
     "conv_p95_ms", KSP2_CELLS, "tail_debounce_excess_ms"),
    ("tail_ksp2_routes_excess_ms", "ms", "program_span", "rebuild",
     "conv_p95_ms", KSP2_CELLS, "tail_rebuild_excess_ms"),
]


def test_every_new_reader_has_a_case_and_an_entry():
    assert {e[0] for e in ENTRIES} == set(READERS)


@pytest.mark.parametrize(
    "name, unit, source, layer, moves, cells, beside", ENTRIES,
    ids=[e[0] for e in ENTRIES])
def test_the_entry_names_its_cells_and_its_layer(
        checkout, name, unit, source, layer, moves, cells, beside):
    bench = load(checkout)
    fields, listed = entry(bench, name)
    layer_of, _ = entry(bench, LAYER_OF[layer])
    assert fields == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": layer_of["layer"], "moves": moves,
    }
    assert in_order(cells, listed)
    # behind what the benchmark had, in the order of ENTRIES
    names = [m["name"] for m in bench["per_layer"]]
    assert in_order(["ell_whole_passes_per_solve"] + [e[0] for e in ENTRIES],
                    names)
    for cell in cells:
        cell = spec.load_cell(checkout, cell)
        assert {name, beside} <= reported(cell)
        assert moves in reported(cell, "end_to_end")


# -- the cells that must run none of the new spans ----------------------------

TINY_TWO_AREAS = {"kind": "two_area_fat_tree", "pods": 3, "ssw_per_plane": 2,
                  "fsw_per_pod": 2, "rsw_per_pod": 4}


@pytest.fixture(scope="module")
def small_multiarea_root(tmp_path_factory):
    return append_config(
        copy_checkout(str(tmp_path_factory.mktemp("checkout"))),
        "multi-area-small", "multi-area-2x1000", TINY_TWO_AREAS,
        {"redist-churn": "multi-area-2x1000.redist-churn"}, "2 x 22 nodes")


@pytest.mark.parametrize("root, cell", [
    ("appended_root", "fabric-tiny.adj-churn"),
    ("appended_root", "fabric-tiny.prefix-churn"),
    ("small_multiarea_root", "multi-area-small.redist-churn"),
], ids=["dense", "prefix-only", "redistribution"])
def test_the_dense_prefix_only_and_redistribution_paths_open_no_new_span(
        request, monkeypatch, capsys, root, cell):
    """The budget of PR 51's spans: none where the stage they describe
    does not run (the dense solve, a window that changes no topology,
    PrefixManager's redistribution), counted over every trace a run of
    the cell's own traffic retires."""
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    names, attrs = {}, set()

    def listen(trace, _ok):
        for s in trace.spans:
            names[s.name] = names.get(s.name, 0) + 1
            attrs.update(
                (s.name, k) for k in NEW_ATTRS.get(s.name, ())
                if k in s.attrs)

    tracer = get_tracer()
    tracer.add_finish_listener(listen)
    try:
        result = run.run_cell(request.getfixturevalue(root), cell,
                              seed=2300000051, seconds=3.0, trace=False)
    finally:
        tracer.remove_finish_listener(listen)
    detail = json.loads(
        capsys.readouterr().out.split("detail: ")[-1].splitlines()[0])
    assert all("needs 200 samples" in p for p in detail["problems"]), detail
    assert result["failed"] == 0 and names.get("fib.program", 0) >= 20
    assert not NEW_SPANS & set(names), names
    assert not attrs
