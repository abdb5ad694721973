"""``solve_readback_mb`` and ``ell_slot_fill_share``: what a solve hands
back and how much of what it streams is an edge, as the program's spans
say them. Each reader on hand-made records (present, absent, a span
without the attribute) and its entry in ``BENCHMARK.json``. Counts
only; nothing here is a time."""

from __future__ import annotations

import pytest

from benchdef import REPO, entry, in_order, load, reported
from chipbench import spec
from chipbench.record import RunRecord, Span

READBACK, FILL = "solve_readback_mb", "ell_slot_fill_share"
# the ELL cells, in the order they joined the benchmark
CELLS = ["fabric-5000.adj-churn", "grid-10000.drain-churn",
         "fabric-50k.adj-churn"]


def read(name, spans):
    record = RunRecord(spans=[
        Span(i, span, 10.0 * i, 1.0, dict(attrs))
        for i, (span, attrs) in enumerate(spans)])
    return spec.load_reader(REPO, "per_layer", name)(record)


def readback(nbytes, **more):
    return ("ops.solve_readback", dict(bytes=nbytes, **more))


def solve(**attrs):
    return ("ops.ell_reconverge", dict(warm=True, **attrs))


@pytest.mark.parametrize("spans, want", [
    # fabric-50k: distances and first hops of 16 rows over 50,304 columns
    ([readback(2 * 16 * 50304 * 4, passes=3, reset_rows=0)] * 5, 6.438912),
    ([readback(2 * 16 * 4992 * 4)] * 3, 0.638976),
    # the median over the window's solves, not their mean
    ([readback(1_000_000), readback(3_000_000), readback(50_000_000)], 3.0),
    # a span that does not say (nor does the parent's say less: it has
    # carried ``bytes`` since PR 23) is left out; alone it gives nothing
    ([("ops.solve_readback", {"passes": 3}), readback(2_000_000)], 2.0),
    ([("ops.solve_readback", {"passes": 3})], None),
    # a window that solved no view on the device (prefix-churn)
    ([("decision.rebuild", {"bytes": 7})], None),
    ([], None),
], ids=["fabric-50k", "fabric-5000", "median", "one-silent", "silent",
        "bypass", "empty"])
def test_readback_is_the_median_of_what_the_spans_say_in_megabytes(
        spans, want):
    got = read(READBACK, spans)
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("spans, want", [
    # fabric-50k: 8 of 8, 84 of 128, 893 of 1,024
    ([solve(slots=1_552_256, edges=1_200_192)] * 4,
     100.0 * 1_200_192 / 1_552_256),
    ([solve(slots=155_136, edges=112_896)], 100.0 * 112_896 / 155_136),
    # degree 2-4 in one band of 8
    ([solve(slots=80_000, edges=39_600)], 49.5),
    # flaps move the edges, never the slots: each is a median
    ([solve(slots=4864, edges=2080), solve(slots=4864, edges=2078),
      solve(slots=4864, edges=2076)], 100.0 * 2078 / 4864),
    ([solve(slots=4864, edges=0)], 0.0),
    # the parent's span says neither; one that says half says nothing
    ([solve(dispatch_ms=1.5, host_overhead_ms=0.2)], None),
    ([solve(slots=4864)], None),
    ([solve(edges=2080)], None),
    ([solve(slots=0, edges=0)], None),
    # the dense formulation, and a window with no solve
    ([("ops.spf_view_batch", {"slots": 10, "edges": 5})], None),
    ([], None),
], ids=["fabric-50k", "fabric-5000", "grid", "medians", "no-edge", "parent",
        "no-edges", "no-slots", "no-band", "dense", "empty"])
def test_fill_is_edges_over_slots_in_percent(spans, want):
    got = read(FILL, spans)
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("name, unit, better, beside", [
    (READBACK, "MB", "lower", "solve_wait_ms"),
    (FILL, "%", "higher", "relax_roofline"),
])
def test_the_entry_names_the_ell_cells_and_its_layer(
        checkout, name, unit, better, beside):
    bench = load(checkout)
    fields, listed = entry(bench, name)
    neighbour, _ = entry(bench, beside)
    assert fields == {
        "name": name, "unit": unit, "better": better,
        "source": "program_span", "layer": neighbour["layer"],
        "moves": "conv_p50_ms",
    }
    assert in_order(CELLS, listed)
    # behind what the benchmark had, READBACK before FILL
    names = [m["name"] for m in bench["per_layer"]]
    assert in_order(["ingest_reuse_share", READBACK, FILL], names)
    for cell in CELLS:
        cell = spec.load_cell(checkout, cell)
        assert name in reported(cell)
        assert "conv_p50_ms" in reported(cell, "end_to_end")
        # a cell that reports the solve's span reports these
        assert "solve_span_ms" in reported(cell)
