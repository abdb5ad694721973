"""``solve_puts_per_solve`` (PR 52): one reader, one entry, added behind
what the benchmark had. A hand-made record on the CPU: counts, never
times."""

from __future__ import annotations

import pytest

from benchdef import REPO, in_order, load
from chipbench import spec
from chipbench.record import RunRecord, Span

NAME = "solve_puts_per_solve"
ELL_CELLS = ["fabric-5000.adj-churn", "grid-10000.drain-churn",
             "fabric-50k.adj-churn"]


def _read(spans):
    return spec.load_reader(REPO, "per_layer", NAME)(RunRecord(
        spans=[Span(i, name, 10.0 * i, 1.0, attrs)
               for i, (name, attrs) in enumerate(spans)],
        device_kind="TPU v5 lite"))


def test_the_entry_is_the_device_solve_layer_in_the_three_ell_cells():
    bench = load(REPO)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    prep, = [m for m in bench["per_layer"] if m["name"] == "solve_prep_ms"]
    assert entry == {
        "name": NAME, "unit": "puts/solve", "better": "lower",
        "source": "program_span", "layer": prep["layer"],
        "moves": "conv_p50_ms", "workloads": ELL_CELLS}
    # the cells of the stage it counts the transfers of, in its order
    assert entry["workloads"] == prep["workloads"]
    names = [m["name"] for m in bench["per_layer"]]
    assert in_order(["solve_prep_ms", "solve_put_ms", NAME], names)


@pytest.mark.parametrize("puts, want", [
    ([3] * 9, 3),              # every window prewarmed
    ([3, 3, 3, 9, 4, 3], 3),   # a fused two-band patch and a new vantage batch
    ([13, 13, 13], 13),        # the parent's count, had it said it
    ([3, 6], 4.5),
])
def test_it_is_the_median_of_what_the_solves_span_says(puts, want):
    spans = [("ops.ell_reconverge", {"warm": True, "put_ms": 0.3, "puts": n})
             for n in puts]
    spans.insert(1, ("ops.solve_readback", {"bytes": 1, "puts": 99}))
    assert _read(spans) == pytest.approx(want)


@pytest.mark.parametrize("spans", [
    [],
    # the parent's side: the span says its times and not its transfers
    [("ops.ell_reconverge", {"warm": True, "put_ms": 0.76,
                             "host_overhead_ms": 2.9})],
    # a window without an ELL solve (the dense and the bypass cells)
    [("ops.spf_view_batch", {"puts": 2})],
], ids=["empty", "parent", "no-ell-solve"])
def test_it_finds_nothing_where_the_span_does_not_say(spans):
    assert _read(spans) is None
