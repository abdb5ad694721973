"""``route_diff_compared``: the reader on hand-made records, and its
entry in ``BENCHMARK.json``. Counts only; nothing here is a time."""

from __future__ import annotations

import json
import os

import pytest

from chipbench import spec
from chipbench.record import RunRecord, Span

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def read(record):
    return spec.load_reader(REPO, "per_layer", "route_diff_compared")(record)


def diff(trace_id, **attrs):
    return Span(trace_id, "decision.route_diff", 100.0 * trace_id, 4.0, attrs)


@pytest.mark.parametrize("spans, want", [
    # one span per rebuild window: the median over the windows
    ([diff(i, updated=u, deleted=0, identical=4991 - c, compared=c)
      for i, (u, c) in enumerate([(3, 3), (40, 52), (8, 8)])], 8.0),
    # every entry the installed object: 0 is a reading, not an absence
    ([diff(1, updated=0, deleted=0, identical=1015, compared=0)], 0.0),
    # the parent's span says how many changed, not how they were found
    ([diff(1, updated=3, deleted=0), diff(2, updated=9, deleted=1)], None),
    # a per-prefix window has no full-db diff at all
    ([Span(1, "decision.rebuild", 0.0, 0.3, {"full_rebuild": False})], None),
    ([], None),
], ids=["median", "all-identical", "parent", "bypass", "empty"])
def test_the_reader_gives_the_median_count_or_nothing(spans, want):
    assert read(RunRecord(spans=spans)) == want


def test_its_entry_names_the_cells_that_run_a_full_db_diff():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "route_diff_compared"]
    assert entry == {
        "name": "route_diff_compared",
        "unit": "routes/rebuild",
        "better": "lower",
        "source": "program_span",
        "layer": next(m["layer"] for m in bench["per_layer"]
                      if m["name"] == "route_diff_ms"),
        "moves": "conv_p50_ms",
        "workloads": ["fabric-1000.adj-churn", "fabric-5000.adj-churn"],
    }
    # beside route_diff_ms, in the same cells: both read one span
    assert entry["workloads"] == next(
        m["workloads"] for m in bench["per_layer"]
        if m["name"] == "route_diff_ms")
    for cell in entry["workloads"]:
        names = [m["name"] for m in
                 spec.load_cell(REPO, cell).metrics("per_layer")]
        assert "route_diff_compared" in names
    bypass = spec.load_cell(REPO, "fabric-5000.prefix-churn")
    assert "route_diff_compared" not in [
        m["name"] for m in bypass.metrics("per_layer")]
