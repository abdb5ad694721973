"""``ingest_reuse_share``: the reader on hand-made records, its entry in
``BENCHMARK.json``, and the line of a traced run of a small fabric cell
under the real cell's mix. Counts only; nothing here is a time."""

from __future__ import annotations

import json

import pytest

from benchdef import (
    REPO,
    TINY,
    append_config,
    copy_checkout,
    entry,
    in_order,
    load,
    reported,
)
from chipbench import run, spec
from chipbench.record import RunRecord

NAME = "ingest_reuse_share"
# the cells whose traffic publishes ``adj:`` keys, in the order they
# joined the benchmark
CELLS = ["fabric-1000.adj-churn", "fabric-5000.adj-churn",
         "grid-10000.drain-churn", "fabric-1000-ksp2.adj-churn",
         "grid-1000-ksp2.drain-churn", "multi-area-2x1000.adj-churn"]
REUSED = "decision.adj_elements_reused"
DECODED = "decision.adj_elements_decoded"


def read(record):
    return spec.load_reader(REPO, "per_layer", NAME)(record)


@pytest.mark.parametrize("counters, want", [
    # 300 events on 84-adjacency nodes, one element changed in each
    ({REUSED: 300 * 83, DECODED: 300}, 100.0 * 83 / 84),
    ({REUSED: 2128, DECODED: 272}, 100.0 * 2128 / 2400),
    # a grid node re-costs every one of its links: 0 is a reading, not
    # an absence
    ({REUSED: 0, DECODED: 960}, 0.0),
    ({REUSED: 40, DECODED: 0}, 100.0),
    # the parent's program decodes every value whole and keeps no such
    # counter: nothing, and no raise
    ({DECODED: 960}, None),
    ({"decision.adj_db_update": 300}, None),
    # a mix with no adj: key in the window (prefix-churn, redist-churn)
    ({REUSED: 0, DECODED: 0}, None),
    ({}, None),
], ids=["fabric-switch", "mixed", "grid", "all-stand", "half-a-parent",
        "parent", "no-adjacency", "empty"])
def test_the_reader_gives_the_share_or_nothing(counters, want):
    got = read(RunRecord(counters=dict(counters)))
    assert got == (want if want is None else pytest.approx(want))


def test_its_entry_names_the_cells_whose_traffic_publishes_adjacencies(
        checkout):
    bench = load(checkout)
    fields, listed = entry(bench, NAME)
    beside, _ = entry(bench, "tail_ingest_excess_ms")
    assert fields == {
        "name": NAME,
        "unit": "%",
        "better": "higher",
        "source": "program_counter",
        "layer": beside["layer"],
        "moves": "conv_p95_ms",
    }
    assert fields["layer"] == "ingest (kvstore, messaging queue)"
    assert in_order(CELLS, listed)
    # behind the layer's other entries and the newest of the last PR
    names = [m["name"] for m in bench["per_layer"]]
    assert in_order(["ingest_ms", "queue_wait_ms", "tail_ingest_excess_ms",
                     "ksp2_matrix_unready_share", NAME], names)
    # exactly the cells whose mix publishes adj: keys report it, and
    # each of them reports the end-to-end metric it moves
    for w in bench["workloads"]:
        cell = spec.load_cell(checkout, w["name"])
        publishes_adj = bool(cell.mix.get("reaches_solver"))
        assert (NAME in reported(cell)) == publishes_adj, w["name"]
        if publishes_adj:
            assert "conv_p95_ms" in reported(cell, "end_to_end"), w["name"]
    for bypass in ("fabric-5000.prefix-churn",
                   "multi-area-2x1000.redist-churn"):
        assert NAME not in reported(spec.load_cell(checkout, bypass))


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """The 22-node fabric of ``benchdef``, added as data under the mix
    of ``fabric-1000.adj-churn`` and, as the bypass, under the mix of
    ``fabric-5000.prefix-churn``."""
    return append_config(
        copy_checkout(str(tmp_path_factory.mktemp("checkout"))),
        "fabric-small", "fabric-1000", TINY,
        {"adj-churn": CELLS[0], "prefix-churn": "fabric-5000.prefix-churn"},
        "22 nodes")


def _traced(root, cell, monkeypatch, capsys, seed):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    result = run.run_cell(root, cell, seed=seed, seconds=3.0, trace=True)
    detail = json.loads(
        capsys.readouterr().out.split("detail: ")[-1].splitlines()[0])
    assert result["failed"] == 0
    return result, detail["counters"]


def test_a_traced_run_of_a_small_fabric_cell_reports_it(
        small_root, monkeypatch, capsys):
    """Every event re-publishes a node's whole database to change one
    adjacency (a metric, or a flap's one element at each end): the line
    of a ``--trace 1`` run carries the share, it is what the counters
    beside it give, and all but one element an event stand."""
    result, counters = _traced(small_root, "fabric-small.adj-churn",
                               monkeypatch, capsys, seed=2480000049)
    metric = result["metrics"][NAME]
    assert metric["unit"] == "%" and 0.0 < metric["value"] <= 100.0
    reused, decoded = counters[REUSED], counters[DECODED]
    assert metric["value"] == pytest.approx(
        100.0 * reused / (reused + decoded))
    published = counters["chipbench.published"]
    assert published >= 20
    # a metric event decodes one element, a flap one at each end where
    # it adds and none where it withdraws
    assert 0 < decoded <= 2 * published
    assert reused >= published


def test_a_traced_run_of_the_bypass_leaves_it_out(
        small_root, monkeypatch, capsys):
    """``prefix-churn`` publishes no ``adj:`` key in its window (its
    closing probe comes after the counters are read): the counters are
    there, at 0, and the line says nothing."""
    result, counters = _traced(small_root, "fabric-small.prefix-churn",
                               monkeypatch, capsys, seed=2480000051)
    assert NAME not in result["metrics"]
    assert counters.get(REUSED, 0) == 0 and counters.get(DECODED, 0) == 0
