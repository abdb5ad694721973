"""``ksp2_matrix_unready_share``: the reader on hand-made records, its
entry in ``BENCHMARK.json``, and the line of a traced run of a small
KSP2 cell. Counts only; nothing here is a time."""

from __future__ import annotations

import json

import pytest

from benchdef import (
    REPO,
    append_config,
    copy_checkout,
    entry,
    in_order,
    load,
    reported,
)
from chipbench import run, spec
from chipbench.record import RunRecord

NAME = "ksp2_matrix_unready_share"
# the cells that build a KSP2 engine, in the order they joined
CELLS = ["fabric-1000-ksp2.adj-churn", "grid-1000-ksp2.drain-churn"]
SYNCS = "decision.ksp2_incremental_syncs"
UNREADY = "decision.ksp2_matrix_unready"


def read(record):
    return spec.load_reader(REPO, "per_layer", NAME)(record)


@pytest.mark.parametrize("counters, want", [
    # every sync found the previous window's matrix landed: 0 is a
    # reading, not an absence
    ({SYNCS: 300, UNREADY: 0}, 0.0),
    ({SYNCS: 300, UNREADY: 3}, 1.0),
    # a burst: every sync queued behind the matrix before it
    ({SYNCS: 40, UNREADY: 40}, 100.0),
    # the parent's program waits for the matrix in every sync and keeps
    # no such counter: nothing, and no raise
    ({SYNCS: 300}, None),
    # no engine, or one that never synced in the window
    ({SYNCS: 0, UNREADY: 0}, None),
    ({}, None),
], ids=["all-landed", "some", "all-queued", "parent", "no-sync", "no-engine"])
def test_the_reader_gives_the_share_or_nothing(counters, want):
    got = read(RunRecord(counters=dict(counters)))
    assert got == (want if want is None else pytest.approx(want))


def test_its_entry_names_the_cells_that_build_a_ksp2_engine(checkout):
    bench = load(checkout)
    fields, listed = entry(bench, NAME)
    beside, its_cells = entry(bench, "ksp2_cold_share")
    assert fields == {
        "name": NAME,
        "unit": "%",
        "better": "lower",
        "source": "program_counter",
        "layer": beside["layer"],
        "moves": "conv_p50_ms",
    }
    assert in_order(CELLS, listed)
    # beside the engine's other counter shares, in the same cells, and
    # behind them in the list (an appended entry)
    assert listed == its_cells
    names = [m["name"] for m in bench["per_layer"]]
    assert in_order(["ksp2_cold_share", "ksp2_all_pairs_pass_roofline",
                     NAME], names)
    for w in bench["workloads"]:
        cell = spec.load_cell(checkout, w["name"])
        assert (NAME in reported(cell)) \
            == ("ksp2_cold_share" in reported(cell)), w["name"]
    assert NAME not in reported(spec.load_cell(checkout,
                                               "fabric-1000.adj-churn"))


def test_a_traced_run_of_a_small_ksp2_cell_reports_it(
        tmp_path_factory, monkeypatch, capsys):
    """The 56-node fabric of ``test_ksp2_cell.py``, added as data under
    the real cell's mix: the line of a ``--trace 1`` run carries the
    share, and the counters beside it say every incremental sync sent
    its matrix solve behind the window."""
    cell = spec.load_cell(REPO, CELLS[0])
    root = append_config(
        copy_checkout(str(tmp_path_factory.mktemp("checkout"))),
        "ksp2-small", cell.config["name"],
        {"kind": "fat_tree", "pods": 3, "ssw_per_plane": 2,
         "fsw_per_pod": 4, "rsw_per_pod": 12},
        {cell.workload["traffic"]: CELLS[0]}, "56 nodes")
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    result = run.run_cell(root, "ksp2-small.adj-churn",
                          seed=2470000047, seconds=3.0, trace=True)
    detail = json.loads(
        capsys.readouterr().out.split("detail: ")[-1].splitlines()[0])
    assert result["failed"] == 0
    metric = result["metrics"][NAME]
    assert metric["unit"] == "%" and 0.0 <= metric["value"] <= 100.0
    counters = detail["counters"]
    assert counters[SYNCS] >= 1
    assert counters["decision.ksp2_matrix_deferred"] == counters[SYNCS]
    assert counters.get(UNREADY, 0) <= counters[SYNCS]
    assert metric["value"] == pytest.approx(
        100.0 * counters.get(UNREADY, 0) / counters[SYNCS])
