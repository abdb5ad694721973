"""What ``fabric-50k.adj-churn`` added to the benchmark, as files and
entries only: the configuration (BASELINE config 5's fabric at
upstream's pod shape, pods cut 1781 -> 893) and the cell, on the lists
of the ELL fabric cell that was there. Then the runner end to end on a
486-node fabric of the same three-band shape, added as data.

Everything here runs on the CPU: counts, never times.
"""

from __future__ import annotations

import json
import os

import pytest

from benchdef import (
    REPO,
    append_config,
    copy_checkout,
    in_order,
    load,
    reaches_solver,
    reported,
    stages,
)
from chipbench import run, spec, topology

CONFIG, CELL = "fabric-50k", "fabric-50k.adj-churn"
LIKE_CONFIG, LIKE = "fabric-5000", "fabric-5000.adj-churn"
PUBLISHED_PODS = 1781
# 40 pods of 2 FSW and 10 RSW, 3 SSW a plane: degree 2 / 13 / 40, so
# ``compile_ell`` bands it k=8 / 16 / 64 as it bands the cell k=8 / 128
# / 1024 (tests/test_fabric_three_band.py holds that path to the
# reference window by window)
THREE_BAND = {"kind": "fat_tree", "pods": 40, "ssw_per_plane": 3,
              "fsw_per_pod": 2, "rsw_per_pod": 10}


def _config(root: str, name: str) -> dict:
    with open(os.path.join(root, "chipbench", "configs", name + ".json"),
              encoding="utf-8") as f:
        return json.load(f)


def _closed_form(group: dict) -> dict:
    """Upstream's createFabric, counted: every FSW of a pod links every
    RSW of its pod and every SSW of its plane."""
    pods, ssw = group["pods"], group["ssw_per_plane"]
    fsw, rsw = group["fsw_per_pod"], group["rsw_per_pod"]
    return {
        "nodes": pods * (fsw + rsw) + fsw * ssw,
        "ssw": fsw * ssw, "fsw": pods * fsw, "rsw": pods * rsw,
        "links": pods * fsw * (ssw + rsw),
        "degree": {"rsw": fsw, "fsw": ssw + rsw, "ssw": pods},
    }


def test_the_size_block_is_what_the_generator_builds(checkout):
    config = _config(checkout, CONFIG)
    group, size = config["topology"], config["size"]
    assert group == {"kind": "fat_tree", "pods": 893, "ssw_per_plane": 36,
                     "fsw_per_pod": 8, "rsw_per_pod": 48}
    want = _closed_form(group)
    assert want["nodes"] == 893 * 56 + 288 == 50296
    assert want["links"] == 893 * 672 == 600096
    assert {k: size[k] for k in want} == want
    assert size["prefixes"] == size["nodes"]
    assert size["degree"] == {"rsw": 8, "fsw": 84, "ssw": 893}
    # the closed form against the generator itself, at 3 pods of the
    # same shape (tests/chipbench/test_tpu_lowering_fabric_50k.py builds
    # all 893 and holds the size block to that)
    small = dict(group, pods=3)
    topo = topology.build(small, config["forwarding"])
    want = _closed_form(small)
    assert len(topo.adj_dbs) == want["nodes"] == 3 * 56 + 288
    assert topo.links() == want["links"] == 3 * 672
    for tier, degree in want["degree"].items():
        assert len(topo.adj_dbs[f"{tier}-0-0"].adjacencies) == degree
        assert sum(n.startswith(tier) for n in topo.adj_dbs) == want[tier]
    assert config["vantage"] in topo.adj_dbs


def test_the_one_cut_is_the_number_of_pods_and_the_file_says_from_what(
        checkout):
    bench = load(checkout)
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    config = _config(checkout, CONFIG)
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["reduced"] == config["reduced"] == ["topology.pods"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for part in ("DecisionBenchmark.cpp:27-29", "RoutingBenchmarkUtils.h:53-58",
                 "BASELINE.json config 5", "1781", "893"):
        assert part in entry["source"] and part in config["source"], part
    published = config["published"]
    assert published["topology.pods"] == PUBLISHED_PODS
    want = _closed_form(dict(config["topology"], pods=PUBLISHED_PODS))
    assert published["nodes"] == want["nodes"] == 100024
    assert published["links"] == want["links"] == 1196832
    assert published["degree"] == want["degree"]
    assert published["stands_for"] and "PR 45" in published["why_893"]
    # every shape is the published one: only the scale is cut
    like = _config(checkout, LIKE_CONFIG)
    assert {k: v for k, v in config["topology"].items() if k != "pods"} \
        == {k: v for k, v in like["topology"].items() if k != "pods"}
    for key in ("served_path", "forwarding", "vantage", "router",
                "solve_counters", "chips", "guarantees", "assumed"):
        assert config[key] == like[key], key
    assert config["layout"] == like["layout"].replace("4991", "50295")
    assert set(config) == set(like) | {"published"}


def test_the_cell_stands_behind_the_multi_area_cells_on_the_fabric_lists(
        checkout):
    bench = load(checkout)
    assert in_order([LIKE_CONFIG, "multi-area-2x1000", CONFIG],
                    [c["name"] for c in bench["configs"]])
    assert in_order([LIKE, "multi-area-2x1000.redist-churn", CELL],
                    [w["name"] for w in bench["workloads"]])
    (workload,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert workload == {
        "name": CELL, "config": CONFIG, "traffic": "adj-churn", "chips": 1,
        "why": workload["why"]}
    assert "outlasts the 10 ms wait" in workload["why"]
    assert "10 ev/s" in workload["why"] and len(workload["why"]) <= 200
    cell, like = (spec.load_cell(checkout, c) for c in (CELL, LIKE))
    assert cell.mix == like.mix and cell.mix["rate_per_s"] == 10
    assert reaches_solver(cell) and stages(cell)
    # on every list the ELL fabric cell is on, behind it
    joined = 0
    for m in bench["end_to_end"] + bench["per_layer"]:
        if LIKE in m.get("workloads", ()):
            assert in_order([LIKE, CELL], m["workloads"]), m["name"]
            joined += 1
    assert joined >= 25
    assert reported(cell) == reported(like)
    assert reported(cell, "end_to_end") == reported(like, "end_to_end") \
        == {"conv_p50_ms", "conv_p95_ms", "setup_s"}
    assert reported(cell) >= {
        "solve_span_ms", "solve_roofline", "relax_roofline",
        "relax_passes_per_solve", "reset_solve_share", "solve_wait_ms",
        "prewarm_ms", "view_sync_ms", "route_diff_ms", "route_diff_compared",
        "spec_hit_share", "speculate_ms", "timer_late_ms", "wait_busy_ms",
        "wait_overrun_share", "decision_busy_share", "paused_samples",
        "tail_ingest_excess_ms", "tail_debounce_excess_ms",
        "tail_rebuild_excess_ms", "tail_fib_excess_ms", "tail_overrun_share",
        "ingest_reuse_share", "solve_readback_mb", "ell_slot_fill_share"}
    # the dense solve's span and the KSP2 engine's do not occur here
    assert not {m for m in reported(cell)
                if m.startswith(("ksp2_", "dense_", "redistribute_"))}


# -- the runner, end to end, on a three-band fabric added as data only --------


@pytest.fixture(scope="module")
def three_band_root(tmp_path_factory):
    return append_config(
        copy_checkout(str(tmp_path_factory.mktemp("checkout"))),
        "fabric-3band", CONFIG, THREE_BAND, {"adj-churn": CELL},
        "486 nodes in three ELL bands")


def _run(root, monkeypatch, capsys, seed, trace):
    from openr_tpu.decision import spf_solver

    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    # the ELL side of the threshold, as the real cell
    monkeypatch.setattr(spf_solver, "SPARSE_NODE_THRESHOLD", 32)
    result = run.run_cell(root, "fabric-3band.adj-churn", seed=seed,
                          seconds=3.0, trace=trace)
    detail = json.loads(
        capsys.readouterr().out.split("detail: ")[-1].splitlines()[0])
    return result, detail


def test_untraced_run_of_a_three_band_cell(three_band_root, monkeypatch,
                                           capsys):
    result, detail = _run(three_band_root, monkeypatch, capsys,
                          seed=2490000011, trace=False)
    # routes equal to both references, nothing lost, nothing compiled in
    # the window (the warm-up reached every band's patch), no fallback,
    # both solve counters moved; 30 events are no p95
    assert all("needs 200 samples" in p for p in detail["problems"]), detail
    assert result["attempted"] == 30 and result["failed"] == 0
    assert set(result["metrics"]) >= {"conv_p50_ms", "setup_s"}
    counters = detail["counters"]
    assert counters["chipbench.published"] == 30
    assert counters.get("chipbench.window_compiles", 0) == 0
    assert counters["decision.ell_warm_solves"] >= 1
    assert counters["decision.ell_prewarms"] == 30
    assert counters.get("decision.ell_cold_solves", 0) == 0
    assert counters.get("decision.ell_full_compiles", 0) == 0
    assert detail["shapes"]["nodes"] == 486
    assert detail["shapes"]["routes"] == 485


def test_traced_run_of_a_three_band_cell_reports_the_two_shape_metrics(
        three_band_root, monkeypatch, capsys):
    result, detail = _run(three_band_root, monkeypatch, capsys,
                          seed=3490000019, trace=True)
    for p in detail["problems"]:
        assert "needs 200 samples" in p or "no operation ran" in p, p
    assert result["failed"] == 0
    metrics = result["metrics"]
    assert {"solve_span_ms", "solve_wait_ms", "prewarm_ms", "rebuild_ms",
            "relax_passes_per_solve", "spec_hit_share",
            "solve_readback_mb", "ell_slot_fill_share"} <= set(metrics)
    # no device time off the chip, so no share of a roofline
    assert "relax_roofline" not in metrics and "solve_roofline" not in metrics
    # 8 source rows (the vantage, its 2 FSWs, padded) over 512 columns,
    # distances then first hops
    assert metrics["solve_readback_mb"] == {
        "value": pytest.approx(2 * 8 * 512 * 4 / 1e6), "unit": "MB"}
    # 2,080 directed edges in 400 x 8 + 80 x 16 + 6 x 64 slots, less
    # the links the window's flaps hold down
    fill = metrics["ell_slot_fill_share"]
    assert fill["unit"] == "%"
    published = detail["counters"]["chipbench.published"]
    assert 100.0 * (2080 - 2 * (published + 170)) / 4864 \
        < fill["value"] <= 100.0 * 2080 / 4864
