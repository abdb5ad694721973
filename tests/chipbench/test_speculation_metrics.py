"""The two per-layer metrics of the stage that the opening publication
of a debounce window makes under the policy wait: ``spec_hit_share``
(counters) and ``speculate_ms`` (the ``decision.speculate`` span), each
on hand-made records, then through the runner on a cell added as data.

Everything here runs on the CPU: counts and where work sits, never
times worth quoting.
"""

from __future__ import annotations

import json
import os
import shutil

import pytest

from chipbench import run, spec, xplane
from chipbench.record import RunRecord, Span

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = ["fabric-1000.adj-churn", "fabric-5000.adj-churn",
         "grid-10000.drain-churn"]
NEW = ("spec_hit_share", "speculate_ms")


def reader(name):
    return spec.load_reader(REPO, "per_layer", name)


def window(trace_id, t0, stages=(), prewarm=0.0):
    """One rebuild window as the program lays it out since the stage
    moved under the debounce: queue wait, a debounce span holding the
    patch and each stage (view sync, dispatch, readback inside it),
    then a rebuild with nothing of the solver's under the route build."""
    spans, t = [], t0

    def add(name, start, dur, **attrs):
        spans.append(Span(trace_id, name, start, dur, attrs))
        return start + dur

    add("kvstore.publish", t, 0.0)
    t = add("decision.queue_wait", t, 0.2)
    debounce_end = add(
        "decision.debounce", t, max(10.0, prewarm + sum(stages) + 0.1))
    if prewarm:
        t = add("decision.prewarm", t, prewarm, rows=3)
    for dur in stages:
        add("decision.speculate", t, dur, staged=1)
        inner = add("graph.view_sync", t + 0.01, 0.02)
        inner = add("ops.ell_reconverge", inner, dur * 0.6)
        add("ops.solve_readback", inner, dur * 0.3)
        t += dur
    t = debounce_end
    add("decision.rebuild", t, 4.0)
    add("decision.route_build", t + 0.1, 1.9)
    add("decision.route_diff", t + 2.0, 1.9)
    t = add("decision.emit", t + 4.0, 0.01)
    t = add("fib.queue_wait", t, 0.3)
    add("fib.program", t, 0.03)
    return spans


@pytest.mark.parametrize("counters, share", [
    # every rebuild found its view solved
    ({"decision.route_build_runs": 300.0, "ops.spec_hits": 300.0,
      "ops.spec_dispatches": 300.0}, 100.0),
    # six windows were joined by a second publication: staged, cancelled
    ({"decision.route_build_runs": 300.0, "ops.spec_hits": 294.0,
      "ops.spec_cancels": 6.0}, 98.0),
    # the parent: the counter is in the registry and never moves
    ({"decision.route_build_runs": 300.0, "ops.spec_hits": 0.0}, 0.0),
    # a program in which nothing ever bumped it: absent, read as 0
    ({"decision.route_build_runs": 300.0}, 0.0),
    # no rebuild in the window: nothing to divide by
    ({"ops.spec_hits": 3.0}, None),
    ({}, None),
])
def test_spec_hit_share_is_hits_over_rebuilds(counters, share):
    got = reader("spec_hit_share")(RunRecord(counters=counters))
    if share is None:
        assert got is None
    else:
        assert got == pytest.approx(share)


def test_speculate_ms_is_summed_per_window_then_the_median_is_taken():
    rec = RunRecord()
    rec.spans += window(1, 0.0, stages=(5.0,), prewarm=2.5)
    rec.spans += window(2, 1000.0, stages=(4.0, 3.0))  # two areas staged
    rec.spans += window(3, 2000.0, stages=(9.0,))
    rec.spans += window(4, 3000.0)  # a prefix-only window: no span
    assert reader("speculate_ms")(rec) == pytest.approx(7.0)
    # the patch is its sibling, not part of it
    assert reader("prewarm_ms")(rec) == pytest.approx(2.5)


@pytest.mark.parametrize("name", NEW)
def test_a_record_without_the_span_or_the_counter_raises_nothing(name):
    """The parent program stages nothing: no ``decision.speculate``
    span, ``ops.spec_hits`` flat or absent. ``speculate_ms`` is left
    out of the line, ``spec_hit_share`` reads 0, with a device trace
    and without one."""
    rec = RunRecord(
        spans=[Span(1, "kvstore.publish", 0.0, 0.0, {}),
               Span(1, "decision.debounce", 1.0, 10.0, {}),
               Span(1, "decision.prewarm", 1.1, 2.0, {"rows": 3}),
               Span(1, "decision.rebuild", 11.0, 8.0, {}),
               Span(1, "decision.route_build", 11.1, 6.0, {}),
               Span(1, "ops.ell_reconverge", 12.0, 3.0, {}),
               Span(1, "fib.program", 19.5, 0.05, {})],
        counters={"decision.route_build_runs": 1.0},
    )
    expected = {"speculate_ms": None, "spec_hit_share": 0.0}[name]
    assert reader(name)(rec) == expected
    rec.device = xplane.DeviceTrace(
        window=(0.0, 5e9), steady=(0.0, 5e9),
        host=[("PjitFunction(solve)", 1e9, 2e9)])
    rec.steady_wall_s = 100.0
    assert reader(name)(rec) == expected


def test_the_solve_spans_keep_their_readers_under_the_debounce():
    """``solve_span_ms``, ``solve_wait_ms`` and ``view_sync_ms`` read a
    span by name wherever it nests; the route build's self time no
    longer has them to subtract; the rebuild is shorter by the stage."""
    rec = RunRecord()
    for i, stage in enumerate((4.0, 5.0, 6.0)):
        rec.spans += window(i, 1000.0 * i, stages=(stage,), prewarm=2.0)
    assert reader("solve_span_ms")(rec) == pytest.approx(3.0)
    assert reader("solve_wait_ms")(rec) == pytest.approx(1.5)
    assert reader("view_sync_ms")(rec) == pytest.approx(0.02)
    assert reader("route_build_ms")(rec) == pytest.approx(1.9)
    assert reader("rebuild_ms")(rec) == pytest.approx(4.0)
    assert reader("rebuild_unattributed_ms")(rec) == pytest.approx(0.2)
    assert reader("debounce_ms")(rec) == pytest.approx(10.0)


def test_idle_gaps_inside_the_stage_go_to_the_solver_spans():
    """The device's idle time while the host stages is the innermost
    span's, as it was when the same spans sat under the route build."""
    dev = xplane.DeviceTrace(
        window=(0.0, 20e9), steady=(0.0, 20e9),
        busy=[[(6e9, 7e9)]],
        ops=[[("%fusion.1 = s32[8]{0} fusion()", 6e9, 7e9)]],
        modules=[[("jit__ell_reconverge(1)", 6e9, 7e9)]], host=[],
    )
    spans = [
        ("decision.debounce", 0.0, 11e9),
        ("decision.prewarm", 0.5e9, 2.5e9),
        ("decision.speculate", 3e9, 9e9),
        ("ops.ell_reconverge", 4e9, 6.5e9),
        ("ops.solve_readback", 6.5e9, 8.5e9),
        ("decision.rebuild", 11e9, 15e9),
        ("decision.route_build", 11.5e9, 13e9),
    ]
    gaps = dict(map(tuple, dev.idle_gaps(spans)))
    assert gaps["ops.ell_reconverge"] == pytest.approx(2.0)
    assert gaps["ops.solve_readback"] == pytest.approx(1.5)
    assert gaps["decision.speculate"] == pytest.approx(1.0 + 0.5)
    assert gaps["decision.prewarm"] == pytest.approx(2.0)
    assert gaps["decision.debounce"] == pytest.approx(0.5 + 0.5 + 2.0)
    assert gaps["decision.route_build"] == pytest.approx(1.5)


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("name, unit, source, better", [
    ("spec_hit_share", "%", "program_counter", "higher"),
    ("speculate_ms", "ms", "program_span", "lower"),
])
def test_benchmark_lists_the_metric_for_the_three_solver_cells(
        name, unit, source, better):
    bench = _bench()
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    debounce = per_layer["debounce_ms"]["layer"]
    assert per_layer[name] == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": debounce, "moves": "conv_p50_ms", "workloads": CELLS,
    }
    # appended: what was there keeps its place
    assert [m["name"] for m in bench["per_layer"]][-2:] == list(NEW)
    assert callable(reader(name))
    for w in bench["workloads"]:
        reported = {m["name"] for m in
                    spec.load_cell(REPO, w["name"]).metrics("per_layer")}
        assert (name in reported) == (w["name"] in CELLS), w["name"]


# -- through the runner, on a cell added as data --------------------------------

TINY = {"kind": "fat_tree", "pods": 3, "ssw_per_plane": 2,
        "fsw_per_pod": 2, "rsw_per_pod": 4}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout with one more configuration (22 nodes, the dense
    formulation) and its two cells, as ``test_span_metrics.py`` builds
    it."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    configs = os.path.join(root, "chipbench", "configs")
    with open(os.path.join(configs, "fabric-1000.json"), encoding="utf-8") as f:
        config = json.load(f)
    config.update(name="fabric-tiny", topology=TINY)
    with open(os.path.join(configs, "fabric-tiny.json"), "w",
              encoding="utf-8") as f:
        json.dump(config, f)
    bench = _bench()
    bench["configs"].append({
        "name": "fabric-tiny", "source": "this test",
        "file": "chipbench/configs/fabric-tiny.json", "reduced": [],
        "why": "22 nodes"})
    for mix in ("adj-churn", "prefix-churn"):
        bench["workloads"].append({
            "name": f"fabric-tiny.{mix}", "config": "fabric-tiny",
            "traffic": mix, "chips": 1, "why": "a cell added as data"})
    for m in bench["per_layer"]:
        if "fabric-1000.adj-churn" in m.get("workloads", ()):
            m["workloads"].append("fabric-tiny.adj-churn")
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(bench, f)
    return root


def _detail(capsys) -> dict:
    return json.loads(
        capsys.readouterr().out.split("detail: ")[-1].splitlines()[0])


def test_a_traced_adjacency_cell_reports_the_stage_and_its_hits(
        tiny_root, monkeypatch, capsys):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    result = run.run_cell(tiny_root, "fabric-tiny.adj-churn",
                          seed=2_330_000_011, seconds=3.0, trace=True)
    detail = _detail(capsys)
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) <= set(got), sorted(got)
    # one publication a window: every rebuild lands on the staged view,
    # and the stage is the window's one solve
    counters = detail["counters"]
    rebuilds = counters["decision.route_build_runs"]
    assert rebuilds >= 1
    if got["pubs_per_rebuild"] == pytest.approx(1.0):
        assert got["spec_hit_share"] == pytest.approx(100.0)
        assert counters.get("ops.spec_cancels", 0) == 0
    assert counters["ops.spec_dispatches"] \
        == counters["ops.spec_hits"] + counters.get("ops.spec_cancels", 0)
    assert got["device_solves_per_rebuild"] <= 1.0 + (
        counters.get("ops.spec_cancels", 0) / rebuilds)
    assert got["speculate_ms"] > 0.0
    # the solver's spans report from under the debounce as they did
    # from under the route build, and the two trees account for their
    # parents: the stage holds view sync, dispatch and readback, the
    # rebuild keeps route build and diff (where work sits, not times)
    assert {"view_sync_ms", "dense_solve_span_ms", "solve_wait_ms",
            "route_build_ms", "route_diff_ms",
            "rebuild_unattributed_ms"} <= set(got)
    assert "prewarm_ms" not in got  # dense formulation: no band patch
    assert (got["view_sync_ms"] + got["dense_solve_span_ms"]
            + got["solve_wait_ms"]) <= got["speculate_ms"] * 1.5
    assert got["rebuild_unattributed_ms"] < got["rebuild_ms"]
    assert (got["route_build_ms"] + got["route_diff_ms"]
            ) <= got["rebuild_ms"] * 1.5
    assert got["speculate_ms"] <= got["debounce_ms"]
    gaps = dict(map(tuple, result["breakdown"]["idle_gaps"]))
    assert "decision.speculate" in gaps or "graph.view_sync" in gaps
    assert counters.get("ops.host_dispatches", 0) == 0
    assert result["failed"] == 0
    for p in detail["problems"]:
        assert "needs 200 samples" in p or "no operation ran" in p, p


def test_a_traced_bypass_cell_stages_nothing(tiny_root, monkeypatch, capsys):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    result = run.run_cell(tiny_root, "fabric-tiny.prefix-churn",
                          seed=2_330_000_017, seconds=3.0, trace=True)
    detail = _detail(capsys)
    assert not set(NEW) & set(result["metrics"])
    counters = detail["counters"]
    # the version never moves: nothing dispatched, no counter moves
    for name in ("ops.spec_dispatches", "ops.spec_hits",
                 "ops.spec_cancels", "ops.spec_skips"):
        assert counters.get(name, 0) == 0, name
    assert result["failed"] == 0
