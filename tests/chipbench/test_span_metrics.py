"""The per-layer metrics that read the program's span tree and the
counters beside it: each reader on a hand-made ``RunRecord``, then all
of them through the runner on a cell added as data.

Everything here runs on the CPU: the numbers are where work sits, never
times worth quoting.
"""

from __future__ import annotations

import json
import os
import shutil

import pytest

from chipbench import run, spantree, spec, xplane
from chipbench.record import RunRecord, Span

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NEW = {
    "queue_wait_ms", "prewarm_ms", "view_sync_ms", "route_build_ms",
    "route_diff_ms", "rebuild_unattributed_ms", "dense_solve_span_ms",
    "solve_wait_ms", "device_solves_per_rebuild", "emit_ms",
    "fib_queue_wait_ms", "gc_pause_ms", "span_clock_skew_us",
}


def reader(name):
    return spec.load_reader(REPO, "per_layer", name)


def window(trace_id, t0, build=20.0, sync=1.0, solve=3.0, wait=5.0,
           diff=4.0, slack=2.0, prewarms=()):
    """One rebuild window's spans as the program lays them out, from
    ``t0`` (ms): queue wait, debounce holding ``prewarms``, a rebuild of
    ``build + diff + slack`` ms whose route build holds the three view
    spans, then emit, Fib's queue wait and programming."""
    spans, t = [], t0

    def add(name, start, dur, **attrs):
        spans.append(Span(trace_id, name, start, dur, attrs))
        return start + dur

    add("kvstore.publish", t, 0.0)
    t = add("decision.queue_wait", t, 0.2)
    debounce_end = add("decision.debounce", t, 10.0 + sum(prewarms))
    for dur in prewarms:
        t = add("decision.prewarm", t, dur, rows=2)
    t = debounce_end
    add("decision.rebuild", t, build + diff + slack)
    add("decision.route_build", t + slack / 2, build)
    inner = add("graph.view_sync", t + slack / 2 + 1.0, sync)
    inner = add("ops.ell_reconverge", inner, solve)
    add("ops.solve_readback", inner, wait)
    add("decision.route_diff", t + slack / 2 + build, diff)
    t = add("decision.emit", t + build + diff + slack, 0.5)
    t = add("fib.queue_wait", t, 0.3)
    add("fib.program", t, 0.05)
    return spans


def test_a_span_reader_gives_the_median_of_its_span_or_nothing():
    rec = RunRecord()
    for i, wait in enumerate((4.0, 5.0, 9.0)):
        rec.spans += window(i, 1000.0 * i, wait=wait, sync=1.0 + i)
    assert reader("queue_wait_ms")(rec) == pytest.approx(0.2)
    assert reader("view_sync_ms")(rec) == pytest.approx(2.0)
    assert reader("solve_wait_ms")(rec) == pytest.approx(5.0)
    assert reader("route_diff_ms")(rec) == pytest.approx(4.0)
    assert reader("emit_ms")(rec) == pytest.approx(0.5)
    assert reader("fib_queue_wait_ms")(rec) == pytest.approx(0.3)
    # the dense solve's span does not occur on the ELL side: absent, not 0
    assert reader("dense_solve_span_ms")(rec) is None
    rec.spans.append(Span(0, "ops.spf_view_batch", 20.0, 0.7, {}))
    assert reader("dense_solve_span_ms")(rec) == pytest.approx(0.7)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_record_of_the_parent_program_gives_nothing(name):
    """The parent has none of the spans and neither counter: every new
    reader returns ``None`` and raises nothing, with a device trace and
    without one."""
    rec = RunRecord(
        spans=[Span(1, "kvstore.publish", 0.0, 0.0, {}),
               Span(1, "decision.debounce", 1.0, 10.0, {}),
               Span(1, "decision.rebuild", 11.0, 8.0, {}),
               Span(1, "ops.ell_reconverge", 12.0, 3.0, {}),
               Span(1, "fib.program", 19.5, 0.05, {})],
        counters={"decision.route_build_runs": 1.0},
    )
    assert reader(name)(rec) is None
    rec.device = xplane.DeviceTrace(
        window=(0.0, 5e9), steady=(0.0, 5e9),
        host=[("PjitFunction(solve)", 1e9, 2e9)])
    rec.steady_wall_s = 100.0
    assert reader(name)(rec) is None


def test_self_times_subtract_what_is_nested_in_their_own_trace_only():
    rec = RunRecord()
    # trace 1: route build 20 holds 1 + 3 + 5 of view spans; the rebuild
    # of 26 holds the route build (20) and the diff (4)
    rec.spans += window(1, 0.0)
    # trace 2 overlaps trace 1 on the clock (a pipelined emit): its
    # spans are not trace 1's children
    rec.spans += window(2, 5.0, build=30.0, sync=2.0, solve=4.0, wait=6.0,
                        diff=2.0, slack=4.0)
    # trace 3, and an odd number of traces, so the median is trace 2's
    rec.spans += window(3, 500.0, build=40.0, sync=2.0, solve=4.0, wait=6.0,
                        diff=2.0, slack=6.0)
    assert reader("route_build_ms")(rec) == pytest.approx(30.0 - 12.0)
    # the view spans lie inside the route build: subtracted once, with
    # it, and not again
    assert reader("rebuild_unattributed_ms")(rec) == pytest.approx(4.0)
    one = RunRecord(spans=window(1, 0.0))
    assert reader("route_build_ms")(one) == pytest.approx(20.0 - 9.0)
    assert reader("rebuild_unattributed_ms")(one) == pytest.approx(2.0)
    rebuild = next(s for s in one.spans if s.name == "decision.rebuild")
    assert spantree.self_ms(rebuild, one.spans) == pytest.approx(2.0)
    # a prefix-only rebuild: the route build has nothing nested in it
    bare = RunRecord(spans=[
        Span(7, "decision.rebuild", 10.0, 0.3, {}),
        Span(7, "decision.route_build", 10.1, 0.05, {"full": False}),
    ])
    assert reader("route_build_ms")(bare) == pytest.approx(0.05)
    assert reader("rebuild_unattributed_ms")(bare) == pytest.approx(0.25)


def test_prewarm_is_summed_per_window_then_the_median_is_taken():
    rec = RunRecord()
    rec.spans += window(1, 0.0, prewarms=(6.0, 7.0))  # two publications
    rec.spans += window(2, 1000.0, prewarms=(12.0,))
    rec.spans += window(3, 2000.0, prewarms=(15.0,))
    rec.spans += window(4, 3000.0)  # nothing to patch: no span, no sample
    assert reader("prewarm_ms")(rec) == pytest.approx(13.0)
    assert reader("prewarm_ms")(RunRecord(spans=window(4, 0.0))) is None


def test_counter_readers():
    solves = reader("device_solves_per_rebuild")
    gc_pause = reader("gc_pause_ms")
    rec = RunRecord(counters={
        "decision.route_build_runs": 300.0,
        "decision.device_solves": 300.0,
        "process.gc_gen2_collections": 1.0,
        "process.gc_gen2_pause_ms": 612.5,
    })
    assert solves(rec) == pytest.approx(1.0)
    assert gc_pause(rec) == pytest.approx(612.5)
    # a bypass cell: the counters exist and did not move
    rec = RunRecord(counters={
        "decision.route_build_runs": 1483.0,
        "decision.device_solves": 0.0,
        "process.gc_gen2_pause_ms": 0.0,
    })
    assert solves(rec) == 0.0
    assert gc_pause(rec) == 0.0
    assert solves(RunRecord(counters={"decision.device_solves": 2.0})) is None


def test_span_clock_skew_on_a_hand_made_trace():
    skew = reader("span_clock_skew_us")
    wall0 = 1_700_000_000.0  # time.time() beside the steady marker
    steady = (2e9, 7e9)  # the marker on the trace's clock, ns
    rec = RunRecord(steady_wall_s=wall0)
    # three rebuilds in the steady part, 1 s apart; the host plane has
    # each route build 30, 50 and 400 us after where the wall clock
    # puts it, and one more before the steady part began
    host = [("decision.route_build", 1.0e9, 1.02e9)]
    for i, off_us in enumerate((30.0, 50.0, 400.0)):
        start_ms = (wall0 + 1.0 + i) * 1e3
        rec.spans.append(Span(i, "decision.route_build", start_ms, 20.0, {}))
        on_trace = steady[0] + (1.0 + i) * 1e9 + off_us * 1e3
        host.append(("decision.route_build", on_trace, on_trace + 20e6))
        host.append(("ops.solve_readback", on_trace + 5e6, on_trace + 9e6))
    # a span before the steady part is not compared
    rec.spans.append(
        Span(9, "decision.route_build", (wall0 - 1.0) * 1e3, 20.0, {}))
    rec.device = xplane.DeviceTrace(window=steady, steady=steady, host=host)
    # time.time() keeps ~0.24 us at this magnitude, in milliseconds
    assert skew(rec) == pytest.approx(50.0, abs=0.5)
    # annotations on the trace but no span of the program in the steady
    # part, or spans and no annotation: nothing to compare
    assert skew(RunRecord(steady_wall_s=wall0, device=rec.device)) is None
    rec.device = xplane.DeviceTrace(
        window=steady, steady=steady, host=[("chipbench.steady", 2e9, 7e9)])
    assert skew(rec) is None


def test_idle_gaps_go_to_the_innermost_of_the_new_spans():
    """``breakdown.idle_gaps`` needs no change for the finer tree: a
    gap inside ``ops.solve_readback`` inside ``decision.route_build``
    inside ``decision.rebuild`` is the readback's."""
    dev = xplane.DeviceTrace(
        window=(0.0, 10e9), steady=(0.0, 10e9),
        busy=[[(4e9, 5e9)]], ops=[[("%fusion.1 = s32[8]{0} fusion()", 4e9, 5e9)]],
        modules=[[("jit_solve(1)", 4e9, 5e9)]], host=[],
    )
    spans = [
        ("decision.debounce", 0.0, 2e9),
        ("decision.prewarm", 0.5e9, 1.5e9),
        ("decision.rebuild", 2e9, 9e9),
        ("decision.route_build", 2.5e9, 8e9),
        ("ops.solve_readback", 3e9, 6e9),
        ("decision.emit", 9e9, 9.5e9),
    ]
    gaps = dict(map(tuple, dev.idle_gaps(spans)))
    assert gaps == {
        "decision.debounce": pytest.approx(1.0),
        "decision.prewarm": pytest.approx(1.0),
        "decision.rebuild": pytest.approx(0.5 + 1.0),
        "decision.route_build": pytest.approx(0.5 + 2.0),
        "ops.solve_readback": pytest.approx(1.0 + 1.0),
        "decision.emit": pytest.approx(0.5),
        xplane.WAITING: pytest.approx(0.5),
    }


# -- through the runner, on a cell added as data --------------------------------

TINY = {"kind": "fat_tree", "pods": 3, "ssw_per_plane": 2,
        "fsw_per_pod": 2, "rsw_per_pod": 4}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout with one more configuration (22 nodes, the dense
    formulation) and its two cells, as ``test_chipbench.py`` builds it."""
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
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
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


def test_a_traced_dense_cell_reports_every_new_metric_it_should(
        tiny_root, monkeypatch, capsys):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    result = run.run_cell(tiny_root, "fabric-tiny.adj-churn",
                          seed=2_400_000_011, seconds=3.0, trace=True)
    got = {k: v["value"] for k, v in result["metrics"].items()}
    # dense formulation: no prewarm span (ELL only); the rest report
    assert NEW - {"prewarm_ms"} <= set(got), sorted(NEW - set(got))
    assert "prewarm_ms" not in got
    assert got["device_solves_per_rebuild"] == pytest.approx(1.0)
    for name in NEW & set(got):
        assert got[name] >= 0.0, name
    # the tree accounts for the rebuild: what no finer span names is a
    # small part of it (a count of where work sits, not a time)
    assert got["rebuild_unattributed_ms"] < got["rebuild_ms"]
    assert (got["route_build_ms"] + got["view_sync_ms"]
            + got["dense_solve_span_ms"] + got["solve_wait_ms"]
            + got["route_diff_ms"]) <= got["rebuild_ms"] * 1.5
    # the gaps of the traced tail are charged to the new names
    gaps = dict(map(tuple, result["breakdown"]["idle_gaps"]))
    assert "decision.route_build" in gaps or "graph.view_sync" in gaps
    detail = json.loads(
        capsys.readouterr().out.split("detail: ")[-1].splitlines()[0])
    assert detail["counters"]["decision.device_solves"] == \
        detail["counters"]["decision.route_build_runs"]
    assert detail["counters"].get("ops.host_dispatches", 0) == 0
    assert not detail["counters"].get("telemetry.traces_bad_nesting")
    assert not detail["counters"].get("telemetry.traces_unclosed_spans")


def test_an_untraced_bypass_cell_never_solves(tiny_root, monkeypatch, capsys):
    """Prefix churn: the per-prefix branch builds routes, nothing is
    dispatched, and the untraced line carries no per-layer metric."""
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    result = run.run_cell(tiny_root, "fabric-tiny.prefix-churn",
                          seed=2_400_000_012, seconds=2.0, trace=False)
    assert not NEW & set(result["metrics"])
    detail = json.loads(
        capsys.readouterr().out.split("detail: ")[-1].splitlines()[0])
    assert detail["counters"]["decision.route_build_runs"] > 0
    assert not detail["counters"].get("decision.device_solves")
