"""The per-layer metrics under ``conv_p95_ms`` and the debounce window's
account: what ``decision.debounce`` waited for (``timer_late_ms``,
``wait_busy_ms``, ``wait_overrun_share``), Decision's loop
(``decision_busy_share``), the slowest decile's stages (``tail_*``) and
the samples a full collection stopped (``paused_samples``). Each reader
on hand-made records, the entries that wait for ``BENCHMARK.json``, then
all of them through the runner on a cell added as data.

Everything here runs on the CPU: counts and where work sits, never
times worth quoting.
"""

from __future__ import annotations

import json
import os
import shutil

import pytest

from chipbench import run, spantail, spec, xplane
from chipbench.record import RunRecord, Span
from openr_tpu.analysis.core import run_analysis
from openr_tpu.analysis.rules import SpanDisciplineRule

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = ["fabric-1000.adj-churn", "fabric-5000.adj-churn",
         "fabric-5000.prefix-churn", "grid-10000.drain-churn",
         "fabric-1000-ksp2.adj-churn"]
DEBOUNCE = "debounce (decision)"
# name -> (unit, source, layer, moves), in the order of their entries
NEW = {
    "timer_late_ms": ("ms", "program_span", DEBOUNCE, "conv_p50_ms"),
    "wait_busy_ms": ("ms", "program_span", DEBOUNCE, "conv_p50_ms"),
    "wait_overrun_share": ("%", "program_span", DEBOUNCE, "conv_p95_ms"),
    "decision_busy_share": ("%", "program_counter",
                            "event loop (utils/eventbase)", "conv_p95_ms"),
    "tail_ingest_excess_ms": ("ms", "program_span",
                              "ingest (kvstore, messaging queue)",
                              "conv_p95_ms"),
    "tail_debounce_excess_ms": ("ms", "program_span", DEBOUNCE,
                                "conv_p95_ms"),
    "tail_rebuild_excess_ms": (
        "ms", "program_span",
        "rebuild, host side (linkstate, snapshot, spf_solver, route build)",
        "conv_p95_ms"),
    "tail_fib_excess_ms": ("ms", "program_span", "route programming (fib)",
                           "conv_p95_ms"),
    "tail_overrun_share": ("%", "program_span", DEBOUNCE, "conv_p95_ms"),
    "paused_samples": ("traces", "program_span",
                       "process runtime (CPython garbage collector)",
                       "conv_p95_ms"),
}
TAIL = [n for n in NEW if n.startswith("tail_")]
# the program's files this account touched
TOUCHED = ("openr_tpu/utils/eventbase.py", "openr_tpu/decision/decision.py",
           "openr_tpu/telemetry/trace.py", "openr_tpu/telemetry/gc_pauses.py")


def reader(name):
    return spec.load_reader(REPO, "per_layer", name)


def window(trace_id, t0, ingest=1.0, wait=10.5, rebuild=4.0, fib=0.3,
           busy=None, slack=None, late=None, pause=None):
    """One rebuild window's spans from ``t0`` (ms). ``busy`` / ``slack``
    / ``late`` are the terms the program closes the debounce span with
    (none: the parent, which does not write them); ``pause`` puts a
    collection of that length inside the rebuild."""
    spans, t = [], t0

    def add(name, start, dur, **attrs):
        spans.append(Span(trace_id, name, start, dur, attrs))
        return start + dur

    add("kvstore.publish", t, 0.0)
    add("decision.queue_wait", t, 0.2)
    t += ingest
    terms = {}
    if slack is not None:
        terms = {"policy_ms": 10.0, "busy_ms": busy, "slack_ms": slack,
                 "timer_late_ms": late}
    waited = add("decision.debounce", t, wait, merged_updates=1, **terms)
    if slack is not None and slack > 0:
        add("decision.policy_idle", waited - slack - late, slack + late)
    t = add("decision.rebuild", waited, rebuild)
    add("decision.route_build", waited + 0.1, rebuild / 2)
    if pause:
        add("process.gc_pause", waited + 0.2, pause, generation=2)
    add("decision.emit", t, 0.01)
    add("fib.queue_wait", t + 0.01, fib - 0.05)
    add("fib.program", t + fib - 0.04, 0.04)
    return spans


def record_with_a_known_tail(n=250):
    """``n`` windows; the slowest tenth takes 6 ms more in the rebuild
    and 1 ms more in the debounce, and four in five of them overran."""
    rec = RunRecord()
    slow = n // 10
    for i in range(n):
        if i < n - slow:
            rec.spans += window(i, 100.0 * i, busy=4.0, slack=6.0, late=0.5)
        else:
            overran = (i - (n - slow)) % 5 != 0
            rec.spans += window(
                i, 100.0 * i, wait=11.5, rebuild=10.0, busy=11.0,
                slack=-1.0 if overran else 0.2, late=0.5)
    return rec


# -- what the window waited for -----------------------------------------------


def test_the_three_terms_are_read_off_the_debounce_spans_attributes():
    rec = RunRecord()
    for i, (busy, slack, late) in enumerate(
            [(4.0, 6.0, 0.3), (8.0, 2.0, 0.9), (11.0, -1.0, 0.05),
             (5.0, 5.0, 0.3), (12.5, -2.5, 0.04)]):
        rec.spans += window(i, 100.0 * i, busy=busy, slack=slack, late=late)
    assert reader("timer_late_ms")(rec) == pytest.approx(0.3)
    assert reader("wait_busy_ms")(rec) == pytest.approx(8.0)
    assert reader("wait_overrun_share")(rec) == pytest.approx(40.0)
    assert spantail.window_terms(rec, "policy_ms") == [10.0] * 5


def test_no_window_overran_reads_zero_not_nothing():
    rec = RunRecord()
    for i in range(3):
        rec.spans += window(i, 100.0 * i, busy=1.0, slack=9.0, late=0.3)
    assert reader("wait_overrun_share")(rec) == 0.0


def test_a_window_that_no_timer_fired_is_left_out():
    """Cold start's end reaches the rebuild with no fire: its span has
    no term, and the medians are over the windows that have them."""
    rec = RunRecord()
    rec.spans += window(0, 0.0, busy=4.0, slack=6.0, late=0.3)
    rec.spans += window(1, 100.0)
    assert reader("timer_late_ms")(rec) == pytest.approx(0.3)
    assert reader("wait_overrun_share")(rec) == 0.0


# -- Decision's loop ------------------------------------------------------------


@pytest.mark.parametrize("counters, share", [
    ({"evb.decision.busy_ms": 3000.0, "evb.decision.idle_ms": 27000.0}, 10.0),
    ({"evb.decision.busy_ms": 0.0, "evb.decision.idle_ms": 30000.0}, 0.0),
    # the loop never ran in the window: nothing to divide by
    ({"evb.decision.busy_ms": 0.0, "evb.decision.idle_ms": 0.0}, None),
    # the parent: its loop keeps no account
    ({"decision.route_build_runs": 300.0}, None),
    ({}, None),
])
def test_decision_busy_share_is_busy_over_busy_plus_idle(counters, share):
    got = reader("decision_busy_share")(RunRecord(counters=counters))
    if share is None:
        assert got is None
    else:
        assert got == pytest.approx(share)


# -- the samples a collection stopped -----------------------------------------


def test_paused_samples_counts_traces_not_pauses():
    rec = RunRecord(counters={"telemetry.traces_paused": 2.0})
    rec.spans += window(0, 0.0)
    rec.spans += window(1, 100.0, pause=1.5)
    rec.spans += window(2, 200.0, pause=0.7)
    rec.spans.append(Span(2, "process.gc_pause", 203.0, 0.2,
                          {"generation": 2}))
    assert reader("paused_samples")(rec) == 2


@pytest.mark.parametrize("counters, expected", [
    # a program that puts pauses on its traces, in a window without one
    ({"telemetry.traces_paused": 0.0}, 0),
    # the parent: no such counter, so no such span either
    ({"process.gc_gen2_pause_ms": 80.0}, None),
])
def test_paused_samples_says_zero_only_where_the_program_would_say_more(
        counters, expected):
    rec = RunRecord(counters=counters)
    rec.spans += window(0, 0.0)
    assert reader("paused_samples")(rec) == expected


def test_a_pause_is_taken_out_of_the_self_time_of_the_span_it_fell_into():
    """``route_build_ms`` is the route build's self time: what the
    collector held up inside it is the pause span's, as the idle gaps
    book it, and is not subtracted from the rebuild a second time."""
    rec = RunRecord()
    rec.spans += window(0, 0.0, rebuild=4.0)
    rec.spans += window(1, 100.0, rebuild=4.0 + 1.5, pause=1.5)
    assert reader("rebuild_ms")(rec) == pytest.approx(4.75)
    # route builds of 2.0 and 2.75 ms; the second holds the 1.5 ms pause
    assert reader("route_build_ms")(rec) == pytest.approx(
        (2.0 + (2.75 - 1.5)) / 2)
    assert reader("rebuild_unattributed_ms")(rec) == pytest.approx(
        ((4.0 - 2.0) + (5.5 - 2.75)) / 2)


# -- the slowest decile ---------------------------------------------------------


def test_the_tail_of_a_record_whose_slow_tenth_is_known():
    rec = record_with_a_known_tail(250)
    found = spantail.tail(rec)
    assert (found.traces, found.decile) == (250, 25)
    assert found.excess_ms == {
        "ingest": pytest.approx(0.0), "debounce": pytest.approx(1.0),
        "rebuild": pytest.approx(6.0), "fib": pytest.approx(0.0),
    }
    assert found.overrun_share == pytest.approx(80.0)
    assert reader("tail_ingest_excess_ms")(rec) == pytest.approx(0.0)
    assert reader("tail_debounce_excess_ms")(rec) == pytest.approx(1.0)
    assert reader("tail_rebuild_excess_ms")(rec) == pytest.approx(6.0)
    assert reader("tail_fib_excess_ms")(rec) == pytest.approx(0.0)
    assert reader("tail_overrun_share")(rec) == pytest.approx(80.0)


def test_the_stages_tile_a_trace():
    rec = RunRecord(spans=window(7, 50.0, ingest=0.8, wait=10.9,
                                 rebuild=4.2, fib=0.35))
    row = spantail._stages(rec.spans)
    assert row["extent"] == pytest.approx(0.8 + 10.9 + 4.2 + 0.35)
    assert sum(row[s] for s in spantail.STAGES) == pytest.approx(
        row["extent"])
    assert row["slack"] is None


def test_a_rebuild_that_went_down_the_ladder_ends_at_its_last_span():
    spans = window(1, 0.0, rebuild=4.0, fib=0.3)
    # a second rung: the rebuild span opens again
    spans.append(Span(1, "decision.rebuild", 15.6, 3.0, {}))
    for s in spans:
        if s.name in ("decision.emit", "fib.queue_wait", "fib.program"):
            s.ts_ms += 3.1
    row = spantail._stages(spans)
    assert row["rebuild"] == pytest.approx(18.6 - 11.5)
    assert row["fib"] == pytest.approx(0.3)


@pytest.mark.parametrize("missing", [
    "kvstore.publish", "decision.debounce", "decision.rebuild",
    "fib.program"])
def test_a_trace_without_a_boundary_is_not_ranked(missing):
    spans = [s for s in window(1, 0.0) if s.name != missing]
    assert spantail._stages(spans) is None


@pytest.mark.parametrize("name", TAIL)
@pytest.mark.parametrize("n, reports", [(199, False), (200, True)])
def test_the_tail_needs_what_a_p95_needs(name, n, reports):
    rec = record_with_a_known_tail(n)
    assert (reader(name)(rec) is not None) == reports


def test_traces_that_did_not_reach_fib_do_not_count_towards_the_200():
    rec = record_with_a_known_tail(210)
    rec.spans = [s for s in rec.spans
                 if not (s.trace_id < 20 and s.name == "fib.program")]
    assert spantail.tail(rec) is None


def test_the_parent_has_stages_but_no_say_on_overruns():
    """A program that writes no term: the four excesses read off the
    spans it does have; the overrun share has nothing to read."""
    rec = RunRecord()
    for i in range(240):
        rec.spans += window(i, 100.0 * i, rebuild=4.0 + (5.0 if i >= 216 else 0))
    assert reader("tail_rebuild_excess_ms")(rec) == pytest.approx(5.0)
    assert reader("tail_debounce_excess_ms")(rec) == pytest.approx(0.0)
    assert reader("tail_overrun_share")(rec) is None


@pytest.mark.parametrize("name", [n for n in NEW if n not in TAIL])
def test_a_record_without_the_attributes_or_counters_raises_nothing(name):
    """The parent's record: no term on ``decision.debounce``, no
    ``evb.*`` and no ``telemetry.traces_paused``; with a device trace
    and without one. The metric is left out of the line."""
    rec = RunRecord(
        spans=window(1, 0.0),
        counters={"decision.route_build_runs": 1.0,
                  "process.gc_gen2_pause_ms": 0.0},
    )
    assert reader(name)(rec) is None
    rec.device = xplane.DeviceTrace(
        window=(0.0, 5e9), steady=(0.0, 5e9),
        host=[("PjitFunction(solve)", 1e9, 2e9)])
    rec.steady_wall_s = 100.0
    assert reader(name)(rec) is None
    assert reader(name)(RunRecord()) is None


def test_policy_idle_takes_the_waits_idle_time_from_the_debounce_span():
    """The idle gaps go to the innermost span that covers them: the
    stretch the policy made the thread wait is ``decision.policy_idle``'s
    and only what no inner span names stays under the debounce."""
    dev = xplane.DeviceTrace(
        window=(0.0, 20e9), steady=(0.0, 20e9), busy=[[(4e9, 5e9)]],
        ops=[[("%fusion.1 = s32[8]{0} fusion()", 4e9, 5e9)]],
        modules=[[("jit__ell_reconverge(1)", 4e9, 5e9)]], host=[],
    )
    spans = [
        ("decision.debounce", 1e9, 12e9),
        ("decision.prewarm", 1.5e9, 3e9),
        ("decision.speculate", 3e9, 7e9),
        ("decision.policy_idle", 7.2e9, 11.9e9),
        ("decision.rebuild", 12e9, 15e9),
    ]
    gaps = dict(map(tuple, dev.idle_gaps(spans)))
    assert gaps["decision.policy_idle"] == pytest.approx(4.7)
    assert gaps["decision.debounce"] == pytest.approx(0.5 + 0.2 + 0.1)
    assert gaps["decision.speculate"] == pytest.approx(3.0)


# -- the entries, pending ---------------------------------------------------------
#
# ``BENCHMARK.json`` does not list the ten yet: its ``per_layer`` list is
# held to END with PR 33's two entries
# (``test_speculation_metrics.py::test_benchmark_lists_the_metric_for_the_three_solver_cells``),
# new entries go at the end, and that file is the benchmark's. The
# entries wait in ``chipbench/pending_per_layer.json`` for the
# ``benchmark`` PR that re-words that line; here they are laid over a
# copy, as that PR will append them.

PENDING = os.path.join(REPO, "chipbench", "pending_per_layer.json")


def _pending():
    with open(PENDING, encoding="utf-8") as f:
        return json.load(f)["per_layer"]


def _bench():
    """``BENCHMARK.json`` with the pending entries appended."""
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    bench["per_layer"] += _pending()
    return bench


@pytest.fixture(scope="module")
def listed_root(tmp_path_factory):
    """A checkout whose ``BENCHMARK.json`` lists the ten."""
    root = str(tmp_path_factory.mktemp("listed"))
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(_bench(), f)
    return root


@pytest.mark.parametrize("name", list(NEW))
def test_the_pending_entry_lists_the_metric_in_all_five_cells(
        name, listed_root):
    unit, source, layer, moves = NEW[name]
    (entry,) = [m for m in _pending() if m["name"] == name]
    assert entry == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": layer, "moves": moves, "workloads": CELLS,
    }
    assert callable(reader(name))
    for cell in CELLS:
        reported = {m["name"] for m in
                    spec.load_cell(listed_root, cell).metrics("per_layer")}
        assert name in reported


def test_the_ten_are_new_names_and_one_layer_is_new():
    """Appended they change nothing that is there. What the list ends
    with is for the PR that appends them to say, not this file."""
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        there = json.load(f)
    assert [m["name"] for m in _pending()] == list(NEW)
    assert not set(NEW) & {m["name"] for m in there["per_layer"]}
    assert _bench()["per_layer"][:len(there["per_layer"])] == there["per_layer"]
    e2e = {m["name"] for m in there["end_to_end"]}
    cells = {w["name"] for w in there["workloads"]}
    for m in _pending():
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
    layers = {m["layer"] for m in _pending()}
    before = {m["layer"] for m in there["per_layer"]}
    assert layers - before == {"event loop (utils/eventbase)"}
    assert os.path.isfile(os.path.join(REPO, "chipbench", "spantail.py"))


# -- the program's files still keep the span rule -----------------------------


def test_the_span_rule_is_clean_on_the_touched_files():
    report = run_analysis(REPO, targets=TOUCHED,
                          rules=[SpanDisciplineRule()])
    assert [f for f in report.unsuppressed] == []


# -- through the runner, on a cell added as data --------------------------------

TINY = {"kind": "fat_tree", "pods": 3, "ssw_per_plane": 2,
        "fsw_per_pod": 2, "rsw_per_pod": 4}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout with one more configuration (22 nodes) and its two
    cells, as ``test_span_metrics.py`` builds it."""
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
        if "fabric-5000.prefix-churn" in m.get("workloads", ()):
            m["workloads"].append("fabric-tiny.prefix-churn")
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(bench, f)
    return root


def _detail(capsys) -> dict:
    return json.loads(
        capsys.readouterr().out.split("detail: ")[-1].splitlines()[0])


def _hold_the_window_account(result, detail):
    got = {k: v["value"] for k, v in result["metrics"].items()}
    # 30 events (150 in the bypass cell) are no p95 and no tail
    assert set(NEW) - set(TAIL) <= set(got), sorted(got)
    assert not set(TAIL) & set(got)
    assert got["timer_late_ms"] >= 0.0
    assert 0.0 <= got["wait_overrun_share"] <= 100.0
    assert 0.0 < got["decision_busy_share"] < 100.0
    assert got["paused_samples"] >= 0
    # every window is busy for less than it lasts, unless it overran
    assert got["wait_busy_ms"] >= 0.0
    if got["wait_overrun_share"] == 0.0:
        assert got["wait_busy_ms"] <= got["debounce_ms"]
    gaps = dict(map(tuple, result["breakdown"]["idle_gaps"]))
    assert "decision.policy_idle" in gaps
    assert gaps["decision.policy_idle"] > gaps.get("decision.debounce", 0.0)
    assert result["failed"] == 0
    assert detail["counters"].get("telemetry.traces_unclosed_spans", 0) == 0
    assert detail["counters"].get("telemetry.traces_bad_nesting", 0) == 0
    return got


def test_a_traced_adjacency_cell_reports_the_window_account(
        tiny_root, monkeypatch, capsys):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    result = run.run_cell(tiny_root, "fabric-tiny.adj-churn",
                          seed=2_340_000_011, seconds=3.0, trace=True)
    detail = _detail(capsys)
    got = _hold_the_window_account(result, detail)
    # the stage runs under the wait: the window's busy time holds it
    if got["pubs_per_rebuild"] == pytest.approx(1.0):
        assert got["wait_busy_ms"] >= got["speculate_ms"] * 0.5
    for p in detail["problems"]:
        assert "needs 200 samples" in p or "no operation ran" in p, p


def test_a_traced_bypass_cell_reports_it_too(tiny_root, monkeypatch, capsys):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    result = run.run_cell(tiny_root, "fabric-tiny.prefix-churn",
                          seed=2_340_000_017, seconds=3.0, trace=True)
    detail = _detail(capsys)
    _hold_the_window_account(result, detail)
    for p in detail["problems"]:
        assert "needs 200 samples" in p or "probe ran nothing" in p, p


def test_an_untraced_run_reads_none_of_them(tiny_root, monkeypatch, capsys):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    result = run.run_cell(tiny_root, "fabric-tiny.adj-churn",
                          seed=2_340_000_023, seconds=2.0, trace=False)
    _detail(capsys)
    assert not set(NEW) & set(result["metrics"])
    assert result["failed"] == 0
