"""What ``grid-1000-ksp2`` brought to the benchmark, as files and entries
only: the configuration file (upstream's own KSP2 graph, the 31 x 31
grid, 60 hops from the corner), the driver file that joins
``pipeline_grid``'s topology and event to ``pipeline_ksp2``'s reference
and stops a run that no KSP2 engine serves, and five per-layer readers
of what the engine now says about itself (passes, refreshed rows, hops).

The cell ``grid-1000-ksp2.drain-churn`` is entered in ``BENCHMARK.json``
(PR 39), behind the fabric's KSP2 cell on every list that names that one.

Everything here runs on the CPU: counts, never times.
"""

from __future__ import annotations

import json
import os

import pytest

from benchdef import REPO, append_config, copy_checkout, load, reaches_solver, stages
from chipbench import reference, reference_ksp2, roofline, roofline_ksp2, run, spec
from chipbench import topology
from chipbench.record import RunRecord, Span
from chipbench.served_paths import pipeline_grid  # noqa: F401 - registers grid

CONFIG = "grid-1000-ksp2"
CELL = "grid-1000-ksp2.drain-churn"
FABRIC_CELL = "fabric-1000-ksp2.adj-churn"
KSP2 = {"algorithm": "KSP2_ED_ECMP", "type": "SR_MPLS"}
ATTRIBUTE_READERS = {
    # metric: (span, attribute)
    "ksp2_all_pairs_passes": ("ops.ksp2_all_pairs", "passes"),
    "ksp2_masked_passes": ("ops.ksp2_masked_solve", "passes"),
    "ksp2_refreshed_rows_per_sync": ("decision.ksp2_sync", "refreshed_rows"),
    "ksp2_trace_hops": ("decision.ksp2_trace", "hops"),
}
PASS_ROOFLINE = "ksp2_all_pairs_pass_roofline"
NEW_READERS = tuple(ATTRIBUTE_READERS) + (PASS_ROOFLINE,)
# the lists the cell joins by appending its name (PERF.md section 7,
# "Left by PR 37" (3)): every list that names the fabric's KSP2 cell
JOINED = (
    "ksp2_sync_ms", "ksp2_all_pairs_ms", "ksp2_masked_solve_ms",
    "ksp2_trace_ms", "ksp2_routes_ms", "ksp2_affected_per_sync",
    "ksp2_cold_share", "ksp2_masked_roofline", "ksp2_all_pairs_roofline",
    "prewarm_ms", "route_diff_ms", "route_diff_compared", "spec_hit_share",
    "speculate_ms", "timer_late_ms", "wait_busy_ms", "wait_overrun_share",
    "decision_busy_share", "tail_ingest_excess_ms", "tail_debounce_excess_ms",
    "tail_rebuild_excess_ms", "tail_fib_excess_ms", "tail_overrun_share",
    "paused_samples",
)


def _json(*path, root=REPO) -> dict:
    with open(os.path.join(root, *path), encoding="utf-8") as f:
        return json.load(f)


# -- the reference on a 3 x 3 grid checked by hand ----------------------------
#
#     node-0 - node-1 - node-2        labels 101 + index; an interface is
#       |        |        |           if_<node>_<neighbour>; a next hop
#     node-3 - node-4 - node-5        reads (neighbour, interface, metric,
#       |        |        |           action, labels), the destination's
#     node-6 - node-7 - node-8        label pushed first


def _hop(via, metric, *labels):
    return (f"node-{via}", f"if_node-0_node-{via}", metric,
            "PUSH" if labels else None, labels)


@pytest.fixture(scope="module")
def three():
    return topology.build({"kind": "grid", "n": 3}, KSP2)


def test_three_by_three_from_the_corner_ranks_metrics_and_stacks(three):
    got = reference_ksp2.routes(three.adj_dbs, three.prefix_dbs, "node-0")
    by_node = {
        node: got.get(db.prefix_entries[0].prefix)
        for node, db in three.prefix_dbs.items()
    }
    assert by_node == {
        "node-0": None,  # its own prefix
        # a neighbour: no stack; the second rank goes round through the
        # centre (105) with the first path's one link taken out
        "node-1": {_hop(1, 1), _hop(3, 3, 102, 105)},
        "node-3": {_hop(3, 1), _hop(1, 3, 104, 105)},
        # along an edge of the grid: one shortest path; the second
        # avoids both its links and comes in from below / from the right
        "node-2": {_hop(1, 2, 103), _hop(3, 4, 103, 106, 105)},
        "node-6": {_hop(3, 2, 107), _hop(1, 4, 107, 108, 105)},
        # two disjoint shortest paths take both of the corner's links:
        # nothing is left for a second rank, here and below
        "node-4": {_hop(1, 2, 105), _hop(3, 2, 105)},
        # three shortest paths, two of them disjoint; predecessor links
        # are walked in the order of their names, so the trace through
        # node-2 (link node-2 - node-5) is found before the one through
        # node-4, whose other branch dead-ends on the spent link 0 - 1
        "node-5": {_hop(1, 3, 106, 103), _hop(3, 3, 106, 105)},
        "node-7": {_hop(1, 3, 108, 105), _hop(3, 3, 108, 107)},
        "node-8": {_hop(1, 4, 109, 106, 103), _hop(3, 4, 109, 108, 105)},
    }


def test_node_label_routes_on_the_three_by_three(three):
    got = reference_ksp2.mpls_routes(three.adj_dbs, "node-0")
    assert set(got) == set(range(101, 110))
    assert got[101] == {(None, None, 0, "POP_AND_LOOKUP", ())}
    # a neighbour's label is popped, one hop before it arrives
    assert got[102] == {("node-1", "if_node-0_node-1", 1, "PHP", ())}
    # the centre and the far corner: swapped towards both first hops
    for label, metric in ((105, 2), (109, 4)):
        assert got[label] == {
            (f"node-{via}", f"if_node-0_node-{via}", metric, "SWAP", (label,))
            for via in (1, 3)}
    # along the top edge: one first hop
    assert got[103] == {("node-1", "if_node-0_node-1", 2, "SWAP", (103,))}


# -- BENCHMARK.json against the files -----------------------------------------


def test_the_five_new_entries_name_their_readers_and_the_fabric_ksp2_cell(
        checkout):
    bench = load(checkout)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert per_layer[name]["workloads"][0] == FABRIC_CELL, name
        assert per_layer[name]["moves"] == "conv_p50_ms"
        assert per_layer[name]["layer"] == per_layer[
            "ksp2_all_pairs_roofline" if name == PASS_ROOFLINE
            else "ksp2_sync_ms"]["layer"]
        assert callable(spec.load_reader(checkout, "per_layer", name))
    assert per_layer[PASS_ROOFLINE]["unit"] == "%"
    assert per_layer[PASS_ROOFLINE]["source"] == "device_trace"
    fabric = spec.load_cell(checkout, FABRIC_CELL)
    assert {m["name"] for m in fabric.metrics("per_layer")} >= set(NEW_READERS)
    # the configuration entered is the file, and its cell comes behind
    # the fabric's on every list it is on
    listed = {c["name"]: c for c in bench["configs"]}[CONFIG]
    config = _json("chipbench", "configs", CONFIG + ".json", root=checkout)
    assert listed["file"] == f"chipbench/configs/{CONFIG}.json"
    assert listed["source"] == config["source"]
    assert listed["reduced"] == config["reduced"] == []
    for name in NEW_READERS + JOINED:
        cells = per_layer[name]["workloads"]
        assert cells.index(CELL) > cells.index(FABRIC_CELL), name


@pytest.fixture(scope="module")
def cell() -> spec.Cell:
    return spec.load_cell(REPO, CELL)


def test_the_configuration_file_and_the_cell_it_makes(cell):
    config = cell.config
    assert config == _json("chipbench", "configs", CONFIG + ".json")
    assert config["name"] == CONFIG and len(config["source"]) <= 200
    for part in ("DecisionBenchmark.cpp:17-19", "KSP2_ED_ECMP", ":12-15",
                 "BM_DecisionGrid", "N=1000", "RoutingBenchmarkUtils.cpp:205"):
        assert part in config["source"]
    assert config["reduced"] == [] and config["architecture"] is None
    assert set(config["assumed"]) >= {
        "vantage", "metric", "prefixes", "node_labels", "traffic"}
    assert config["forwarding"] == KSP2
    assert config["topology"] == {"kind": "grid", "n": 31}
    assert config["vantage"] == "node-0" and config["chips"] == 1
    # what it states of itself it states as its fabric twin does, word
    # for word: the options of the node, the mechanism's counters, the
    # guarantees (reference, host replay, nothing lost, no compile, no
    # fallback)
    twin = _json("chipbench", "configs", "fabric-1000-ksp2.json")
    for key in ("router", "solve_counters", "guarantees"):
        assert config[key] == twin[key], key
    assert config["layout"] == twin["layout"].replace("1015", "960")
    assert config["served_path"] == "pipeline_grid_ksp2"
    # the traffic is grid-10000.drain-churn's file, whole
    assert cell.workload == {
        "name": CELL, "config": CONFIG, "traffic": "drain-churn",
        "chips": 1, "why": cell.workload["why"]}
    assert len(cell.workload["why"]) <= 200
    assert cell.mix == _json("chipbench", "traffic", "drain-churn.json")
    assert cell.mix["kinds"] == {"node-metric": 0.8, "flap": 0.2}
    from chipbench.served_paths import pipeline_ksp2
    driver = spec.load_driver(REPO, config["served_path"])
    assert issubclass(driver, pipeline_ksp2.Driver)
    # what the cell reports: the five new ones, every list of the
    # fabric's KSP2 cell, the end-to-end three
    reported = {m["name"] for m in cell.metrics("per_layer")}
    assert {m["name"] for m in cell.metrics("end_to_end")} \
        >= {"conv_p50_ms", "conv_p95_ms", "setup_s"}
    assert reaches_solver(cell) and stages(cell)
    assert reported >= set(NEW_READERS) | set(JOINED) | {
        "device_busy_ms", "rebuild_ms", "route_build_ms", "debounce_ms",
        "traced_conv_p50_ms", "hbm_peak_mb"}
    # the engine serves the view off its all-pairs matrix here too
    assert not reported & {
        "solve_span_ms", "dense_solve_span_ms", "solve_wait_ms",
        "view_sync_ms", "solve_roofline", "relax_roofline",
        "relax_passes_per_solve", "reset_solve_share"}


def test_the_network_is_upstreams_own_ksp2_graph(cell):
    topo = topology.build(cell.config["topology"], cell.config["forwarding"])
    size = cell.config["size"]
    assert len(topo.adj_dbs) == size["nodes"] == 31 * 31 == 961
    assert topo.links() == size["links"] == 2 * 31 * 30 == 1860
    degrees = sorted({len(db.adjacencies) for db in topo.adj_dbs.values()})
    assert degrees == sorted(size["degree"].values()) == [2, 3, 4]
    entries = [e for db in topo.prefix_dbs.values() for e in db.prefix_entries]
    assert len(entries) == size["prefixes"] == 961
    assert {(e.forwarding_algorithm.name, e.forwarding_type.name)
            for e in entries} == {("KSP2_ED_ECMP", "SR_MPLS")}
    labels = {db.node_label for db in topo.adj_dbs.values()}
    assert len(labels) == size["node_labels"] == 961 and 0 not in labels
    assert size["ksp2_destinations"] == 960
    # 60 links to the far corner: what a masked batch, which starts
    # cold, runs in relax passes at the least
    assert reference.relax_passes(topo.adj_dbs, [cell.config["vantage"]]) \
        == size["hops_corner_to_corner"] == 60


# -- the readers --------------------------------------------------------------


class _Trace:
    """As much of ``xplane.DeviceTrace`` as a roofline reader touches."""

    steady = (0.0, 5e9)

    def __init__(self, modules):
        self._modules = modules

    def module_seconds(self, within=None):
        return self._modules


def _record(modules=None, spans=()) -> RunRecord:
    rec = RunRecord(device_kind="TPU v5 lite", spans=list(spans))
    rec.shapes = {"nodes": 961, "links": 1860, "vantage_degree": 2,
                  "ksp2_dsts": 960, "ksp2_passes": 60}
    rec.steady_wall_s = 100.0
    if modules is not None:
        rec.device = _Trace(modules)
    return rec


def _reader(name):
    return spec.load_reader(REPO, "per_layer", name)


def _sync(trace_id, at_ms, all_pairs, masked, refreshed, hops):
    """One window's KSP2 spans, saying what the program now says."""
    spans = [
        Span(trace_id, "decision.ksp2_sync", at_ms, 40.0,
             {"changed_pairs": 4, "affected": 12, "cold": False,
              "refreshed_rows": refreshed}),
        Span(trace_id, "ops.ksp2_all_pairs", at_ms + 1, 8.0,
             {"rows": 1024, "batches": 1, "passes": all_pairs}),
        Span(trace_id, "decision.ksp2_trace", at_ms + 10, 1.0,
             {"dsts": 12, "rank": 1, "hops": hops}),
        Span(trace_id, "ops.ksp2_masked_solve", at_ms + 12, 9.0,
             {"rows": 12, "batches": 1, "passes": masked}),
    ]
    if refreshed:
        spans.append(Span(
            trace_id, "ops.ksp2_masked_solve", at_ms + 22, 15.0,
            {"rows": refreshed, "batches": 1, "refresh": True,
             "passes": masked + 2}))
    return spans


def test_the_new_readers_on_a_hand_made_record():
    t0 = 100.0 * 1e3  # the steady part begins here, on the wall clock
    spans = (_sync(1, t0 - 900.0, 1, 61, 948, 7)  # before the trace
             + _sync(2, t0 + 100.0, 60, 61, 948, 60)
             + _sync(3, t0 + 200.0, 2, 63, 0, 118)
             + _sync(4, t0 + 300.0, 60, 65, 940, 33))
    modules = {"jit__ell_all_view_rows(2)": (0.012, 3),
               "jit__ell_masked_source_batch(1)": (0.040, 5)}
    rec = _record(modules, spans)
    assert _reader("ksp2_all_pairs_passes")(rec) == pytest.approx(31.0)
    # seven spans: 61, 63, 61, 63, 63, 65, 67
    assert _reader("ksp2_masked_passes")(rec) == pytest.approx(63.0)
    assert _reader("ksp2_refreshed_rows_per_sync")(rec) == pytest.approx(944.0)
    assert _reader("ksp2_trace_hops")(rec) == pytest.approx(46.5)
    # the fused program: 3 executions in the steady part, whose spans
    # carried 60, 2 and 60 passes (the one before it is not counted);
    # each plus the pass that builds the init
    each = roofline.least_seconds(
        *roofline_ksp2.all_pairs(961, 2 * 1860, passes=122 / 3 + 1.0),
        "TPU v5 lite")[0]
    share = _reader(PASS_ROOFLINE)(rec)
    assert share == pytest.approx(100.0 * 3 * each / 0.012)
    assert 0 < share < 100
    # at the passes that ran the least time is many times the 2 passes
    # ksp2_all_pairs_roofline reckons
    assert share > 10 * _reader("ksp2_all_pairs_roofline")(rec)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_finds_nothing_where_the_program_does_not_say(name):
    """The parent commit: the same spans and the same programs, no
    ``passes``, ``refreshed_rows`` or ``hops`` on them; and a cell with
    no KSP2 span at all. Nothing is returned and nothing is raised."""
    bare = [
        Span(1, "decision.ksp2_sync", 100100.0, 20.0,
             {"changed_pairs": 1, "affected": 40, "cold": False}),
        Span(1, "ops.ksp2_all_pairs", 100101.0, 8.0,
             {"rows": 1024, "batches": 1}),
        Span(1, "decision.ksp2_trace", 100110.0, 1.0, {"dsts": 40, "rank": 1}),
        Span(1, "ops.ksp2_masked_solve", 100112.0, 6.0,
             {"rows": 40, "batches": 1}),
    ]
    modules = {"jit__ell_all_view_rows(2)": (0.060, 3),
               "jit__ell_masked_source_batch(1)": (0.004, 2)}
    plain = [Span(1, "decision.rebuild", 100100.0, 5.0, {})]
    for rec in (_record(modules, bare), _record({}, bare), _record(None, bare),
                _record({"jit__spf_view_batch(1)": (0.01, 50)}, plain),
                _record()):
        assert _reader(name)(rec) is None


# -- the runner, end to end, on a 12 x 12 grid added as data only -------------


@pytest.fixture(scope="module")
def small_root(tmp_path_factory, cell):
    """A checkout with a 12 x 12 grid of 144 nodes, 143 KSP2
    destinations, 22 hops from the corner (past the hop gate the program
    had until PR 39), under the real cell's mix."""
    return append_config(
        copy_checkout(str(tmp_path_factory.mktemp("checkout"))),
        "grid-ksp2-small", cell.config["name"], {"kind": "grid", "n": 12},
        {cell.workload["traffic"]: FABRIC_CELL}, "144 nodes")


def _detail(capsys) -> dict:
    return json.loads(
        capsys.readouterr().out.split("detail: ")[-1].splitlines()[0])


def test_untraced_run_of_a_small_grid_ksp2_cell(small_root, monkeypatch, capsys):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    result = run.run_cell(small_root, "grid-ksp2-small.drain-churn",
                          seed=2390000011, seconds=3.0, trace=False)
    detail = _detail(capsys)
    # routes with their stacks and the node-label routes equal to the
    # reference in Decision, Fib and the agent, bit-identical to the
    # host replay, nothing lost, nothing compiled in the window, no
    # fallback, the engine's counters moved; too few events for a p95,
    # and that is the one thing said
    assert all("needs 200 samples" in p for p in detail["problems"]), detail
    assert result["attempted"] == detail["counters"]["chipbench.published"]
    assert result["failed"] == 0
    assert set(result["metrics"]) >= {"conv_p50_ms", "setup_s"}
    counters = detail["counters"]
    assert counters["decision.ksp2_incremental_syncs"] >= 1
    assert counters["decision.ksp2_warm_dispatches"] >= 1
    assert counters.get("decision.ksp2_host_fallbacks", 0) == 0
    assert counters.get("decision.ksp2_cold_builds", 0) == 0
    # every masked batch starts cold: the corner's 22 hops in passes,
    # or more where its masks lengthen a path
    assert counters["ops.ksp2.masked_passes"] \
        >= 22 * counters["decision.ksp2_device_batches"]
    assert counters["ops.ksp2.all_pairs_passes"] \
        >= counters["decision.ksp2_incremental_syncs"]
    assert detail["shapes"]["routes"] == 143
    assert detail["shapes"]["mpls_routes"] == 144
    assert detail["shapes"]["ksp2_dsts"] == 143


def test_traced_run_of_a_small_grid_ksp2_cell(small_root, monkeypatch, capsys):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    result = run.run_cell(small_root, "grid-ksp2-small.drain-churn",
                          seed=3390000017, seconds=3.0, trace=True)
    detail = _detail(capsys)
    # off the chip the trace has no device plane: that, and the sample
    # rule, are all that is said
    for p in detail["problems"]:
        assert "needs 200 samples" in p or "no operation ran" in p, p
    assert result["failed"] == 0
    metrics = result["metrics"]
    assert set(ATTRIBUTE_READERS) | {
        "ksp2_sync_ms", "ksp2_all_pairs_ms", "ksp2_masked_solve_ms",
        "ksp2_trace_ms", "ksp2_routes_ms", "ksp2_affected_per_sync",
        "ksp2_cold_share", "speculate_ms", "spec_hit_share",
        "route_diff_ms"} <= set(metrics)
    # no device time off the chip, so no share of a roofline either
    assert not {PASS_ROOFLINE, "ksp2_all_pairs_roofline",
                "ksp2_masked_roofline"} & set(metrics)
    assert detail["shapes"]["ksp2_passes"] == 22
    assert metrics["ksp2_masked_passes"]["value"] >= 22
    assert 1 <= metrics["ksp2_all_pairs_passes"]["value"] <= 144
    # a node re-costs two to four links at once, and the walk-reach
    # proof answers for each: no row is solved again only to keep it
    # exact (until PR 39 every row the window did not name was)
    assert metrics["ksp2_refreshed_rows_per_sync"]["value"] == 0
    assert 1 <= metrics["ksp2_trace_hops"]["value"] <= 143
    assert metrics["ksp2_cold_share"]["value"] == 0


def test_a_program_that_answers_from_the_host_is_stopped_in_set_up(
        tmp_path, cell, monkeypatch):
    """The control: the same configuration on ``solver_backend: host``
    builds no KSP2 engine (as a program with a hop gate of 16 builds
    none 22 hops from the corner), and the run ends after the bulk load
    with the rule's reason, before a single warm-up burst."""
    root = append_config(
        copy_checkout(str(tmp_path)), "grid-ksp2-host", cell.config["name"],
        {"kind": "grid", "n": 12}, {cell.workload["traffic"]: FABRIC_CELL},
        "144 nodes, host backend")
    path = os.path.join(root, "chipbench", "configs", "grid-ksp2-host.json")
    config = _json(path)
    config["router"]["solver_backend"] = "host"
    with open(path, "w", encoding="utf-8") as f:
        json.dump(config, f)
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    from chipbench.served_paths import pipeline
    published = []
    monkeypatch.setattr(pipeline.Driver, "_publish",
                        lambda self, ev: published.append(ev))
    with pytest.raises(RuntimeError, match="built no KSP2 engine") as failed:
        run.run_cell(root, "grid-ksp2-host.drain-churn",
                     seed=2390000019, seconds=3.0, trace=False)
    assert "solver_backend='host'" in str(failed.value)
    assert "decision.ksp2_cold_builds" in str(failed.value)
    # after the bulk load, before the first warm-up burst
    assert published == []


@pytest.mark.parametrize("routes,published", [({}, 0), ({"::/0": 1}, 2)])
def test_the_engine_rule_stops_a_run_whose_set_up_moved_it(routes, published):
    """The rule hangs on the first wait of ``pipeline.Driver.set_up``,
    which is the bulk load's: if a later edit of ``set_up`` puts another
    wait first, or publishes before it, the place no longer looks like
    the end of the bulk load and the run says so instead of going on."""
    from types import SimpleNamespace

    from chipbench.served_paths import pipeline_grid_ksp2
    driver = object.__new__(pipeline_grid_ksp2.Driver)
    driver._engine_held, driver._published = False, published
    driver.agent = SimpleNamespace(unicast=routes)
    with pytest.raises(RuntimeError, match="has lost its place"):
        driver._wait(lambda: True, 1.0, "never")
