"""What ``fabric-1000-ksp2.adj-churn`` added to the benchmark, as files
and entries only: the plain reference for ``KSP2_ED_ECMP`` over
``SR_MPLS`` (``chipbench/reference_ksp2.py``), the driver file whose
``correct`` uses it (``served_paths/pipeline_ksp2.py``), the
configuration, the operations-and-bytes functions of the two KSP2 device
solves and the per-layer readers.

Everything here runs on the CPU: counts, never times.
"""

from __future__ import annotations

import json
import os
import shutil
from dataclasses import replace

import pytest

from chipbench import reference_ksp2, roofline, roofline_ksp2, run, spec
from chipbench import topology, traffic
from chipbench.record import RunRecord, Span

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "fabric-1000-ksp2.adj-churn"
KSP2 = {"algorithm": "KSP2_ED_ECMP", "type": "SR_MPLS"}
SPAN_READERS = ("ksp2_sync_ms", "ksp2_all_pairs_ms", "ksp2_masked_solve_ms",
                "ksp2_trace_ms", "ksp2_routes_ms")
COUNTER_READERS = ("ksp2_affected_per_sync", "ksp2_cold_share")
ROOFLINES = ("ksp2_masked_roofline", "ksp2_all_pairs_roofline")
NEW_READERS = SPAN_READERS + COUNTER_READERS + ROOFLINES


def _json(*path) -> dict:
    with open(os.path.join(REPO, *path), encoding="utf-8") as f:
        return json.load(f)


# -- the reference on graphs checked by hand ----------------------------------
#
# Node labels are 101 + the node's place among the sorted names
# (``topology.build``); an interface is ``if_<node>_<neighbour>``. A next
# hop reads (neighbour, interface, metric, action, labels), the
# destination's label pushed first.


def _hop(via, metric, *labels, src):
    return (via, f"if_{src}_{via}", metric, "PUSH" if labels else None, labels)


@pytest.fixture()
def ring(monkeypatch):
    """v - a - b - c - d - v with the chord a - c, every metric 1.
    Labels: a 101, b 102, c 103, d 104, v 105."""
    monkeypatch.setitem(topology.KINDS, "edges", lambda edges: [
        (a, b, 1) for a, b in edges])
    return topology.build({"kind": "edges", "edges": [
        ("v", "a"), ("a", "b"), ("b", "c"), ("c", "d"), ("d", "v"),
        ("a", "c")]}, KSP2)


def _by_node(topo, routes) -> dict:
    return {
        node: routes.get(db.prefix_entries[0].prefix)
        for node, db in topo.prefix_dbs.items()
    }


def test_ring_with_a_chord_ranks_metrics_and_stacks(ring):
    got = _by_node(ring, reference_ksp2.routes(
        ring.adj_dbs, ring.prefix_dbs, "v"))
    hop = lambda *a: _hop(*a, src="v")  # noqa: E731
    assert got == {
        "v": None,  # its own prefix
        # one hop: no stack; the second rank goes round, through c
        "a": {hop("a", 1), hop("d", 3, 101, 103)},
        "d": {hop("d", 1), hop("a", 3, 104, 103)},
        # one shortest path; the second avoids both its links
        "b": {hop("a", 2, 102), hop("d", 3, 102, 103)},
        # two disjoint shortest paths use both of v's links: no rank 2
        "c": {hop("a", 2, 103), hop("d", 2, 103)},
    }
    graph = reference_ksp2.Graph(ring.adj_dbs)
    first, second = reference_ksp2.kth_paths(graph, "v", "b")
    assert [[(frm, to) for _, frm, to in p] for p in first] == [
        [("v", "a"), ("a", "b")]]
    assert [[(frm, to) for _, frm, to in p] for p in second] == [
        [("v", "d"), ("d", "c"), ("c", "b")]]


def _anycast(ring):
    """c advertises a's loopback too, with an equal entry."""
    shared = ring.prefix_dbs["a"].prefix_entries[0]
    db = ring.prefix_dbs["c"]
    ring.prefix_dbs["c"] = replace(
        db, prefix_entries=db.prefix_entries + (shared,))
    return shared.prefix


def test_a_second_path_that_contains_a_first_is_dropped(ring, monkeypatch):
    prefix = _anycast(ring)
    hop = lambda *a: _hop(*a, src="v")  # noqa: E731
    want = {hop("a", 1), hop("a", 2, 103), hop("d", 2, 103)}
    assert reference_ksp2.routes(
        ring.adj_dbs, ring.prefix_dbs, "v")[prefix] == want
    # a's second path v-d-c-a runs through c's first path v-d-c; kept,
    # it would spray twice over d
    monkeypatch.setattr(reference_ksp2, "contains", lambda a, b: False)
    assert reference_ksp2.routes(
        ring.adj_dbs, ring.prefix_dbs, "v")[prefix] \
        == want | {hop("d", 3, 101, 103)}


def test_node_label_routes_on_the_ring(ring):
    assert reference_ksp2.mpls_routes(ring.adj_dbs, "v") == {
        105: {(None, None, 0, "POP_AND_LOOKUP", ())},
        101: {("a", "if_v_a", 1, "PHP", ())},
        104: {("d", "if_v_d", 1, "PHP", ())},
        102: {("a", "if_v_a", 2, "SWAP", (102,))},
        103: {("a", "if_v_a", 2, "SWAP", (103,)),
              ("d", "if_v_d", 2, "SWAP", (103,))},
    }


@pytest.fixture()
def two_pods():
    """2 pods x (2 FSW + 2 RSW) under 2 planes x 2 SSW. Labels, by
    sorted name: fsw-0-0 101, fsw-0-1 102, fsw-1-0 103, fsw-1-1 104,
    rsw-0-0 105 ... rsw-1-1 108, ssw-0-0 109 ... ssw-1-1 112."""
    return topology.build({
        "kind": "fat_tree", "pods": 2, "ssw_per_plane": 2,
        "fsw_per_pod": 2, "rsw_per_pod": 2}, KSP2)


def test_two_pod_fabric_tie_break_and_stacks(two_pods):
    src = "rsw-0-0"
    hop = lambda *a: _hop(*a, src=src)  # noqa: E731
    got = _by_node(two_pods, reference_ksp2.routes(
        two_pods.adj_dbs, two_pods.prefix_dbs, src))
    # across the fabric there are four shortest paths and two uplinks:
    # two disjoint traces, and the name order picks the first SSW of a
    # plane (ssw-k-0) both times
    assert got["rsw-1-0"] == {
        hop("fsw-0-0", 4, 107, 103, 109), hop("fsw-0-1", 4, 107, 104, 111)}
    # same pod: one path per FSW, and nothing left for a second rank
    assert got["rsw-0-1"] == {hop("fsw-0-0", 2, 106), hop("fsw-0-1", 2, 106)}
    # one hop with no stack; the second rank goes down and up again
    assert got["fsw-0-0"] == {hop("fsw-0-0", 1), hop("fsw-0-1", 3, 101, 106)}
    # the vantage's side of its first uplink costs 5: one path is
    # shortest, and the other uplink is what is left for rank 2
    db = two_pods.adj_dbs[src]
    two_pods.adj_dbs[src] = replace(db, adjacencies=tuple(
        replace(a, metric=5) if a.other_node_name == "fsw-0-0" else a
        for a in db.adjacencies))
    got = _by_node(two_pods, reference_ksp2.routes(
        two_pods.adj_dbs, two_pods.prefix_dbs, src))
    assert got["rsw-0-1"] == {hop("fsw-0-1", 2, 106), hop("fsw-0-0", 6, 106)}
    # a withdrawn side takes the link away
    db = two_pods.adj_dbs["fsw-0-1"]
    two_pods.adj_dbs["fsw-0-1"] = replace(db, adjacencies=tuple(
        a for a in db.adjacencies if a.other_node_name != src))
    got = _by_node(two_pods, reference_ksp2.routes(
        two_pods.adj_dbs, two_pods.prefix_dbs, src))
    assert got["rsw-0-1"] == {hop("fsw-0-0", 6, 106)}


def test_the_reference_refuses_what_it_does_not_cover(ring):
    sp = topology.build({"kind": "edges", "edges": [("v", "a")]},
                        {"algorithm": "SP_ECMP", "type": "IP"})
    with pytest.raises(ValueError, match="KSP2_ED_ECMP"):
        reference_ksp2.routes(sp.adj_dbs, sp.prefix_dbs, "v")
    ring.adj_dbs["b"] = replace(ring.adj_dbs["b"], is_overloaded=True)
    with pytest.raises(ValueError, match="overloaded"):
        reference_ksp2.routes(ring.adj_dbs, ring.prefix_dbs, "v")


def test_the_reference_imports_no_program_code():
    with open(os.path.join(REPO, "chipbench", "reference_ksp2.py"),
              encoding="utf-8") as f:
        source = f.read()
    assert "import openr_tpu" not in source
    assert "from openr_tpu" not in source


# -- the system against the reference, and the reference mutated --------------


def _system_routes(topo, vantage, backend="host"):
    from openr_tpu.decision.decision import Decision
    from openr_tpu.messaging.queue import ReplicateQueue
    from openr_tpu.types import Publication

    gen = traffic.Generator(topo, 1, {"kinds": {"metric": 1.0}}, vantage)
    kv_q = ReplicateQueue(name="ksp2-cell:kvstore")
    decision = Decision(
        vantage, kvstore_updates_queue=kv_q,
        route_updates_queue=ReplicateQueue(name="ksp2-cell:routes"),
        solver_backend=backend)
    try:
        decision.process_publication(Publication(
            key_vals=dict(gen.initial_key_vals()), area="0"))
        decision.rebuild_routes("LOAD")
        return decision.route_db.to_route_db(vantage)
    finally:
        kv_q.close()


def test_the_system_agrees_and_a_mutated_reference_does_not(ring, monkeypatch):
    _anycast(ring)
    live = _system_routes(ring, "v")

    def agree() -> bool:
        return reference_ksp2.routes_of(live) == reference_ksp2.routes(
            ring.adj_dbs, ring.prefix_dbs, "v")

    assert agree()
    assert reference_ksp2.mpls_routes_of(live) \
        == reference_ksp2.mpls_routes(ring.adj_dbs, "v")
    # a second path kept although it contains a first
    with monkeypatch.context() as m:
        m.setattr(reference_ksp2, "contains", lambda a, b: False)
        assert not agree()
    # a label missing from a stack
    whole = reference_ksp2.next_hop

    def short_stack(graph, path):
        via, iface, metric, action, labels = whole(graph, path)
        return (via, iface, metric, action, labels[:1])

    with monkeypatch.context() as m:
        m.setattr(reference_ksp2, "next_hop", short_stack)
        assert not agree()
    assert agree()
    # ``reference.py``'s shape forgets the action and the labels: the
    # comparison that decides ``correct`` here must not
    nh = next(iter(reference_ksp2.routes_of(live).values()))
    assert all(len(hop) == 5 for hop in nh)


# -- BENCHMARK.json against the files -----------------------------------------


@pytest.fixture(scope="module")
def cell() -> spec.Cell:
    return spec.load_cell(REPO, CELL)


def test_every_new_name_has_its_file_and_the_file_says_what_the_entry_says(cell):
    bench = _json("BENCHMARK.json")
    entry = {c["name"]: c for c in bench["configs"]}["fabric-1000-ksp2"]
    config = cell.config
    assert entry["file"] == "chipbench/configs/fabric-1000-ksp2.json"
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    for part in ("DecisionBenchmark.cpp:17-19", ":27-29", "N=1000",
                 "RoutingBenchmarkUtils.h:53-58"):
        assert part in config["source"]
    assert entry["reduced"] == config["reduced"] == []
    assert config["architecture"] is None
    assert set(config["assumed"]) >= {
        "pairing", "vantage", "metric", "prefixes", "traffic"}
    # the pair differs in the forwarding algorithm, and in what that
    # brings (the driver file, its counters, its guarantee), alone
    plain = _json("chipbench", "configs", "fabric-1000.json")
    assert config["forwarding"] == KSP2
    for key in ("topology", "vantage", "router", "chips", "layout"):
        assert config[key] == plain[key], key
    assert {k: config["size"][k] for k in plain["size"]} == plain["size"]
    assert config["served_path"] == "pipeline_ksp2"
    assert set(config["solve_counters"]) >= {"decision.ksp2_incremental_syncs"}
    assert cell.workload["chips"] == 1 and len(cell.workload["why"]) <= 200
    assert f"{cell.mix['rate_per_s']:g} ev/s" in cell.workload["why"]
    assert cell.mix["rate_per_s"] >= 7 and cell.mix["reaches_solver"]
    # the traffic is adj-churn's but, at most, for its rate and warm-up
    theirs = _json("chipbench", "traffic", "adj-churn.json")
    for key in set(theirs) - {"rate_per_s", "warmup", "what"}:
        assert cell.mix[key] == theirs[key], key
    driver = spec.load_driver(REPO, config["served_path"])
    from chipbench.served_paths import pipeline
    assert issubclass(driver, pipeline.Driver)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert per_layer[name]["workloads"] == [CELL], name
        assert per_layer[name]["moves"] == "conv_p50_ms"
        assert callable(spec.load_reader(REPO, "per_layer", name))
    for name in ROOFLINES:
        assert per_layer[name]["unit"] == "%"
        assert per_layer[name]["source"] == "device_trace"
    reported = {m["name"] for m in cell.metrics("per_layer")}
    assert reported >= set(NEW_READERS) | {
        "prewarm_ms", "device_busy_ms", "rebuild_ms", "route_build_ms"}
    # the engine serves the view off its all-pairs matrix, so the spans
    # and counters of a view solve never occur here; and
    # tests/chipbench/test_route_diff_compared.py pins the two
    # route_diff lists to the SP_ECMP fabric cells
    assert not reported & {
        "solve_span_ms", "dense_solve_span_ms", "solve_wait_ms",
        "view_sync_ms", "solve_roofline", "relax_roofline",
        "relax_passes_per_solve", "reset_solve_share",
        "route_diff_ms", "route_diff_compared"}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0


def test_the_network_is_fabric_1000_with_every_prefix_ksp2(cell):
    topo = topology.build(cell.config["topology"], cell.config["forwarding"])
    size = cell.config["size"]
    assert len(topo.adj_dbs) == size["nodes"] == 1016
    assert topo.links() == size["links"] == 8736
    entries = [e for db in topo.prefix_dbs.values() for e in db.prefix_entries]
    assert len(entries) == size["prefixes"]
    assert {(e.forwarding_algorithm.name, e.forwarding_type.name)
            for e in entries} == {("KSP2_ED_ECMP", "SR_MPLS")}
    labels = {db.node_label for db in topo.adj_dbs.values()}
    assert len(labels) == size["node_labels"] == 1016 and 0 not in labels
    assert size["ksp2_destinations"] == 1015


# -- operations and bytes ------------------------------------------------------


def test_both_ksp2_solves_are_memory_bound_at_the_cells_shapes():
    edges = 2 * 8736
    ops, nbytes = roofline_ksp2.masked_batch(1016, edges, 1015, 6.0)
    assert ops == 6 * 2.0 * 1015 * edges
    # a pass streams the slots once, one mask byte per row and edge,
    # and reads and writes the rows; the rows are written once more
    assert nbytes == 6 * (8.0 * edges + 1015 * edges + 8.0 * 1015 * 1016) \
        + 4.0 * 1015 * 1016
    assert roofline.least_seconds(ops, nbytes, "TPU v5 lite")[1] == "memory"
    ops, nbytes = roofline_ksp2.all_pairs(1016, edges)
    assert ops == 2 * 2.0 * 1016 * edges
    assert nbytes == 2 * (8.0 * edges + 8.0 * 1016 * 1016) + 4.0 * 1016 * 1016
    assert roofline.least_seconds(ops, nbytes, "TPU v5 lite")[1] == "memory"


# -- the readers --------------------------------------------------------------


class _Trace:
    """As much of ``xplane.DeviceTrace`` as a roofline reader touches."""

    steady = (0.0, 5e9)

    def __init__(self, modules):
        self._modules = modules

    def module_seconds(self, within=None):
        return self._modules


def _record(counters=None, modules=None, spans=()) -> RunRecord:
    rec = RunRecord(counters=dict(counters or {}),
                    device_kind="TPU v5 lite", spans=list(spans))
    rec.shapes = {"nodes": 1016, "links": 8736, "vantage_degree": 8,
                  "ksp2_dsts": 1015, "ksp2_passes": 4}
    rec.steady_wall_s = 100.0
    if modules is not None:
        rec.device = _Trace(modules)
    return rec


def _reader(name):
    return spec.load_reader(REPO, "per_layer", name)


def _rebuild(trace_id, at_ms, masked_rows=0):
    """One rebuild's KSP2 spans, nested as the program nests them."""
    spans = [
        Span(trace_id, "decision.rebuild", at_ms, 30.0, {}),
        Span(trace_id, "decision.route_build", at_ms + 1, 28.0, {}),
        Span(trace_id, "decision.ksp2_sync", at_ms + 2, 20.0,
             {"changed_pairs": 1, "affected": 40, "cold": False}),
        Span(trace_id, "ops.ksp2_all_pairs", at_ms + 3, 8.0,
             {"rows": 1024, "batches": 1}),
        Span(trace_id, "decision.ksp2_trace", at_ms + 12, 1.0,
             {"dsts": 40, "rank": 1}),
        Span(trace_id, "decision.ksp2_routes", at_ms + 23, 4.0,
             {"prefixes": 1016, "reused": 975}),
    ]
    if masked_rows:
        spans += [
            Span(trace_id, "ops.ksp2_masked_solve", at_ms + 14, 6.0,
                 {"rows": masked_rows, "batches": 1}),
            Span(trace_id, "decision.ksp2_trace", at_ms + 17, 2.0,
                 {"dsts": masked_rows, "rank": 2}),
        ]
    return spans


def test_the_new_readers_on_a_hand_made_record():
    t0 = 100.0 * 1e3  # the steady part begins here, on the wall clock
    spans = (_rebuild(1, t0 - 900.0, masked_rows=16)  # before the trace
             + _rebuild(2, t0 + 100.0, masked_rows=40)
             + _rebuild(3, t0 + 200.0)
             + _rebuild(4, t0 + 300.0, masked_rows=8))
    counters = {"decision.ksp2_incremental_syncs": 290,
                "decision.ksp2_cold_builds": 10,
                "decision.ksp2_affected_dsts": 11600}
    modules = {"jit__ell_masked_source_batch(1)": (0.004, 2),
               "jit__ell_all_view_rows(2)": (0.060, 3),
               "jit_patch(3)": (0.001, 3)}
    rec = _record(counters, modules, spans)
    assert _reader("ksp2_sync_ms")(rec) == pytest.approx(20.0)
    assert _reader("ksp2_all_pairs_ms")(rec) == pytest.approx(8.0)
    # self time: the nested second-rank trace is not the solve's
    assert _reader("ksp2_masked_solve_ms")(rec) == pytest.approx(4.0)
    # per rebuild: 1 + 2 where it re-solved, 1 where it did not
    assert _reader("ksp2_trace_ms")(rec) == pytest.approx(3.0)
    assert _reader("ksp2_routes_ms")(rec) == pytest.approx(4.0)
    assert _reader("ksp2_affected_per_sync")(rec) == pytest.approx(40.0)
    assert _reader("ksp2_cold_share")(rec) == pytest.approx(100.0 * 10 / 300)
    # the masked program: 48 rows in the steady part (the span before it
    # is not counted), 5 passes, over 4 ms of device
    edges = 2 * 8736
    least = roofline.least_seconds(
        *roofline_ksp2.masked_batch(1016, edges, 48, 5.0), "TPU v5 lite")[0]
    share = _reader("ksp2_masked_roofline")(rec)
    assert share == pytest.approx(100.0 * least / 0.004)
    # the fused program: 3 executions of the all-pairs solve
    each = roofline.least_seconds(
        *roofline_ksp2.all_pairs(1016, edges), "TPU v5 lite")[0]
    fused = _reader("ksp2_all_pairs_roofline")(rec)
    assert fused == pytest.approx(100.0 * 3 * each / 0.060)
    assert 0 < share < 105 and 0 < fused < 105
    # a window that re-solved nothing ran the fused program alone, and
    # a program of another name is not it
    rec = _record(counters, {"jit__ell_all_view_rows(9)": (0.010, 2),
                             "jit__ell_all_view_rows_masked(7)": (0.5, 2)},
                  spans)
    assert _reader("ksp2_all_pairs_roofline")(rec) \
        == pytest.approx(100.0 * 2 * each / 0.010)
    assert _reader("ksp2_masked_roofline")(rec) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_finds_nothing_where_there_is_nothing_to_read(name):
    """An SP_ECMP cell, or a program without the spans: its rebuilds,
    its counters and its solve programs are there, KSP2's are not."""
    spans = [Span(1, "decision.rebuild", 100100.0, 5.0, {}),
             Span(1, "decision.route_build", 100101.0, 3.0, {})]
    counters = {"decision.route_build_runs": 300,
                "decision.ksp2_affected_dsts": 0}
    modules = {"jit__spf_view_batch(1)": (0.01, 50),
               "jit__ell_reconverge(2)": (0.01, 50)}
    for rec in (_record(counters, modules, spans), _record(counters, {}, spans),
                _record(counters, None, spans), _record()):
        assert _reader(name)(rec) is None


def test_a_parent_without_spans_still_reports_its_counters_and_modules():
    """The program before this PR counts the engine's syncs and runs the
    same programs, but opens no KSP2 span."""
    counters = {"decision.ksp2_incremental_syncs": 280,
                "decision.ksp2_cold_builds": 20,
                "decision.ksp2_affected_dsts": 5600}
    modules = {"jit__ell_all_view_rows(2)": (0.060, 3),
               "jit__ell_masked_source_batch(1)": (0.004, 2)}
    rec = _record(counters, modules)
    for name in SPAN_READERS:
        assert _reader(name)(rec) is None
    assert _reader("ksp2_affected_per_sync")(rec) == pytest.approx(20.0)
    assert _reader("ksp2_cold_share")(rec) == pytest.approx(100.0 * 20 / 300)
    assert _reader("ksp2_all_pairs_roofline")(rec) > 0
    # rows solved are the spans' to say
    assert _reader("ksp2_masked_roofline")(rec) is None


# -- the runner, end to end, on a small KSP2 fabric added as data only --------


@pytest.fixture(scope="module")
def small_root(tmp_path_factory, cell):
    """A checkout with a 3-pod fabric of 56 nodes, 55 KSP2 destinations
    (the engine engages from ``KSP2_DEVICE_MIN_DSTS`` = 32), under the
    real cell's mix."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = dict(cell.config, name="ksp2-small", topology={
        "kind": "fat_tree", "pods": 3, "ssw_per_plane": 2,
        "fsw_per_pod": 4, "rsw_per_pod": 12})
    with open(os.path.join(root, "chipbench", "configs", "ksp2-small.json"),
              "w", encoding="utf-8") as f:
        json.dump(config, f)
    bench = _json("BENCHMARK.json")
    bench["configs"].append({
        "name": "ksp2-small", "source": "this test",
        "file": "chipbench/configs/ksp2-small.json", "reduced": [],
        "why": "56 nodes"})
    bench["workloads"].append({
        "name": "ksp2-small.adj-churn", "config": "ksp2-small",
        "traffic": cell.workload["traffic"], "chips": 1,
        "why": "a cell added as data"})
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("ksp2-small.adj-churn")
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(bench, f)
    return root


def _detail(capsys) -> dict:
    return json.loads(
        capsys.readouterr().out.split("detail: ")[-1].splitlines()[0])


def test_untraced_run_of_a_small_ksp2_cell(small_root, monkeypatch, capsys):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    result = run.run_cell(small_root, "ksp2-small.adj-churn",
                          seed=2320000011, seconds=3.0, trace=False)
    detail = _detail(capsys)
    # unicast routes with their stacks and the node-label routes equal
    # to the reference in Decision, Fib and the agent, bit-identical to
    # the host replay, nothing lost, nothing compiled in the window, no
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
    assert detail["shapes"]["routes"] == 55
    assert detail["shapes"]["mpls_routes"] == 56
    assert detail["shapes"]["ksp2_dsts"] == 55


def test_traced_run_of_a_small_ksp2_cell(small_root, monkeypatch, capsys):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    result = run.run_cell(small_root, "ksp2-small.adj-churn",
                          seed=3320000017, seconds=3.0, trace=True)
    detail = _detail(capsys)
    # off the chip the trace has no device plane: that, and the sample
    # rule, are all that is said
    for p in detail["problems"]:
        assert "needs 200 samples" in p or "no operation ran" in p, p
    assert result["failed"] == 0
    metrics = result["metrics"]
    assert set(SPAN_READERS + COUNTER_READERS) | {
        "rebuild_ms", "prewarm_ms", "route_build_ms"} <= set(metrics)
    # no device time off the chip, so no share of a roofline either
    assert not set(ROOFLINES) & set(metrics)
    assert not {"solve_wait_ms", "view_sync_ms", "solve_span_ms"} & set(metrics)
    assert 0 < metrics["ksp2_affected_per_sync"]["value"] <= 55
    assert 0 <= metrics["ksp2_cold_share"]["value"] < 100
    assert metrics["ksp2_sync_ms"]["value"] \
        >= metrics["ksp2_all_pairs_ms"]["value"] > 0
    assert detail["shapes"]["ksp2_passes"] >= 4
    gaps = {name for name, _ in result["breakdown"]["idle_gaps"]}
    assert {"decision.ksp2_sync", "ops.ksp2_all_pairs"} <= gaps
