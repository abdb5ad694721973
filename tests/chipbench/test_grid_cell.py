"""What ``grid-10000.drain-churn`` added to the benchmark, as files and
entries only: the ``grid`` topology kind and the ``node-metric`` event
kind (both registered by the driver file ``served_paths/pipeline_grid.py``),
the configuration, the traffic mix, three per-layer readers.

Everything here runs on the CPU: counts, never times.
"""

from __future__ import annotations

import json
import os
import shutil

import numpy as np
import pytest

from chipbench import reference, run, spec, topology, traffic
from chipbench.record import RunRecord

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "grid-10000.drain-churn"
SP_ECMP = {"algorithm": "SP_ECMP", "type": "IP"}
NEW_READERS = ("relax_passes_per_solve", "reset_solve_share", "relax_roofline")


def _json(*path) -> dict:
    with open(os.path.join(REPO, *path), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cell() -> spec.Cell:
    cell = spec.load_cell(REPO, CELL)
    # loading the driver file is what registers the two kinds
    spec.load_driver(REPO, cell.config["served_path"])
    return cell


@pytest.fixture(scope="module")
def grid(cell):
    return topology.build(cell.config["topology"], cell.config["forwarding"])


# -- the network --------------------------------------------------------------


def test_the_grid_is_upstreams_largest_point(cell, grid):
    size = cell.config["size"]
    assert len(grid.adj_dbs) == size["nodes"] == 10000
    assert grid.links() == size["links"] == 19800
    degrees = sorted({len(db.adjacencies) for db in grid.adj_dbs.values()})
    assert degrees == sorted(size["degree"].values()) == [2, 3, 4]
    vantage = cell.config["vantage"]
    assert len(grid.adj_dbs[vantage].adjacencies) == size["degree"]["corner"]
    assert {a.metric for db in grid.adj_dbs.values()
            for a in db.adjacencies} == {1}
    assert all(len(db.prefix_entries) == 1 for db in grid.prefix_dbs.values())
    # a Bellman-Ford solve from the corner needs its hop eccentricity
    assert reference.relax_passes(grid.adj_dbs, [vantage]) \
        == size["hops_corner_to_corner"] == 198


def test_the_copy_equals_the_programs_grid_edge_for_edge(cell, grid):
    from openr_tpu.models import topologies

    theirs = topologies.grid(cell.config["topology"]["n"])

    def edges(topo):
        return sorted(
            (n, a.other_node_name, a.if_name, a.other_if_name, a.metric)
            for n, db in topo.adj_dbs.items() for a in db.adjacencies)

    assert edges(grid) == edges(theirs)
    assert {n: db.prefix_entries for n, db in grid.prefix_dbs.items()} \
        == {n: db.prefix_entries for n, db in theirs.prefix_dbs.items()}


# -- BENCHMARK.json against the files -----------------------------------------


def test_every_new_name_has_its_file_and_the_file_says_what_the_entry_says(cell):
    bench = _json("BENCHMARK.json")
    entry = {c["name"]: c for c in bench["configs"]}["grid-10000"]
    config = cell.config
    assert entry["file"] == "chipbench/configs/grid-10000.json"
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    for part in ("DecisionBenchmark.cpp:12-15", "N=10000", "createGrid"):
        assert part in config["source"]
    assert entry["reduced"] == config["reduced"] == []
    assert set(config["assumed"]) >= {
        "vantage", "node_names", "metric", "prefixes", "rate"}
    fabric = _json("chipbench", "configs", "fabric-5000.json")
    for key in ("router", "guarantees", "forwarding", "solve_counters"):
        assert config[key] == fabric[key], key
    assert cell.workload["chips"] == config["chips"] == 1
    assert os.path.isfile(os.path.join(
        REPO, "chipbench", "served_paths", config["served_path"] + ".py"))
    # the mix is adj-churn's with ``metric`` read as ``node-metric``
    theirs = json.dumps(_json("chipbench", "traffic", "adj-churn.json"),
                        sort_keys=True).replace('"metric"', '"node-metric"')
    mine = dict(cell.mix, what=json.loads(theirs)["what"])
    assert json.dumps(mine, sort_keys=True) == theirs
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert per_layer[name]["workloads"] == ["fabric-5000.adj-churn", CELL]
        assert per_layer[name]["moves"] == "conv_p50_ms"
        assert callable(spec.load_reader(REPO, "per_layer", name))
    reported = {m["name"] for m in cell.metrics("per_layer")}
    assert reported >= set(NEW_READERS) | {
        "solve_span_ms", "solve_wait_ms", "prewarm_ms", "view_sync_ms",
        "device_busy_ms"}
    # tests/chipbench/test_route_diff_compared.py pins the two
    # route_diff lists to the two fabric cells: left as they were
    assert not reported & {"solve_roofline", "dense_solve_span_ms",
                           "route_diff_ms", "route_diff_compared"}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0


# -- the event kind -----------------------------------------------------------


def _generator(grid, cell, seed, **over):
    gen = traffic.Generator(
        grid, seed, dict(cell.mix, **over), cell.config["vantage"])
    gen.initial_key_vals()
    return gen


def test_node_metric_is_a_function_of_the_seed(cell, grid):
    def draw(seed):
        gen = _generator(grid, cell, seed)
        return [(e.kind, e.key, e.value.hash)
                for e in (gen.draw() for _ in range(120))]

    a, b, c = draw(2300000011), draw(2300000011), draw(2300000012)
    assert a == b != c
    share = sum(kind == "node-metric" for kind, _, _ in a) / len(a)
    assert 0.7 < share < 0.9


def test_node_metric_moves_every_adjacency_of_one_node_one_step(cell, grid):
    gen = _generator(grid, cell, 5, kinds={"node-metric": 1.0})
    seen = {}
    for _ in range(60):
        before = dict(gen.adj_dbs)
        ev = gen.event("node-metric")
        changed = [n for n in before if gen.adj_dbs[n] is not before[n]]
        assert changed == [ev.value.originator_id]
        assert ev.kind == "node-metric" and ev.key == f"adj:{changed[0]}"
        assert ev.value.version > seen.get(ev.key, 1)
        seen[ev.key] = ev.value.version
        old, new = (db[changed[0]].adjacencies for db in (before, gen.adj_dbs))
        assert len(old) == len(new) >= 2
        for o, n in zip(old, new):
            assert n.metric == 1 + (o.metric % 10)
            assert (n.other_node_name, n.if_name) == (o.other_node_name, o.if_name)
    # ten steps bring a node's links back to where they were
    node = "node-5050"
    links = gen.adj_dbs[node].adjacencies
    gen._pick = lambda: node
    for _ in range(10):
        gen.event("node-metric")
    assert gen.adj_dbs[node].adjacencies == links


def test_most_events_raise_a_link_that_points_away_from_the_corner(cell, grid):
    """What makes nine solves in ten the long one: from the corner every
    link to a node one hop farther is on a shortest path, so an event
    that raises or withdraws one (a ``node-metric`` anywhere but the far
    corner below metric 10, a flap's withdrawal) is tight in
    ``_warm_seed``. (A withdrawal towards the corner takes the link's
    other direction with it and is tight too; it is not counted here.)"""
    n = cell.config["topology"]["n"]

    def hops(name):
        i = int(name.split("-")[1])
        return i // n + i % n

    gen = _generator(grid, cell, 2300000011)
    raised = 0
    for _ in range(300):
        before = dict(gen.adj_dbs)
        ev = gen.draw()
        node = ev.value.originator_id
        old = {a.other_node_name: a.metric for a in before[node].adjacencies}
        new = {a.other_node_name: a.metric
               for a in gen.adj_dbs[node].adjacencies}
        raised += any(
            hops(peer) > hops(node) and new.get(peer, 1 << 30) > metric
            for peer, metric in old.items())
    assert raised >= 0.8 * 300


# -- the readers --------------------------------------------------------------


class _Trace:
    """As much of ``xplane.DeviceTrace`` as a roofline reader touches."""

    steady = (0.0, 5e9)

    def __init__(self, modules):
        self._modules = modules

    def module_seconds(self, within=None):
        return self._modules


def _record(counters, modules=None) -> RunRecord:
    rec = RunRecord(counters=counters, device_kind="TPU v5 lite")
    rec.shapes = {"nodes": 10000, "links": 19800, "vantage_degree": 2}
    if modules is not None:
        rec.device = _Trace(modules)
    return rec


def _reader(name):
    return spec.load_reader(REPO, "per_layer", name)


def test_the_new_readers_on_a_hand_made_record():
    counters = {
        "ops.ell.relax_passes.count": 290, "ops.ell.relax_passes.sum": 51620,
        "decision.ell_warm_solves": 290, "decision.ell_reset_solves": 261,
    }
    modules = {"jit__ell_reconverge(123)": (0.5, 48),
               "jit__patch_band(7)": (0.001, 50)}
    rec = _record(counters, modules)
    assert _reader("relax_passes_per_solve")(rec) == pytest.approx(178.0)
    assert _reader("reset_solve_share")(rec) == pytest.approx(90.0)
    # memory-bound: 179 passes of (8 bytes an edge + 8 bytes a row slot)
    # plus the packed view, at 819 GB/s, 48 executions in 0.5 s
    per_pass = 8.0 * 39600 + 8.0 * 8 * 10000
    least = (179.0 * per_pass + 8.0 * 8 * 10000) / 819e9
    share = _reader("relax_roofline")(rec)
    assert share == pytest.approx(100.0 * 48 * least / 0.5)
    assert 0 < share < 105
    # a window with nothing on the device: nothing to divide by
    assert _reader("relax_roofline")(_record(counters, {})) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_finds_nothing_where_the_program_counts_nothing(name):
    """The parent's side: no such observation, no such counter."""
    parent = {"decision.ell_warm_solves": 290, "decision.route_build_runs": 290}
    modules = {"jit__ell_reconverge(123)": (0.5, 48)}
    assert _reader(name)(_record(parent, modules)) is None
    assert _reader(name)(_record(parent)) is None
    assert _reader(name)(_record({})) is None


# -- the runner, end to end, on a small grid added as data only ---------------


@pytest.fixture(scope="module")
def small_grid_root(tmp_path_factory, cell):
    """A checkout with a 65 x 65 grid (4,225 nodes: the ELL side of the
    threshold, as the real cell) under the real cell's mix."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = dict(cell.config, name="grid-small",
                  topology={"kind": "grid", "n": 65})
    with open(os.path.join(root, "chipbench", "configs", "grid-small.json"),
              "w", encoding="utf-8") as f:
        json.dump(config, f)
    bench = _json("BENCHMARK.json")
    bench["configs"].append({
        "name": "grid-small", "source": "this test",
        "file": "chipbench/configs/grid-small.json", "reduced": [],
        "why": "4225 nodes"})
    bench["workloads"].append({
        "name": "grid-small.drain-churn", "config": "grid-small",
        "traffic": "drain-churn", "chips": 1, "why": "a cell added as data"})
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("grid-small.drain-churn")
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(bench, f)
    return root


def _detail(capsys) -> dict:
    return json.loads(
        capsys.readouterr().out.split("detail: ")[-1].splitlines()[0])


def test_untraced_run_of_a_small_grid_cell(small_grid_root, monkeypatch, capsys):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    result = run.run_cell(small_grid_root, "grid-small.drain-churn",
                          seed=2300000011, seconds=3.0, trace=False)
    detail = _detail(capsys)
    # routes equal to both references, nothing lost, nothing compiled in
    # the window, no fallback, both solve counters moved; 30 events are
    # no p95, and that is the one thing said
    assert all("needs 200 samples" in p for p in detail["problems"]), detail
    assert result["attempted"] == 30 and result["failed"] == 0
    assert set(result["metrics"]) >= {"conv_p50_ms", "setup_s"}
    counters = detail["counters"]
    assert counters["chipbench.published"] == 30
    assert counters["decision.ell_warm_solves"] >= 1
    assert counters["decision.ell_prewarms"] == 30
    assert counters["decision.ell_reset_solves"] >= 1
    assert counters["ops.ell.relax_passes.count"] \
        == counters["decision.ell_warm_solves"]
    assert detail["shapes"]["routes"] == 65 * 65 - 1


def test_traced_run_of_a_small_grid_cell(small_grid_root, monkeypatch, capsys):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    result = run.run_cell(small_grid_root, "grid-small.drain-churn",
                          seed=3300000017, seconds=3.0, trace=True)
    detail = _detail(capsys)
    # off the chip the trace has no device plane: that, and the sample
    # rule, are all that is said
    for p in detail["problems"]:
        assert "needs 200 samples" in p or "no operation ran" in p, p
    assert result["failed"] == 0
    metrics = result["metrics"]
    assert {"solve_span_ms", "solve_wait_ms", "prewarm_ms", "rebuild_ms",
            "relax_passes_per_solve", "reset_solve_share"} <= set(metrics)
    assert "solve_roofline" not in metrics
    # no device time off the chip, so no share of a roofline either
    assert "relax_roofline" not in metrics
    assert 1 <= metrics["relax_passes_per_solve"]["value"] <= 129
    assert 0 <= metrics["reset_solve_share"]["value"] <= 100
    assert detail["shapes"]["relax_passes"] >= 128


# -- the solve program at the cell's real shapes, for a described chip --------


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile is written to the persistent cache but cannot be
    # read back without a chip; keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def test_ell_reconverge_lowers_at_grid_10000(one_chip, cell, grid):
    """``jit__ell_reconverge`` over the one band ``compile_ell`` gives
    the grid (10,000 rows padded to 10,112, 8 slots), 8 source rows (the
    corner, its 2 neighbours, padded), the patch of a ``node-metric``
    event (up to 4 rows, 4 increase edges). The outputs are the bands,
    the packed view, the distance rows and the solve's two scalars."""
    import jax
    import jax.numpy as jnp

    from chipbench import roofline
    from openr_tpu.graph import snapshot
    from openr_tpu.graph.linkstate import LinkState
    from openr_tpu.ops import spf_sparse

    ls = LinkState(area=grid.area)
    for name in sorted(grid.adj_dbs):
        ls.update_adjacency_database(grid.adj_dbs[name])
    graph = spf_sparse.compile_ell(ls)
    assert [(b.rows, b.k) for b in graph.bands] == [(10000, 8)]
    assert graph.n_pad == 10112
    batch = len(spf_sparse.ell_source_batch(graph, ls, cell.config["vantage"]))
    assert batch == roofline.batch_rows(2) == 8
    rows = snapshot.pad_patch_rows(np.arange(4, dtype=np.int32)).shape[0]
    inc = spf_sparse.pad_increase_edges([(0, 1, 1)] * 4)[0].shape[0]
    i32 = jnp.int32

    def shape(*dims, dtype=i32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def per_band(shape_of):
        return tuple(shape(*shape_of(b)) for b in graph.bands)

    compiled = spf_sparse._ell_reconverge.lower(
        per_band(lambda b: (b.rows, b.k)),
        per_band(lambda b: (b.rows, b.k)),
        per_band(lambda b: (rows,)),
        per_band(lambda b: (rows, b.k)),
        per_band(lambda b: (rows, b.k)),
        shape(inc), shape(inc), shape(inc),
        shape(graph.n_pad, dtype=jnp.bool_),
        shape(batch, graph.n_pad),
        shape(batch),
        bands=graph.bands, n=graph.n_pad,
    ).compile()
    out = jax.tree_util.tree_leaves(compiled.out_info)
    assert [tuple(o.shape) for o in out] == [
        (10000, 8), (10000, 8), (2 * batch, graph.n_pad),
        (batch, graph.n_pad), (2,)]
    assert compiled.memory_analysis().temp_size_in_bytes > 0
