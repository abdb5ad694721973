"""The benchmark's own tests: its yardstick, not the program.

Everything here runs on the CPU. A number these tests see under a
metric's name is a count of where work sits, never a time worth
quoting; the runner itself refuses to print one off the chip, and the
only thing that lifts that refusal is ``monkeypatch`` below.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import (
    openloop,
    reference,
    roofline,
    run,
    spec,
    stats,
    topology,
    traffic,
    xplane,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
TINY = {"kind": "fat_tree", "pods": 3, "ssw_per_plane": 2,
        "fsw_per_pod": 2, "rsw_per_pod": 4}
SP_ECMP = {"algorithm": "SP_ECMP", "type": "IP"}


def _bench() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


# -- the percentile rule ------------------------------------------------------


def test_percentile_interpolates_between_order_statistics():
    samples = [float(i) for i in range(1, 202)]
    assert stats.percentile(samples, 0.95) == pytest.approx(191.0)
    assert stats.median([3.0, 1.0, 2.0, 10.0]) == pytest.approx(2.5)


@pytest.mark.parametrize("n,ok", [(199, False), (200, True)])
def test_p95_needs_ten_samples_beyond_it(n, ok):
    samples = [1.0] * n
    if ok:
        assert stats.percentile(samples, 0.95) == 1.0
    else:
        with pytest.raises(stats.TooFewSamples):
            stats.percentile(samples, 0.95)


# -- traffic and topology -----------------------------------------------------


def _mix(**over) -> dict:
    with open(os.path.join(REPO, "chipbench", "traffic", "adj-churn.json"),
              encoding="utf-8") as f:
        mix = json.load(f)
    mix.update(over)
    return mix


def test_topology_is_upstreams_fabric_shape():
    with open(os.path.join(REPO, "chipbench", "configs", "fabric-1000.json"),
              encoding="utf-8") as f:
        config = json.load(f)
    topo = topology.build(config["topology"], config["forwarding"])
    size = config["size"]
    assert len(topo.adj_dbs) == size["nodes"] == 1016
    assert topo.links() == size["links"] == 8736
    degree = {
        tier: len(topo.adj_dbs[f"{tier}-0-0"].adjacencies)
        for tier in ("rsw", "fsw", "ssw")
    }
    assert degree == size["degree"]
    assert config["vantage"] in topo.adj_dbs


def test_unknown_topology_kind_is_an_error():
    with pytest.raises(ValueError):
        topology.build({"kind": "torus", "n": 3}, SP_ECMP)


def test_schedule_is_a_function_of_the_seed():
    topo = topology.build(TINY, SP_ECMP)

    def draw(seed):
        gen = traffic.Generator(topo, seed, _mix(), "rsw-0-0")
        gen.initial_key_vals()
        return [gen.draw() for _ in range(200)]

    a, b, c = draw(7), draw(7), draw(8)
    assert [(e.key, e.value.hash) for e in a] == [(e.key, e.value.hash) for e in b]
    assert [(e.key, e.value.hash) for e in a] != [(e.key, e.value.hash) for e in c]
    share = sum(e.kind == "flap" for e in a) / len(a)
    assert 0.1 < share < 0.3
    # versions only ever go up, per key
    seen = {}
    for e in a:
        assert e.value.version > seen.get(e.key, 1)
        seen[e.key] = e.value.version


def test_flaps_spare_the_vantage():
    topo = topology.build(TINY, SP_ECMP)
    gen = traffic.Generator(
        topo, 3, _mix(kinds={"flap": 1.0}), "rsw-0-0")
    gen.initial_key_vals()
    for _ in range(60):
        gen.draw()
    assert len(gen.adj_dbs["rsw-0-0"].adjacencies) == 2
    assert all(
        any(a.other_node_name == "rsw-0-0" for a in gen.adj_dbs[n].adjacencies)
        for n in ("fsw-0-0", "fsw-0-1")
    )


def test_a_metric_change_only_lands_on_a_link_that_is_up_both_ways():
    topo = topology.build(TINY, SP_ECMP)
    gen = traffic.Generator(
        topo, 5, _mix(kinds={"metric": 0.5, "flap": 0.5}), "rsw-0-0")
    gen.initial_key_vals()
    for _ in range(200):
        before = {n: db.adjacencies for n, db in gen.adj_dbs.items()}
        dead = gen._dead()
        ev = gen.draw()
        if ev.kind != "metric":
            continue
        node = ev.value.originator_id
        changed = set(gen.adj_dbs[node].adjacencies) - set(before[node])
        assert len(changed) == 1
        assert (node, changed.pop().other_node_name) not in dead


@pytest.mark.parametrize("over", [
    {"kinds": {"reboot": 1.0}},
    {"warmup": [["reboot"]]},
    {"node_choice": {"law": "zipf", "exponent": 1.1}},
])
def test_unknown_event_kind_or_node_law_is_an_error(over):
    topo = topology.build(TINY, SP_ECMP)
    with pytest.raises(ValueError):
        traffic.Generator(topo, 1, _mix(**over), "rsw-0-0")


def test_due_offsets_are_steady_and_an_unknown_pattern_is_an_error():
    steady = traffic.due_offsets({"rate_per_s": 10}, 30)
    assert len(steady) == 300 and steady[1] == pytest.approx(0.1)
    with pytest.raises(ValueError):
        traffic.due_offsets(
            {"rate_per_s": 10, "arrivals": {"pattern": "on_off"}}, 30)


def test_open_loop_sends_on_schedule_and_drops_what_is_past_the_end():
    sent = []
    due, late = openloop.run(
        [0.0, 0.02, 0.04, 5.0], sent.append, seconds=0.1)
    assert sent == [0, 1, 2]
    assert len(due) == len(late) == 3
    assert all(0 <= x < 0.05 for x in late)


# -- where a sample ends ------------------------------------------------------


def test_a_sample_ends_in_the_agent_or_at_an_empty_update():
    from openr_tpu.decision.rib import DecisionRouteUpdate
    from chipbench.served_paths import pipeline

    agent = pipeline.TableFibAgent()
    retired = pipeline.RetiredUpdates("fibUpdates", agent)
    reader = retired.get_reader("observer")
    with_routes = DecisionRouteUpdate(unicast_routes_to_delete=["p"])
    # programmed: the clock stopped when the agent's call returned
    agent.delete_unicast_routes(0, ["p"])
    t_call = agent._returned_at
    retired.push(with_routes)
    # empty: nothing to program, the clock stops at the push
    retired.push(DecisionRouteUpdate())
    # routes, and no programming call since the last update
    retired.push(with_routes)
    rows = retired.rows_since(0)
    assert [ok for _, _, ok in rows] == [True, True, False]
    assert rows[0][1] == t_call < rows[1][1] <= rows[2][1]
    assert retired.count() == 3 and retired.rows_since(2) == rows[2:]
    # and it is still Fib's queue: an observer reads what was pushed
    assert reader.try_get() is with_routes


# -- the plain reference ------------------------------------------------------


def test_reference_ecmp_two_way_links_and_relax_passes():
    topo = topology.build(TINY, SP_ECMP)
    want = reference.routes(topo.adj_dbs, topo.prefix_dbs, "rsw-0-0")
    assert len(want) == len(topo.adj_dbs) - 1
    far = topo.prefix_dbs["rsw-2-3"].prefix_entries[0].prefix
    assert {(n, m) for n, _i, m in want[far]} == {("fsw-0-0", 4), ("fsw-0-1", 4)}
    # one side withdraws the adjacency: the link is gone both ways
    from dataclasses import replace

    db = topo.adj_dbs["fsw-0-0"]
    adj_dbs = dict(topo.adj_dbs)
    adj_dbs["fsw-0-0"] = replace(db, adjacencies=tuple(
        a for a in db.adjacencies if a.other_node_name != "rsw-0-0"))
    cut = reference.routes(adj_dbs, topo.prefix_dbs, "rsw-0-0")
    assert {n for n, _i, _m in cut[far]} == {"fsw-0-1"}
    assert reference.relax_passes(topo.adj_dbs, ["rsw-0-0"]) == 4


# -- roofline and peaks -------------------------------------------------------


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "source", "TPU v9"):
        with pytest.raises(KeyError):
            roofline.peaks(kind)


def test_solve_costs_at_the_cells_shapes():
    assert roofline.batch_rows(8) == 16
    ops, nbytes = roofline.dense_view_batch(1016, 16, 4)
    assert ops == 4 * 2 * 16 * 1016 * 1016
    seconds, bound = roofline.least_seconds(ops, nbytes, "TPU v5 lite")
    assert bound == "memory" and 15e-6 < seconds < 30e-6
    ops, nbytes = roofline.ell_reconverge(4992, 2 * 56448, 16)
    assert roofline.least_seconds(ops, nbytes, "TPU v5 lite")[1] == "memory"


# -- the trace reduction ------------------------------------------------------


def test_busy_union_and_idle_gaps_on_a_hand_made_trace():
    assert xplane.merge([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    ops = [("%while.4 = (s32[16,8]{1,0}, pred[]) while(...)", 1e9, 3e9),
           ("%fusion.1 = s32[16,8]{1,0:T(8,128)} fusion(...)", 1e9, 2e9),
           ("%fusion.1 = s32[16,8]{1,0:T(8,128)} fusion(...)", 2e9, 2.8e9),
           ("%copy.2 = s32[8]{0} copy(...)", 9e9, 9.5e9)]
    dev = xplane.DeviceTrace(
        window=(0.0, 10e9), steady=(0.0, 8e9),
        busy=[xplane.merge((s, e) for _, s, e in ops)],
        ops=[ops],
        modules=[[("jit_solve(17)", 1e9, 3e9), ("jit_copy(5)", 9e9, 9.5e9)]],
        host=[("PjitFunction(solve)", 0.2e9, 0.9e9),
              ("chipbench.steady", 0, 8e9)],
    )
    assert dev.busy_s() == pytest.approx(2.5)
    assert dev.busy_s(dev.steady) == pytest.approx(2.0)
    assert dev.window_s == pytest.approx(10.0)
    assert dev.module_seconds(dev.steady) == {"jit_solve(17)": (2.0, 1)}
    # a while's time is its body's: self time, named module/op shape
    # and what ran after the steady part is the closing probe's
    assert dev.top_ops() == [
        ["jit_solve/%fusion.1 s32[16,8]", pytest.approx(1.8)],
        ["probe: jit_copy/%copy.2 s32[8]", pytest.approx(0.5)],
        ["jit_solve/%while.4", pytest.approx(0.2)],
    ]
    gaps = dict(map(tuple, dev.idle_gaps([("decision.rebuild", 3e9, 8.9e9)])))
    assert gaps == {
        "decision.rebuild": pytest.approx(5.0),
        xplane.AFTER_WINDOW: pytest.approx(1.5),
        "PjitFunction(solve)": pytest.approx(1.0),
    }
    assert sum(gaps.values()) == pytest.approx(dev.window_s - dev.busy_s())


def test_a_trace_without_a_probe_is_its_steady_part():
    ops = [("%fusion.1 = s32[8]{0} fusion(...)", 1e9, 2e9)]
    dev = xplane.DeviceTrace(
        window=(0.0, 4e9), steady=(0.0, 4e9), busy=[[(1e9, 2e9)]],
        ops=[ops], modules=[[("jit_solve(17)", 1e9, 2e9)]], host=[],
    )
    assert dev.busy_s() == dev.busy_s(dev.steady) == pytest.approx(1.0)
    assert dev.top_ops() == [["jit_solve/%fusion.1 s32[8]", pytest.approx(1.0)]]
    assert dict(map(tuple, dev.idle_gaps())) == {
        xplane.WAITING: pytest.approx(3.0)}


def test_reduction_of_a_recorded_tpu_trace():
    """``data/fabric-1000.xplane.pb``: a traced run of
    ``fabric-1000.adj-churn`` on one TPU v5e (PR 22), cut short."""
    dev = xplane.reduce(os.path.join(DATA, "fabric-1000.xplane.pb"))
    assert len(dev.busy) == 1 and dev.ops[0] and dev.modules[0]
    assert dev.steady[0] == dev.window[0] < dev.steady[1] <= dev.window[1]
    busy = dev.busy_s()
    assert 0 < busy < dev.window_s
    # the union never exceeds the sum of the operations
    assert busy <= sum(e - s for _, s, e in dev.ops[0]) / 1e9 + 1e-9
    solve = [m for m in dev.module_seconds() if m.startswith("jit__spf_view_batch")]
    assert solve, sorted(dev.module_seconds())
    gaps = dev.idle_gaps()
    assert 0 < len(gaps) <= 10
    assert sum(s for _, s in gaps) == pytest.approx(dev.window_s - busy, rel=1e-6)
    assert len(dev.top_ops()) <= 10


# -- BENCHMARK.json against the files -----------------------------------------


def test_every_name_in_benchmark_json_has_its_file():
    bench = _bench()
    assert bench["command"] == ["python3", "-m", "chipbench.run"]
    configs = {c["name"]: c for c in bench["configs"]}
    for c in configs.values():
        assert NAME.match(c["name"])
        assert os.path.isfile(os.path.join(REPO, c["file"]))
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
    four = 0
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        cell = spec.load_cell(REPO, w["name"])
        assert cell.config["name"] == w["config"] in configs
        assert cell.config["chips"] == w["chips"]
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "served_paths",
            cell.config["served_path"] + ".py"))
        four += w["chips"] == 4
        for group in ("end_to_end", "per_layer"):
            names = [m["name"] for m in cell.metrics(group)]
            assert names, (w["name"], group)
            for name in names:
                assert callable(spec.load_reader(REPO, group, name))
        assert "setup_s" in [m["name"] for m in cell.metrics("end_to_end")]
    assert four <= max(1, len(bench["workloads"]) // 2)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


# -- the runner, end to end, on cells added as data only ----------------------


@pytest.fixture(scope="module")
def data_only_root(tmp_path_factory):
    """A checkout with one more configuration, one more traffic mix and
    two more ``workloads`` entries — files and entries only; no file of
    the benchmark is edited."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(root, "chipbench", "configs", "fabric-1000.json"),
              encoding="utf-8") as f:
        config = json.load(f)
    config.update(name="fabric-tiny", topology=TINY)
    with open(os.path.join(root, "chipbench", "configs", "fabric-tiny.json"),
              "w", encoding="utf-8") as f:
        json.dump(config, f)
    with open(os.path.join(root, "chipbench", "traffic", "prefix-fast.json"),
              "w", encoding="utf-8") as f:
        json.dump({"rate_per_s": 80,
                   "kinds": {"prefix": 1.0}, "warmup": [["prefix"], 2],
                   "reaches_solver": False, "drain_deadline_s": 20,
                   "trace_probe": "metric"}, f)
    bench = _bench()
    bench["configs"].append({
        "name": "fabric-tiny", "source": "this test",
        "file": "chipbench/configs/fabric-tiny.json", "reduced": [],
        "why": "22 nodes"})
    for mix in ("adj-churn", "prefix-fast"):
        bench["workloads"].append({
            "name": f"fabric-tiny.{mix}", "config": "fabric-tiny",
            "traffic": mix, "chips": 1, "why": "a cell added as data"})
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("fabric-tiny.adj-churn")
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(bench, f)
    return root


RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_untraced_run_of_a_data_only_cell(data_only_root, monkeypatch, capsys):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    result = run.run_cell(data_only_root, "fabric-tiny.prefix-fast",
                          seed=11, seconds=3.0, trace=False)
    assert set(result) == RESULT_KEYS
    assert result["attempted"] == 240 and result["failed"] == 0
    detail = json.loads(
        capsys.readouterr().out.split("detail: ")[-1].splitlines()[0])
    # the RouteDatabase matched both references and nothing fell back;
    # the one thing a loaded CPU may cost the run is its sample count
    assert all("needs 200 samples" in p for p in detail["problems"]), detail
    assert result["correct"] == (not detail["problems"])
    assert detail["counters"].get("ops.host_dispatches", 0) == 0
    assert set(result["metrics"]) >= {"conv_p50_ms", "setup_s"}
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(result["device"]) == {
        "platform", "kind", "count", "memory_peak_bytes"}
    assert os.path.isfile(os.path.join(
        data_only_root, "chipbench_out", "fabric-tiny.prefix-fast",
        "last_run.json"))


def test_traced_run_of_a_data_only_cell(data_only_root, monkeypatch, capsys):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    result = run.run_cell(data_only_root, "fabric-tiny.adj-churn",
                          seed=12, seconds=3.0, trace=True)
    assert set(result) == RESULT_KEYS | {"breakdown"}
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert result["device"]["window_s"] > 0
    per_layer = {m["name"] for m in _bench()["per_layer"]}
    assert set(result["metrics"]) <= per_layer
    assert {"debounce_ms", "rebuild_ms", "fib_program_ms", "ingest_ms",
            "pubs_per_rebuild", "cold_build_s"} <= set(result["metrics"])
    detail = json.loads(
        capsys.readouterr().out.split("detail: ")[-1].splitlines()[0])
    # off the chip the trace has no device plane, and 30 events are no
    # p95: both are said, and nothing else is wrong
    for p in detail["problems"]:
        assert "needs 200 samples" in p or "no operation ran" in p, p
    assert result["correct"] is False
    assert result["failed"] == 0


def test_traced_run_of_a_bypass_cell_ends_with_its_probe(
        data_only_root, monkeypatch, capsys):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    result = run.run_cell(data_only_root, "fabric-tiny.prefix-fast",
                          seed=13, seconds=3.0, trace=True)
    detail = json.loads(
        capsys.readouterr().out.split("detail: ")[-1].splitlines()[0])
    # the probe is one more event of the journal, after the window: the
    # RouteDatabase still matches both references
    for p in detail["problems"]:
        assert "needs 200 samples" in p or "probe ran nothing" in p, p
    assert result["attempted"] == 240 and result["failed"] == 0
    # the traced window is the last 1.5 s of the 3 plus the probe
    gaps = dict(map(tuple, result["breakdown"]["idle_gaps"]))
    assert gaps[xplane.AFTER_WINDOW] > 0
    assert result["device"]["window_s"] == pytest.approx(sum(gaps.values()))
    assert result["device"]["window_s"] > 1.5 + gaps[xplane.AFTER_WINDOW] - 0.1


def test_the_command_refuses_to_run_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "fabric-1000.adj-churn", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2
    assert proc.stdout == ""
    assert "needs a tpu" in proc.stderr
