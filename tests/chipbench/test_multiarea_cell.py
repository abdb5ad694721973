"""What ``multi-area-2x1000`` added to the benchmark, as files and entries
only: the plain two-area reference (``chipbench/reference_multiarea.py``),
the driver file (``served_paths/pipeline_multiarea.py``: the two-area
topology kind, a generator an area, the border node with its
PrefixManager, the sample's second end), the configuration, the traffic
mix ``redist-churn`` and three per-layer readers.

Everything here runs on the CPU: counts, never times.
"""

from __future__ import annotations

import json
import os
import random
import time
from dataclasses import replace

import pytest

from benchdef import REPO, append_config, copy_checkout, in_order, load
from benchdef import reaches_solver, reported, stages
from chipbench import reference, reference_multiarea, run, spec, topology
from chipbench.record import RunRecord, Span

CONFIG = "multi-area-2x1000"
ADJ = "multi-area-2x1000.adj-churn"
REDIST = "multi-area-2x1000.redist-churn"
READERS = ("redistribute_ms", "redistribute_kv_calls",
           "redistribute_keys_per_update")
LAYER = "redistribution (prefixmgr)"
TINY = {"kind": "two_area_fat_tree", "pods": 3, "ssw_per_plane": 2,
        "fsw_per_pod": 2, "rsw_per_pod": 4}


def _json(*path, root=REPO) -> dict:
    with open(os.path.join(root, *path), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def driver():
    """The driver file's own names; loading it registers the kind."""
    config = _json("chipbench", "configs", CONFIG + ".json")
    cls = spec.load_driver(REPO, config["served_path"])
    return cls.set_up.__globals__


# -- the reference on upstream's four nodes, checked by hand ------------------
#
# DecisionTest.cpp:4930 MultiAreaBestPathCalculation: 1 - 2 - 4 in area A,
# 1 - 3 - 4 in area B, every metric 10; 1 and 4 are in both areas. Node 1
# and 2 originate into A, 3 and 4 into B.


def _four_nodes(extra=None):
    from openr_tpu.types import (
        Adjacency, AdjacencyDatabase, IpPrefix, PrefixEntry)

    def db(node, area, *nbrs):
        return AdjacencyDatabase(this_node_name=node, area=area, adjacencies=tuple(
            Adjacency(other_node_name=n, if_name=f"if_{node}{n}",
                      other_if_name=f"if_{n}{node}", metric=10) for n in nbrs))

    def entry(n, **kw):
        return PrefixEntry(prefix=IpPrefix.from_str(f"fd00:{n}::/64"), **kw)

    lsdb = {
        "A": ({"1": db("1", "A", "2"), "2": db("2", "A", "1", "4"),
               "4": db("4", "A", "2")},
              {"1": [entry(1)], "2": [entry(2)]}),
        "B": ({"1": db("1", "B", "3"), "3": db("3", "B", "1", "4"),
               "4": db("4", "B", "3")},
              {"3": [entry(3)], "4": [entry(4)]}),
    }
    for area, node, e in extra or ():
        lsdb[area][1].setdefault(node, []).append(e)
    return lsdb, entry


def test_upstreams_four_nodes_cross_area_ecmp():
    lsdb, entry = _four_nodes()
    got = reference_multiarea.routes(lsdb, "1")
    assert got == {
        entry(2).prefix: {("2", "if_12", 10, "A")},
        entry(3).prefix: {("3", "if_13", 10, "B")},
        # originated into B alone, reached over BOTH areas at 20: the
        # advertiser node is looked up in every area's graph
        entry(4).prefix: {("2", "if_12", 20, "A"), ("3", "if_13", 20, "B")},
    }
    # a node of one area holds that area's LSDB alone
    assert reference_multiarea.routes({"A": lsdb["A"]}, "2") == {
        entry(1).prefix: {("1", "if_21", 10, "A")},
    }
    assert reference_multiarea.routes({"B": lsdb["B"]}, "3") == {
        entry(4).prefix: {("4", "if_34", 10, "B")},
    }


def test_upstreams_four_nodes_what_the_border_owes():
    lsdb, entry = _four_nodes()
    owed = reference_multiarea.reoriginations(lsdb, "1", ["A", "B"])
    assert owed == {
        ("B", entry(2).prefix): ("RIB", 1, ("A",)),
        ("A", entry(3).prefix): ("RIB", 1, ("B",)),
        ("A", entry(4).prefix): ("RIB", 1, ("B",)),
    }
    # its own prefix is never re-originated; a node of one area owes nothing
    assert reference_multiarea.reoriginations(
        {"A": lsdb["A"]}, "2", ["A"]) == {}


def test_selection_distance_and_the_stack():
    from openr_tpu.types import PrefixType
    from openr_tpu.types.lsdb import PrefixMetrics

    _, entry = _four_nodes()
    p2 = entry(2).prefix
    both = ["A", "B"]
    # node 4 re-originates 2's prefix into B (distance 1, stack A): it
    # loses to the original, which alone is best
    copy = entry(2, type=PrefixType.RIB, area_stack=("A",),
                 metrics=PrefixMetrics(distance=1))
    lsdb, _ = _four_nodes([("B", "4", copy)])
    assert reference_multiarea.routes(lsdb, "1")[p2] == {("2", "if_12", 10, "A")}
    assert reference_multiarea.reoriginations(
        lsdb, "1", both)[("B", p2)] == ("RIB", 1, ("A",))
    # the original withdrawn: the copy alone is below the zero tuple
    # that upstream's selection starts from: no route, nothing owed
    lsdb["A"][1]["2"] = []
    assert p2 not in reference_multiarea.routes(lsdb, "1")
    assert not [k for k in reference_multiarea.reoriginations(
        lsdb, "1", both) if k[1] == p2]
    # a higher path preference beats a lower distance: 4's entry wins,
    # 4 is reached over both areas at 20, and A is owed it with B, where
    # it was learned, on the stack
    preferred = entry(2, metrics=PrefixMetrics(path_preference=10, distance=5))
    lsdb, _ = _four_nodes([("B", "4", preferred)])
    assert reference_multiarea.routes(lsdb, "1")[p2] == {
        ("2", "if_12", 20, "A"), ("3", "if_13", 20, "B")}
    owed = reference_multiarea.reoriginations(lsdb, "1", both)
    assert owed[("A", p2)] == ("RIB", 6, ("B",)) and ("B", p2) not in owed
    # the same entry with A on its stack already goes nowhere
    lsdb, _ = _four_nodes([("B", "4", replace(preferred, area_stack=("A",)))])
    assert not [k for k in reference_multiarea.reoriginations(
        lsdb, "1", both) if k[1] == p2]
    # and the vantage among the best advertisers means no route at all
    lsdb, _ = _four_nodes([("A", "1", entry(2))])
    assert p2 not in reference_multiarea.routes(lsdb, "1")


def test_the_reference_imports_no_program_solver_or_prefixmanager():
    with open(os.path.join(REPO, "chipbench", "reference_multiarea.py"),
              encoding="utf-8") as f:
        source = f.read()
    assert "import openr_tpu" not in source
    assert "from openr_tpu" not in source


# -- the network --------------------------------------------------------------


@pytest.fixture(scope="module")
def config():
    return _json("chipbench", "configs", CONFIG + ".json")


@pytest.fixture(scope="module")
def areas(driver, config):
    return driver["build"](config)


def test_two_areas_of_upstreams_fabric_joined_by_two_borders(config, areas):
    size, borders = config["size"], config["borders"]
    assert list(areas) == config["areas"] == ["A", "B"]
    fabric = topology.build(
        dict(config["topology"], kind="fat_tree"), config["forwarding"])
    for area, topo in areas.items():
        assert len(topo.adj_dbs) == size["nodes_per_area"] == 1016
        assert topo.links() == size["links_per_area"] == 8736
        # the graph is fabric-1000's: the same degrees, node for node
        assert sorted(len(d.adjacencies) for d in topo.adj_dbs.values()) \
            == sorted(len(d.adjacencies) for d in fabric.adj_dbs.values())
        assert {a.metric for d in topo.adj_dbs.values()
                for a in d.adjacencies} == {1}
        assert {d.area for d in topo.adj_dbs.values()} == {area}
        for k, border in enumerate(borders):
            nbrs = {a.other_node_name for a in topo.adj_dbs[border].adjacencies}
            # an RSW's place: that pod's 8 FSWs, of this area alone
            assert nbrs == {f"{area.lower()}-fsw-{k}-{j}" for j in range(8)}
        assert reference.relax_passes(topo.adj_dbs, [config["vantage"]]) == 4
    a, b = (set(t.adj_dbs) for t in areas.values())
    assert a & b == set(borders) and config["vantage"] in borders
    assert len(a | b) == size["nodes"] == 2030
    assert sum(t.links() for t in areas.values()) == size["links"] == 17472
    # one loopback a node, numbered over the union; a border's is one
    loopbacks = {n: t.prefix_dbs[n].prefix_entries
                 for t in areas.values() for n in t.prefix_dbs}
    assert len({e[0].prefix for e in loopbacks.values()}) \
        == size["loopbacks"] == 2030
    assert all(len(e) == 1 for e in loopbacks.values())


def test_the_initial_lsdb_gives_every_remote_prefix_two_originators(
        driver, config, areas):
    vantage, borders = config["vantage"], config["borders"]
    (peer,) = [b for b in borders if b != vantage]
    size = config["size"]
    peer_keys = driver["peer_reoriginations"](areas, peer, borders)
    assert sum(map(len, peer_keys.values())) == size["peer_reoriginations"]
    for area, dbs in peer_keys.items():
        (other,) = [a for a in areas if a != area]
        for key, db in dbs.items():
            (entry,) = db.prefix_entries
            assert key == f"prefix:{peer}:{area}:[{entry.prefix.to_str()}]"
            assert (entry.type.name, entry.metrics.distance, entry.area_stack,
                    db.area) == ("RIB", 1, (other,), area)
    gen = driver["TwoAreaTraffic"](
        areas, 1, {"kinds": {"metric": 1.0}}, vantage, borders)
    lsdb = gen.lsdb(peer_keys)
    entries = reference_multiarea._entries(lsdb)
    assert sum(map(len, entries.values())) == size["prefix_entries_initial"]
    remote = [p for p, e in entries.items() if vantage not in {n for n, _ in e}]
    assert len(remote) == size["routes"] == 2029
    assert all(len(entries[p]) == 2 for p in remote)
    want = reference_multiarea.routes(lsdb, vantage)
    assert set(want) == set(remote)
    # a pod mate is two links away through 8 FSWs, the peer four links
    # away through both areas at once
    mate = areas["A"].prefix_dbs["a-rsw-0-1"].prefix_entries[0].prefix
    assert {(m, a) for _, _, m, a in want[mate]} == {(2, "A")}
    assert len(want[mate]) == 8
    peers = areas["A"].prefix_dbs[peer].prefix_entries[0].prefix
    assert {(m, a) for _, _, m, a in want[peers]} == {(4, "A"), (4, "B")}
    assert len(want[peers]) == 16
    owed = reference_multiarea.reoriginations(lsdb, vantage, list(areas))
    assert len(owed) == size["vantage_reoriginations"] == 2029
    assert set(owed.values()) == {("RIB", 1, ("A",)), ("RIB", 1, ("B",))}
    # the peer's own loopback, best in both areas, goes by the least
    # (node, area): learned in A, owed to B
    assert owed[("B", peers)] == ("RIB", 1, ("A",)) and ("A", peers) not in owed


# -- the traffic --------------------------------------------------------------


def _traffic(driver, config, areas, mix, seed=2300000011):
    gen = driver["TwoAreaTraffic"](
        areas, seed, mix, config["vantage"], config["borders"])
    gen.initial_key_vals()
    return gen


def test_an_events_area_is_its_nodes_and_a_border_weighs_one_half(
        driver, config, areas):
    mix = _json("chipbench", "traffic", "adj-churn.json")
    draw = lambda seed: [  # noqa: E731
        (e.area, e.kind, e.key, e.value.hash)
        for g in [_traffic(driver, config, areas, mix, seed)]
        for e in (g.draw() for _ in range(400))]
    a, b, c = draw(2300000011), draw(2300000011), draw(2300000012)
    assert a == b != c
    assert 0.4 < sum(area == "A" for area, *_ in a) / len(a) < 0.6
    for area, _, key, _ in a:
        node = key.split(":", 1)[1]
        assert node in config["borders"] or node.startswith(area.lower() + "-")
    # within an area a border is drawn half as often as another node
    gen = _traffic(driver, config, areas, mix, 7).gens["A"]
    picks = [gen._pick() for _ in range(200000)]
    share = sum(p in config["borders"] for p in picks) / len(picks)
    assert share == pytest.approx(2 * 0.5 / 1015, rel=0.2)
    # a node of both areas keeps its (key, version) pairs apart
    both = _traffic(driver, config, areas, dict(mix, kinds={"metric": 1.0}), 7)
    for g in both.gens.values():
        g._pick = lambda: config["vantage"]
    seen = {(e.key, e.value.version) for e in (both.draw() for _ in range(40))}
    assert len(seen) == 40


def test_redist_churn_toggles_one_prefix_of_a_non_border_node(
        driver, config, areas):
    mix = _json("chipbench", "traffic", "redist-churn.json")
    assert (mix["rate_per_s"], mix["kinds"], mix["reaches_solver"],
            mix["trace_probe"], mix["drain_deadline_s"]) \
        == (25, {"prefix": 1.0}, False, "metric", 20)
    assert mix["node_choice"] == {"law": "uniform", "exclude": "borders"}
    assert mix["warmup"] == _json(
        "chipbench", "traffic", "prefix-churn.json")["warmup"]
    gen = _traffic(driver, config, areas, mix)
    toggled = set()
    for _ in range(600):
        ev = gen.draw()
        node = ev.value.originator_id
        assert ev.kind == "prefix" and node not in config["borders"]
        assert node.startswith(ev.area.lower() + "-")
        toggled.add(node)
    # numbered over the union: no two nodes, of either area, toggle the
    # same /128
    extras = {e.prefix for g in gen.gens.values()
              for db in g.prefix_dbs.values() for e in db.prefix_entries[1:]}
    now_on = sum(len(db.prefix_entries) == 2
                 for g in gen.gens.values() for db in g.prefix_dbs.values())
    assert len(extras) == now_on > 100 and len(toggled) > 400


# -- BENCHMARK.json against the files -----------------------------------------


def test_every_new_name_has_its_file_and_the_file_says_what_the_entry_says(
        checkout):
    bench = load(checkout)
    adj, redist = (spec.load_cell(checkout, c) for c in (ADJ, REDIST))
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    config = adj.config
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    for part in ("DecisionTest.cpp:4930", "MultiAreaBestPathCalculation",
                 "PrefixManager.cpp", "DecisionBenchmark.cpp:27-29", "N=1000"):
        assert part in config["source"]
    assert entry["reduced"] == config["reduced"] == []
    assert set(config["assumed"]) >= {
        "pairing", "borders", "vantage", "peer", "traffic"}
    fabric = _json("chipbench", "configs", "fabric-1000.json", root=checkout)
    for key in ("router", "forwarding"):
        assert config[key] == fabric[key], key
    assert {k: v for k, v in config["topology"].items() if k != "kind"} \
        == {k: v for k, v in fabric["topology"].items() if k != "kind"}
    assert config["solve_counters"] == ["decision.device_solves"]
    assert len(config["guarantees"]) == 5
    assert os.path.isfile(os.path.join(
        checkout, "chipbench", "served_paths", config["served_path"] + ".py"))
    cells = {w["name"]: w for w in bench["workloads"]}
    for cell, mix in ((adj, "adj-churn"), (redist, "redist-churn")):
        assert cell.workload == cells[cell.workload["name"]]
        assert (cell.workload["config"], cell.workload["traffic"],
                cell.workload["chips"]) == (CONFIG, mix, config["chips"])
        assert 0 < len(cell.workload["why"]) <= 200
    # the accepted mix, untouched
    assert adj.mix == _json("chipbench", "traffic", "adj-churn.json")
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        m = per_layer[name]
        assert in_order([ADJ, REDIST], m["workloads"])
        assert (m["layer"], m["moves"]) == (LAYER, "conv_p50_ms")
        assert callable(spec.load_reader(checkout, "per_layer", name))
    assert per_layer["redistribute_ms"]["source"] == "program_span"
    assert per_layer["redistribute_kv_calls"]["source"] == "program_counter"
    # each cell stands behind the cell it resembles on every list
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = m.get("workloads") or ()
        assert ("fabric-1000.adj-churn" in listed) == (
            ADJ in listed and m["name"] not in READERS) or m["name"] in READERS
        assert ("fabric-5000.prefix-churn" in listed) == (
            REDIST in listed and m["name"] not in READERS) \
            or m["name"] in READERS
        if ADJ in listed and m["name"] not in READERS:
            assert in_order(["fabric-1000.adj-churn", ADJ], listed)
        if REDIST in listed and m["name"] not in READERS:
            assert in_order(["fabric-5000.prefix-churn", REDIST], listed)
    assert reaches_solver(adj) and stages(adj)
    assert not reaches_solver(redist)
    assert reported(adj) >= set(READERS) | {
        "solve_roofline", "dense_solve_span_ms", "view_sync_ms",
        "solve_wait_ms", "route_diff_ms", "route_diff_compared",
        "spec_hit_share", "speculate_ms", "timer_late_ms", "tail_fib_excess_ms"}
    assert reported(redist) >= set(READERS) | {
        "timer_late_ms", "tail_fib_excess_ms", "decision_busy_share"}
    assert not reported(redist) & {
        "solve_roofline", "dense_solve_span_ms", "view_sync_ms",
        "solve_wait_ms", "route_diff_ms", "route_diff_compared",
        "spec_hit_share", "speculate_ms", "solve_span_ms", "relax_roofline"}
    for cell in (adj, redist):
        assert reported(cell, "end_to_end") == {
            "conv_p50_ms", "conv_p95_ms", "setup_s"}


# -- the readers --------------------------------------------------------------


def _reader(name):
    return spec.load_reader(REPO, "per_layer", name)


def _record(counters, spans=()) -> RunRecord:
    rec = RunRecord(counters=counters)
    rec.spans = [Span(i, name, 0.0, dur, attrs)
                 for i, (name, dur, attrs) in enumerate(spans)]
    return rec


def test_the_new_readers_on_a_hand_made_record():
    spans = [("prefixmgr.redistribute", d, {"routes": 1, "keys_set": 1,
                                            "keys_cleared": 0})
             for d in (0.2, 0.4, 0.9)] + [("decision.rebuild", 5.0, {})]
    delta = _record({"prefixmgr.redistribute_runs": 750,
                     "prefixmgr.kvstore_calls": 750,
                     "prefixmgr.redistributed_keys": 380,
                     "prefixmgr.withdrawn_keys": 370}, spans)
    assert _reader("redistribute_ms")(delta) == pytest.approx(0.4)
    assert _reader("redistribute_kv_calls")(delta) == pytest.approx(1.0)
    assert _reader("redistribute_keys_per_update")(delta) == pytest.approx(1.0)
    # a sync of the whole table, an update: the calls say so
    table = _record({"prefixmgr.redistribute_runs": 100,
                     "prefixmgr.kvstore_calls": 203000,
                     "prefixmgr.redistributed_keys": 50,
                     "prefixmgr.withdrawn_keys": 50})
    assert _reader("redistribute_kv_calls")(table) == pytest.approx(2030.0)
    # adjacency churn: updates, and nothing to hand to KvStore
    still = _record({"prefixmgr.redistribute_runs": 300,
                     "prefixmgr.kvstore_calls": 0,
                     "prefixmgr.redistributed_keys": 0,
                     "prefixmgr.withdrawn_keys": 0})
    assert _reader("redistribute_kv_calls")(still) == 0.0
    assert _reader("redistribute_keys_per_update")(still) == 0.0


@pytest.mark.parametrize("name", READERS)
def test_a_new_reader_finds_nothing_where_the_program_counts_nothing(name):
    """The parent's side: no such span, no such counter."""
    parent = _record({"decision.route_build_runs": 750},
                     [("decision.rebuild", 5.0, {}), ("fib.program", 1.0, {})])
    assert _reader(name)(parent) is None
    assert _reader(name)(_record({})) is None


# -- the program against the reference, on random metrics ---------------------


@pytest.mark.parametrize("seed", [3, 11, 2300000011])
def test_the_device_backend_agrees_with_the_reference_on_random_metrics(
        driver, config, seed):
    from openr_tpu.decision.rib import DecisionRouteUpdate
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.graph.linkstate import LinkState
    from openr_tpu.messaging.queue import ReplicateQueue
    from openr_tpu.prefixmgr.prefix_manager import PrefixManager
    from openr_tpu.types import PrefixDatabase

    small = dict(config, topology=dict(TINY, pods=4, rsw_per_pod=3))
    topos = driver["build"](small)
    vantage, borders = small["vantage"], small["borders"]
    (peer,) = [b for b in borders if b != vantage]
    rng = random.Random(seed)
    for topo in topos.values():
        for node, db in topo.adj_dbs.items():
            topo.adj_dbs[node] = replace(db, adjacencies=tuple(
                replace(a, metric=rng.randint(1, 4)) for a in db.adjacencies))
    peer_keys = driver["peer_reoriginations"](topos, peer, borders)
    gen = driver["TwoAreaTraffic"](
        topos, seed, {"kinds": {"metric": 1.0}}, vantage, borders)
    lsdb = gen.lsdb(peer_keys)

    area_ls, ps = {}, PrefixState()
    for area, (adj_dbs, advertised) in lsdb.items():
        ls = area_ls[area] = LinkState(area=area)
        for db in adj_dbs.values():
            ls.update_adjacency_database(db)
        for node, entries in advertised.items():
            ps.update_prefix_database(PrefixDatabase(
                this_node_name=node, prefix_entries=tuple(entries), area=area))
    rdb = SpfSolver(vantage, backend="device").build_route_db(
        vantage, area_ls, ps)
    got = {
        p: frozenset((nh.neighbor_node_name, nh.address.if_name, nh.metric,
                      nh.area) for nh in e.nexthops)
        for p, e in rdb.unicast_routes.items()}
    want = reference_multiarea.routes(lsdb, vantage)
    assert got == want and len(want) == 2 * (4 * 2 + 2 * 2 + 4 * 3) - 3
    assert len({m for nhs in want.values() for _, _, m, _ in nhs}) > 3

    class Client:
        def persist_key(self, *a, **k): pass
        def clear_key(self, *a, **k): pass

    q = ReplicateQueue(name="routeUpdates")
    pm = PrefixManager(vantage, Client(), decision_route_updates_queue=q,
                       areas=list(topos))
    pm.start()
    try:
        update = DecisionRouteUpdate()
        update.unicast_routes_to_update.update(rdb.unicast_routes)
        q.push(update)
        owed = reference_multiarea.reoriginations(lsdb, vantage, list(topos))
        deadline = time.monotonic() + 10
        while len(pm.get_redistributed()) < len(want) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        held = {
            (area, p): (e.type.name, e.metrics.distance, e.area_stack)
            for p, (e, targets) in pm.get_redistributed().items()
            for area in targets}
        assert held == owed and len(owed) == len(want)
    finally:
        pm.stop()


# -- the runner, end to end, on a small two-area fabric added as data ---------


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """A checkout with two areas of a 22-node fabric under both mixes."""
    return append_config(
        copy_checkout(str(tmp_path_factory.mktemp("checkout"))),
        "multi-area-small", CONFIG, TINY,
        {"adj-churn": ADJ, "redist-churn": REDIST}, "2 x 22 nodes")


def _detail(capsys) -> dict:
    return json.loads(
        capsys.readouterr().out.split("detail: ")[-1].splitlines()[0])


def test_untraced_run_of_the_small_adj_churn_cell(
        small_root, monkeypatch, capsys):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    result = run.run_cell(small_root, "multi-area-small.adj-churn",
                          seed=2300000011, seconds=3.0, trace=False)
    detail = _detail(capsys)
    # routes equal to both references, KvStore holding what the vantage
    # owes, nothing lost, nothing compiled in the window, no fallback,
    # the device solved; 30 events are no p95, and that is all that is said
    assert all("needs 200 samples" in p for p in detail["problems"]), detail
    assert result["attempted"] == 30 and result["failed"] == 0
    assert set(result["metrics"]) >= {"conv_p50_ms", "setup_s"}
    counters = detail["counters"]
    assert counters["chipbench.published"] == 30
    assert counters["decision.device_solves"] >= 25
    # the shapes of the graph a solve reads: ONE area's, not the union's
    # 42 nodes and 72 links (solve_roofline multiplies them)
    shapes = detail["shapes"]
    assert (shapes["nodes"], shapes["links"], shapes["vantage_degree"],
            shapes["areas"]) == (22, 36, 2, 2)
    assert shapes["routes"] >= 38 and shapes["reoriginated_keys"] >= 38


def test_traced_run_of_the_small_redist_churn_cell(
        small_root, monkeypatch, capsys):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    result = run.run_cell(small_root, "multi-area-small.redist-churn",
                          seed=3300000017, seconds=3.0, trace=True)
    detail = _detail(capsys)
    # off the chip the trace has no device plane, so the closing probe
    # shows nothing on the device: that, and the sample rule, are all
    for p in detail["problems"]:
        assert "needs 200 samples" in p or "closing probe" in p, p
    assert result["attempted"] == 75 and result["failed"] == 0
    counters = detail["counters"]
    assert counters["chipbench.published"] == 75
    # 40 nodes share 75 events: where two events on one prefix met in
    # one window there was nothing to re-originate, and their samples
    # end at Fib alone; it is counted
    assert counters.get("chipbench.owed_unmatched", 0) < 20
    # the tombstones that joined the next window's count of merged
    # updates were taken for no event: nothing failed, nothing waited
    # for the drain's deadline
    assert counters["decision.route_build_runs"] <= 75 + 1  # + the probe
    assert counters.get("decision.coalesced_publications", 0) > 10
    metrics = result["metrics"]
    assert set(READERS) <= set(metrics)
    # a key an event; a window that carried two events owes two
    assert 0.9 <= metrics["redistribute_keys_per_update"]["value"] <= 1.5
    # a delta: a call a key, whatever the table holds
    assert metrics["redistribute_kv_calls"]["value"] <= 4
    assert 0 < metrics["redistribute_ms"]["value"] < 50
    assert "solve_roofline" not in metrics and "speculate_ms" not in metrics
    assert detail["shapes"]["nodes"] == 22


def test_a_sample_ends_when_kvstore_has_the_reoriginated_key(
        small_root, monkeypatch, capsys):
    """Not at Fib alone: hold every key of the vantage's own back for
    50 ms on its way into the store, and every sample is 50 ms longer."""
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    load_driver = spec.load_driver

    def slow_store(root, served_path):
        cls = load_driver(root, served_path)
        store = cls.set_up.__globals__["AreaStore"]
        accept = store.set_key_vals

        def set_key_vals(self, area, params, sender_id=None):
            if not hasattr(params, "owes") and any(
                    k.startswith(self.own_prefix) for k in params.key_vals):
                time.sleep(0.05)
            accept(self, area, params, sender_id)

        store.set_key_vals = set_key_vals
        return cls

    monkeypatch.setattr(run.spec, "load_driver", slow_store)
    result = run.run_cell(small_root, "multi-area-small.redist-churn",
                          seed=5, seconds=2.0, trace=False)
    detail = _detail(capsys)
    assert all("needs 200 samples" in p for p in detail["problems"]), detail
    assert result["failed"] == 0 and detail["samples"] >= 20
    assert result["metrics"]["conv_p50_ms"]["value"] >= 50.0
