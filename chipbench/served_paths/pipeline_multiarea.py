"""Served path ``pipeline_multiarea``: the ``pipeline`` driver for a
border router of two areas, for ``multi-area-2x1000``.

A configuration's ``served_path`` names a driver file, so this is where
what the two cells need and the yardstick lacks arrives:

- the topology kind ``two_area_fat_tree``: upstream's fabric twice
  (``a-<name>`` in the configuration's first area, ``b-<name>`` in its
  second), with the configuration's ``borders`` standing in both where
  ``rsw-<k>-0`` stands in each, so border *k* has ``fsw_per_pod``
  uplinks to pod *k* in either area. ``topology.build`` numbers the
  union (loopbacks, labels and link-local addresses are one space, as
  they are in a network) and ``split_areas`` cuts it into one
  ``Topology`` an area;
- one seeded ``traffic.Generator`` an area, composed (``TwoAreaTraffic``):
  an event's node is uniform over all the distinct nodes, its area is
  its node's, and a border's event draws one of the border's two areas
  (so an area is drawn first, evenly, and a border weighs one half
  within it). A mix whose ``node_choice`` says ``"exclude": "borders"``
  never lands on one. An event carries its area (``AreaEvent``); a
  warm-up burst goes whole to one area, the areas in turn, so that each
  bucket of changed rows the accepted warm-up reaches is reached in one
  graph;
- the node as ``openr_tpu/daemon.py`` builds a border: ``KvStore`` with
  both areas, ``Decision``, ``Fib`` and ``PrefixManager(areas=...,
  decision_route_updates_queue=route_updates)`` on the one
  ``KvStoreClient``, per-prefix keys as it ships;
- the peer border's re-originations as part of the bulk load, built
  here from upstream's rule (``peer_reoriginations``), so every remote
  prefix has two originators from the start; set-up then waits for the
  first routes in Fib AND for the vantage's own first re-originations
  (what ``reference_multiarea.reoriginations`` says it owes the initial
  LSDB) to be in KvStore, before the warm-up;
- the plain reference ``chipbench/reference_multiarea.py`` in
  ``reference.py``'s place in ``_verify``, a two-area host replay, and
  one rule more: each area's KvStore holds exactly the live
  re-originated keys the reference derives for the final LSDB.

**What a sample is.** ``pipeline.Driver.measure`` is untouched: one
sample a rebuild window, from when the window's oldest publication was
due to when the window's update was retired. Here a window whose oldest
publication is a ``prefix`` event is retired when the border has done
BOTH things it owes the network for that event: the later of the last
programming call for the window's update in the benchmark's own
``FibService`` (``TableFibAgent``) and the moment the re-originated key
for the event's prefix, or its tombstone, was accepted by the other
area's KvStore. That moment is taken on this process's
``time.monotonic`` inside the store handle of the ``KvStoreClient`` the
benchmark hands ``PrefixManager`` (``AreaStore.set_key_vals``, after the
store's own call has returned), never from a program span or counter:
a program that re-originates at all can be timed. An adjacency event
owes KvStore nothing, so ``multi-area-2x1000.adj-churn``'s samples end
where ``pipeline.py``'s do.

**What counts as carried.** The program's own publications pass through
``Decision`` beside the generator's: a re-originated key is dropped
there as the node's own reflection, a tombstone is merged and changes
nothing, yet joins the next window's count of merged updates. So the
counter arithmetic of ``pipeline.Driver._progress`` would take a
tombstone for a carried event. Here every publication is counted where
it is made (the generator's as sent, the program's as accepted by the
store) and ``_converged`` asks the program's modules themselves: the
sent events are carried when ``Decision`` holds nothing unread and
nothing pending, every rebuild begun is retired, and every key the
events sent so far make the vantage owe is in the state they leave it.

``record.shapes`` are those of the graph a solve reads, ONE area's
(``solve_roofline`` multiplies them), never the union's.
"""

from __future__ import annotations

import bisect
import os
import random
import threading
import time
from dataclasses import replace
from types import SimpleNamespace
from typing import Dict, List, Optional, Tuple

import jax
from openr_tpu.decision.decision import Decision
from openr_tpu.fib.fib import Fib
from openr_tpu.kvstore.client import KvStoreClient
from openr_tpu.kvstore.store import KvStore
from openr_tpu.messaging.queue import ReplicateQueue
from openr_tpu.prefixmgr.prefix_manager import PrefixManager
from openr_tpu.telemetry import (
    get_registry,
    get_tracer,
    reset_flight_recorder,
)
from openr_tpu.types import (
    TTL_INFINITY,
    KeyDumpParams,
    KeySetParams,
    PrefixDatabase,
    PrefixType,
    Publication,
)
from openr_tpu.utils import compile_cache, wire
from openr_tpu.utils import keys as keyutil
from openr_tpu.utils.eventbase import OpenrEventBase

from chipbench import reference, reference_multiarea, topology, traffic
from chipbench.compiles import CompileCounter
from chipbench.served_paths import pipeline

KIND = "two_area_fat_tree"
TAGS = ("a", "b")
# what keeps (key, version) of a node that is in both areas apart
SECOND_AREA_VERSIONS = 1_000_000


# -- the network --------------------------------------------------------------


def _two_area_fat_tree(pods: int, ssw_per_plane: int, fsw_per_pod: int,
                       rsw_per_pod: int, borders=("border-0", "border-1")
                       ) -> List[topology.Edge]:
    stands_for = {f"rsw-{k}-0": name for k, name in enumerate(borders)}
    one = topology.KINDS["fat_tree"](
        pods, ssw_per_plane, fsw_per_pod, rsw_per_pod
    )
    return [
        tuple(stands_for.get(n, f"{tag}-{n}") for n in (a, b)) + (metric,)
        for tag in TAGS for a, b, metric in one
    ]


topology.KINDS[KIND] = _two_area_fat_tree


def split_areas(union: topology.Topology, areas, borders
                ) -> Dict[str, topology.Topology]:
    """One ``Topology`` an area out of the union ``topology.build``
    numbered: a node is in the area of its tag, a border in both with
    the adjacencies it has there."""
    out = {}
    for tag, area in zip(TAGS, areas):
        topo = topology.Topology(name=union.name, area=area)
        for node, db in union.adj_dbs.items():
            if not node.startswith(tag + "-") and node not in borders:
                continue
            topo.adj_dbs[node] = replace(db, area=area, adjacencies=tuple(
                a for a in db.adjacencies
                if tag + "-" in (node[:2], a.other_node_name[:2])
            ))
            topo.prefix_dbs[node] = replace(union.prefix_dbs[node], area=area)
        out[area] = topo
    return out


def build(config: dict) -> Dict[str, topology.Topology]:
    borders = config["borders"]
    union = topology.build(
        dict(config["topology"], borders=borders), config["forwarding"]
    )
    return split_areas(union, config["areas"], borders)


def peer_reoriginations(topos: Dict[str, topology.Topology], peer: str,
                        borders) -> Dict[str, Dict[str, PrefixDatabase]]:
    """area -> {key: database}: what the border ``peer`` advertises into
    each area of the other area's loopbacks, by upstream's rule: every
    loopback that is not a border's, as type ``RIB``, ``distance + 1``,
    the area it was learned in on the stack."""
    out: Dict[str, Dict[str, PrefixDatabase]] = {a: {} for a in topos}
    for learned_in, topo in topos.items():
        for node, db in topo.prefix_dbs.items():
            if node in borders:
                continue
            for entry in db.prefix_entries:
                copy = replace(
                    entry, type=PrefixType.RIB, area_stack=(learned_in,),
                    metrics=replace(
                        entry.metrics, distance=entry.metrics.distance + 1),
                )
                for area in topos:
                    if area != learned_in:
                        out[area][keyutil.per_prefix_key(
                            peer, area, entry.prefix
                        )] = PrefixDatabase(
                            this_node_name=peer, prefix_entries=(copy,),
                            area=area,
                        )
    return out


# -- the traffic --------------------------------------------------------------


# (this file is loaded by path and stands in no ``sys.modules``, which
# ``dataclass`` looks its class's module up in: plain classes)
class AreaEvent(traffic.Event):
    """One publication, ready to send, and the area it goes to."""

    def __init__(self, ev: traffic.Event, area: str) -> None:
        super().__init__(ev.kind, ev.key, ev.value)
        object.__setattr__(self, "area", area)


class AreaKeySet(KeySetParams):
    """What the send loop hands to the store: where it goes, and the
    key it makes the vantage owe the other area with the state it
    leaves that key in (a ``prefix`` event; ``None`` for the rest)."""

    def __init__(self, ev: AreaEvent,
                 owes: Optional[Tuple[Tuple[str, str], bool]]) -> None:
        super().__init__(
            key_vals={ev.key: ev.value},
            originator_id=ev.value.originator_id,
        )
        self.area = ev.area
        self.owes = owes


class TwoAreaTraffic:
    """One seeded generator an area; see the head of the file."""

    def __init__(self, topos: Dict[str, topology.Topology], seed: int,
                 mix: dict, vantage: str, borders):
        self.areas = list(topos)
        # as ``topology.build`` numbered the union
        numbered = {n: i for i, n in enumerate(sorted(
            {n for t in topos.values() for n in t.adj_dbs}))}
        self._rng = random.Random(seed)
        self._bursts = 0
        self.gens: Dict[str, traffic.Generator] = {}
        exclude = mix.get("node_choice", {}).get("exclude")
        if exclude not in (None, "borders"):
            raise ValueError(f"unknown node_choice exclude {exclude!r}")
        border_weight = 0.0 if exclude == "borders" else 0.5
        for area, topo in topos.items():
            gen = traffic.Generator(topo, f"{seed}/{area}", mix, vantage)
            # the extra /128 of a prefix event is numbered as the
            # loopbacks are: over the union, or two areas would toggle
            # the same prefix
            gen._node_idx = {n: numbered[n] for n in gen._nodes}
            gen._pick = self._chooser(gen, borders, border_weight)
            self.gens[area] = gen

    @staticmethod
    def _chooser(gen: traffic.Generator, borders, border_weight: float):
        nodes = [n for n in gen._nodes
                 if n not in borders or border_weight > 0]
        upto, total = [], 0.0
        for n in nodes:
            total += border_weight if n in borders else 1.0
            upto.append(total)
        return lambda: nodes[min(
            bisect.bisect_right(upto, gen._rng.random() * total),
            len(nodes) - 1,
        )]

    def initial_key_vals(self) -> Dict[str, Dict[str, object]]:
        out = {a: g.initial_key_vals() for a, g in self.gens.items()}
        first = self.gens[self.areas[0]]
        for gen in list(self.gens.values())[1:]:
            for key in gen._versions:
                if key in first._versions:
                    gen._versions[key] += SECOND_AREA_VERSIONS
        return out

    def _area(self) -> str:
        n = len(self.areas)
        return self.areas[int(self._rng.random() * n) % n]

    def draw(self) -> AreaEvent:
        area = self._area()
        return AreaEvent(self.gens[area].draw(), area)

    def event(self, kind: str) -> AreaEvent:
        area = self._area()
        return AreaEvent(self.gens[area].event(kind), area)

    def burst(self, spec) -> List[AreaEvent]:
        area = self.areas[self._bursts % len(self.areas)]
        self._bursts += 1
        return [AreaEvent(ev, area) for ev in self.gens[area].burst(spec)]

    # what ``pipeline.Driver._verify`` reads off its one generator: the
    # vantage's neighbours, for the batch of sources a solve has
    @property
    def adj_dbs(self):
        return self.gens[self.areas[0]].adj_dbs

    @property
    def prefix_dbs(self):
        return self.gens[self.areas[0]].prefix_dbs

    def lsdb(self, extra: Dict[str, Dict[str, PrefixDatabase]]):
        """The LSDB as ``reference_multiarea`` takes it: the generators'
        databases as they stand, and ``extra``'s static keys."""
        out = {}
        for area, gen in self.gens.items():
            advertised = {
                n: list(db.prefix_entries) for n, db in gen.prefix_dbs.items()
            }
            for db in extra[area].values():
                advertised.setdefault(db.this_node_name, []).extend(
                    db.prefix_entries)
            out[area] = (gen.adj_dbs, advertised)
        return out

    def relax_passes(self, vantage: str) -> int:
        """Of ONE graph: the most any area's solve needs."""
        return max(
            reference.relax_passes(gen.adj_dbs, [vantage] + sorted(
                a.other_node_name for a in gen.adj_dbs[vantage].adjacencies))
            for gen in self.gens.values()
        )


def live_entries(store, areas, own_prefix: str) -> Dict[str, list]:
    """area -> the entries of the live per-prefix keys under
    ``own_prefix`` in that area's KvStore; a ``delete_prefix`` tombstone
    is absent."""
    out = {}
    for area in areas:
        pub = store.dump_with_filters(area, KeyDumpParams(prefix=own_prefix))
        out[area] = [
            entry
            for key, value in pub.key_vals.items()
            if keyutil.parse_per_prefix_key(key) is not None
            for db in [wire.loads(value.value, PrefixDatabase)]
            if not db.delete_prefix
            for entry in db.prefix_entries
        ]
    return out


# -- the store handle, and the clock's second end -----------------------------


class AreaStore:
    """The ``KvStore`` as the driver and the node's ``KvStoreClient``
    hold it. A generator's key set goes to the area it names and is
    counted as sent, with what it makes the vantage owe; every key the
    vantage's PrefixManager sets or clears is noted when the store has
    accepted it: (when, area, key, live)."""

    def __init__(self, store: KvStore, own_prefix: str) -> None:
        self._store = store
        self.own_prefix = own_prefix
        self._lock = threading.Lock()
        self.sent = 0
        self._owed: Dict[Tuple[str, str], bool] = {}
        self._live: Dict[Tuple[str, str], bool] = {}
        self._accepted: List[Tuple[float, str, str]] = []

    def __getattr__(self, name):
        return getattr(self._store, name)

    def set_key_vals(self, area, params, sender_id=None) -> None:
        if isinstance(params, AreaKeySet):
            with self._lock:
                self.sent += 1
                if params.owes is not None:
                    self._owed[params.owes[0]] = params.owes[1]
            self._store.set_key_vals(params.area, params, sender_id)
            return
        self._store.set_key_vals(area, params, sender_id)
        own = [(k, v) for k, v in params.key_vals.items()
               if k.startswith(self.own_prefix) and v.value is not None]
        if own:
            now = time.monotonic()
            with self._lock:
                for key, value in own:
                    self._accepted.append((now, area, key))
                    # a tombstone is the one value of a finite life
                    self._live[(area, key)] = value.ttl == TTL_INFINITY

    def count_live(self) -> int:
        with self._lock:
            return sum(self._live.values())

    def paid(self) -> bool:
        """Every key the events sent so far make the vantage owe is in
        the state the last of them leaves it."""
        with self._lock:
            return all(self._live.get(k, False) == live
                       for k, live in self._owed.items())

    def accepted_since(self, count: int) -> List[Tuple[float, str, str]]:
        with self._lock:
            return self._accepted[count:]


class RetiredWhenOwedIsPaid(pipeline.RetiredUpdates):
    """``pipeline.RetiredUpdates``, whose rows ``measure`` reads once,
    after the drain: a row ends at the later of what it ended at and
    when the driver says the window's oldest event was paid in
    KvStore."""

    paid_at = staticmethod(lambda trace_id: None)

    def rows_since(self, count: int):
        rows = []
        for trace_id, t_end, programmed in super().rows_since(count):
            paid = self.paid_at(trace_id)
            rows.append((
                trace_id, t_end if paid is None else max(t_end, paid),
                programmed,
            ))
        return rows


# -- the driver ---------------------------------------------------------------


class Driver(pipeline.Driver):
    def set_up(self) -> None:
        setup = self.record.setup
        config, mix = self.config, self.mix
        t_imports = time.monotonic()
        setup["import_and_device_s"] = t_imports - self.t_process0
        compile_cache.enable()
        reset_flight_recorder(dump_dir=os.path.join(self.out_dir, "flight"))
        self.compiles = CompileCounter().install()
        self._registry = get_registry()
        self._tracer = get_tracer()
        self._device = jax.devices()[0]

        # the two areas, and every publication of this run, from the seed
        self.areas = list(config["areas"])
        self.area = self.areas[0]
        borders = config["borders"]
        self.vantage = name = config["vantage"]
        (self.peer,) = [b for b in borders if b != name]
        topos = build(config)
        if any(name not in t.adj_dbs for t in topos.values()):
            raise ValueError(f"vantage {name!r} is not in every area")
        gen = TwoAreaTraffic(topos, self.seed, mix, name, borders)
        self._peer_keys = peer_reoriginations(topos, self.peer, borders)
        initial = gen.initial_key_vals()
        for area, dbs in self._peer_keys.items():
            for key, db in dbs.items():
                initial[area][key] = traffic._value(
                    1, self.peer, wire.dumps(db))
        self._owed0 = reference_multiarea.reoriginations(
            gen.lsdb(self._peer_keys), name, self.areas)
        warm = [gen.burst(b) for b in mix.get("warmup", [])]
        self.offsets = traffic.due_offsets(mix, self.seconds)
        self.events = [gen.draw() for _ in self.offsets]
        probe_kind = mix.get("trace_probe")
        self.probe = (
            gen.event(probe_kind) if self.trace and probe_kind else None
        )
        self.generator = gen  # its databases are now the final LSDB
        self._initial = initial
        self._journal = [ev for burst in warm for ev in burst]
        self._journal += self.events + ([self.probe] if self.probe else [])
        t_traffic = time.monotonic()
        setup["topology_and_schedule_s"] = t_traffic - t_imports

        # the node, as daemon.py builds a border
        router = config["router"]
        store = KvStore(node_id=name, areas=self.areas)
        self.kvstore = AreaStore(store, f"{keyutil.PREFIX_DB_MARKER}{name}:")
        self._pubs = store.updates_queue.get_reader("chipbench")
        self._own_pubs = store.updates_queue.get_reader("chipbench-owed")
        self.route_updates = ReplicateQueue(name=f"{name}:routeUpdates")
        self.client_evb = OpenrEventBase(name=f"kvclient:{name}")
        self.kvstore_client = KvStoreClient(
            self.client_evb, name, self.kvstore
        )
        self.decision = Decision(
            name,
            kvstore_updates_queue=store.updates_queue,
            route_updates_queue=self.route_updates,
            static_routes_queue=ReplicateQueue(name=f"{name}:staticRoutes"),
            debounce_min_s=router["debounce_min_ms"] / 1e3,
            debounce_max_s=router["debounce_max_ms"] / 1e3,
            solver_backend=router["solver_backend"],
        )
        self.agent = pipeline.TableFibAgent()
        self.retired = RetiredWhenOwedIsPaid(f"{name}:fibUpdates", self.agent)
        self.fib = Fib(
            name,
            self.agent,
            self.route_updates,
            fib_updates_queue=self.retired,
            kvstore_client=self.kvstore_client,
            area=self.area,
        )
        self.prefix_manager = PrefixManager(
            name,
            self.kvstore_client,
            decision_route_updates_queue=self.route_updates,
            areas=self.areas,
        )
        self._tracer.add_finish_listener(self._on_finish)
        store.start()
        self.client_evb.run_in_thread()
        self.decision.start()
        self.fib.start()
        self.prefix_manager.start()
        self._started = True

        # bulk load of both areas, to the first RouteDatabase in Fib and
        # the vantage's first re-originations in KvStore
        t_load = time.monotonic()
        for area in self.areas:
            store.set_key_vals(
                area, KeySetParams(key_vals=dict(initial[area]))
            )
        self._wait(lambda: len(self.agent.unicast) > 0, 900.0,
                   "the first routes never reached Fib")
        self._wait(
            lambda: self.kvstore.count_live() >= len(self._owed0)
            and self._converged(0), 900.0,
            f"the vantage's first {len(self._owed0)} re-originations "
            "never settled in KvStore",
        )
        setup["cold_build_s"] = time.monotonic() - t_load

        # warm-up: each burst converges before the next is published
        for burst in warm:
            target = self._progress()[0] + len(burst)
            for ev in burst:
                self._publish(ev)
            self._wait(lambda: self._converged(target), 600.0,
                       f"a warm-up burst of {len(burst)} never converged")
        self._quiesce(0.3)
        for reader in (self._pubs, self._own_pubs):
            while reader.try_get() is not None:
                pass
        with self._traces_lock:
            self._traces.clear()
        self._rows0 = self.retired.count()
        self._accepted0 = len(self.kvstore.accepted_since(0))
        self._carried0 = self._progress()[0]
        count, seconds = self.compiles.read()
        setup["compiles"] = count
        setup["compile_s"] = seconds
        setup["warmup_s"] = time.monotonic() - t_load - setup["cold_build_s"]
        # of the graph a solve reads: ONE area (solve_roofline
        # multiplies them), never the union
        one = topos[self.area]
        self.record.shapes = {
            "nodes": len(one.adj_dbs),
            "links": one.links(),
            "vantage_degree": len(one.adj_dbs[name].adjacencies),
            "areas": len(topos),
        }
        self.retired.paid_at = self._paid_at
        self._paid: Optional[Dict[int, float]] = None
        # pre-build what the send loop hands to KvStore
        self._key_sets = [self._key_set(ev) for ev in self.events]
        setup["setup_s"] = time.monotonic() - self.t_process0

    # -- publications, counted where they are made ------------------------

    def _key_set(self, ev: AreaEvent) -> AreaKeySet:
        return AreaKeySet(ev, self._owes(ev))

    def _owes(self, ev: AreaEvent) -> Optional[Tuple[Tuple[str, str], bool]]:
        """The key a ``prefix`` event makes the vantage owe the other
        area, and whether the event leaves it live."""
        if ev.kind != "prefix":
            return None
        gen = self.generator.gens[ev.area]
        extra = traffic.extra_prefix(gen._node_idx[ev.value.originator_id])
        db = wire.loads(ev.value.value, PrefixDatabase)
        live = any(e.prefix == extra for e in db.prefix_entries)
        (other,) = [a for a in self.areas if a != ev.area]
        return (other, keyutil.per_prefix_key(self.vantage, other, extra)), live

    def _publish(self, ev: AreaEvent) -> None:
        self.kvstore.set_key_vals(ev.area, self._key_set(ev))

    def _progress(self) -> Tuple[int, int]:
        """Generator publications carried, and rebuilds begun whose
        update is not yet retired. Asked of the modules themselves:
        every publication sent is carried when every rebuild begun is
        retired, KvStore holds what the vantage owes, and Decision, on
        its own thread, has read every publication and holds none
        pending. While the node works, one at least is not carried."""
        decision = self.decision
        runs = decision.counters["decision.route_build_runs"]
        in_flight = runs - self.retired.count()
        idle = (
            in_flight <= 0
            and self.kvstore.paid()
            and decision.evb.call_and_wait(
                lambda: decision._kv_reader.size() == 0
                and not decision.pending.needs_route_update()
            )
            and runs == decision.counters["decision.route_build_runs"]
        )
        return self.kvstore.sent - (0 if idle else 1), in_flight

    # -- the clock's second end -------------------------------------------

    def _paid_at(self, trace_id) -> Optional[float]:
        """When the re-origination the window's oldest event makes the
        vantage owe was accepted by the other area's KvStore. The k-th
        event of the window on a key is paid by the k-th acceptance of
        that key in the window; where the two counts differ (two events
        on one prefix met in one rebuild window and left nothing to
        re-originate) the key's events are timed to Fib alone."""
        if self._paid is None:
            trace_of = {}
            while True:
                pub = self._own_pubs.try_get()
                if pub is None:
                    break
                for key, value in pub.key_vals.items():
                    trace_of[(pub.area, key, value.version)] = getattr(
                        pub.trace, "trace_id", None)
            accepted: Dict[Tuple[str, str], List[float]] = {}
            for at, area, key in self.kvstore.accepted_since(
                self._accepted0
            ):
                accepted.setdefault((area, key), []).append(at)
            events: Dict[Tuple[str, str], List[Optional[int]]] = {}
            for ev, key_set in zip(self.events, self._key_sets):
                trace = trace_of.get((ev.area, ev.key, ev.value.version))
                if key_set.owes is not None and trace is not None:
                    events.setdefault(key_set.owes[0], []).append(trace)
            self._paid, unmatched = {}, 0
            for key, traces in events.items():
                times = accepted.get(key, [])
                if len(times) == len(traces):
                    self._paid.update(zip(traces, times))
                else:
                    unmatched += len(traces)
            self.record.counters["chipbench.owed_unmatched"] = unmatched
        return self._paid.get(trace_id)

    # -- correctness ------------------------------------------------------

    def _verify(self) -> None:
        rec, gen = self.record, self.generator
        lsdb = gen.lsdb(self._peer_keys)
        want = reference_multiarea.routes(lsdb, self.vantage)
        plain, pipeline.reference = pipeline.reference, SimpleNamespace(
            routes=lambda adj_dbs, prefix_dbs, vantage: want,
            routes_of=reference_multiarea.routes_of,
            relax_passes=lambda adj_dbs, batch: gen.relax_passes(
                self.vantage),
        )
        try:
            super()._verify()
        finally:
            pipeline.reference = plain
        agent = SimpleNamespace(
            unicast_routes=self.agent.get_route_table_by_client(0))
        if reference_multiarea.routes_of(agent) != want:
            rec.problems.append(
                "the agent's unicast table differs from the two-area "
                "reference over the final LSDB")
            rec.failed = rec.attempted
        owed = reference_multiarea.reoriginations(
            lsdb, self.vantage, self.areas)
        held = reference_multiarea.owed_of(live_entries(
            self.kvstore, self.areas, self.kvstore.own_prefix))
        if held != owed:
            missing = sorted(str(k) for k in set(owed) - set(held))
            stale = sorted(str(k) for k in set(held) - set(owed))
            other = sorted(
                str(k) for k in set(held) & set(owed) if held[k] != owed[k])
            rec.problems.append(
                "the vantage's live re-originated keys in KvStore differ "
                f"from what the reference derives: {len(missing)} missing "
                f"{missing[:3]}, {len(stale)} it does not owe {stale[:3]}, "
                f"{len(other)} with another type, distance or stack "
                f"{other[:3]}")
            rec.failed = rec.attempted
        rec.shapes["reoriginated_keys"] = len(owed)

    def _host_replay(self):
        """The whole journal of both areas, unshedded and
        single-threaded, through a fresh Decision on the host backend,
        then one full rebuild. It has no PrefixManager: the vantage's
        own re-originations come back to a Decision only to be dropped
        as its own reflection."""
        kv_q = ReplicateQueue(name="replay:kvstore")
        replay = Decision(
            self.vantage,
            kvstore_updates_queue=kv_q,
            route_updates_queue=ReplicateQueue(name="replay:routes"),
            solver_backend="host",
        )
        try:
            for area in self.areas:
                replay.process_publication(Publication(
                    key_vals=dict(self._initial[area]), area=area))
            for ev in self._journal:
                replay.process_publication(Publication(
                    key_vals={ev.key: ev.value}, area=ev.area))
            replay.pending.set_needs_full_rebuild()
            replay.rebuild_routes("REPLAY")
            return replay.route_db.to_route_db(self.vantage)
        finally:
            kv_q.close()

    def close(self) -> None:
        if getattr(self, "_started", False):
            self.prefix_manager.stop()
        super().close()
