"""Served path ``pipeline``: one router node, KvStore -> Decision -> Fib.

Wired as ``openr_tpu/daemon.py`` wires a node: a ``ReplicateQueue``
between per-module event bases, ``Decision`` with the options the daemon
passes (no admission object, eager emit), ``Fib`` with its
``KvStoreClient`` so each programmed update advertises its fib time
back through the store, as a deployed node does. The platform's
``FibService`` — the switch agent, which is not part of openr — is the
benchmark's own in-memory table. The rest of the fabric exists as the
LSDB it would flood: this process publishes it.

What is timed, and on whose clock: one sample per rebuild window, for
the oldest publication the window adopted (upstream's
``fib.convergence_time_ms`` rule). It starts when that publication was
DUE on the open-loop schedule and ends when the last programming call
for the window's update returned inside the benchmark's own agent, or,
where the delta was empty, when ``Fib`` handed the update to the
benchmark's ``fibUpdates`` queue; both ends are read off
``time.monotonic`` here. The program's trace only joins the two: the
publication object carries its trace id out of ``KvStore`` (a reader of
the benchmark's on the updates queue, emptied after the drain) and the
update carries the same id into ``Fib``. The program's tracer is asked
for spans (the per-layer metrics), never for a time.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
from openr_tpu.decision.decision import Decision
from openr_tpu.fib.fib import Fib
from openr_tpu.kvstore.client import KvStoreClient
from openr_tpu.kvstore.store import KvStore
from openr_tpu.messaging.queue import ReplicateQueue
from openr_tpu.telemetry import (
    get_registry,
    get_tracer,
    reset_flight_recorder,
)
from openr_tpu.types import KeySetParams, Publication
from openr_tpu.utils import compile_cache, wire
from openr_tpu.utils.eventbase import OpenrEventBase

from chipbench import openloop, reference, topology, traffic
from chipbench.compiles import CompileCounter
from chipbench.profiling import TracedTail
from chipbench.record import RunRecord, Span

# any of these above zero over the window means a rebuild ended on a
# path that hides the device (chip_smoke.py's list)
FALLBACK_COUNTERS = (
    "decision.backend_switches",
    "decision.fallbacks",
    "decision.degradations",
    "decision.device_state_resets",
    "decision.spf_host_fallback",
    "decision.ksp2_host_fallbacks",
    "route_engine.fallbacks",
    "ops.aot_fallbacks",
    "ops.autotune_disqualified",
    "serve.errors",
)
TRACE_TAIL_S = 5.0


class TableFibAgent:
    """The switch agent: an in-memory route table (``FibService``).

    Each programming call notes when it returned, on the benchmark's
    clock: a sample ends there."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.unicast: Dict[object, object] = {}
        self.mpls: Dict[int, object] = {}
        self._alive_since = int(time.time())
        self._returned_at: Optional[float] = None

    def add_unicast_routes(self, client_id, routes) -> None:
        with self._lock:
            for r in routes:
                self.unicast[r.dest] = r
            self._returned_at = time.monotonic()

    def delete_unicast_routes(self, client_id, prefixes) -> None:
        with self._lock:
            for p in prefixes:
                self.unicast.pop(p, None)
            self._returned_at = time.monotonic()

    def add_mpls_routes(self, client_id, routes) -> None:
        with self._lock:
            for r in routes:
                self.mpls[r.top_label] = r
            self._returned_at = time.monotonic()

    def delete_mpls_routes(self, client_id, labels) -> None:
        with self._lock:
            for label in labels:
                self.mpls.pop(label, None)
            self._returned_at = time.monotonic()

    def sync_fib(self, client_id, routes) -> None:
        with self._lock:
            self.unicast = {r.dest: r for r in routes}
            self._returned_at = time.monotonic()

    def sync_mpls_fib(self, client_id, routes) -> None:
        with self._lock:
            self.mpls = {r.top_label: r for r in routes}
            self._returned_at = time.monotonic()

    def take_returned_at(self) -> Optional[float]:
        """When the last programming call since the previous take
        returned; ``None`` if there was none."""
        with self._lock:
            at, self._returned_at = self._returned_at, None
            return at

    def get_route_table_by_client(self, client_id):
        with self._lock:
            return list(self.unicast.values())

    def get_mpls_route_table_by_client(self, client_id):
        with self._lock:
            return list(self.mpls.values())

    def alive_since(self) -> int:
        return self._alive_since


# trace id, when retired (``time.monotonic``), whether programmed
Row = Tuple[Optional[int], float, bool]


class RetiredUpdates(ReplicateQueue):
    """``Fib``'s ``fibUpdates`` queue, and where the benchmark's clock
    stops. ``Fib`` pushes every update here, on its own thread, after
    the programming calls for it have returned, or straight away when
    the delta was empty. One row per rebuild window, in order: the
    trace id the update carries, when it was retired, and whether it
    was programmed (an update with routes that reached no programming
    call was not)."""

    def __init__(self, name: str, agent: TableFibAgent) -> None:
        super().__init__(name=name)
        self._agent = agent
        self._rows_lock = threading.Lock()
        self._rows: List[Row] = []

    def push(self, update) -> bool:
        now = time.monotonic()
        programmed_at = self._agent.take_returned_at()
        trace = getattr(update, "trace", None)
        row = (
            getattr(trace, "trace_id", None),
            now if programmed_at is None else programmed_at,
            programmed_at is not None or update.empty(),
        )
        with self._rows_lock:
            self._rows.append(row)
        return super().push(update)

    def count(self) -> int:
        with self._rows_lock:
            return len(self._rows)

    def rows_since(self, count: int) -> List[Row]:
        with self._rows_lock:
            return self._rows[count:]


def _key_set(ev: traffic.Event) -> KeySetParams:
    return KeySetParams(
        key_vals={ev.key: ev.value}, originator_id=ev.value.originator_id
    )


class Driver:
    """One run of one cell through the pipeline."""

    def __init__(self, out_dir: str, config: dict, mix: dict, seed: int,
                 seconds: float, trace: bool, t_process0: float):
        self.out_dir = out_dir
        self.config = config
        self.mix = mix
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.t_process0 = t_process0
        self.record = RunRecord()
        self.area = "0"
        # the program's traces as it finishes them, for their spans
        self._traces: Dict[int, object] = {}
        self._traces_lock = threading.Lock()
        # where the counted part begins: rows of the retired-updates
        # queue, and publications carried by Decision's rebuilds
        self._rows0 = 0
        self._carried0 = 0

    # -- set-up -----------------------------------------------------------

    def set_up(self) -> None:
        setup = self.record.setup
        t_imports = time.monotonic()
        setup["import_and_device_s"] = t_imports - self.t_process0
        # the cache where the program ships it: JAX_COMPILATION_CACHE_DIR
        # when set, else <checkout>/.jax_cache
        compile_cache.enable()
        # the program's post-mortem dumps default to a fixed /tmp path
        reset_flight_recorder(dump_dir=os.path.join(self.out_dir, "flight"))
        self.compiles = CompileCounter().install()
        self._registry = get_registry()
        self._tracer = get_tracer()
        self._device = jax.devices()[0]

        # the network, and every publication of this run, from the seed
        topo = topology.build(
            self.config["topology"], self.config["forwarding"], self.area
        )
        self.vantage = self.config["vantage"]
        if self.vantage not in topo.adj_dbs:
            raise ValueError(f"vantage {self.vantage!r} is not a node")
        gen = traffic.Generator(topo, self.seed, self.mix, self.vantage)
        initial = gen.initial_key_vals()
        warm = [gen.burst(b) for b in self.mix.get("warmup", [])]
        self.offsets = traffic.due_offsets(self.mix, self.seconds)
        self.events = [gen.draw() for _ in self.offsets]
        # a mix that bypasses the device names one event (``trace_probe``)
        # that a traced run publishes after the counted window, inside
        # the profiler's session, so that its trace shows the device
        # path alive; it is drawn last, so the window's events are the
        # same with and without it
        probe_kind = self.mix.get("trace_probe")
        self.probe = (
            gen.event(probe_kind) if self.trace and probe_kind else None
        )
        self.generator = gen  # its databases are now the final LSDB
        self._initial = initial
        self._journal = [ev for burst in warm for ev in burst]
        self._journal += self.events + ([self.probe] if self.probe else [])
        t_traffic = time.monotonic()
        setup["topology_and_schedule_s"] = t_traffic - t_imports

        # the node, as daemon.py builds it
        router = self.config["router"]
        name = self.vantage
        self.kvstore = KvStore(node_id=name, areas=[self.area])
        self._pubs = self.kvstore.updates_queue.get_reader("chipbench")
        self.route_updates = ReplicateQueue(name=f"{name}:routeUpdates")
        self.client_evb = OpenrEventBase(name=f"kvclient:{name}")
        self.kvstore_client = KvStoreClient(
            self.client_evb, name, self.kvstore
        )
        self.decision = Decision(
            name,
            kvstore_updates_queue=self.kvstore.updates_queue,
            route_updates_queue=self.route_updates,
            static_routes_queue=ReplicateQueue(name=f"{name}:staticRoutes"),
            debounce_min_s=router["debounce_min_ms"] / 1e3,
            debounce_max_s=router["debounce_max_ms"] / 1e3,
            solver_backend=router["solver_backend"],
        )
        self.agent = TableFibAgent()
        self.retired = RetiredUpdates(f"{name}:fibUpdates", self.agent)
        self.fib = Fib(
            name,
            self.agent,
            self.route_updates,
            fib_updates_queue=self.retired,
            kvstore_client=self.kvstore_client,
            area=self.area,
        )
        self._tracer.add_finish_listener(self._on_finish)
        self.kvstore.start()
        self.client_evb.run_in_thread()
        self.decision.start()
        self.fib.start()
        self._started = True

        # bulk load to the first RouteDatabase in Fib
        t_load = time.monotonic()
        self.kvstore.set_key_vals(
            self.area, KeySetParams(key_vals=dict(initial))
        )
        self._wait(lambda: len(self.agent.unicast) > 0, 900.0,
                   "the first routes never reached Fib")
        setup["cold_build_s"] = time.monotonic() - t_load

        # warm-up: each burst converges before the next is published
        for burst in warm:
            target = self._progress()[0] + len(burst)
            for ev in burst:
                self._publish(ev)
            self._wait(lambda: self._converged(target), 600.0,
                       f"a warm-up burst of {len(burst)} never converged")
        self._quiesce(0.3)
        while self._pubs.try_get() is not None:
            pass
        with self._traces_lock:
            self._traces.clear()
        self._rows0 = self.retired.count()
        self._carried0 = self._progress()[0]
        count, seconds = self.compiles.read()
        setup["compiles"] = count
        setup["compile_s"] = seconds
        setup["warmup_s"] = time.monotonic() - t_load - setup["cold_build_s"]
        self.record.shapes = {
            "nodes": len(topo.adj_dbs),
            "links": topo.links(),
            "vantage_degree": len(topo.adj_dbs[self.vantage].adjacencies),
        }
        # pre-build what the send loop hands to KvStore
        self._key_sets = [_key_set(ev) for ev in self.events]
        setup["setup_s"] = time.monotonic() - self.t_process0

    # -- the window -------------------------------------------------------

    def measure(self) -> RunRecord:
        rec = self.record
        counters0 = self._counters()
        compiles0, _ = self.compiles.read()
        tail: Optional[TracedTail] = None
        if self.trace:
            now = time.monotonic() + 0.05
            tail_s = min(TRACE_TAIL_S, self.seconds / 2.0)
            tail = TracedTail(
                os.path.join(self.out_dir, "trace"),
                start_at=now + self.seconds - tail_s,
                steady_until=now + self.seconds,
                probe=self.probe is not None,
            )
        due, late = openloop.run(
            self.offsets,
            lambda i: self.kvstore.set_key_vals(self.area, self._key_sets[i]),
            self.seconds,
        )
        published = len(due)
        target = self._carried0 + published
        deadline = time.monotonic() + float(self.mix["drain_deadline_s"])
        while time.monotonic() < deadline and not self._converged(target):
            time.sleep(0.005)
        self._quiesce(0.5)
        carried, in_flight = self._progress()
        # an unretired rebuild holds at least one publication
        pending = max(0, target - carried) + max(0, in_flight)
        counters1 = self._counters()
        compiles1, _ = self.compiles.read()
        rec.counters = {
            k: counters1[k] - counters0.get(k, 0)
            for k in counters1
            if isinstance(counters1[k], (int, float))
        }
        rec.counters["chipbench.published"] = published
        rec.counters["chipbench.window_compiles"] = compiles1 - compiles0
        self._compiled_in_window = self.compiles.names_since(compiles0)[
            : compiles1 - compiles0
        ]
        rec.attempted = len(self.offsets)
        rec.failed = (len(self.offsets) - published) + pending
        rec.lateness_ms = [x * 1e3 for x in late]
        if pending:
            rec.problems.append(
                f"{pending} published events were still queued or pending "
                "at the drain's deadline"
            )
        if published < len(self.offsets):
            rec.problems.append(
                f"{len(self.offsets) - published} events were due and "
                "never published"
            )

        # join: publication -> trace id (out of KvStore) -> retired update
        opened_by: Dict[int, int] = {}
        index = {(ev.key, ev.value.version): i
                 for i, ev in enumerate(self.events[:published])}
        while True:
            pub = self._pubs.try_get()
            if pub is None:
                break
            for key, value in pub.key_vals.items():
                i = index.get((key, value.version))
                if i is not None and pub.trace is not None:
                    opened_by[pub.trace.trace_id] = i
        with self._traces_lock:
            traces = dict(self._traces)
        unprogrammed, newest = 0, -1
        for trace_id, t_end, programmed in self.retired.rows_since(
            self._rows0
        ):
            i = opened_by.get(trace_id)
            if i is None:
                continue
            if i <= newest:
                rec.problems.append(
                    f"the window opened by event {i} retired after the "
                    f"one opened by event {newest}"
                )
            newest = i
            if not programmed:
                unprogrammed += 1
                continue
            rec.samples_ms.append((t_end - due[i]) * 1e3)
            trace = traces.get(trace_id)
            for s in trace.spans if trace is not None else ():
                rec.spans.append(Span(
                    trace_id, s.name, s.ts_ms, s.dur_ms or 0.0, dict(s.attrs)
                ))
        if unprogrammed:
            rec.problems.append(
                f"{unprogrammed} updates with routes were retired without "
                "a programming call; they give no sample"
            )

        if tail is not None:
            if self.probe is not None:
                self._publish(self.probe)
                self._wait(lambda: self._converged(target + 1), 120.0,
                           "the closing probe never converged")
                self._quiesce(0.3)
            rec.device = tail.finish()
            rec.steady_wall_s = tail.steady_wall_s
        stats = self._device.memory_stats() or {}
        rec.memory_peak_bytes = int(stats.get("peak_bytes_in_use", 0))
        self._verify()
        return rec

    # -- correctness ------------------------------------------------------

    def _verify(self) -> None:
        rec = self.record
        c = rec.counters
        if c["chipbench.window_compiles"]:
            rec.problems.append(
                f"{c['chipbench.window_compiles']:g} backend compiles "
                f"inside the window: {sorted(self._compiled_in_window)}"
            )
        for name in FALLBACK_COUNTERS:
            if c.get(name, 0):
                rec.problems.append(f"fallback counter {name} = {c[name]:g}")
        for name in self.config["solve_counters"]:
            moved = c.get(name, 0)
            if self.mix["reaches_solver"] and not moved:
                rec.problems.append(f"mechanism counter {name} never moved")
            if not self.mix["reaches_solver"] and moved:
                rec.problems.append(
                    f"{name} moved by {moved:g} in a window whose traffic "
                    "bypasses the solver"
                )

        if rec.device is not None:
            busy = rec.device.busy_s(rec.device.steady)
            if self.mix["reaches_solver"] and not busy:
                rec.problems.append(
                    "no operation ran on the device in the traced window"
                )
            if not self.mix["reaches_solver"] and busy:
                rec.problems.append(
                    f"the device ran for {busy:g} s in a traced window "
                    "whose traffic bypasses the solver"
                )
            if self.probe is not None and not rec.device.busy_s() > busy:
                rec.problems.append(
                    "the closing probe ran nothing on the device"
                )

        live = self.decision.evb.call_and_wait(
            lambda: self.decision.route_db.to_route_db(self.vantage)
        )
        gen = self.generator
        want = reference.routes(gen.adj_dbs, gen.prefix_dbs, self.vantage)
        wrong = []
        if reference.routes_of(live) != want:
            wrong.append("the per-source Dijkstra over the final LSDB")
        programmed = reference.routes_of(self.fib.get_route_db())
        if programmed != want:
            wrong.append("what Fib holds as programmed")
        in_agent = len(self.agent.get_route_table_by_client(0))
        if in_agent != len(want):
            wrong.append(f"the agent's table size ({in_agent})")
        if wire.dumps(live) != wire.dumps(self._host_replay()):
            wrong.append("the host-backend replay of the journal")
        if wrong:
            rec.problems.append(
                "RouteDatabase differs from " + "; ".join(wrong)
            )
            rec.failed = rec.attempted
        rec.shapes["routes"] = len(want)
        if self.trace:
            batch = [self.vantage] + sorted(
                a.other_node_name
                for a in gen.adj_dbs[self.vantage].adjacencies
            )
            rec.shapes["relax_passes"] = reference.relax_passes(
                gen.adj_dbs, batch
            )

    def _host_replay(self):
        """The whole journal, unshedded and single-threaded, through a
        fresh Decision on the host backend (per-source Dijkstra in
        ``openr_tpu``, no device code), then one full rebuild."""
        kv_q = ReplicateQueue(name="replay:kvstore")
        replay = Decision(
            self.vantage,
            kvstore_updates_queue=kv_q,
            route_updates_queue=ReplicateQueue(name="replay:routes"),
            solver_backend="host",
        )
        try:
            replay.process_publication(
                Publication(key_vals=dict(self._initial), area=self.area)
            )
            for ev in self._journal:
                replay.process_publication(
                    Publication(key_vals={ev.key: ev.value}, area=self.area)
                )
            replay.pending.set_needs_full_rebuild()
            replay.rebuild_routes("REPLAY")
            return replay.route_db.to_route_db(self.vantage)
        finally:
            kv_q.close()

    # -- plumbing ---------------------------------------------------------

    def _on_finish(self, trace, ok: bool) -> None:
        """The program's tracer, with a finished trace: kept for its
        spans, which the per-layer metrics read."""
        with self._traces_lock:
            self._traces[trace.trace_id] = trace

    def _progress(self) -> Tuple[int, int]:
        """Off the program's counters: publications carried by the
        rebuilds Decision has begun (one per rebuild plus those it
        coalesced), and rebuilds begun whose update is not yet retired.
        The benchmark's own readers cannot tell what the newest window
        carried beyond the publication that opened it."""
        runs = self.decision.counters["decision.route_build_runs"]
        carried = runs + self._registry.counter_get(
            "decision.coalesced_publications"
        )
        return carried, runs - self.retired.count()

    def _converged(self, target: int) -> bool:
        """Every publication up to ``target`` is carried by a rebuild
        and every rebuild begun has been retired by Fib."""
        carried, in_flight = self._progress()
        return carried >= target and in_flight <= 0

    def _publish(self, ev: traffic.Event) -> None:
        self.kvstore.set_key_vals(self.area, _key_set(ev))

    def _counters(self) -> Dict[str, float]:
        out = dict(self._registry.snapshot())
        out.update(self.decision.get_counters())
        return out

    def _quiesce(self, quiet_s: float) -> None:
        """Wait until nothing has been retired for ``quiet_s``."""
        last, since = None, time.monotonic()
        limit = since + 30.0
        while time.monotonic() < limit:
            time.sleep(0.01)
            with self._traces_lock:
                now = (self.retired.count(), len(self._traces))
            if now != last:
                last, since = now, time.monotonic()
            elif time.monotonic() - since >= quiet_s:
                return

    @staticmethod
    def _wait(pred, timeout_s: float, what: str) -> None:
        deadline = time.monotonic() + timeout_s
        while not pred():
            if time.monotonic() > deadline:
                raise TimeoutError(what)
            time.sleep(0.002)

    def close(self) -> None:
        if not getattr(self, "_started", False):
            return
        self._tracer.remove_finish_listener(self._on_finish)
        self.fib.stop()
        self.decision.stop()
        self.kvstore_client.stop()
        self.client_evb.stop()
        self.client_evb.join()
        self.kvstore.stop()
        self._started = False
