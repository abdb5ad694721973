"""Decision module: LSDB stream -> debounced route computation -> deltas.

Behavioral parity with the reference ``openr/decision/Decision.{h,cpp}``:

- subscribes to the KvStore publication queue; dispatches ``adj:`` /
  ``prefix:`` / ``fibtime:`` keys (processPublication, Decision.cpp:1722)
- maintains one LinkState per area plus the global PrefixState; per-prefix
  keys merge into a per-node synthetic PrefixDatabase
  (updateNodePrefixDatabase, Decision.cpp:1668)
- batches churn behind an AsyncDebounce (10..250 ms by default, matching
  common/Flags.cpp:87-96) and tracks whether the batch needs a *full*
  rebuild (any topology/node-label change, or local link-attribute
  change) or an *incremental* per-prefix pass
  (DecisionPendingUpdates, Decision.h:130; rebuildRoutes, Decision.cpp:1860)
- publishes DecisionRouteUpdate deltas on the route-updates queue with the
  batch's oldest perf-event chain attached
- cold-start hold gates the first route publication (Decision.cpp:1403)
- ordered-FIB hold decrement timer (Decision.cpp:1930 decrementOrderedFibHolds)

The solver behind it runs the TPU kernels (see spf_solver.py).
"""

from __future__ import annotations

import base64
import threading
import time
from typing import Callable, Dict, Optional, Set, Tuple

from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.rib import DecisionRouteDb, DecisionRouteUpdate
from openr_tpu.decision.spf_solver import (
    SPF_COUNTERS,
    SpfSolver,
    get_spf_counters,
)
from openr_tpu.graph.linkstate import LinkState, LinkStateChange
from openr_tpu.messaging.queue import ReplicateQueue
from openr_tpu.types import (
    AdjacencyDatabase,
    IpPrefix,
    PerfEvents,
    Publication,
    PrefixDatabase,
    PrefixEntry,
)
from openr_tpu.analysis.annotations import fault_boundary, solve_window
from openr_tpu.faults.supervisor import DegradationSupervisor, HealthState
from openr_tpu.integrity import get_auditor, quarantine_active
from openr_tpu.load.admission import AdmissionControl
from openr_tpu.ops import dispatch_accounting as da
from openr_tpu.telemetry import (
    get_registry,
    get_tracer,
    install_default_triggers,
    install_gc_hook,
    settle_heap,
)
from openr_tpu.utils import keys as keyutil
from openr_tpu.utils import wire
from openr_tpu.utils.eventbase import (
    AsyncDebounce,
    FiredWindow,
    OpenrEventBase,
)


class DecisionPendingUpdates:
    """reference: openr/decision/Decision.h:130."""

    def __init__(self, my_node_name: str):
        self._my_node_name = my_node_name
        self.count = 0
        self.perf_events: Optional[PerfEvents] = None
        self._needs_full_rebuild = False
        self.updated_prefixes: Set[IpPrefix] = set()
        # telemetry trace for the debounce window. The FIRST adopted
        # trace wins (publications arrive in order, so first == oldest
        # — the same convergence-from-earliest rule as perf_events);
        # later traces in the window are counted as merged and dropped.
        self.trace = None
        self._debounce_span = None
        # the event loop's busy seconds when that span opened
        self._busy_at_open: Optional[float] = None

    def needs_full_rebuild(self) -> bool:
        return self._needs_full_rebuild

    def set_needs_full_rebuild(self) -> None:
        self._needs_full_rebuild = True

    def needs_route_update(self) -> bool:
        return self._needs_full_rebuild or bool(self.updated_prefixes)

    def apply_link_state_change(
        self,
        node_name: str,
        change: LinkStateChange,
        perf_events: Optional[PerfEvents] = None,
    ) -> None:
        self._needs_full_rebuild |= (
            change.topology_changed
            or change.node_label_changed
            # link attributes (nexthop addr / adj label) only matter for
            # our own links: they alter our programmed nexthops
            or (
                change.link_attributes_changed
                and node_name == self._my_node_name
            )
        )
        self._add_update(perf_events)

    def apply_prefix_state_change(
        self,
        changed: Set[IpPrefix],
        perf_events: Optional[PerfEvents] = None,
    ) -> None:
        self.updated_prefixes |= changed
        self._add_update(perf_events)

    def _add_update(self, perf_events: Optional[PerfEvents]) -> None:
        self.count += 1
        # keep the *oldest* event chain so convergence is measured from the
        # earliest update in the debounced batch
        if self.perf_events is None or (
            perf_events is not None
            and perf_events.events
            and self.perf_events.events
            and self.perf_events.events[0].unix_ts
            > perf_events.events[0].unix_ts
        ):
            self.perf_events = (
                PerfEvents(events=list(perf_events.events))
                if perf_events is not None
                else PerfEvents()
            )
            self.add_event("DECISION_RECEIVED")

    def add_event(self, descr: str) -> None:
        if self.perf_events is not None:
            self.perf_events.add(self._my_node_name, descr)

    def move_out_events(self) -> Optional[PerfEvents]:
        events = self.perf_events
        self.perf_events = None
        return events

    def adopt_trace(self, trace, evb: Optional[OpenrEventBase] = None) -> None:
        """``evb``: the loop this window waits on, for the account of
        the wait that ``move_out_trace`` closes the span with."""
        if trace is None:
            return
        if self.trace is None:
            self.trace = trace
            self._debounce_span = trace.begin_span("decision.debounce")
            self._busy_at_open = (
                evb.busy_seconds() if evb is not None else None
            )
        else:
            get_registry().counter_bump("telemetry.traces_merged")

    def move_out_trace(self, fired: Optional[FiredWindow] = None):
        """End the debounce span and hand the trace to the rebuild.

        ``fired`` (the debounce timer's terms, when this is its fire)
        makes the span say what it waited for, in ms: ``policy_ms``
        (deadline - first arm: what the policy asked the timer for),
        ``busy_ms`` (the loop's busy time from the span's start to the
        fire: the rest of the opening callback and every callback that
        ran inside the window), ``slack_ms`` (deadline - end of the last
        callback before the fire; negative = the work outlasted the
        wait) and ``timer_late_ms`` (fire - the later of the two: the
        timer's own lateness, the overrun taken out). The idle stretch
        itself, last callback's end -> fire, is the closed span
        ``decision.policy_idle`` inside it."""
        trace, span = self.trace, self._debounce_span
        busy_at_open = self._busy_at_open
        self.trace = None
        self._debounce_span = None
        self._busy_at_open = None
        if trace is not None and span is not None:
            attrs = {}
            if fired is not None and busy_at_open is not None:
                fire = fired.deadline + fired.late_s
                idle_from = fired.idle_since
                trace.closed_span(
                    "decision.policy_idle",
                    span.mark_at(idle_from),
                    (fire - idle_from) * 1e3,
                )
                attrs = {
                    "policy_ms": (fired.deadline - fired.armed_at) * 1e3,
                    "busy_ms": (fired.busy_s - busy_at_open) * 1e3,
                    "slack_ms": (fired.deadline - idle_from) * 1e3,
                    "timer_late_ms": (
                        fire - max(fired.deadline, idle_from)
                    ) * 1e3,
                }
            trace.end_span(span, merged_updates=self.count, **attrs)
            get_registry().observe(
                "decision.debounce_ms", span.dur_ms or 0.0
            )
        return trace

    def release_trace(self) -> None:
        """Reclaim an adopted trace that will never reach a rebuild
        (overload resets, teardown): the ``decision.debounce`` span MUST
        close on this path too, or sustained load leaks one open span
        per reset and the smoke gate's well-formedness check trips."""
        trace, span = self.trace, self._debounce_span
        self.trace = None
        self._debounce_span = None
        self._busy_at_open = None
        if trace is not None and span is not None:
            trace.end_span(span, aborted=True)
            get_registry().counter_bump("decision.debounce_spans_reclaimed")

    def reset(self) -> None:
        self.count = 0
        self.perf_events = None
        self._needs_full_rebuild = False
        self.updated_prefixes = set()
        self.release_trace()


# route_db is mutated on the event base only (_emit_update, inline at
# the end of rebuild_routes); other threads reach it through
# evb.call_and_wait. It carries no @thread_confined exemption: the
# shared-state rule infers the confinement and convicts a second writer.
class Decision:
    def __init__(
        self,
        my_node_name: str,
        kvstore_updates_queue: ReplicateQueue,
        route_updates_queue: ReplicateQueue,
        static_routes_queue: Optional[ReplicateQueue] = None,
        debounce_min_s: float = 0.010,
        debounce_max_s: float = 0.250,
        cold_start_s: float = 0.0,
        enable_v4: bool = False,
        compute_lfa_paths: bool = False,
        enable_ordered_fib: bool = False,
        bgp_dry_run: bool = False,
        enable_best_route_selection: bool = True,
        solver_backend: str = "device",
        enable_rib_policy: bool = True,
        admission: Optional[AdmissionControl] = None,
        state_plane=None,
    ):
        # crash-safe state plane (openr_tpu.state.StatePlane): engine
        # warm material is snapshotted after each debounced rebuild and
        # warm_boot() rehydrates from its recover() result
        self._state_plane = state_plane
        # incident replay plane: a Decision that owns a state plane IS
        # the durable production pipeline, so its adopted post-CRDT
        # publications feed the flight recorder's event journal and its
        # WAL position anchors every post-mortem bundle. Memory-only
        # Decisions (tests, oracles) stay out of the shared journal.
        self._flight_journal = state_plane is not None
        if self._flight_journal:
            from openr_tpu.telemetry.flight import get_flight_recorder

            get_flight_recorder().set_anchor_provider(
                state_plane.flight_anchor
            )
        self._enable_rib_policy = enable_rib_policy
        self.my_node_name = my_node_name
        self.evb = OpenrEventBase(name=f"decision:{my_node_name}")
        self.route_updates_queue = route_updates_queue
        self.spf_solver = SpfSolver(
            my_node_name,
            enable_v4=enable_v4,
            compute_lfa_paths=compute_lfa_paths,
            enable_ordered_fib=enable_ordered_fib,
            bgp_dry_run=bgp_dry_run,
            enable_best_route_selection=enable_best_route_selection,
            backend=solver_backend,
        )
        # degradation ladder for the rebuild path: warm device solve →
        # device-state reset + cold rebuild → non-device backend (see
        # _fallback_backend); for an already-host backend all rungs
        # run the same solve, which is harmless.
        self._primary_backend = solver_backend
        self.supervisor = DegradationSupervisor("decision")
        # standing anomaly set (p99 breach vs rolling baseline,
        # compile-after-warmup, reshard delta): always-on from the
        # moment a pipeline exists, idempotent across instances
        install_default_triggers()
        # full garbage collections are what reaches convergence's tail
        # on a large LSDB: counted process-wide, installed once
        install_gc_hook()
        # monotonic stamp of the last route db installed while the
        # ladder was fully warm and no engine sat in integrity
        # quarantine — the staleness gauge ages from it while degraded
        self._last_good_route_ts: Optional[float] = None
        # the stamp is written on the event base (_emit_update) and
        # read by the registry's gauge thread — a dedicated lock keeps
        # the pair race-free without dragging the gauge into the emit
        # path's wider critical sections
        self._emit_mu = threading.Lock()
        get_registry().gauge(
            "decision.route_staleness_ms", self._route_staleness_ms
        )
        self.area_link_states: Dict[str, LinkState] = {}
        self.prefix_state = PrefixState()
        self.route_db = DecisionRouteDb()
        # the handshake that lets a rebuild install what its build
        # touched instead of diffing the table (_emit_update):
        # (solver, seq) of the last build whose result route_db took IN
        # FULL, every key of it then holding the solver's own object.
        # None after anything else wrote route_db or nothing has; the
        # reason is kept for the next whole diff's span
        self._route_anchor: Optional[tuple] = None
        self._anchor_lost = ""
        self.pending = DecisionPendingUpdates(my_node_name)
        self.fib_times: Dict[str, float] = {}
        self.rib_policy = None  # set via set_rib_policy
        self._enable_ordered_fib = enable_ordered_fib
        # per-node view assembled from per-prefix keys
        # (reference: perPrefixPrefixEntries_ / fullDbPrefixEntries_)
        self._per_prefix_entries: Dict[
            Tuple[str, str], Dict[IpPrefix, PrefixEntry]
        ] = {}
        self._full_db_entries: Dict[
            Tuple[str, str], Dict[IpPrefix, PrefixEntry]
        ] = {}
        # (area, adj: key) -> the value last decoded for it (its bytes,
        # the database, where its adjacencies begin): a publication
        # re-encodes the node's whole database to change one adjacency,
        # so the next value of the key is decoded against this one and
        # keeps every adjacency whose bytes stand (wire.loads_reusing)
        self._adj_decoded: Dict[Tuple[str, str], wire.Decoded] = {}
        self.counters: Dict[str, int] = {
            "decision.adj_db_update": 0,
            "decision.adj_elements_decoded": 0,
            "decision.adj_elements_reused": 0,
            "decision.prefix_db_update": 0,
            "decision.route_build_runs": 0,
            "decision.publications": 0,
        }

        self._rebuild_debounced = AsyncDebounce(
            self.evb, debounce_min_s, debounce_max_s, self._on_debounce_fire
        )
        # speculation latch: at most ONE speculative view solve per
        # debounce window (set where it is staged, reset when the
        # rebuild fires)
        self._spec_fired_this_window = False
        # a KSP2 engine built cold inside a stage, outside the bracket
        # rebuild_routes keeps for settle_heap: the window's rebuild
        # settles for it
        self._staged_cold_build = False
        # area -> its graph's topology version at the last rebuild
        self._rebuilt_versions: Dict[str, int] = {}
        # admission/backpressure path (service plane): the controller
        # adapts the debounce ceiling to the reader backlog, and the
        # consume path sheds-by-coalescing once the backlog is deep
        self._admission = admission
        if self._admission is not None:
            self._admission.bind_debounce(
                self._rebuild_debounced, debounce_max_s
            )
        self._cold_start_until = (
            time.monotonic() + cold_start_s if cold_start_s > 0 else 0.0
        )
        if cold_start_s > 0:
            self.evb.schedule_timeout(cold_start_s, self._on_cold_start_done)

        self._kv_reader = kvstore_updates_queue.get_reader(
            f"decision:{my_node_name}"
        )
        self.evb.add_queue_reader(self._kv_reader, self._on_publication)
        if static_routes_queue is not None:
            self.evb.add_queue_reader(
                static_routes_queue.get_reader(f"decision:{my_node_name}"),
                self._on_static_routes,
            )
        self._ordered_fib_timer = None

    # -- lifecycle --------------------------------------------------------

    def start(self) -> None:
        self.evb.run_in_thread()

    def stop(self) -> None:
        self.evb.stop()
        self.evb.join()

    # -- queue handlers (run on the module thread) ------------------------

    def _on_publication(self, pub: Publication) -> None:
        if pub.trace is not None:
            # kvstore.publish -> here: the ReplicateQueue hop and this
            # thread's wake-up, closed on arrival
            pub.trace.gap_span("decision.queue_wait")
        if self._admission is not None:
            # admission path: observe backlog depth (adapting the
            # debounce ceiling) and, under a deep backlog, drain +
            # coalesce it into net-effect publications — superseded
            # per-key versions are shed, net state is untouched
            batch = self._admission.admit(pub, self._kv_reader)
            pubs, traces = batch.publications, batch.traces
            self.counters["decision.publications"] += batch.pubs_in
        else:
            pubs, traces = [pub], [pub.trace]
            self.counters["decision.publications"] += 1
        for p in pubs:
            self.process_publication(p)
        if self.pending.needs_route_update():
            # arrival order: the first (oldest) trace wins the window,
            # later ones are counted merged — same rule as perf_events
            for trace in traces:
                self.pending.adopt_trace(trace, self.evb)
        else:
            for trace in traces:
                if trace is not None:
                    # publication with no route impact (e.g. fibtime
                    # keys): the trace dies here, visibly
                    get_registry().counter_bump(
                        "telemetry.traces_no_route_impact"
                    )
        if self.pending.needs_route_update():
            # arm the window first (upstream's order: processPublication
            # -> rebuildRoutesDebounced_), then patch inside it: the
            # callback runs on this thread, so it cannot fire before
            # prewarm has returned, and the band scatter for this
            # publication's topology delta costs the window nothing as
            # long as it is shorter than the policy wait. By the time
            # the debounced rebuild dispatches its fused solve the
            # resident bands are already current.
            self._rebuild_debounced()
            overlap = self._admission is None or (
                self._admission.allow_prewarm(self._kv_reader.size())
            )
            if overlap:
                self.spf_solver.prewarm(
                    self.area_link_states, trace=self.pending.trace
                )
            # the first publication of a window that finds nothing
            # queued behind it (at one publication a window, every rate
            # under the knee: the one that opens it) also stages the
            # root's view solve under the same policy wait: the LSDB as
            # it stands now is what the rebuild will compute for, so the
            # rebuild lands on a solved view (ops.spec_hits). Once per
            # window (the latch: a storm cannot multiply device work),
            # only for a window that will solve a view at all (a
            # prefix-only one runs the per-prefix pass; a host backend
            # has nothing to overlap), and never with a publication
            # already queued: the composition is then known to be
            # stale. A publication that joins later moves the version:
            # the stage is discarded by version (ops.spec_cancels) and
            # the rebuild re-solves, bit-identical.
            if (
                not self._spec_fired_this_window
                and overlap
                and self.pending.needs_full_rebuild()
                and self.spf_solver.backend == "device"
                and self._kv_reader.size() == 0
            ):
                self._spec_fired_this_window = True
                self._speculate_views()

    def _speculate_views(self) -> None:
        """Stage the root's views (where a KSP2 engine serves the view:
        the engine's sync) for the window's rebuild, traced as the
        rebuild's own solve would be: ``decision.speculate`` inside
        ``decision.debounce``, with the window's trace active on this
        thread so that the view's sync / dispatch / readback spans (the
        engine's ``decision.ksp2_sync`` and its children) nest beneath
        it, and an event window of its own for the host-touch
        accounting."""
        trace = self.pending.trace
        tracer = get_tracer()
        if trace is not None:
            tracer.activate(trace)
        cold_builds = SPF_COUNTERS["decision.ksp2_cold_builds"]
        try:
            with tracer.span(
                "decision.speculate", trace=trace
            ) as span, da.event_window("decision.speculate"):
                staged = self.spf_solver.speculate_views(
                    self.my_node_name,
                    self.area_link_states,
                    self.prefix_state,
                )
                if span is not None:
                    span.attrs["staged"] = staged
        finally:
            if SPF_COUNTERS["decision.ksp2_cold_builds"] != cold_builds:
                self._staged_cold_build = True
            if trace is not None:
                tracer.deactivate()

    def _on_static_routes(self, delta) -> None:
        """Static MPLS routes pushed by the platform/plugin layer
        (reference: Decision static routes fiber)."""
        to_update = {
            r.top_label: list(r.next_hops)
            for r in getattr(delta, "mpls_routes_to_update", [])
        }
        to_delete = list(getattr(delta, "mpls_routes_to_delete", []))
        self.spf_solver.update_static_mpls_routes(to_update, to_delete)
        self.pending.set_needs_full_rebuild()
        self._rebuild_debounced()

    def process_publication(self, pub: Publication) -> None:
        """reference: Decision.cpp:1722 processPublication."""
        area = pub.area
        link_state = self.area_link_states.get(area)
        if link_state is None:
            link_state = self.area_link_states[area] = LinkState(area)

        for key, value in pub.key_vals.items():
            if value.value is None:
                continue  # ttl refresh only
            node_name = keyutil.get_node_name_from_key(key)
            try:
                if keyutil.is_adj_key(key):
                    slot = (area, key)
                    decoded = wire.loads_reusing(
                        value.value,
                        AdjacencyDatabase,
                        "adjacencies",
                        self._adj_decoded.get(slot),
                    )
                    adj_db = decoded.obj
                    assert adj_db.this_node_name == node_name
                    self._adj_decoded[slot] = decoded
                    self.counters["decision.adj_elements_reused"] += (
                        decoded.reused
                    )
                    self.counters["decision.adj_elements_decoded"] += (
                        decoded.decoded
                    )
                    if adj_db.area != area:
                        adj_db = AdjacencyDatabase(
                            this_node_name=adj_db.this_node_name,
                            is_overloaded=adj_db.is_overloaded,
                            adjacencies=adj_db.adjacencies,
                            node_label=adj_db.node_label,
                            area=area,
                            perf_events=adj_db.perf_events,
                        )
                    hold_up, hold_down = self._ordered_fib_holds(
                        link_state, node_name
                    )
                    self.counters["decision.adj_db_update"] += 1
                    self.pending.apply_link_state_change(
                        node_name,
                        link_state.update_adjacency_database(
                            adj_db, hold_up, hold_down
                        ),
                        adj_db.perf_events,
                    )
                    if self._flight_journal:
                        self._journal_adopted(area, key, value, pub)
                    if (
                        self._enable_ordered_fib
                        and link_state.has_holds()
                        and self._ordered_fib_timer is None
                    ):
                        self._schedule_ordered_fib_tick()
                elif keyutil.is_prefix_key(key):
                    prefix_db = wire.loads(value.value, PrefixDatabase)
                    assert prefix_db.this_node_name == node_name
                    node_db = self._update_node_prefix_db(
                        key, prefix_db, area
                    )
                    if node_db is None:
                        continue
                    self.counters["decision.prefix_db_update"] += 1
                    self.pending.apply_prefix_state_change(
                        self.prefix_state.update_prefix_database(node_db),
                        prefix_db.perf_events,
                    )
                    if self._flight_journal:
                        self._journal_adopted(area, key, value, pub)
                elif keyutil.is_fib_time_key(key):
                    try:
                        self.fib_times[node_name] = float(
                            value.value.decode()
                        )
                    except ValueError:
                        pass
            except Exception:  # noqa: BLE001 - bad LSDB values are skipped
                continue

        for key in pub.expired_keys:
            node_name = keyutil.get_node_name_from_key(key)
            if keyutil.is_adj_key(key):
                self._adj_decoded.pop((area, key), None)
                self.pending.apply_link_state_change(
                    node_name,
                    link_state.delete_adjacency_database(node_name),
                )
            elif keyutil.is_prefix_key(key):
                delete_db = PrefixDatabase(
                    this_node_name=node_name, delete_prefix=True, area=area
                )
                node_db = self._update_node_prefix_db(key, delete_db, area)
                if node_db is None:
                    continue
                self.pending.apply_prefix_state_change(
                    self.prefix_state.update_prefix_database(node_db)
                )

    def _journal_adopted(
        self, area: str, key: str, value, pub: Publication
    ) -> None:
        """Feed one adopted post-CRDT key into the flight recorder's
        event journal (the incident replay plane). The serialized value
        is the post-merge winner — replaying the journal over the
        bundle's anchor is exactly the state plane's recovery fold."""
        from openr_tpu.telemetry.flight import get_flight_recorder

        fr = get_flight_recorder()
        if not fr.enabled or value.value is None:
            return
        fr.journal_note(
            area,
            key,
            value_b64=base64.b64encode(value.value).decode("ascii"),
            version=value.version,
            originator=value.originator_id,
            trace_id=getattr(pub.trace, "trace_id", None),
        )

    def _update_node_prefix_db(
        self, key: str, prefix_db: PrefixDatabase, area: str
    ) -> Optional[PrefixDatabase]:
        """Merge a per-prefix or full-db advertisement into the node's
        synthetic PrefixDatabase (reference: Decision.cpp:1668
        updateNodePrefixDatabase)."""
        node = prefix_db.this_node_name
        slot = (node, area)
        parsed = keyutil.parse_per_prefix_key(key)
        if parsed is not None:
            _, _, prefix = parsed
            per = self._per_prefix_entries.setdefault(slot, {})
            if prefix_db.delete_prefix:
                per.pop(prefix, None)
            else:
                assert len(prefix_db.prefix_entries) == 1
                entry = prefix_db.prefix_entries[0]
                # ignore self-redistributed route reflection
                if (
                    node == self.my_node_name
                    and entry.area_stack
                    and entry.area_stack[-1] in self.area_link_states
                ):
                    return None
                per[prefix] = entry
        else:
            if prefix_db.delete_prefix:
                self._full_db_entries.pop(slot, None)
            else:
                self._full_db_entries[slot] = {
                    e.prefix: e for e in prefix_db.prefix_entries
                }

        per = self._per_prefix_entries.get(slot, {})
        full = self._full_db_entries.get(slot, {})
        entries = list(per.values()) + [
            e for p, e in full.items() if p not in per
        ]
        return PrefixDatabase(
            this_node_name=node,
            prefix_entries=tuple(entries),
            area=area,
            perf_events=prefix_db.perf_events,
        )

    # -- ordered fib holds ------------------------------------------------

    def _ordered_fib_holds(
        self, link_state: LinkState, node_name: str
    ) -> Tuple[int, int]:
        """Hold TTLs so farther routers program before nearer ones
        (RFC 6976 style; reference: Decision.cpp:1745-1752)."""
        if not self._enable_ordered_fib:
            return (0, 0)
        hops = link_state.get_hops_from_a_to_b(self.my_node_name, node_name)
        if hops is None:
            return (0, 0)
        hold_up = hops
        hold_down = max(0, link_state.get_max_hops_to_node(node_name) - hold_up)
        return (hold_up, hold_down)

    def _schedule_ordered_fib_tick(self) -> None:
        """Tick period = the slowest FIB in the network (reference:
        Decision.cpp:1943 getMaxFib, floor 1 ms)."""
        max_fib_s = max(self.fib_times.values(), default=1.0) / 1000.0
        self._ordered_fib_timer = self.evb.schedule_timeout(
            max(0.001, max_fib_s), self._decrement_ordered_fib_holds
        )

    def _decrement_ordered_fib_holds(self) -> None:
        """reference: Decision.cpp:1930 decrementOrderedFibHolds."""
        self._ordered_fib_timer = None
        still_has_holds = False
        topo_changed = False
        for link_state in self.area_link_states.values():
            change = link_state.decrement_holds()
            topo_changed |= change.topology_changed
            still_has_holds |= link_state.has_holds()
        if topo_changed:
            self.pending.set_needs_full_rebuild()
            self._rebuild_debounced()
        if still_has_holds:
            self._schedule_ordered_fib_tick()

    # -- rebuild ----------------------------------------------------------

    def _on_cold_start_done(self) -> None:
        self._cold_start_until = 0.0
        if self.pending.needs_route_update():
            self.rebuild_routes("COLD_START_UPDATE")

    def _on_debounce_fire(self) -> None:
        self._spec_fired_this_window = False
        self.rebuild_routes("DECISION_DEBOUNCE")
        # debounce terminal: close the journal's replay window — every
        # pub adopted since the previous mark rode THIS rebuild
        if self._flight_journal:
            from openr_tpu.telemetry.flight import get_flight_recorder

            get_flight_recorder().journal_mark(
                "wave",
                window="DECISION_DEBOUNCE",
                vantages=[self.my_node_name],
            )
        # snapshot AFTER the solve window closes: the capture reads the
        # resident distance rows back to host
        if self._state_plane is not None:
            self.checkpoint_state()
        # the audit plane rides the same post-converge hook — NEVER
        # inside rebuild_routes, where a probe dispatch would serialize
        # the solve window it is auditing. Audit errors are contained
        # inside the auditor (counted, never raised): the event loop
        # must not die for a probe.
        get_auditor().on_converge()

    def _fallback_backend(self) -> str:
        """Backend of the ladder's last rung: the native C++ core for a
        device-configured Decision, or the Python oracle on a machine
        that has no compiler to build it with (native_spf logs that
        once). Resolved when the rung runs, so a healthy daemon never
        pays the native build."""
        if self._primary_backend != "device":
            return self._primary_backend
        from openr_tpu.graph import native_spf

        return "native" if native_spf.is_available() else "host"

    def _route_staleness_ms(self) -> float:
        """How long the installed routes have been serving without a
        verified-good refresh: 0 while the ladder is warm and no engine
        is quarantined (or before the first install), else the age of
        the last route db installed in that state. Self-heal zeroes it."""
        with self._emit_mu:
            ts = self._last_good_route_ts
        if ts is None:
            return 0.0
        if (
            self.supervisor.state is HealthState.HEALTHY
            and not quarantine_active()
        ):
            return 0.0
        return (time.monotonic() - ts) * 1000.0

    def checkpoint_state(self) -> None:
        """Persist the engines' warm-start material to the state plane.

        Runs outside any solve window (one small device->host readback
        per area); failures are counted, never fatal — a crashed
        capture just means the next boot seeds cold for that area.
        """
        if self._state_plane is None:
            return
        from openr_tpu.state import capture_engine_snapshot

        for area, ls in self.area_link_states.items():
            try:
                snap = capture_engine_snapshot(area, ls)
                if snap is not None:
                    self._state_plane.record_engine_snapshot(snap)
            except Exception:  # noqa: BLE001 - capture is best-effort
                get_registry().counter_bump("state.capture_errors")
        # cadence-gated: the journal IS the crash record between cuts;
        # collapsing it on every converge would turn the WAL into a
        # full-LSDB write per event
        self._state_plane.maybe_checkpoint(only_if_due=True)

    def warm_boot(self, recovered) -> int:
        """Rehydrate from a ``StatePlane.recover()`` result.

        Rebuilds the per-area LinkStates from the journal-recovered
        LSDB, seeds the resident ELL engines from the persisted
        snapshots (digest-gated — a journal that advanced past a
        snapshot seeds cold, never wrong), and runs one rebuild so
        ``route_db`` is serveable and the first route update reaches
        Fib (ending its graceful-restart hold). Call BEFORE start().
        Returns the number of areas seeded warm.
        """
        from openr_tpu.state import rehydrate_engine

        tracer = get_tracer()
        trace = tracer.start("recovery.warm_boot", node=self.my_node_name)
        span = trace.begin_span("recovery.replay_lsdb")
        for area, key_vals in sorted(recovered.key_vals_by_area.items()):
            self.process_publication(
                Publication(key_vals=dict(key_vals), area=area)
            )
        trace.end_span(span, areas=len(recovered.key_vals_by_area))
        span = trace.begin_span("recovery.rehydrate_engines")
        warm = 0
        for area, ls in sorted(self.area_link_states.items()):
            if rehydrate_engine(ls, recovered.engine_snapshots.get(area)):
                warm += 1
        trace.end_span(
            span, warm=warm, areas=len(self.area_link_states)
        )
        span = trace.begin_span("recovery.rebuild")
        self.rebuild_routes("WARM_BOOT")
        trace.end_span(span)
        tracer.finish(trace, ok=True)
        get_registry().counter_bump("state.warm_boots")
        return warm

    @solve_window
    def rebuild_routes(self, event: str) -> None:
        """reference: Decision.cpp:1860 rebuildRoutes."""
        if self._cold_start_until and time.monotonic() < self._cold_start_until:
            return
        self.pending.add_event(event)
        self.counters["decision.route_build_runs"] += 1
        cold_builds = SPF_COUNTERS["decision.ksp2_cold_builds"]
        if self.pending.count > 1:
            # a debounce window folded several publications into THIS
            # one rebuild: downstream, the device churn path pays one
            # fused dispatch + one delta readback for the whole burst
            # (EllState merges the stacked patch journals; the route
            # engine takes the union affected set) — count the folds
            # so burst coalescing is observable next to
            # decision.route_build_runs
            get_registry().counter_bump(
                "decision.coalesced_publications",
                self.pending.count - 1,
            )

        # close the debounce span, open the rebuild span, and activate
        # the trace on this thread so deep call sites (the ELL
        # reconverge in ops.spf_sparse) can nest their own spans
        trace = self.pending.move_out_trace(self._rebuild_debounced.fired)
        tracer = get_tracer()
        rebuild_span = None
        full = self.pending.needs_full_rebuild()
        # the areas whose graph moved since the last rebuild: each of
        # them is a view to solve, the others are cache hits
        versions = {
            area: ls.topology_version
            for area, ls in self.area_link_states.items()
        }
        areas_moved = sum(
            version != self._rebuilt_versions.get(area)
            for area, version in versions.items()
        )
        self._rebuilt_versions = versions
        if trace is not None:
            rebuild_span = trace.begin_span(
                "decision.rebuild",
                full_rebuild=full,
                areas=len(versions),
                areas_moved=areas_moved,
            )
            tracer.activate(trace)
        t_rebuild0 = time.perf_counter()

        # degradation ladder: warm solve with the configured backend →
        # reset all device-derived state and rebuild cold → flip to the
        # non-device backend. Every rung produces the same
        # DecisionRouteDb (the parity suite proves it per rung), so the
        # emitted delta is rung-independent. A LadderExhausted
        # propagates to the event loop after the finally closes the
        # trace span; pending is NOT reset on that path, so the next
        # publication retriggers the rebuild.
        payload = None
        try:
            with da.event_window("decision.rebuild"):
                payload = self.supervisor.run(
                    (
                    (
                        "warm",
                        lambda: self._solve_update(
                            full,
                            reset=False,
                            backend=self._primary_backend,
                        ),
                    ),
                    (
                        "cold",
                        lambda: self._solve_update(
                            True,
                            reset=True,
                            backend=self._primary_backend,
                        ),
                    ),
                    (
                        "host",
                        lambda: self._solve_update(
                            True,
                            reset=True,
                            backend=self._fallback_backend(),
                        ),
                    ),
                )
            )
        finally:
            get_registry().observe(
                "decision.rebuild_ms",
                (time.perf_counter() - t_rebuild0) * 1000.0,
            )
            if trace is not None:
                tracer.deactivate()
                if payload is None:
                    # ladder exhausted: no emit stage will run for this
                    # rebuild, so the span closes here
                    trace.end_span(
                        rebuild_span, routes_updated=-1, routes_deleted=-1
                    )
            if payload is None:
                # whatever the rungs did to the solver's table, none
                # of it reached route_db
                self._route_anchor = None
                self._anchor_lost = "ladder_exhausted"

        self.pending.add_event("ROUTE_UPDATE")
        perf_events = self.pending.move_out_events()
        self.pending.reset()
        self._emit_update(payload, trace, rebuild_span, perf_events)
        if (
            self._staged_cold_build
            or SPF_COUNTERS["decision.ksp2_cold_builds"] != cold_builds
        ):
            # a KSP2 engine rebuilt whole (here, or in the window's
            # stage): what it left on the heap lives until its next
            # cold build. After the update is on its way to Fib, not
            # before
            self._staged_cold_build = False
            settle_heap()

    def _emit_update(
        self, payload, trace, rebuild_span, perf_events
    ) -> None:
        """Emit stage of a rebuild: diff the solved routes against the
        installed ones, apply, and publish. Runs inline on the module
        thread, the only place route_db is mutated.

        A full build comes as the solver's record of what it wrote. If
        its untouched entries date from the very build route_db last
        took in full (``_route_anchor``), route_db holds the solver's
        own object under every key the build did not touch, so the
        touched keys are the whole diff (``path="carried"``). Anything
        else (``why``) takes the whole table: materialised, diffed by
        ``calculate_update``, which heals identity, and anchored
        anew."""
        tracer = get_tracer()
        kind, value, build, why = payload
        if kind == "delta":
            update = value
        else:
            # the diff runs HERE, not in the solve rung: this stage
            # is where the installed table is adopted (calculate_update
            # heals identity on it), and the ladder's rungs stay free
            # of route_db so a failed rung leaves nothing to undo
            registry = get_registry()
            with tracer.span("decision.route_diff", trace=trace) as span:
                if kind == "build" and self._route_anchor != (
                    build.solver, build.base_seq
                ):
                    # the table moved on without route_db (a ctrl
                    # query built in between), or route_db without
                    # the table
                    why = self._anchor_lost or "built_in_between"
                    kind, value = "db", build.materialise()
                if kind == "build":
                    update = self.route_db.calculate_touched_update(
                        build.touched_unicast,
                        build.touched_mpls,
                        build.size,
                    )
                    registry.counter_bump("decision.route_delta_builds")
                else:
                    update = self.route_db.calculate_update(value)
                    registry.counter_bump("decision.route_delta_fallbacks")
                if span is not None:
                    span.attrs.update(
                        updated=len(update.unicast_routes_to_update),
                        deleted=len(update.unicast_routes_to_delete),
                        identical=update.diff_identical,
                        compared=update.diff_compared,
                        path="carried" if kind == "build" else "whole",
                        why=why,
                    )
            # identical / (identical + compared) is the share of the
            # table the diff settled by object identity
            registry.counter_bump(
                "decision.route_diff_identical", update.diff_identical
            )
            registry.counter_bump(
                "decision.route_diff_compared", update.diff_compared
            )
        if trace is not None:
            trace.end_span(
                rebuild_span,
                routes_updated=len(update.unicast_routes_to_update),
                routes_deleted=len(update.unicast_routes_to_delete),
            )
        # closed BEFORE the push: once the update is on the queue the
        # trace is Fib's, which records the hop as fib.queue_wait
        with tracer.span("decision.emit", trace=trace):
            self.route_db.update(update)
            # in step with the solver's table only after a full build's
            # own routes went in: a per-prefix delta or a policy's
            # rewrite leaves route_db on objects the table does not hold
            if build is not None:
                self._route_anchor = (build.solver, build.seq)
                self._anchor_lost = ""
            else:
                self._route_anchor = None
                self._anchor_lost = why or "prefix_delta"
            if (
                self.supervisor.state is HealthState.HEALTHY
                and not quarantine_active()
            ):
                with self._emit_mu:
                    self._last_good_route_ts = time.monotonic()
            update.perf_events = perf_events
            update.trace = trace
        self.route_updates_queue.push(update)

    @fault_boundary
    def _solve_update(
        self, full: bool, reset: bool, backend: str
    ) -> Tuple[str, object, object, str]:
        """One ladder rung: compute this rebuild's routes. ``reset``
        drops every device-derived cache first (so a torn dispatch
        can't leak into the result); a backend flip does the same
        implicitly. A reset or flip forces the full-rebuild branch even
        for a per-prefix batch — the full route db is a superset of the
        per-prefix entries and the emit stage's ``calculate_update``
        diffs against the installed db, so the emitted delta is
        identical.

        Returns an emit payload ``(kind, value, build, why)`` — for a
        full build ``("build", None, RouteBuild, "")`` where the solver
        patched its table in place (the emit stage installs what the
        build touched, if route_db is in step with that table), else
        ``("db", DecisionRouteDb, build, why)`` (the emit stage diffs
        the whole db; ``build`` is None where the db is no longer the
        solver's table entry for entry: a rib policy rewrote it); or
        ``("delta", DecisionRouteUpdate, None, "")`` for the per-prefix
        incremental pass — so the rung itself never touches route_db
        and a failed rung leaves nothing to undo."""
        flipped = self.spf_solver.backend != backend
        if reset:
            self.spf_solver.reset_device_state()
        if flipped:
            self.spf_solver.set_backend(backend)
        full_build = full or reset or flipped
        prefixes = (
            self.prefix_state.prefixes()
            if full_build
            else self.pending.updated_prefixes
        )
        rung = (
            "warm" if not reset
            else "cold" if backend == self._primary_backend
            else "host"
        )
        # everything this rung does to turn LSDB into routes; a view
        # built on a cache miss nests its own spans inside, so this
        # span's self time is route materialisation
        with get_tracer().span(
            "decision.route_build",
            full=full_build,
            prefixes=len(prefixes),
            rung=rung,
        ) as span:
            if full_build:
                build = self.spf_solver.build_routes(
                    self.my_node_name,
                    self.area_link_states,
                    self.prefix_state,
                )
                if build is None:
                    return ("db", DecisionRouteDb(), None, "no_routes")
                if span is not None:
                    # the keys the build wrote: the table's where it
                    # filled a new one
                    span.attrs["touched"] = build.touched
                if (
                    self.rib_policy is not None
                    and self.rib_policy.is_active()
                ):
                    # the policy rewrites the db it is given: a copy,
                    # never the solver's table
                    new_db = build.materialise()
                    self.rib_policy.apply_policy(new_db.unicast_routes)
                    return ("db", new_db, None, "rib_policy")
                if build.base_seq is None:
                    why = build.why if rung == "warm" else f"rung_{rung}"
                    return ("db", build.materialise(), build, why)
                return ("build", None, build, "")
            update = DecisionRouteUpdate()
            for prefix in prefixes:
                entry = self.spf_solver.create_route_for_prefix(
                    self.my_node_name,
                    self.area_link_states,
                    self.prefix_state,
                    prefix,
                )
                if entry is not None:
                    update.unicast_routes_to_update[prefix] = entry
                else:
                    update.unicast_routes_to_delete.append(prefix)
            if self.rib_policy is not None and self.rib_policy.is_active():
                change = self.rib_policy.apply_policy(
                    update.unicast_routes_to_update
                )
                update.unicast_routes_to_delete.extend(change.deleted_routes)
            return ("delta", update, None, "")

    # -- public (thread-safe) APIs ---------------------------------------

    def get_decision_route_db(
        self, node: Optional[str] = None
    ) -> DecisionRouteDb:
        """Compute (any-source!) routes on demand — first-class API, same
        solver as the hot path (reference: Decision.cpp:1492)."""
        node = node or self.my_node_name

        def compute() -> DecisionRouteDb:
            return (
                self.spf_solver.build_route_db(
                    node, self.area_link_states, self.prefix_state
                )
                or DecisionRouteDb()
            )

        return self.evb.call_and_wait(compute)

    def get_adj_dbs(self) -> Dict[str, Dict[str, AdjacencyDatabase]]:
        return self.evb.call_and_wait(
            lambda: {
                area: dict(ls.get_adjacency_databases())
                for area, ls in self.area_link_states.items()
            }
        )

    def get_received_route_count(self) -> int:
        return self.evb.call_and_wait(
            lambda: len(self.prefix_state.prefixes())
        )

    def set_rib_policy(self, policy) -> None:
        """Install a TTL'd policy; a rebuild is scheduled at expiry so its
        effects revert (reference: Decision.cpp:1600 setRibPolicy +
        ribPolicyTimer_). Inline validation mirrors the reference's
        thrift::OpenrError cases: feature knob off (Decision.cpp:1593)
        and an empty policy (DecisionTest RibPolicyError)."""
        if not self._enable_rib_policy:
            raise RuntimeError("rib policy feature is disabled by config")
        if policy is not None and not policy.statements:
            raise ValueError("rib policy must carry >= 1 statement")

        def install() -> None:
            self.rib_policy = policy
            self.pending.set_needs_full_rebuild()
            self._rebuild_debounced()
            if policy is not None:
                self.evb.schedule_timeout(
                    policy.get_ttl_remaining_s() + 0.001,
                    self._on_rib_policy_expiry,
                )

        self.evb.call_and_wait(install)

    def _on_rib_policy_expiry(self) -> None:
        if self.rib_policy is not None and not self.rib_policy.is_active():
            self.pending.set_needs_full_rebuild()
            self._rebuild_debounced()

    def get_rib_policy(self):
        if not self._enable_rib_policy:
            raise RuntimeError("rib policy feature is disabled by config")
        return self.evb.call_and_wait(lambda: self.rib_policy)

    def get_counters(self) -> Dict[str, int]:
        return self.evb.call_and_wait(self._collect_counters)

    def _collect_counters(self) -> Dict[str, int]:
        """Event counters + global gauges (reference: Decision.cpp:1964
        updateGlobalCounters)."""
        out = dict(self.counters)
        num_adjacencies = 0
        num_partial = 0
        nodes = set()
        for ls in self.area_link_states.values():
            num_adjacencies += ls.num_links
            spf = ls.get_spf_result(self.my_node_name) if ls.has_node(
                self.my_node_name
            ) else {}
            for name, adj_db in ls.get_adjacency_databases().items():
                nodes.add(name)
                num_links = len(ls.links_from_node(name))
                # partial adjacency: declared but not bidirectional, only
                # counted for reachable, non-isolated nodes
                if name in spf and num_links != 0:
                    num_partial += max(
                        0, len(adj_db.adjacencies) - num_links
                    )
        conflicting = sum(
            1
            for entries in self.prefix_state.prefixes().values()
            if PrefixState.has_conflicting_forwarding_info(entries)
        )
        out["decision.num_conflicting_prefixes"] = conflicting
        out["decision.num_partial_adjacencies"] = num_partial
        out["decision.num_complete_adjacencies"] = num_adjacencies
        out["decision.num_nodes"] = max(len(nodes), 1)
        out["decision.num_prefixes"] = len(self.prefix_state.prefixes())
        out.update(get_spf_counters())
        return out
