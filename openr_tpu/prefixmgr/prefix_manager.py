"""PrefixManager: owns the prefixes this node advertises into the LSDB.

Behavioral parity with the reference ``openr/prefix-manager/PrefixManager``:
- advertise/withdraw/sync per PrefixType (LOOPBACK, CONFIG, BGP, ...)
  (reference: PrefixManager.h:72 advertisePrefixes)
- serializes to per-prefix KvStore keys ``prefix:<node>:<area>:[<prefix>]``
  via the KvStore client (persist + TTL refresh)
- accepts requests through a queue (PrefixEvent) and via direct API
- cross-area re-distribution: subscribes to Decision's route updates and
  re-originates each best route into the areas it was *not* learned from,
  as a ``PrefixType.RIB`` entry with the source area appended to
  ``area_stack`` (loop prevention: never advertised into any area already
  on the stack). Reference: PrefixManager consuming
  decisionRouteUpdatesQueue + areaStack loop suppression
  (openr/prefix-manager/PrefixManager.cpp, SURVEY §2.1).
- KvStore is synced by delta, as upstream syncs it: a route update or an
  advertise/withdraw touches only the keys of the prefixes it names, in
  each area (set, or cleared with a ``delete_prefix`` tombstone). What
  the store holds afterwards is what a sync of the whole table leaves.
  One update's redistribution is the span ``prefixmgr.redistribute`` on
  the update's trace and the counters ``prefixmgr.redistribute_runs``,
  ``.redistributed_keys``, ``.withdrawn_keys`` and ``.kvstore_calls``.
"""

from __future__ import annotations

import enum
import time
from dataclasses import dataclass, field, replace
from typing import Dict, Iterable, List, Optional, Set, Tuple

from openr_tpu.messaging.queue import ReplicateQueue
from openr_tpu.telemetry import get_registry
from openr_tpu.types import IpPrefix, PrefixDatabase, PrefixEntry, PrefixType
from openr_tpu.types.lsdb import PrefixMetrics
from openr_tpu.utils import keys as keyutil
from openr_tpu.utils import wire
from openr_tpu.utils.constants import (
    DEFAULT_PATH_PREFERENCE,
    DEFAULT_SOURCE_PREFERENCE,
    KVSTORE_TOMBSTONE_TTL_MS,
)
from openr_tpu.utils.eventbase import OpenrEventBase


class PrefixEventType(enum.IntEnum):
    ADD_PREFIXES = 1
    WITHDRAW_PREFIXES = 2
    SYNC_PREFIXES_BY_TYPE = 3
    WITHDRAW_PREFIXES_BY_TYPE = 4


@dataclass
class PrefixEvent:
    event_type: PrefixEventType
    type: Optional[PrefixType] = None
    prefixes: List[PrefixEntry] = field(default_factory=list)


class PrefixManager:
    def __init__(
        self,
        my_node_name: str,
        kvstore_client,
        prefix_updates_queue: Optional[ReplicateQueue] = None,
        decision_route_updates_queue: Optional[ReplicateQueue] = None,
        areas: Optional[List[str]] = None,
        per_prefix_keys: bool = True,
    ):
        self.my_node_name = my_node_name
        self.evb = OpenrEventBase(name=f"prefixmgr:{my_node_name}")
        self._client = kvstore_client
        self._areas = areas or ["0"]
        self._per_prefix_keys = per_prefix_keys
        # (type, prefix) -> entry
        self._prefixes: Dict[Tuple[PrefixType, IpPrefix], PrefixEntry] = {}
        # cross-area redistribution: prefix -> (entry, target areas)
        self._redistributed: Dict[
            IpPrefix, Tuple[PrefixEntry, Tuple[str, ...]]
        ] = {}
        # what KvStore holds of ours: (area, key) -> payload
        self._advertised: Dict[Tuple[str, str], bytes] = {}
        if prefix_updates_queue is not None:
            self.evb.add_queue_reader(
                prefix_updates_queue.get_reader(f"pm:{my_node_name}"),
                self._on_event,
            )
        if decision_route_updates_queue is not None:
            self.evb.add_queue_reader(
                decision_route_updates_queue.get_reader(
                    f"pm-redist:{my_node_name}"
                ),
                self._on_route_update,
            )

    def start(self) -> None:
        self.evb.run_in_thread()

    def stop(self) -> None:
        self.evb.stop()
        self.evb.join()

    # -- queue interface --------------------------------------------------

    def _on_event(self, event: PrefixEvent) -> None:
        if event.event_type == PrefixEventType.ADD_PREFIXES:
            self._advertise(event.prefixes)
        elif event.event_type == PrefixEventType.WITHDRAW_PREFIXES:
            self._withdraw([e.prefix for e in event.prefixes])
        elif event.event_type == PrefixEventType.SYNC_PREFIXES_BY_TYPE:
            assert event.type is not None
            self._sync_by_type(event.type, event.prefixes)
        elif event.event_type == PrefixEventType.WITHDRAW_PREFIXES_BY_TYPE:
            assert event.type is not None
            self._withdraw(
                [
                    p
                    for (t, p) in list(self._prefixes)
                    if t == event.type
                ]
            )

    def _on_route_update(self, update) -> None:
        """Re-originate Decision's best routes into other areas
        (reference: PrefixManager's decisionRouteUpdatesQueue consumer).
        Only the prefixes whose redistribution this update changes
        reach KvStore."""
        start = (time.time() * 1000.0, time.perf_counter())
        to_update = getattr(update, "unicast_routes_to_update", {})
        to_delete = getattr(update, "unicast_routes_to_delete", [])
        touched: Set[IpPrefix] = set()
        own_prefixes = {
            p for (t, p) in self._prefixes if t != PrefixType.RIB
        }
        for prefix, entry in to_update.items():
            redist = (
                None
                if prefix in own_prefixes
                else self._redistribution_of(prefix, entry)
            )
            # a prefix we originate ourselves, or whose best entry has
            # crossed every area of ours, is not redistributed: drop
            # what was recorded before
            if self._redistributed.get(prefix) != redist:
                if redist is None:
                    del self._redistributed[prefix]
                else:
                    self._redistributed[prefix] = redist
                touched.add(prefix)
        for prefix in to_delete:
            if self._redistributed.pop(prefix, None) is not None:
                touched.add(prefix)
        keys_set, keys_cleared = (
            self._sync_kvstore(touched) if touched else (0, 0)
        )
        registry = get_registry()
        registry.counter_bump("prefixmgr.redistribute_runs")
        registry.counter_bump("prefixmgr.redistributed_keys", keys_set)
        registry.counter_bump("prefixmgr.withdrawn_keys", keys_cleared)
        registry.counter_bump(
            "prefixmgr.kvstore_calls", keys_set + keys_cleared
        )
        trace = getattr(update, "trace", None)
        if trace is not None:
            # the trace is Fib's by now, on its own thread: recorded
            # after the fact, closed, beside whatever Fib has open
            trace.closed_span(
                "prefixmgr.redistribute",
                start,
                (time.perf_counter() - start[1]) * 1e3,
                depth=0,
                routes=len(to_update) + len(to_delete),
                keys_set=keys_set,
                keys_cleared=keys_cleared,
            )

    def _redistribution_of(
        self, prefix: IpPrefix, entry
    ) -> Optional[Tuple[PrefixEntry, Tuple[str, ...]]]:
        """The ``RIB`` entry a best route is re-originated as, and the
        areas it goes to; None where it goes nowhere."""
        best = entry.best_prefix_entry
        if best is None:
            return None
        new_stack = tuple(best.area_stack)
        if entry.best_area and entry.best_area not in new_stack:
            new_stack = new_stack + (entry.best_area,)
        targets = tuple(a for a in self._areas if a not in new_stack)
        if not targets:
            return None
        redist = PrefixEntry(
            prefix=prefix,
            type=PrefixType.RIB,
            forwarding_type=best.forwarding_type,
            forwarding_algorithm=best.forwarding_algorithm,
            min_nexthop=best.min_nexthop,
            # bump distance so the re-originated copy always loses
            # best-route selection to the original — without this,
            # two border routers' identical-metric copies can tie
            # with the source and oscillate advertise/withdraw
            metrics=replace(
                best.metrics, distance=best.metrics.distance + 1
            ),
            tags=best.tags,
            area_stack=new_stack,
        )
        return (redist, targets)

    # -- public API (thread-safe) -----------------------------------------

    def advertise_prefixes(self, entries: List[PrefixEntry]) -> None:
        self.evb.call_and_wait(lambda: self._advertise(entries))

    def withdraw_prefixes(self, prefixes: List[IpPrefix]) -> None:
        self.evb.call_and_wait(lambda: self._withdraw(prefixes))

    def sync_prefixes_by_type(
        self, prefix_type: PrefixType, entries: List[PrefixEntry]
    ) -> None:
        self.evb.call_and_wait(lambda: self._sync_by_type(prefix_type, entries))

    def get_prefixes(self) -> List[PrefixEntry]:
        return self.evb.call_and_wait(
            lambda: sorted(self._prefixes.values(), key=lambda e: e.prefix)
        )

    def get_redistributed(self) -> Dict[IpPrefix, Tuple[PrefixEntry, Tuple[str, ...]]]:
        """Cross-area re-originated routes (entry, target areas)."""
        return self.evb.call_and_wait(lambda: dict(self._redistributed))

    # -- internals --------------------------------------------------------

    def _record_own(self, entry: PrefixEntry) -> None:
        """Record one own advertisement (shared by advertise + sync)."""
        if entry.metrics == PrefixMetrics():
            # origination default (reference: buildOriginatedPrefixDb)
            entry = replace(
                entry,
                metrics=PrefixMetrics(
                    path_preference=DEFAULT_PATH_PREFERENCE,
                    source_preference=DEFAULT_SOURCE_PREFERENCE,
                ),
            )
        self._prefixes[(entry.type, entry.prefix)] = entry
        if entry.type != PrefixType.RIB:
            # an own advertisement supersedes any cross-area
            # redistribution of the same prefix
            self._redistributed.pop(entry.prefix, None)

    def _advertise(self, entries: List[PrefixEntry]) -> None:
        """reference: PrefixManager.cpp advertisePrefixesImpl."""
        for entry in entries:
            self._record_own(entry)
        self._sync_kvstore({e.prefix for e in entries})

    def _withdraw(self, prefixes: List[IpPrefix]) -> None:
        gone = set(prefixes)
        for key in [k for k in self._prefixes if k[1] in gone]:
            del self._prefixes[key]
        self._sync_kvstore(gone)

    def _sync_by_type(
        self, prefix_type: PrefixType, entries: List[PrefixEntry]
    ) -> None:
        old = [k for k in self._prefixes if k[0] == prefix_type]
        for key in old:
            del self._prefixes[key]
        for entry in entries:
            self._record_own(replace(entry, type=prefix_type))
        self._sync_kvstore(
            {p for _, p in old} | {e.prefix for e in entries}
        )

    def _best_own_entries(self) -> Dict[IpPrefix, PrefixEntry]:
        """One advertisement per prefix: the best-metrics entry among the
        types advertising it, deterministic tie-break by lowest type
        (reference: PrefixManager.cpp:346-348 syncKvStore picks
        selectBestPrefixMetrics across the per-type entries)."""
        best: Dict[IpPrefix, Tuple[tuple, PrefixEntry]] = {}
        for (ptype, prefix), entry in self._prefixes.items():
            rank = (entry.metrics.comparison_key(), -int(ptype))
            cur = best.get(prefix)
            if cur is None or rank > cur[0]:
                best[prefix] = (rank, entry)
        return {p: e for p, (_, e) in best.items()}

    def _sync_kvstore(self, prefixes: Iterable[IpPrefix]) -> Tuple[int, int]:
        """Bring the keys of ``prefixes`` in every area to what this
        node owes KvStore for them: its best own entry, else the
        redistributed one where the area is a target, else nothing (the
        key is cleared with a delete marker, so that other Decisions
        drop the entry). Returns how many keys were set and how many
        cleared."""
        own = self._best_own_entries()
        if not self._per_prefix_keys:
            return (self._sync_full_db(own), 0)
        keys_set = keys_cleared = 0
        for prefix in prefixes:
            entry, targets = (
                (own[prefix], self._areas)
                if prefix in own
                else self._redistributed.get(prefix, (None, ()))
            )
            for area in self._areas:
                key = keyutil.per_prefix_key(
                    self.my_node_name, area, prefix
                )
                if area in targets:
                    payload = wire.dumps(
                        PrefixDatabase(
                            this_node_name=self.my_node_name,
                            prefix_entries=(entry,),
                            area=area,
                        )
                    )
                    if self._advertised.get((area, key)) != payload:
                        self._client.persist_key(area, key, payload)
                        self._advertised[(area, key)] = payload
                        keys_set += 1
                elif self._advertised.pop((area, key), None) is not None:
                    delete_db = PrefixDatabase(
                        this_node_name=self.my_node_name,
                        prefix_entries=(PrefixEntry(prefix=prefix),),
                        delete_prefix=True,
                        area=area,
                    )
                    self._client.clear_key(
                        area,
                        key,
                        wire.dumps(delete_db),
                        ttl=KVSTORE_TOMBSTONE_TTL_MS,
                    )
                    keys_cleared += 1
        return (keys_set, keys_cleared)

    def _sync_full_db(self, own: Dict[IpPrefix, PrefixEntry]) -> int:
        """Full-db mode: one key an area holds every entry, so any
        change rewrites it."""
        key = keyutil.prefix_db_key(self.my_node_name)
        keys_set = 0
        for area in self._areas:
            entries = dict(own)
            for prefix, (entry, targets) in self._redistributed.items():
                if area in targets and prefix not in own:
                    entries[prefix] = entry
            payload = wire.dumps(
                PrefixDatabase(
                    this_node_name=self.my_node_name,
                    prefix_entries=tuple(
                        e for _, e in sorted(entries.items())
                    ),
                    area=area,
                )
            )
            if self._advertised.get((area, key)) != payload:
                self._client.persist_key(area, key, payload)
                self._advertised[(area, key)] = payload
                keys_set += 1
        return keys_set
