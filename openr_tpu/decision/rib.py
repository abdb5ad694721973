"""Internal RIB representation and route-delta computation.

Behavioral parity with the reference ``openr/decision/RibEntry.h``,
``openr/decision/RouteUpdate.h`` and ``DecisionRouteDb``
(openr/decision/Decision.cpp:112 calculateUpdate / :146 update).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from operator import attrgetter
from typing import Callable, Dict, FrozenSet, List, Optional, Set, Tuple

from openr_tpu.analysis.annotations import thread_confined
from openr_tpu.types import (
    IpPrefix,
    MplsRoute,
    NextHop,
    PerfEvents,
    PrefixEntry,
    PrefixType,
    RouteDatabase,
    RouteDatabaseDelta,
    UnicastRoute,
)


@dataclass
class RibUnicastEntry:
    """reference: openr/decision/RibEntry.h:37 RibUnicastEntry"""

    prefix: IpPrefix
    nexthops: Set[NextHop] = field(default_factory=set)
    best_prefix_entry: Optional[PrefixEntry] = None
    best_area: str = ""
    do_not_install: bool = False

    def __eq__(self, other) -> bool:
        # equality drives delta computation; best_area intentionally NOT
        # compared (matches reference RibUnicastEntry::operator==)
        return (
            isinstance(other, RibUnicastEntry)
            and self.prefix == other.prefix
            and self.best_prefix_entry == other.best_prefix_entry
            and self.do_not_install == other.do_not_install
            and self.nexthops == other.nexthops
        )

    def to_unicast_route(self) -> UnicastRoute:
        prefix_type = None
        data = None
        if (
            self.best_prefix_entry is not None
            and self.best_prefix_entry.type == PrefixType.BGP
        ):
            prefix_type = PrefixType.BGP
            data = self.best_prefix_entry.data
        return UnicastRoute(
            dest=self.prefix,
            next_hops=tuple(self.nexthops),
            do_not_install=self.do_not_install,
            prefix_type=prefix_type,
            data=data,
        )


@dataclass
class RibMplsEntry:
    """reference: openr/decision/RibEntry.h:93 RibMplsEntry"""

    label: int
    nexthops: Set[NextHop] = field(default_factory=set)

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, RibMplsEntry)
            and self.label == other.label
            and self.nexthops == other.nexthops
        )

    def to_mpls_route(self) -> MplsRoute:
        return MplsRoute(top_label=self.label, next_hops=tuple(self.nexthops))


_PREFIX_OF = attrgetter("prefix")
_LABEL_OF = attrgetter("label")


def _diff_table(
    installed: Dict, new: Dict, key_of: Callable
) -> Tuple[List, List, int]:
    """One table of ``DecisionRouteDb.calculate_update``: the entries of
    ``new`` to install and the keys of ``installed`` to drop, both in
    the order the plain two-loop diff gives them, then how many entries
    of ``new`` were skipped as the installed object itself (the others
    took the field-by-field test).

    A route build hands back the object it returned last time for every
    route it did not re-derive, and the installed table holds that very
    object, so identity settles most of a large table without a call to
    ``__eq__`` (an object equals itself, so the answer is the one
    equality would give). The passes over the whole table run in C on
    the hashes the dicts already store; only what the build re-derived
    is hashed and compared in Python. Every writer files an entry
    under its own key (``table[key_of(entry)] is entry``; ``update``
    asserts it for the one key it is handed), so an object found in
    both tables sits under the same key in both.

    An entry of ``new`` that equals the installed one without being it
    (re-derived to the same route, or built after the solver lost its
    cache) is not in the delta, and ``installed`` takes it in place of
    its equal twin: the builder will hand that object back from now on,
    and left alone the pair would go to ``__eq__`` in every later diff.
    """
    held = set(map(id, installed.values()))
    fresh = [e for e in new.values() if id(e) not in held]
    identical = len(new) - len(fresh)
    changed = []
    kept = identical  # keys of installed that new has too
    for entry in fresh:
        key = key_of(entry)
        old = installed.get(key)
        if old is None:
            changed.append(entry)
            continue
        kept += 1
        if old != entry:
            changed.append(entry)
        else:
            installed[key] = entry
    gone: List = []
    if kept < len(installed):
        # the key objects that come back are installed's own
        lost = set(map(id, set(installed).difference(new)))
        gone = [k for k in installed if id(k) in lost]
    return changed, gone, identical


def _diff_touched(installed: Dict, touched: Dict) -> Tuple[List, List, int]:
    """``_diff_table`` for a build that says which keys it wrote
    (``touched``: key -> its new entry, None for one it dropped) and
    left every other key of ``installed`` on the object it was: the
    entries to install, the keys to drop, and how many entries took the
    field-by-field test (or were new), by the same rules. An entry that
    equals the installed one without being it is not in the delta and
    takes its twin's place. Nothing but ``touched`` is read."""
    changed: List = []
    gone: List = []
    compared = 0
    for key, entry in touched.items():
        old = installed.get(key)
        if entry is None:
            if old is not None:
                gone.append(key)
        elif old is not entry:
            compared += 1
            if old is None or old != entry:
                changed.append(entry)
            else:
                installed[key] = entry
    return changed, gone, compared


@dataclass
class DecisionRouteUpdate:
    """Route delta published by Decision, consumed by Fib / PrefixManager.
    reference: openr/decision/RouteUpdate.h:22 DecisionRouteUpdate."""

    unicast_routes_to_update: Dict[IpPrefix, RibUnicastEntry] = field(
        default_factory=dict
    )
    unicast_routes_to_delete: List[IpPrefix] = field(default_factory=list)
    mpls_routes_to_update: List[RibMplsEntry] = field(default_factory=list)
    mpls_routes_to_delete: List[int] = field(default_factory=list)
    perf_events: Optional[PerfEvents] = None
    # in-process telemetry trace adopted from the triggering
    # publication (oldest-chain rule, same as perf_events)
    trace: Optional[object] = None
    # how the full-db diff that made this update decided "unchanged":
    # entries of the new db that ARE the installed object, and entries
    # that went to the field-by-field test (adds included). Telemetry
    # riders like the two above; 0 on a per-prefix delta, which no diff
    # produced
    diff_identical: int = field(default=0, compare=False)
    diff_compared: int = field(default=0, compare=False)

    def empty(self) -> bool:
        return not (
            self.unicast_routes_to_update
            or self.unicast_routes_to_delete
            or self.mpls_routes_to_update
            or self.mpls_routes_to_delete
        )

    def to_route_db_delta(self, node_name: str = "") -> RouteDatabaseDelta:
        return RouteDatabaseDelta(
            this_node_name=node_name,
            unicast_routes_to_update=[
                e.to_unicast_route()
                for _, e in sorted(
                    self.unicast_routes_to_update.items(),
                    key=lambda kv: kv[0],
                )
            ],
            unicast_routes_to_delete=sorted(self.unicast_routes_to_delete),
            mpls_routes_to_update=[
                e.to_mpls_route()
                for e in sorted(
                    self.mpls_routes_to_update, key=lambda e: e.label
                )
            ],
            mpls_routes_to_delete=sorted(self.mpls_routes_to_delete),
            perf_events=self.perf_events,
        )


# a passive container with a single owner at any moment: a solve
# builds one on whatever thread it runs on, and the installed one is
# mutated on Decision's event base only (see Decision.route_db's
# confinement) — it carries no lock of its own by design
@thread_confined("owner", "unicast_routes", "mpls_routes")
@dataclass
class DecisionRouteDb:
    """The full computed RIB. reference: openr/decision/Decision.h:95."""

    unicast_routes: Dict[IpPrefix, RibUnicastEntry] = field(default_factory=dict)
    mpls_routes: Dict[int, RibMplsEntry] = field(default_factory=dict)

    def add_unicast_route(self, entry: RibUnicastEntry) -> None:
        self.unicast_routes[entry.prefix] = entry

    def add_mpls_route(self, entry: RibMplsEntry) -> None:
        self.mpls_routes[entry.label] = entry

    def calculate_update(self, new_db: "DecisionRouteDb") -> DecisionRouteUpdate:
        """Delta from self -> new_db (reference: Decision.cpp:112). The
        one thing it changes in self: an entry that equals new_db's
        without being the same object gives way to new_db's
        (``_diff_table``). ``__eq__`` cannot tell the two apart, so no
        later delta differs; ``best_area``, which ``__eq__`` leaves out
        and nothing reads off the installed db, becomes the newer one."""
        u_new, u_gone, u_same = _diff_table(
            self.unicast_routes, new_db.unicast_routes, _PREFIX_OF
        )
        m_new, m_gone, m_same = _diff_table(
            self.mpls_routes, new_db.mpls_routes, _LABEL_OF
        )
        identical = u_same + m_same
        return DecisionRouteUpdate(
            unicast_routes_to_update={e.prefix: e for e in u_new},
            unicast_routes_to_delete=u_gone,
            mpls_routes_to_update=m_new,
            mpls_routes_to_delete=m_gone,
            diff_identical=identical,
            diff_compared=(
                len(new_db.unicast_routes) + len(new_db.mpls_routes) - identical
            ),
        )

    def calculate_touched_update(
        self, touched_unicast: Dict, touched_mpls: Dict, size: int
    ) -> DecisionRouteUpdate:
        """``calculate_update`` against a db of ``size`` routes that
        differs from self in the touched keys at most, every other key
        holding the very object self holds (``_diff_touched``): the
        same delta, from the touched keys alone. Entries come in the
        order the build wrote them, which is where the new table has
        them; keys to delete in that order too."""
        u_new, u_gone, u_compared = _diff_touched(
            self.unicast_routes, touched_unicast
        )
        m_new, m_gone, m_compared = _diff_touched(
            self.mpls_routes, touched_mpls
        )
        compared = u_compared + m_compared
        return DecisionRouteUpdate(
            unicast_routes_to_update={e.prefix: e for e in u_new},
            unicast_routes_to_delete=u_gone,
            mpls_routes_to_update=m_new,
            mpls_routes_to_delete=m_gone,
            diff_identical=size - compared,
            diff_compared=compared,
        )

    def update(self, delta: DecisionRouteUpdate) -> None:
        """Apply a delta in place (reference: Decision.cpp:146)."""
        for prefix in delta.unicast_routes_to_delete:
            self.unicast_routes.pop(prefix, None)
        for prefix, entry in delta.unicast_routes_to_update.items():
            # calculate_update's identity pass relies on it
            assert entry.prefix == prefix, (prefix, entry.prefix)
            self.unicast_routes[prefix] = entry
        for label in delta.mpls_routes_to_delete:
            self.mpls_routes.pop(label, None)
        for entry in delta.mpls_routes_to_update:
            self.mpls_routes[entry.label] = entry

    def to_route_db(self, node_name: str = "") -> RouteDatabase:
        return RouteDatabase(
            this_node_name=node_name,
            unicast_routes=[
                e.to_unicast_route()
                for _, e in sorted(self.unicast_routes.items(), key=lambda kv: kv[0])
            ],
            mpls_routes=[
                e.to_mpls_route()
                for _, e in sorted(self.mpls_routes.items(), key=lambda kv: kv[0])
            ],
        ).canonicalize()
