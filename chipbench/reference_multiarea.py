"""The plain two-area reference: which routes a border must hold, and
which prefixes it owes each area's KvStore, for a final LSDB.

Independent of the code under test: none of ``openr_tpu``'s solver,
graph, route or PrefixManager code, only the wire types an LSDB is made
of and ``reference.py``'s Dijkstra. An LSDB here is, per area, the
adjacency databases of the area's nodes and the prefix entries each
node advertises INTO that area::

    {area: (adj_dbs, {node: [PrefixEntry, ...]})}

Routes, as upstream's ``SpfSolver`` has them for ``SP_ECMP`` over IP
(``Decision.cpp:737`` selectBestRoutes, ``:847`` selectBestPathsSpf,
``:1124`` getNextHopsWithMetric):

- a prefix's entries are its ``(node, area)`` advertisements over all
  areas; the best are those with the greatest ``(path_preference,
  source_preference, -distance)``, and none is selected where even that
  is below ``(0, 0, 0)`` (``Util.h:549`` starts from the zero tuple: a
  prefix left with nothing but re-originated copies, each at a positive
  distance, has no route);
- no route where the vantage is among the best advertisers;
- each area's graph is solved on its own (one Dijkstra from the
  vantage); in every area the nearest best ADVERTISER NODES are looked
  up by name, whichever area they advertised into (upstream's
  getMinCostNodes drops the pair's area: ``MultiAreaBestPathCalculation``
  reaches a prefix originated into B over area A as well);
- the route takes the least of those distances over the areas, and as
  next hops the union, over the areas at that distance, of the first
  hops towards the nearest advertisers there, each carrying the
  distance and its area; no route where no area reaches an advertiser.

Re-originations, as upstream's ``PrefixManager`` has them: each route's
representative best entry (the vantage's own if it has one, else the
least ``(node, area)`` among the best, ``Util.cpp:1057``) is advertised
by the border into every area of its own that is neither the entry's
area nor on its ``area_stack``, as type ``RIB`` with ``distance + 1``
and that area appended to the stack.

Departures from upstream's description, none of which a configuration
here reaches: no drained node is filtered, no ``min_nexthop`` is held,
no BGP metric vector compared, no label pushed; a first hop found in
one area is taken over that area's link to it (upstream looks a
first-hop node up by name over the links of every area: the same
wherever no neighbour of the vantage is its neighbour in two areas); an
entry of the vantage's own that came back to it through its own stack
is expected to have been dropped before selection (the caller leaves
the vantage's re-originated keys out of the LSDB).
"""

from __future__ import annotations

from typing import Dict, FrozenSet, Iterable, List, Tuple

from chipbench import reference

# prefix -> {(neighbour, interface, metric, area)}
Routes = Dict[object, FrozenSet[Tuple[str, str, int, str]]]
# (area, prefix) -> (type name, distance, area_stack)
Owed = Dict[Tuple[str, object], Tuple[str, int, Tuple[str, ...]]]

RIB = "RIB"


def _rank(entry) -> Tuple[int, int, int]:
    m = entry.metrics
    return (m.path_preference, m.source_preference, -m.distance)


def _entries(lsdb) -> Dict[object, Dict[Tuple[str, str], object]]:
    """prefix -> {(node, area): entry} over every area."""
    out: Dict[object, Dict[Tuple[str, str], object]] = {}
    for area, (_, advertised) in lsdb.items():
        for node, entries in advertised.items():
            for entry in entries:
                out.setdefault(entry.prefix, {})[(node, area)] = entry
    return out


def _solved(lsdb, vantage: str):
    """area -> (distance, first hops) from the vantage, for the areas
    the vantage is a node of."""
    return {
        area: reference.shortest_paths(adj_dbs, vantage)[:2]
        for area, (adj_dbs, _) in lsdb.items() if vantage in adj_dbs
    }


def _selected(lsdb, vantage: str):
    """For each prefix that gets a route: its next hops, and the best
    entry that represents it with that entry's area."""
    solved = _solved(lsdb, vantage)
    for prefix, entries in _entries(lsdb).items():
        top = max(_rank(e) for e in entries.values())
        if top < (0, 0, 0):
            continue
        best = sorted(na for na, e in entries.items() if _rank(e) == top)
        nodes = {node for node, _ in best}
        if vantage in nodes:
            continue
        least, next_hops = None, set()
        for area in sorted(solved):
            dist, first = solved[area]
            reach = [dist[n] for n in nodes if n in dist]
            if not reach or (least is not None and min(reach) > least):
                continue
            if least is None or min(reach) < least:
                least, next_hops = min(reach), set()
            for node in nodes:
                if dist.get(node) == least:
                    next_hops |= {
                        (nbr, if_name, least, area)
                        for nbr, if_name in first[node]
                    }
        if next_hops:
            yield prefix, frozenset(next_hops), entries[best[0]], best[0][1]


def routes(lsdb, vantage: str) -> Routes:
    return {prefix: nhs for prefix, nhs, _, _ in _selected(lsdb, vantage)}


def reoriginations(lsdb, vantage: str, areas: Iterable[str]) -> Owed:
    """What the vantage, a member of ``areas``, owes each of them."""
    areas = list(areas)
    owed: Owed = {}
    for prefix, _, entry, learned_in in _selected(lsdb, vantage):
        stack = tuple(entry.area_stack)
        if learned_in not in stack:
            stack += (learned_in,)
        for area in areas:
            if area not in stack:
                owed[(area, prefix)] = (
                    RIB, entry.metrics.distance + 1, stack
                )
    return owed


def routes_of(route_db) -> Routes:
    """The same shape from a ``RouteDatabase`` the system produced."""
    return {
        r.dest: frozenset(
            (nh.neighbor_node_name, nh.address.if_name, nh.metric, nh.area)
            for nh in r.next_hops
        )
        for r in route_db.unicast_routes
    }


def owed_of(entries_by_area: Dict[str, List[object]]) -> Owed:
    """The same shape from the prefix entries a node holds in each
    area's KvStore (live keys only)."""
    return {
        (area, e.prefix): (e.type.name, e.metrics.distance, tuple(e.area_stack))
        for area, entries in entries_by_area.items() for e in entries
    }
