"""The plain reference: per-source Dijkstra over the final LSDB.

Independent of the code under test: no ``openr_tpu`` solver, graph or
route code is used, only the wire types the LSDB is made of. It answers
one question — which ``RouteDatabase`` must the vantage node hold once
every publication has been applied — for what the configurations here
state: ``SP_ECMP`` over IP forwarding, one originator per prefix, no
overloaded node, no parallel links. A configuration that states more
needs a reference that covers it.

Semantics, as upstream's ``LinkState``/``SpfSolver`` have them:

- a link is usable only while BOTH ends advertise the adjacency;
- the cost of crossing it from ``a`` is the metric ``a`` advertises;
- the route to a prefix takes every first hop that lies on some
  shortest path to its originator (ECMP), each next hop carrying the
  path's total metric; the vantage's own prefixes get no route.
"""

from __future__ import annotations

import heapq
from typing import Dict, FrozenSet, List, Set, Tuple

# prefix -> {(neighbour, interface, metric)}
Routes = Dict[object, FrozenSet[Tuple[str, str, int]]]


def _usable(adj_dbs) -> Dict[str, List[Tuple[str, str, int]]]:
    """node -> [(neighbour, interface, metric)] over two-way links."""
    says = {
        (n, a.other_node_name)
        for n, db in adj_dbs.items() for a in db.adjacencies
    }
    return {
        n: [
            (a.other_node_name, a.if_name, a.metric)
            for a in db.adjacencies if (a.other_node_name, n) in says
        ]
        for n, db in adj_dbs.items()
    }


def shortest_paths(adj_dbs, src: str):
    """(distance, first hops, hops) per reachable node. First hops are
    (neighbour, interface) pairs; ``hops`` is the fewest links among
    the shortest paths, which is how many relaxation passes a
    Bellman-Ford formulation needs before that node is final."""
    out = _usable(adj_dbs)
    dist: Dict[str, int] = {src: 0}
    hops: Dict[str, int] = {src: 0}
    first: Dict[str, Set[Tuple[str, str]]] = {src: set()}
    heap: List[Tuple[int, str]] = [(0, src)]
    done: Set[str] = set()
    while heap:
        d, u = heapq.heappop(heap)
        if u in done:
            continue
        done.add(u)
        for v, if_name, metric in out[u]:
            nd = d + metric
            via = {(v, if_name)} if u == src else first[u]
            old = dist.get(v)
            if old is None or nd < old:
                dist[v] = nd
                hops[v] = hops[u] + 1
                first[v] = set(via)
                heapq.heappush(heap, (nd, v))
            elif nd == old:
                first[v] |= via
                hops[v] = min(hops[v], hops[u] + 1)
    return dist, first, hops


def routes(adj_dbs, prefix_dbs, vantage: str) -> Routes:
    dist, first, _ = shortest_paths(adj_dbs, vantage)
    out: Routes = {}
    for node, db in prefix_dbs.items():
        if node == vantage or node not in dist:
            continue
        nhs = frozenset((n, i, dist[node]) for n, i in first[node])
        for entry in db.prefix_entries:
            if entry.prefix in out:
                raise ValueError(
                    f"{entry.prefix} has two originators: the plain "
                    "reference does not select best routes"
                )
            out[entry.prefix] = nhs
    return out


def routes_of(route_db) -> Routes:
    """The same shape from a ``RouteDatabase`` the system produced."""
    return {
        r.dest: frozenset(
            (nh.neighbor_node_name, nh.address.if_name, nh.metric)
            for nh in r.next_hops
        )
        for r in route_db.unicast_routes
    }


def relax_passes(adj_dbs, sources) -> int:
    """Passes a Bellman-Ford solve from ``sources`` needs: the largest
    fewest-links count among all shortest paths."""
    return max(
        max(shortest_paths(adj_dbs, s)[2].values()) for s in sources
    )
