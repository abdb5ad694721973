"""The plain reference for ``KSP2_ED_ECMP`` over ``SR_MPLS``: two ranks of
edge-disjoint shortest paths per destination, as label-switched next
hops, and the node-label MPLS routes beside them.

Independent of the code under test, as ``reference.py`` is: no
``openr_tpu`` solver, graph or route code, not even its wire types are
imported — the LSDB and the ``RouteDatabase`` are read by attribute. It
answers which unicast and MPLS routes the vantage node must hold once
every publication has been applied, for what the configurations that
name it state: every prefix ``KSP2_ED_ECMP`` / ``SR_MPLS``, no
overloaded node or link, no parallel links, metrics of 1 or more, node
labels unique, no prepend label. It refuses an LSDB that states more.

Semantics, as upstream has them:

- a link is usable only while BOTH ends advertise the adjacency, and
  crossing it from ``a`` costs the metric ``a`` advertises
  (``LinkState``);
- rank 1 (``LinkState.cpp:763`` getKthPaths, k = 1): a Dijkstra from
  the vantage; then link-disjoint paths are traced from the
  destination back along predecessor links, depth first, each link
  used by at most one trace and spent even where its trace dead-ends
  (``LinkState.cpp:399`` traceOnePath), until no trace reaches the
  vantage;
- rank 2 (k = 2): the same over a Dijkstra that ignores every link of
  the destination's rank-1 paths, one Dijkstra per destination;
- the route (``Decision.cpp:908`` selectBestPathsKsp2): the rank-1
  paths to every advertiser of the prefix, then each advertiser's
  rank-2 paths except those that contain one of those rank-1 paths as
  a run of consecutive links (``LinkState.h:396`` pathAInPathB: with
  anycast in a mesh the second path to one advertiser can run through
  the first path to another, and would spray twice); no route to a
  prefix the vantage advertises itself;
- one next hop per path (``Decision.cpp:1211`` getNextHopsThrift's
  fields): the first link's interface and neighbour, the path's whole
  metric, and ``PUSH`` of the node labels of the path's nodes after
  the first hop, the destination's label pushed first (bottom of
  stack); a one-hop path pushes nothing and has no MPLS action. Next
  hops are a set, so two paths that agree in all of this are one;
- node-label routes (``Decision.cpp:600-650``): for every labelled
  node the vantage reaches, its label swapped (``SWAP`` to the same
  label) towards every ECMP first hop, or popped (``PHP``) where the
  first hop is the node itself, each at the shortest metric; the
  vantage's own label is ``POP_AND_LOOKUP``.

Departures from upstream, each one also the program's:

- upstream walks predecessor links in the order of an unordered
  container, so which disjoint paths a trace finds is unspecified
  there. Here, as in ``openr_tpu``'s ``LinkState._trace_one_path``,
  they are walked in the order of the link's name, the sorted pair of
  its two (node, interface) ends: upstream's ``Link::operator<``;
- upstream weighs advertisers of one prefix by best-route selection;
  here all advertisers of a prefix must carry equal entries, which
  selects them all.
"""

from __future__ import annotations

import heapq
from typing import Dict, FrozenSet, List, Optional, Set, Tuple

from chipbench import reference

# (neighbour, interface, metric, MPLS action, labels pushed or swapped)
NextHop = Tuple[Optional[str], Optional[str], int, Optional[str], Tuple[int, ...]]
Routes = Dict[object, FrozenSet[NextHop]]
# one link of a path, in walking order: (link, from, to)
Hop = Tuple[int, str, str]
Path = List[Hop]

KSP2 = ("KSP2_ED_ECMP", "SR_MPLS")


class Graph:
    """The two-way links of an LSDB, numbered in the order of their
    names, so that sorting by number is upstream's tie-break."""

    def __init__(self, adj_dbs) -> None:
        by_pair: Dict[Tuple[str, str], list] = {}
        for node, db in adj_dbs.items():
            if db.is_overloaded:
                raise ValueError(f"{node} is overloaded: not covered")
            for adj in db.adjacencies:
                if adj.is_overloaded or adj.metric < 1:
                    raise ValueError(
                        f"{node}: an overloaded or zero-metric adjacency "
                        "is not covered"
                    )
                by_pair.setdefault((node, adj.other_node_name), []).append(adj)
        links = []
        for (a, b), there in by_pair.items():
            back = by_pair.get((b, a))
            if a < b and back is not None:
                if len(there) != 1 or len(back) != 1:
                    raise ValueError(f"parallel links {a} - {b}: not covered")
                name = tuple(sorted(
                    ((a, there[0].if_name), (b, back[0].if_name))
                ))
                links.append((name, a, there[0], b, back[0]))
        links.sort(key=lambda link: link[0])
        # node -> [(neighbour, metric, link)]
        self.out: Dict[str, List[Tuple[str, int, int]]] = {
            n: [] for n in adj_dbs
        }
        # (link, from) -> (interface, metric)
        self.side: Dict[Tuple[int, str], Tuple[str, int]] = {}
        for lid, (_, a, fwd, b, back) in enumerate(links):
            self.out[a].append((b, fwd.metric, lid))
            self.out[b].append((a, back.metric, lid))
            self.side[(lid, a)] = (fwd.if_name, fwd.metric)
            self.side[(lid, b)] = (back.if_name, back.metric)
        self.label = {n: db.node_label for n, db in adj_dbs.items()}

    def spf(self, src: str, ignore: Set[int] = frozenset(),
            stop: Optional[str] = None):
        """Dijkstra: node -> distance, and node -> its predecessor
        links [(link, previous node)] over every shortest path. With
        ``stop`` it ends once that node is final; the predecessors of it
        and of every nearer node are complete by then (metrics >= 1)."""
        dist: Dict[str, int] = {src: 0}
        preds: Dict[str, List[Tuple[int, str]]] = {src: []}
        done: Set[str] = set()
        heap: List[Tuple[int, str]] = [(0, src)]
        while heap:
            d, u = heapq.heappop(heap)
            if u in done:
                continue
            done.add(u)
            if u == stop:
                break
            for v, metric, lid in self.out[u]:
                if lid in ignore or v in done:
                    continue
                nd = d + metric
                old = dist.get(v)
                if old is None or nd < old:
                    dist[v] = nd
                    preds[v] = [(lid, u)]
                    heapq.heappush(heap, (nd, v))
                elif nd == old:
                    preds[v].append((lid, u))
        return dist, preds


def _trace_one(src: str, node: str, preds, spent: Set[int]) -> Optional[Path]:
    if node == src:
        return []
    for lid, prev in sorted(preds[node]):
        if lid in spent:
            continue
        spent.add(lid)
        path = _trace_one(src, prev, preds, spent)
        if path is not None:
            path.append((lid, prev, node))
            return path
    return None


def _trace_all(src: str, dest: str, dist, preds) -> List[Path]:
    if dest not in dist:
        return []
    spent: Set[int] = set()
    paths: List[Path] = []
    path = _trace_one(src, dest, preds, spent)
    while path:
        paths.append(path)
        path = _trace_one(src, dest, preds, spent)
    return paths


def kth_paths(graph: Graph, src: str, dest: str,
              first_spf=None) -> Tuple[List[Path], List[Path]]:
    """(rank-1 paths, rank-2 paths) from ``src`` to ``dest``.
    ``first_spf`` is ``graph.spf(src)``, which every destination
    shares."""
    dist, preds = first_spf if first_spf is not None else graph.spf(src)
    first = _trace_all(src, dest, dist, preds)
    if not first:
        return [], []
    used = {lid for path in first for lid, _, _ in path}
    dist2, preds2 = graph.spf(src, ignore=used, stop=dest)
    return first, _trace_all(src, dest, dist2, preds2)


def contains(inner: Path, outer: Path) -> bool:
    """``inner``'s links as consecutive links of ``outer``."""
    a = [lid for lid, _, _ in inner]
    b = [lid for lid, _, _ in outer]
    return any(b[i:i + len(a)] == a for i in range(len(b) - len(a) + 1))


def next_hop(graph: Graph, path: Path) -> NextHop:
    lid, src, neighbour = path[0]
    metric = sum(graph.side[(hop[0], hop[1])][1] for hop in path)
    labels = tuple(graph.label[to] for _, _, to in reversed(path[1:]))
    return (neighbour, graph.side[(lid, src)][0], metric,
            "PUSH" if labels else None, labels)


def _advertisers(prefix_dbs) -> Dict[object, List[str]]:
    entries: Dict[object, list] = {}
    for node, db in prefix_dbs.items():
        for entry in db.prefix_entries:
            entries.setdefault(entry.prefix, []).append((node, entry))
    out = {}
    for prefix, rows in entries.items():
        first = rows[0][1]
        if (first.forwarding_algorithm.name, first.forwarding_type.name) != KSP2:
            raise ValueError(f"{prefix} is not KSP2_ED_ECMP over SR_MPLS")
        if first.prepend_label is not None:
            raise ValueError(f"{prefix} has a prepend label: not covered")
        if any(entry != first for _, entry in rows):
            raise ValueError(
                f"{prefix}: its advertisers' entries differ, and the plain "
                "reference does not select best routes"
            )
        out[prefix] = sorted(node for node, _ in rows)
    return out


def routes(adj_dbs, prefix_dbs, vantage: str) -> Routes:
    graph = Graph(adj_dbs)
    if len(set(graph.label.values())) != len(graph.label):
        raise ValueError("two nodes share a label: not covered")
    first_spf = graph.spf(vantage)
    paths_to: Dict[str, Tuple[List[Path], List[Path]]] = {}
    out: Routes = {}
    for prefix, nodes in _advertisers(prefix_dbs).items():
        if vantage in nodes:
            continue
        nodes = [n for n in nodes if n in first_spf[0]]
        for n in nodes:
            if n not in paths_to:
                paths_to[n] = kth_paths(graph, vantage, n, first_spf)
        firsts = [p for n in nodes for p in paths_to[n][0]]
        seconds = [
            p for n in nodes for p in paths_to[n][1]
            if not any(contains(f, p) for f in firsts)
        ]
        if firsts or seconds:
            out[prefix] = frozenset(
                next_hop(graph, p) for p in firsts + seconds
            )
    return out


def mpls_routes(adj_dbs, vantage: str) -> Dict[int, FrozenSet[NextHop]]:
    """Node-label routes, off ``reference.py``'s Dijkstra."""
    dist, first, _ = reference.shortest_paths(adj_dbs, vantage)
    out = {}
    for node, db in adj_dbs.items():
        label = db.node_label
        if not label or node not in dist:
            continue
        if node == vantage:
            out[label] = frozenset({(None, None, 0, "POP_AND_LOOKUP", ())})
            continue
        out[label] = frozenset(
            (n, i, dist[node]) + (("PHP", ()) if n == node else ("SWAP", (label,)))
            for n, i in first[node]
        )
    return out


def _next_hops_of(route) -> FrozenSet[NextHop]:
    out = set()
    for nh in route.next_hops:
        action = nh.mpls_action
        labels: Tuple[int, ...] = ()
        if action is not None:
            labels = tuple(action.push_labels or ())
            if action.swap_label is not None:
                labels += (action.swap_label,)
        out.add((nh.neighbor_node_name, nh.address.if_name, nh.metric,
                 action.action.name if action is not None else None, labels))
    return frozenset(out)


def routes_of(route_db) -> Routes:
    """``routes``' shape from a ``RouteDatabase`` the system produced:
    every next hop with its MPLS action and labels, so a wrong stack is
    a wrong route."""
    return {r.dest: _next_hops_of(r) for r in route_db.unicast_routes}


def mpls_routes_of(route_db) -> Dict[int, FrozenSet[NextHop]]:
    return {r.top_label: _next_hops_of(r) for r in route_db.mpls_routes}
