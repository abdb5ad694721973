"""Served path ``pipeline_grid``: the ``pipeline`` driver, unchanged, for
configurations whose network is upstream's grid.

A configuration's ``served_path`` names a driver file, so this is where
the two things ``grid-10000.drain-churn`` needs and the yardstick lacks
are registered, before the driver builds anything:

- the topology kind ``grid``: a copy of ``grid`` in
  ``openr_tpu/models/topologies.py`` (which mirrors upstream's
  ``RoutingBenchmarkUtils.cpp`` createGrid:205), kept here, as
  ``fat_tree`` is kept in ``topology.py``, so that the network the cell
  measures cannot change under it. ``n`` x ``n`` nodes ``node-<r*n+c>``,
  each linked to its right and its lower neighbour;
- the event kind ``node-metric``: one node, by the mix's law, moves the
  metric of EVERY adjacency in its database one step (1 -> 2 -> ... ->
  10 -> 1) and publishes the database once: a node being soft-drained,
  or re-measuring its links. From a corner of a uniform grid every link
  that points away from the corner lies on a shortest path, and every
  node but the far corner has one, so the event raises a tight edge and
  the warm solve restarts rows from the cold init: the corner's hop
  eccentricity in relax passes. (``metric`` changes one side of one
  link, and of a grid link's two directions exactly one is tight: a
  coin, not a cell.) It withdraws nothing, so it needs none of the
  flap's rules; like ``metric`` it lands only on a node that has a link
  up both ways, because a change to the surviving halves of dead links
  alone changes no route and no rebuild would ever carry it.

Everything else (the node, the clock, the comparison that decides
``correct``) is ``pipeline.Driver``, re-exported.
"""

from __future__ import annotations

from dataclasses import replace
from typing import List

from chipbench import topology, traffic
from chipbench.served_paths.pipeline import Driver  # noqa: F401


def _grid(n: int) -> List[topology.Edge]:
    def node(r: int, c: int) -> str:
        return f"node-{r * n + c}"

    edges: List[topology.Edge] = []
    for r in range(n):
        for c in range(n):
            if c + 1 < n:
                edges.append((node(r, c), node(r, c + 1), 1))
            if r + 1 < n:
                edges.append((node(r, c), node(r + 1, c), 1))
    return edges


def _node_metric(gen: traffic.Generator) -> traffic.Event:
    dead = gen._dead()
    node = gen._pick_where(lambda n: any(
        (n, a.other_node_name) not in dead
        for a in gen.adj_dbs[n].adjacencies
    ))
    db = gen.adj_dbs[node]
    gen.adj_dbs[node] = replace(db, adjacencies=tuple(
        replace(a, metric=1 + (a.metric % 10)) for a in db.adjacencies
    ))
    return gen._emit_adj("node-metric", node)


topology.KINDS["grid"] = _grid
traffic.Generator._KINDS["node-metric"] = _node_metric
