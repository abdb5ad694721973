"""Topologies, built from a configuration file's ``topology`` group.

A copy of ``fat_tree`` in ``openr_tpu/models/topologies.py`` (which
mirrors upstream's ``RoutingBenchmarkUtils.cpp`` createFabric:356), kept
here so that the network a cell measures cannot change under it.
``kind`` selects the generator; the other keys of the group are its
arguments. Interface names, link-local next hops and
loopback prefixes are a function of the sorted node names alone, so a
topology is the same in every run; ``--seed`` never touches it.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Tuple

from openr_tpu.types import (
    Adjacency,
    AdjacencyDatabase,
    BinaryAddress,
    IpPrefix,
    PrefixDatabase,
    PrefixEntry,
)
from openr_tpu.types.lsdb import (
    PrefixForwardingAlgorithm,
    PrefixForwardingType,
)

Edge = Tuple[str, str, int]


@dataclass
class Topology:
    name: str
    area: str
    adj_dbs: Dict[str, AdjacencyDatabase] = field(default_factory=dict)
    prefix_dbs: Dict[str, PrefixDatabase] = field(default_factory=dict)

    def links(self) -> int:
        return sum(len(d.adjacencies) for d in self.adj_dbs.values()) // 2


def _fat_tree(pods: int, ssw_per_plane: int, fsw_per_pod: int,
              rsw_per_pod: int) -> List[Edge]:
    """Three tiers: FSW k of a pod uplinks to every SSW of plane k, and
    every RSW of a pod connects to every FSW of its pod."""
    edges: List[Edge] = []
    for pod in range(pods):
        for k in range(fsw_per_pod):
            fsw = f"fsw-{pod}-{k}"
            for s in range(ssw_per_plane):
                edges.append((f"ssw-{k}-{s}", fsw, 1))
            for r in range(rsw_per_pod):
                edges.append((fsw, f"rsw-{pod}-{r}", 1))
    return edges


KINDS: Dict[str, Callable[..., List[Edge]]] = {
    "fat_tree": _fat_tree,
}


def _adjacency(a: str, ai: int, b: str, bi: int, metric: int) -> Adjacency:
    v6 = (0xFE80 << 112) | (bi << 32) | ai
    v4 = (10 << 24) | ((bi & 0xFFF) << 12) | (ai & 0xFFF)
    return Adjacency(
        other_node_name=b,
        if_name=f"if_{a}_{b}",
        other_if_name=f"if_{b}_{a}",
        metric=metric,
        next_hop_v6=BinaryAddress(addr=v6.to_bytes(16, "big")),
        next_hop_v4=BinaryAddress(addr=v4.to_bytes(4, "big")),
    )


def loopback(node_idx: int) -> IpPrefix:
    val = (0xFD00 << 112) | node_idx
    return IpPrefix(BinaryAddress(addr=val.to_bytes(16, "big")), 128)


def build(group: dict, forwarding: dict, area: str = "0") -> Topology:
    """``group``: a configuration's ``topology`` (``kind`` + arguments);
    ``forwarding``: its ``forwarding`` (``algorithm``, ``type``), named as
    upstream's enums are."""
    args = {k: v for k, v in group.items() if k != "kind"}
    if group["kind"] not in KINDS:
        raise ValueError(f"unknown topology kind {group['kind']!r}")
    edges = KINDS[group["kind"]](**args)
    algorithm = PrefixForwardingAlgorithm[forwarding["algorithm"]]
    fwd_type = PrefixForwardingType[forwarding["type"]]
    names = sorted({n for e in edges for n in e[:2]})
    idx = {n: i for i, n in enumerate(names)}
    neighbors: Dict[str, List[Adjacency]] = {n: [] for n in names}
    for a, b, metric in edges:
        neighbors[a].append(_adjacency(a, idx[a], b, idx[b], metric))
        neighbors[b].append(_adjacency(b, idx[b], a, idx[a], metric))
    topo = Topology(name=group["kind"], area=area)
    for n in names:
        topo.adj_dbs[n] = AdjacencyDatabase(
            this_node_name=n,
            adjacencies=tuple(neighbors[n]),
            node_label=idx[n] + 101,
            area=area,
        )
        topo.prefix_dbs[n] = PrefixDatabase(
            this_node_name=n,
            prefix_entries=(
                PrefixEntry(
                    prefix=loopback(idx[n]),
                    forwarding_algorithm=algorithm,
                    forwarding_type=fwd_type,
                ),
            ),
            area=area,
        )
    return topo
