"""Sparse (edge-list) SPF kernels for very large topologies.

The dense kernels in ``openr_tpu.ops.spf`` carry an [N, N] metric matrix
— infeasible at the 100k-node north-star scale (10^10 cells, 40 GB).
Here the graph is a padded edge list compiled *directly from the host
LinkState* (no dense matrix anywhere, host or device) and one relaxation
step costs S x E work via gather + segment-min instead of S x N x N:

    cand[s, e] = d[s, edge_src[e]] + edge_w[e]
    d'[s, v]   = min(d[s, v], min_{e: edge_dst[e] == v} cand[s, e])

which converges to the same fixed point as the reference's per-source
Dijkstra (openr/decision/LinkState.cpp:809 runSpf) in diameter steps
inside a ``lax.while_loop``.

Semantics parity with the dense kernels:
- transit exclusion: out-edges of overloaded nodes never extend a
  path. The mask sits on the DISTANCES, not the edges: every relax
  reads ``where(overloaded[None, :], INF, d)`` at the edges' tails
  (_mask_transit_cols, the sparse twin of ops.spf._mask_transit_rows),
  one select over [S, N] per pass. Masking the edge slots instead
  (the weight of every edge whose tail is overloaded set to INF) is
  the same term bit for bit but a scalar gather per edge per pass,
  which was 81% of the device's time at 4992 nodes. The *initial* rows
  are one relaxation with no mask at all from the unit init (diagonal
  0), which equals the sources' direct-edge rows — so an overloaded
  source still originates (reference: LinkState.cpp:831-838).
- hop-count mode: all edge weights 1.
- INF saturation: d + w clips at INF = 2**30 - 1 (int32-safe).

Edges are sorted by destination (host-side, once per snapshot version)
so segment-min runs with ``indices_are_sorted=True``; padding edges
carry weight INF and can never win a min.

Source-axis sharding mirrors ``openr_tpu.parallel.mesh``: every device
owns a block of source rows, the edge lists are replicated (O(E), tiny
next to the distance block), and the only cross-device traffic is the
1-bit convergence psum per iteration. Per-device memory at 100k nodes
on a 32-device mesh: 100k/32 x 100k x 4 B ~= 1.25 GB of distance rows
plus the O(E) edge list — well inside HBM.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, replace as _replace
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from openr_tpu.graph.snapshot import pad_patch_rows
from openr_tpu.ops.spf import INF

_EDGE_PAD = 128
_NODE_PAD = 128

# Churn-path health counters for the resident-band machinery, surfaced
# through decision.spf_solver.get_spf_counters() with a "decision."
# prefix and asserted by the churn smoke test: a refactor that silently
# knocks the hot path back to full recompiles shows up as
# ell_incremental_syncs staying flat while ell_cold_solves climbs.
# Registry-backed shim since the telemetry spine: same bare keys and
# `ELL_COUNTERS[k] += 1` idiom, stored in the process registry under
# the exported "decision." names, so the registry snapshot and
# get_spf_counters() agree by construction.
from openr_tpu.analysis.annotations import donates, solve_window
from openr_tpu.ops import dispatch_accounting as _da
from openr_tpu.ops.aot_cache import aot_call as _aot_call
from openr_tpu.telemetry import get_registry as _get_registry
from openr_tpu.telemetry import get_tracer as _get_tracer

ELL_COUNTERS = _get_registry().counter_dict(
    [
        "ell_incremental_syncs",  # delta scatters into resident bands
        "ell_warm_solves",        # solves seeded from the previous d
        "ell_cold_solves",        # solves from the unit init
        "ell_widen_events",       # widen-on-overflow band re-uploads
        "ell_patch_merges",       # stacked patches coalesced warm
        "ell_structural_warm_solves",  # overload/link flips kept warm
        "ell_reset_solves",       # solves that restarted >= 1 row from d0
    ],
    prefix="decision.",
)


def _pad_up(n: int, align: int) -> int:
    return max(align, ((n + align - 1) // align) * align)


@dataclass(frozen=True)
class SparseGraph:
    """Padded, dst-sorted directed edge lists + node interning for one
    LinkState topology version. ``full_*`` carries every up link (used
    for the init step); ``transit_*`` drops out-edges of overloaded
    nodes (used for relaxation)."""

    node_names: Tuple[str, ...]
    node_index: Dict[str, int]
    n: int
    n_pad: int
    full_src: np.ndarray
    full_dst: np.ndarray
    full_w: np.ndarray
    transit_src: np.ndarray
    transit_dst: np.ndarray
    transit_w: np.ndarray


def _pack(srcs: List[int], dsts: List[int], ws: List[int]):
    e = len(srcs)
    e_pad = _pad_up(e, _EDGE_PAD)
    src = np.zeros(e_pad, dtype=np.int32)
    dst = np.zeros(e_pad, dtype=np.int32)
    w = np.full(e_pad, INF, dtype=np.int32)
    src[:e] = srcs
    dst[:e] = dsts
    w[:e] = ws
    order = np.argsort(dst, kind="stable")
    return src[order], dst[order], w[order]


def compile_sparse(ls, use_link_metric: bool = True,
                   align: int = _NODE_PAD) -> SparseGraph:
    """Edge-list compilation straight from the LinkState — never builds
    an N x N matrix, so it scales to topologies where the dense snapshot
    cannot."""
    names = tuple(sorted(ls.get_adjacency_databases().keys()))
    index = {name: i for i, name in enumerate(names)}
    n = len(names)
    full: Tuple[List[int], List[int], List[int]] = ([], [], [])
    transit: Tuple[List[int], List[int], List[int]] = ([], [], [])
    for name in names:
        i = index[name]
        overloaded = ls.is_node_overloaded(name)
        for link in ls.ordered_links_from_node(name):
            if not link.is_up():
                continue
            j = index.get(link.other_node(name))
            if j is None:
                continue
            w = (
                min(int(link.metric_from(name)), int(INF) - 1)
                if use_link_metric
                else 1
            )
            full[0].append(i)
            full[1].append(j)
            full[2].append(w)
            if not overloaded:
                transit[0].append(i)
                transit[1].append(j)
                transit[2].append(w)
    fs, fd, fw = _pack(*full)
    ts, td, tw = _pack(*transit)
    return SparseGraph(
        node_names=names,
        node_index=index,
        n=n,
        n_pad=_pad_up(n, align),
        full_src=fs,
        full_dst=fd,
        full_w=fw,
        transit_src=ts,
        transit_dst=td,
        transit_w=tw,
    )


def _relax(d, edge_src, edge_dst, edge_w, n):
    """One batched relaxation: [S, N] -> [S, N]."""
    cand = jnp.minimum(d[:, edge_src] + edge_w[None, :], INF)  # [S, E]

    def seg(row):
        return jax.ops.segment_min(
            row, edge_dst, num_segments=n, indices_are_sorted=True
        )

    relaxed = jax.vmap(seg)(cand)  # [S, N]; empty segments come back max
    return jnp.minimum(d, jnp.minimum(relaxed, INF).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("n",))
def _sparse_from_sources(
    src_ids: jnp.ndarray,
    full_src: jnp.ndarray,
    full_dst: jnp.ndarray,
    full_w: jnp.ndarray,
    t_src: jnp.ndarray,
    t_dst: jnp.ndarray,
    t_w: jnp.ndarray,
    n: int,
):
    s = src_ids.shape[0]
    unit = jnp.full((s, n), INF, dtype=jnp.int32)
    unit = unit.at[jnp.arange(s), src_ids].set(0)
    # init rows == direct edges of each source (+ 0 diagonal): one relax
    # over the FULL edge list, so overloaded sources still originate
    d0 = _relax(unit, full_src, full_dst, full_w, n)

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed, it < n)

    def body(state):
        d, _, it = state
        nxt = _relax(d, t_src, t_dst, t_w, n)
        return nxt, jnp.any(nxt < d), it + 1

    d, _, _ = jax.lax.while_loop(cond, body, (d0, jnp.bool_(True), 0))
    return d


def _as_device_ids(src_ids) -> jnp.ndarray:
    """int32 device ids; a jax array passes through WITHOUT a host sync
    (chained-dispatch timing depends on ids staying on device)."""
    if isinstance(src_ids, jax.Array):
        # one the caller holds as int32 (the KSP2 engine's view batch)
        # is the argument as it is
        if src_ids.dtype == jnp.int32:
            return src_ids
        return src_ids.astype(jnp.int32)
    return jnp.asarray(np.asarray(src_ids, dtype=np.int32))


def sparse_distances_from_sources(graph: SparseGraph, src_ids):
    """Distances [S, N_pad] from a batch of sources over the sparse edge
    lists. Fixed-point-equal to ``ops.spf.distances_from_sources`` on
    the same topology."""
    return _sparse_from_sources(
        _as_device_ids(src_ids),
        jnp.asarray(graph.full_src),
        jnp.asarray(graph.full_dst),
        jnp.asarray(graph.full_w),
        jnp.asarray(graph.transit_src),
        jnp.asarray(graph.transit_dst),
        jnp.asarray(graph.transit_w),
        graph.n_pad,
    )


# -- ELL (fixed-slot) format: the incremental-churn shape ----------------
#
# The flat edge list above is dst-sorted, so patching one node's edges
# after a topology change would reshuffle the whole list. The ELL layout
# gives every node a fixed band of in-edge slots: row j holds
# (src[j, k], w[j, k]) for every edge INTO j, and relaxation is a pure
# gather + K-reduce —
#
#     d'[s, j] = min(d[s, j], min_k d[s, src[j, k]] + w[j, k])
#
# — no scatter/segment-min anywhere (TPU scatters serialize; gathers
# vectorize).
#
# A single uniform band would be sized by the MAX degree, which is
# catastrophic on degree-skewed graphs (a 10k fat-tree: rack switches
# have 8 links, spine switches ~600 — a uniform band is ~98% padding and
# relaxation work becomes O(N x K_max) instead of O(E)). Nodes are
# therefore renumbered by (degree class, name) so that each power-of-two
# degree class occupies a contiguous id range with its own right-sized
# band ("sliced ELL"): total slots stay O(E) and the per-class
# gather-reduce writes a contiguous output slice — still no scatter.
#
# A churn event touches only the affected nodes' band rows (a LinkState
# link is bidirectional, so a node's in-edges are exactly its own links'
# reverse directions and the journal's affected set covers them): an
# O(rows x K_class) device scatter patch, the same resident-array
# pattern as the dense reconverge_step. This is what makes "1k adj
# events/s at 10k nodes" (BASELINE.json config 4) feasible: per event,
# host work and transfer are O(degree), device work O(S x E).

_ELL_SLOT_PAD = 8


@dataclass(frozen=True)
class EllBand:
    """One degree class: nodes [start, start + rows) hold <= k in-edges."""

    start: int
    rows: int
    k: int


@dataclass(frozen=True)
class EllGraph:
    node_names: Tuple[str, ...]  # index == dense id (class-grouped order!)
    node_index: Dict[str, int]
    n: int
    n_pad: int
    bands: Tuple[EllBand, ...]  # static per-topology; jit specializes on it
    src: Tuple[np.ndarray, ...]  # per band [rows, k] int32 (self-loop pad)
    w: Tuple[np.ndarray, ...]  # per band [rows, k] int32 (INF pad)
    overloaded: np.ndarray  # [n_pad] bool
    # band index -> band-local changed row ids, set by ell_patch so
    # EllState.reconverge scatters only those rows; None == full graph
    changed: Optional[Dict[int, np.ndarray]] = None
    # band indices whose k was grown in-place by ell_patch(widen=True)
    # (a row outgrew its slot class): node ids are UNCHANGED, but the
    # band's tensors have a new shape — consumers must re-upload those
    # bands wholesale instead of row-scattering into resident tensors
    widened: Optional[frozenset] = None
    # "in": row j holds edges INTO j (the forward-relax layout);
    # "out": row j holds edges OUT of j (the reversed-graph layout the
    # destination-major route sweep relaxes over)
    direction: str = "in"
    # per-link slot index for "in" graphs, two-level: node id ->
    # {link key -> (band idx, band-local row, slot)}. What makes a
    # single parallel link excludable in the masked KSP2 kernel. The
    # nesting keeps ell_patch's copy O(N) shallow (replace affected
    # nodes' inner dicts) instead of O(E) deep per churn event.
    slot_of: Optional[Dict[int, Dict[Tuple, Tuple[int, int, int]]]] = None
    # filled slots over all bands (w < INF: the directed edges the
    # relax needs, a parallel link each in an "in" graph); the rest of
    # sum(rows * k) is padding. compile_ell counts, ell_patch keeps it
    # by the rows it re-derives; EllState.reconverge says both on its
    # span.
    edges: int = 0


def _in_edges(ls, name, index) -> Dict[int, int]:
    """origin id -> min reverse-direction metric (parallel links: min)."""
    best: Dict[int, int] = {}
    for link in ls.ordered_links_from_node(name):
        if not link.is_up():
            continue
        other = link.other_node(name)
        i = index.get(other)
        if i is None:
            continue
        m = min(int(link.metric_from(other)), int(INF) - 1)
        if i not in best or m < best[i]:
            best[i] = m
    return best


_EMPTY_SLOTS: dict = {}


def link_key(link) -> Tuple:
    """Canonical per-link identity — Link's own precomputed identity
    tuple (the (node, iface) pair set, the same identity the reference
    gives first-class Links, LinkState.h:82 orderedNames_). Parallel
    links between one node pair differ in their iface pairs."""
    return link.ordered_names


import weakref as _weakref

# weakly keyed by the LIVE LinkState (an id()-keyed memo can alias a
# recycled address whose new graph passes through the same version —
# the SP-reuse soak caught that as a cross-world parity break)
_IN_SLOTS_MEMO: "_weakref.WeakKeyDictionary" = (
    _weakref.WeakKeyDictionary()
)


def _in_edge_slots(ls, name, index) -> List[Tuple[int, int, Tuple]]:
    """PER-LINK in-edge slots of ``name``: [(origin id, metric, link
    key)], sorted (origin id, key). Unlike _in_edges, parallel links
    keep their own slots — the KSP2 edge-disjoint masks must be able
    to exclude ONE member of a LAG without killing its siblings
    (reference: LinkState.cpp:763 getKthPaths' linksToIgnore).

    Memoized per live graph x (topology version, node): every input
    below (membership, liveness, metrics incl. holds) bumps the
    topology version when it changes, and churn-path callers re-derive
    the same high-degree node several times per event (padded patch
    rows repeat names). The id mapping is validated by identity on the
    cached entry rather than keyed by ``id(index)`` — a dict id can be
    recycled across garbage-collected mappings within one topology
    version, which would replay slots for the wrong numbering. Callers
    must not mutate the list."""
    per_ls = _IN_SLOTS_MEMO.get(ls)
    if per_ls is None:
        per_ls = {}
        _IN_SLOTS_MEMO[ls] = per_ls
    memo_key = (ls.topology_version, name)
    cached = per_ls.get(memo_key)
    if cached is not None and cached[0] is index:
        return cached[1]
    slots: List[Tuple[int, int, Tuple]] = []
    for link in ls.ordered_links_from_node(name):
        if not link.is_up():
            continue
        other = link.other_node(name)
        i = index.get(other)
        if i is None:
            continue
        m = min(int(link.metric_from(other)), int(INF) - 1)
        slots.append((i, m, link_key(link)))
    slots.sort(key=lambda t: (t[0], t[2]))
    while len(per_ls) > 256:
        per_ls.pop(next(iter(per_ls)))
    per_ls[memo_key] = (index, slots)
    return slots


def _out_edges(ls, name, index) -> Dict[int, int]:
    """dst id -> min forward-direction metric (parallel links: min).
    Row ``name`` of an out-ELL graph holds (dst, w(name -> dst)) — the
    in-edge bands of the REVERSED graph, which is what the
    destination-major route sweep (ops.route_sweep) relaxes over."""
    best: Dict[int, int] = {}
    for link in ls.ordered_links_from_node(name):
        if not link.is_up():
            continue
        other = link.other_node(name)
        i = index.get(other)
        if i is None:
            continue
        m = min(int(link.metric_from(name)), int(INF) - 1)
        if i not in best or m < best[i]:
            best[i] = m
    return best


def _fill_row(src_row, w_row, edges) -> None:
    for slot, (i, m) in enumerate(sorted(edges.items())):
        src_row[slot] = i
        w_row[slot] = m


def _band_of(graph: EllGraph, node_id: int) -> Tuple[int, EllBand]:
    for bi, band in enumerate(graph.bands):
        if band.start <= node_id < band.start + band.rows:
            return bi, band
    raise KeyError(node_id)


def compile_ell(ls, align: int = _NODE_PAD,
                direction: str = "in") -> EllGraph:
    """Sliced-ELL compilation from the LinkState: O(E) host work and
    O(E) total slots, no dense matrix. ``direction="out"`` builds the
    reversed-graph bands (row j = out-edges of j) consumed by
    ops.route_sweep.

    Direction "in" gives every LINK its own slot (parallel links are
    NOT min-collapsed) and records a slot index, so build_edge_masks
    can exclude one member of a parallel group — the KSP2 requirement.
    Distances are unchanged (the relax min()s across slots). Direction
    "out" keeps the collapsed per-neighbor layout: the route sweep's
    next-hop counts are per-NEIGHBOR there, matching the grouped
    backend's digest semantics."""
    per_link = direction == "in"
    edges_of = _in_edges if direction == "in" else _out_edges
    raw_names = sorted(ls.get_adjacency_databases().keys())
    raw_index = {name: i for i, name in enumerate(raw_names)}
    if per_link:
        # banding only needs the SLOT COUNT, which is independent of
        # the id mapping — skip the full slot derivation (metric reads,
        # link keys, sort) the fill pass below will do anyway
        degree = {
            name: max(
                1,
                sum(
                    1
                    for link in ls.ordered_links_from_node(name)
                    if link.is_up()
                    and link.other_node(name) in raw_index
                ),
            )
            for name in raw_names
        }
    else:
        degree = {
            name: max(1, len(edges_of(ls, name, raw_index)))
            for name in raw_names
        }
    # class id = padded power-of-two >= degree; group by (class, name)
    def class_k(d: int) -> int:
        k = _ELL_SLOT_PAD
        while k < d:
            k *= 2
        return k

    names = tuple(
        sorted(raw_names, key=lambda nm: (class_k(degree[nm]), nm))
    )
    index = {name: i for i, name in enumerate(names)}
    n = len(names)
    n_pad = _pad_up(n, align)

    bands: List[EllBand] = []
    srcs: List[np.ndarray] = []
    ws: List[np.ndarray] = []
    slot_of: Dict[int, Dict[Tuple, Tuple[int, int, int]]] = {}
    overloaded = np.zeros(n_pad, dtype=bool)
    n_edges = 0
    i = 0
    while i < n:
        k = class_k(degree[names[i]])
        j = i
        while j < n and class_k(degree[names[j]]) == k:
            j += 1
        rows = j - i
        src_b = np.tile(
            np.arange(i, j, dtype=np.int32)[:, None], (1, k)
        )  # self-loop padding: inert with w=INF
        w_b = np.full((rows, k), INF, dtype=np.int32)
        for r, name in enumerate(names[i:j]):
            if per_link:
                nid = index[name]
                nd: Dict[Tuple, Tuple[int, int, int]] = {}
                row_slots = _in_edge_slots(ls, name, index)
                for slot, (sid, m, key) in enumerate(row_slots):
                    src_b[r, slot] = sid
                    w_b[r, slot] = m
                    nd[key] = (len(bands), r, slot)
                slot_of[nid] = nd
                n_edges += len(row_slots)
            else:
                edges = edges_of(ls, name, index)
                _fill_row(src_b[r], w_b[r], edges)
                n_edges += len(edges)
        bands.append(EllBand(start=i, rows=rows, k=k))
        srcs.append(src_b)
        ws.append(w_b)
        i = j
    for name in names:
        overloaded[index[name]] = ls.is_node_overloaded(name)
    return EllGraph(
        node_names=names, node_index=index, n=n, n_pad=n_pad,
        bands=tuple(bands), src=tuple(srcs), w=tuple(ws),
        overloaded=overloaded, direction=direction,
        slot_of=slot_of if per_link else None,
        edges=n_edges,
    )


def ell_patch(
    graph: EllGraph, ls, affected, widen: bool = False
) -> Optional[EllGraph]:
    """New EllGraph with only the affected nodes' band rows re-derived;
    ``patched.changed`` maps band index -> band-local row ids. Returns
    None when the node set changed, or — unless ``widen`` — when a row
    outgrew its slot-class band (callers fall back to a full compile,
    which may renumber).

    ``widen=True`` grows an overflowing band's k in place instead
    (slots double to the next power of two; node ids are UNCHANGED, so
    resident per-node device state like the route engine's DR matrix
    stays valid). Widened band indices are recorded in
    ``patched.widened``: their tensors changed SHAPE, so a consumer
    holding resident band tensors must re-upload those bands wholesale
    (a row-scatter into the old shape cannot represent them) and
    expects a one-time jit recompile (band shapes are static args)."""
    # node-set validation without sorting 100k names per event: a
    # removal alone changes the count; an add (or rename = remove+add)
    # puts the new name in ``affected``, where the per-name
    # node_index lookup below rejects it
    if len(ls.get_adjacency_databases()) != graph.n:
        return None
    per_link = graph.slot_of is not None
    edges_of = _in_edges if graph.direction == "in" else _out_edges
    src = list(graph.src)
    w = list(graph.w)
    bands = list(graph.bands)
    overloaded = graph.overloaded.copy()
    slot_of = dict(graph.slot_of) if per_link else None
    changed: Dict[int, List[int]] = {}
    widened: set = set()
    copied: set = set()
    n_edges = graph.edges
    for name in affected:
        i = graph.node_index.get(name)
        if i is None:
            return None
        if per_link:
            slots = _in_edge_slots(ls, name, graph.node_index)
        else:
            edges = edges_of(ls, name, graph.node_index)
        bi, band = _band_of(graph, i)
        band = bands[bi]  # may already have been widened this event
        n_entries = len(slots) if per_link else len(edges)
        if n_entries > band.k:
            if not widen:
                return None
            new_k = band.k
            while new_k < n_entries:
                new_k *= 2
            grow = new_k - band.k
            # self-loop src + INF w padding: inert in every relax
            pad_src = np.tile(
                np.arange(
                    band.start, band.start + band.rows, dtype=np.int32
                )[:, None],
                (1, grow),
            )
            src[bi] = np.concatenate([src[bi], pad_src], axis=1)
            w[bi] = np.concatenate(
                [w[bi], np.full((band.rows, grow), INF, np.int32)],
                axis=1,
            )
            bands[bi] = EllBand(
                start=band.start, rows=band.rows, k=new_k
            )
            band = bands[bi]
            widened.add(bi)
            copied.add(bi)  # concatenate already made fresh arrays
        if bi not in copied:
            src[bi] = src[bi].copy()
            w[bi] = w[bi].copy()
            copied.add(bi)
        r = i - band.start
        n_edges += n_entries - int(np.count_nonzero(w[bi][r] < INF))
        src[bi][r] = np.full(band.k, i, dtype=np.int32)
        w[bi][r] = INF
        if per_link:
            # replace this node's inner slot dict wholesale (the outer
            # copy above was shallow, so the old graph keeps its own)
            nd: Dict[Tuple, Tuple[int, int, int]] = {}
            for slot, (sid, m, key) in enumerate(slots):
                src[bi][r, slot] = sid
                w[bi][r, slot] = m
                nd[key] = (bi, r, slot)
            slot_of[i] = nd
        else:
            _fill_row(src[bi][r], w[bi][r], edges)
        overloaded[i] = ls.is_node_overloaded(name)
        changed.setdefault(bi, []).append(r)
    return EllGraph(
        node_names=graph.node_names, node_index=graph.node_index,
        n=graph.n, n_pad=graph.n_pad, bands=tuple(bands),
        src=tuple(src), w=tuple(w), overloaded=overloaded,
        changed={bi: np.asarray(sorted(rs), dtype=np.int32)
                 for bi, rs in changed.items()},
        direction=graph.direction,
        slot_of=slot_of,
        widened=frozenset(widened) if widened else None,
        edges=n_edges,
    )


def band_row_edge_changes(
    old: EllGraph, patched: EllGraph
) -> List[Tuple[int, int, int, int]]:
    """ALL directed-edge weight changes implied by a patch's changed
    rows: [(tail id, head id, old collapsed weight, new collapsed
    weight)] for every (tail, head) whose min-over-parallel-slots
    weight moved (removal reads as old_w -> INF, addition as
    INF -> new_w). O(changed rows x K_class) host work, no band scan.
    The full (old, new) pair is what lets the warm-start journal MERGE
    stacked patches: the first touch of an edge snapshots the weight
    the resident distances were solved under, later touches only move
    the current side."""
    out: List[Tuple[int, int, int, int]] = []
    changed = patched.changed or {}
    for bi, rows in changed.items():
        band = patched.bands[bi]
        for r in np.asarray(rows):
            r = int(r)
            head = band.start + r
            old_w: Dict[int, int] = {}
            for s, wv in zip(old.src[bi][r], old.w[bi][r]):
                s = int(s)
                wv = int(wv)
                if s == head or wv >= INF:
                    continue  # self-loop / INF padding slots
                if wv < old_w.get(s, INF):
                    old_w[s] = wv
            new_w: Dict[int, int] = {}
            for s, wv in zip(patched.src[bi][r], patched.w[bi][r]):
                s = int(s)
                wv = int(wv)
                if s == head or wv >= INF:
                    continue
                if wv < new_w.get(s, INF):
                    new_w[s] = wv
            for s, wo in old_w.items():
                wn = new_w.get(s, INF)
                if wn != wo:
                    out.append((s, head, wo, wn))
            for s, wn in new_w.items():
                if s not in old_w:
                    out.append((s, head, INF, wn))
    return out


def band_row_edge_delta(
    old: EllGraph, patched: EllGraph
) -> List[Tuple[int, int, int]]:
    """Directed-edge weight INCREASES implied by a patch's changed
    rows: [(tail id, head id, old collapsed weight)] for every
    (tail, head) whose min-over-parallel-slots weight went UP (an edge
    removal reads as old_w -> INF). Decreases are deliberately absent:
    a min-relaxation warm start only needs the increase-affected cone
    — decreased rows keep their previous distances as valid upper
    bounds. Thin view over band_row_edge_changes."""
    return [
        (s, h, wo)
        for s, h, wo, wn in band_row_edge_changes(old, patched)
        if wn > wo
    ]


# sentinel "increase" edge that flags EVERY row's seed for reset (the
# tight test d[0] + 0 == d[0] holds unconditionally): encoding a full
# cold restart as a 1-edge delta keeps the warm and cold paths on ONE
# compiled executable instead of two
_FORCE_RESET_EDGE = (0, 0, 0)


def pad_increase_edges(
    inc: List[Tuple[int, int, int]], bucket_min: int = 4
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pack an increase-edge delta into pow-of-two bucketed arrays
    (tails, heads, old weights). Padding entries carry w = INF, which
    the tight test masks out, so every bucket size is one compiled
    shape."""
    bucket = bucket_min
    while bucket < len(inc):
        bucket *= 2
    tails = np.zeros(bucket, dtype=np.int32)
    heads = np.zeros(bucket, dtype=np.int32)
    ws = np.full(bucket, INF, dtype=np.int32)
    for x, (t, h, w) in enumerate(inc):
        tails[x] = t
        heads[x] = h
        ws[x] = w
    return tails, heads, ws


def direct_metrics(graph: EllGraph, src_id: int, node_ids) -> np.ndarray:
    """Host-side direct min-metric src_id -> each node in node_ids (INF
    when not adjacent), read from the in-edge bands."""
    out = np.full(len(node_ids), INF, dtype=np.int32)
    for x, j in enumerate(node_ids):
        bi, band = _band_of(graph, int(j))
        r = int(j) - band.start
        hits = graph.src[bi][r] == src_id
        if hits.any():
            out[x] = graph.w[bi][r][hits].min()
    return out


def _mask_transit_cols(d, overloaded):
    """Distance columns of overloaded nodes read as INF: the rows a relax
    gathers for their out-edges then never extend a path. One select
    over [S, N]; ``overloaded=None`` is the unmasked (origination) relax
    and applies nothing, decided at trace time."""
    if overloaded is None:
        return d
    return jnp.where(overloaded[None, :], INF, d)


def _ell_band_relaxed(d_t, bands, srcs_t, ws_t):
    """The gather + add + K-reduce of one pass, band by band: yields
    (first column, rows, relaxed [S, rows]) where relaxed[s, v] is the
    least min(d_t[s, u] + w, INF) over v's in-slots (u, w). ``d_t`` is
    read as given: the caller has masked what must not extend a path."""
    pos = 0
    for band, s_b, w_b in zip(bands, srcs_t, ws_t):
        assert band.start == pos, (band, pos)
        gathered = d_t[:, s_b]  # [S, rows, k]
        relaxed = jnp.min(
            jnp.minimum(gathered + w_b[None, :, :], INF), axis=2
        )
        yield pos, band.rows, relaxed.astype(jnp.int32)
        pos += band.rows


def _ell_relax(d, bands, srcs_t, ws_t, overloaded):
    """One masked relaxation over the class bands: [S, N] -> [S, N] as
    pure gather + reduce per band, writing contiguous output slices.
    Edges originating at overloaded nodes never extend paths: the mask
    sits on the distance columns the gather reads (_mask_transit_cols),
    not on the edge slots. min(INF + w, INF) and min(d + INF, INF) are
    the same INF, and a mask looked up per edge slot is a scalar gather
    per edge per pass: 7 ms of the 8.9 ms the chip was busy in a
    4992-node solve. Each band's own columns take the min with the
    UNMASKED d."""
    d_t = _mask_transit_cols(d, overloaded)
    parts = []
    end = 0
    for pos, rows, relaxed in _ell_band_relaxed(d_t, bands, srcs_t, ws_t):
        end = pos + rows
        parts.append(jnp.minimum(d[:, pos:end], relaxed))
    parts.append(d[:, end:])  # padding columns: unchanged
    return jnp.concatenate(parts, axis=1)


def _ell_relax_raw(d, bands, srcs_t, ws_t, overloaded):
    """_ell_relax without its final min with ``d``: what each column's
    in-slots OFFER it under ``d``, [S, N], INF in the padding columns
    (they have no in-slot). The same pass over the same slots, so it
    costs what a relax pass costs; _cone_seed asks it which columns
    their in-edges still support."""
    d_t = _mask_transit_cols(d, overloaded)
    parts = [
        relaxed
        for _, _, relaxed in _ell_band_relaxed(d_t, bands, srcs_t, ws_t)
    ]
    real = sum(band.rows for band in bands)
    s, n = d.shape
    parts.append(jnp.full((s, n - real), INF, dtype=jnp.int32))
    return jnp.concatenate(parts, axis=1)


def _tight_increases(d_prev, inc_tail, inc_head, inc_w):
    """[S, E] bool: increased edge e lay on an old shortest path of
    batch row s, d_prev[s, head] == d_prev[s, tail] + w_old. Padding
    entries (w_old = INF, pad_increase_edges) are never tight."""
    return (
        jnp.minimum(d_prev[:, inc_tail] + inc_w[None, :], INF)
        == d_prev[:, inc_head]
    ) & (inc_w[None, :] < INF)


def _warm_seed(d_prev, inc_tail, inc_head, inc_w, d0):
    """Seed the relaxation fixed point from the previous distance rows,
    resetting WHOLE every batch row that has one tight increased edge.
    Returns (seed [S, N], reset [S] bool: the rows that restarted).

    Soundness: the masked min-relax closure of any seed S with
    d* <= S <= d0 equals d* (monotone closure squeezed between the
    fixed point and the cold init's closure). d0 >= d* always; a
    previous row d_prev[s] >= d*_new[s] unless some increased edge lay
    on an old shortest path from s — exactly when the edge was TIGHT
    under the old distances (_tight_increases). A tight row restarts
    from the cold init d0 in every column, not only in the cone behind
    the increased edge, so its closure takes the source's hop
    eccentricity in passes whatever the edge was (4-6 on a fat-tree;
    198 from a corner of the 100x100 grid, where every link pointing
    away from the corner is tight). That is the price the callers of
    _ell_fixed_point(warm=...) and _tenant_view_solve still pay, whose
    loops vote across a mesh or a tenant axis; the served single-chip
    program, _ell_reconverge, takes this function's ``reset`` and
    narrows it with _cone_seed. Every other row seeds min(d_prev, d0)
    (the min keeps the unmasked-origination first-hop floor that d_prev
    already carries and d0 re-derives). Raw (unmasked) old weights make
    the test conservative under overload masks; mask CHANGES must reach
    it as increases (EllState's journal emits a drained node's
    out-edges) or be forced to a full reset by the caller (the
    _FORCE_RESET_EDGE sentinel). Bit-identical to a cold solve: int32
    min-relaxation has a unique fixed point, no float reassociation."""
    reset = jnp.any(
        _tight_increases(d_prev, inc_tail, inc_head, inc_w), axis=1
    )
    return jnp.where(reset[:, None], d0, jnp.minimum(d_prev, d0)), reset


def _cone_seed(reset, whole, d0, d_prev, relax_raw):
    """_warm_seed narrowed from the row to the cone: inside a row that
    the tight test flags (``reset`` [S]) keep d_prev[s, v] in every
    column that an in-edge still SUPPORTS, and fall back to d0[s, v]
    only in the rest. Returns (seed [S, N], support passes run).

    ``relax_raw(d)`` is one pass over the PATCHED bands under the
    CURRENT overload mask without the final min with d (_ell_relax_raw
    closed over them). Column v of a flagged row is supported when
      - base: d0[s, v] <= d_prev[s, v] (the source, its neighbours at an
        unchanged or lower metric) or d_prev[s, v] == INF, or
      - step: some in-slot (u, v, w) has min(mask(d_prev)[s, u] + w,
        INF) <= d_prev[s, v] with u supported (an overloaded u reads
        INF and supports nothing).
    Computed as the least fixed point of its complement: nothing is
    invalid to begin with; a pass marks every non-base column whose
    offer under where(invalid, INF, d_prev) is above d_prev; the loop
    ends at the first pass that marks nothing. A cone of depth c takes
    c + 1 passes, each at the cost of a relax pass, and the loop runs
    ZERO times when no row is flagged (or all flagged rows are
    ``whole``). The relax loop then re-derives the cone from its rim,
    c + 1 passes more, instead of the row from its source.

    Soundness (the squeeze of _warm_seed wants d* <= seed <= d0).
    seed <= d0 by construction. A supported non-base v has a supported
    parent u with d_prev[u] + w <= d_prev[v]; with every weight >= 1
    that is d_prev[u] < d_prev[v], so the chain of supports descends
    strictly and ends at a base column, where d* <= d0 <= d_prev;
    walking back, d*[v] <= d*[u] + w <= d_prev[u] + w <= d_prev[v].
    Nothing is asked of d_prev but to be the rows this batch was last
    solved for (EllState's _warm_key), and no increase list is needed
    for soundness: ``reset`` only says where looking is worth a pass.

    The one precondition is weights >= 1: two columns joined by
    zero-metric links could support each other after both lost their
    real parent. ``whole`` [S] marks the flagged rows for which the
    caller cannot rule that out; they restart in every column, as
    _warm_seed has it, with no support pass. _ell_reconverge sets it
    for a row with a tight increased edge of old weight 0, and for
    every flagged row when a real slot of the patched bands carries 0.
    The first is also exactly _FORCE_RESET_EDGE = (0, 0, 0), so a cold
    or re-keyed solve (d_prev zeros, or another batch's rows) needs no
    case of its own."""
    whole = reset & whole
    cand = (reset & ~whole)[:, None] & (d0 > d_prev)

    def cond(state):
        _, grew, _ = state
        return grew

    def body(state):
        invalid, _, it = state
        offer = relax_raw(jnp.where(invalid, INF, d_prev))
        nxt = invalid | (cand & (offer > d_prev))
        return nxt, jnp.any(nxt != invalid), it + 1

    # every pass but the last adds a column to a finite set: no bound
    # on ``it`` is needed, and one that cut the loop short would leave
    # an unsupported column in the seed
    invalid, _, passes = jax.lax.while_loop(
        cond,
        body,
        (jnp.broadcast_to(whole[:, None], d_prev.shape), jnp.any(cand), 0),
    )
    return jnp.where(invalid, d0, jnp.minimum(d_prev, d0)), passes


def _solve_stats(passes, reset_rows):
    """The two scalars a solve carries out beside its packed view, as
    one int32[2]: the passes over the bands its ``while_loop``s ran
    (relax passes, and in _ell_reconverge the support passes of
    _cone_seed before them: each streams the same slots; the pass that
    builds the cold init is not one of them) and the batch rows that a
    tight increased edge flagged (_ell_reconverge: the rows whose cone
    was computed or that restarted whole; the cold program: all)."""
    return jnp.stack([
        jnp.asarray(passes, dtype=jnp.int32),
        jnp.asarray(reset_rows, dtype=jnp.int32),
    ])


def _device_direct_metrics(srcs_t, ws_t, srcs, bands):
    """On-device direct min-metric srcs[0] -> each batch node (INF when
    not adjacent, and for the source itself) — the resident-band mirror
    of host direct_metrics + _batch_args, so the fused churn dispatch
    needs no host band reads at all."""
    src_id = srcs[0]
    cols = []
    for band, s_b, w_b in zip(bands, srcs_t, ws_t):
        cols.append(jnp.min(jnp.where(s_b == src_id, w_b, INF), axis=1))
    direct = jnp.concatenate(cols)  # [real rows]
    w_sv = direct[srcs]
    return jnp.where(srcs == src_id, INF, w_sv).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bands", "n"))
def _ell_view_batch(srcs_t, ws_t, overloaded, srcs, w_sv, bands, n):
    """Batched {src} + neighbors distances + packed first hops over the
    sliced-ELL graph — the sparse mirror of ops.spf._spf_view_batch.
    w_sv: [B] host-computed direct metric source -> batch node.
    Returns (packed [2B, N], _solve_stats: every row starts cold)."""
    b = srcs.shape[0]
    unit = jnp.full((b, n), INF, dtype=jnp.int32)
    unit = unit.at[jnp.arange(b), srcs].set(0)
    # init rows: one UNMASKED relax (overloaded sources still originate)
    d0 = _ell_relax(unit, bands, srcs_t, ws_t, None)

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed, it < n)

    def body(state):
        d, _, it = state
        nxt = _ell_relax(d, bands, srcs_t, ws_t, overloaded)
        return nxt, jnp.any(nxt < d), it + 1

    d, _, it = jax.lax.while_loop(cond, body, (d0, jnp.bool_(True), 0))
    fh = _first_hops_from_rows(d, srcs, w_sv, overloaded, n)
    packed = jnp.concatenate([d, fh.astype(jnp.int32)], axis=0)
    return packed, _solve_stats(it, b)


def _first_hops_from_rows(d, srcs, w_sv, overloaded, n):
    """ECMP first-hop bits [B, N] from the batch's distance rows (same
    algebra as the dense kernel): neighbor v forwards toward j iff
    w(src,v) + d(v, j) == d(src, j), plus the direct-neighbor case.
    Shared by _ell_view_batch and the KSP2 engine's rows solves
    (_pack_view_rows) — the engine's preloaded view must stay
    byte-identical to the fallback dispatch."""
    b = srcs.shape[0]
    d_src = d[0]
    is_neighbor = w_sv < INF
    reachable = d_src < INF
    total = jnp.minimum(w_sv[:, None] + d, INF)
    transit_ok = (
        is_neighbor[:, None]
        & (~overloaded[srcs])[:, None]
        & (total == d_src[None, :])
    )
    col_is_self = srcs[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (b, n), 1
    )
    direct_ok = col_is_self & (is_neighbor & (w_sv == d_src[srcs]))[:, None]
    return (transit_ok | direct_ok) & reachable[None, :]


def _scatter_band_rows(src, w, ids, rows_src, rows_w):
    """One band's row scatter, written once: _ell_reconverge traces it
    in-program per band, _patch_band is the same expression as a
    program of its own. ``ids`` is a pad_patch_rows bucket (padding
    repeats a row — an idempotent scatter)."""
    return src.at[ids, :].set(rows_src), w.at[ids, :].set(rows_w)


@functools.partial(
    jax.jit,
    # the resident band tensors are dead after the call, as in
    # _ell_reconverge: scatter in place instead of copying the band
    donate_argnums=(0, 1),
)
def _patch_band(src, w, ids, rows_src, rows_w):
    """The solve-free patch (EllState.apply_patch): ONE program per
    band, keyed (band shape x bucket) — never fused across bands,
    which would key on the cross product of their buckets."""
    return _scatter_band_rows(src, w, ids, rows_src, rows_w)


def _reconverge_seed(srcs_t, ws_t, inc_tail, inc_head, inc_w, overloaded,
                     d_prev, srcs, bands, n):
    """The seed _ell_reconverge relaxes from, over the PATCHED bands:
    (seed [B, N], cold init d0 [B, N], support passes run, reset [B]
    bool). Traced into that one program; a function of its own so a
    test can hold the seed itself, and not only the fixed point it
    closes to, to d* <= seed <= d0."""
    b = srcs.shape[0]
    unit = jnp.full((b, n), INF, dtype=jnp.int32)
    unit = unit.at[jnp.arange(b), srcs].set(0)
    # init rows: one UNMASKED relax (overloaded sources still originate)
    d0 = _ell_relax(unit, bands, srcs_t, ws_t, None)
    tight = _tight_increases(d_prev, inc_tail, inc_head, inc_w)
    reset = jnp.any(tight, axis=1)
    # what _cone_seed's induction cannot stand: a weight below 1
    # (padding slots carry INF)
    zero_slot = functools.reduce(
        jnp.logical_or, [jnp.any(w_b < 1) for w_b in ws_t]
    )
    whole = jnp.any(tight & (inc_w[None, :] < 1), axis=1) | zero_slot
    with jax.named_scope("ell.cone_seed"):
        seed, support_passes = _cone_seed(
            reset, whole, d0, d_prev,
            lambda x: _ell_relax_raw(x, bands, srcs_t, ws_t, overloaded),
        )
    return seed, d0, support_passes, reset


@functools.partial(
    jax.jit,
    static_argnames=("bands", "n"),
    # the previous bands and distance rows are dead after the call —
    # donating them lets XLA scatter/relax in place instead of copying
    # multi-hundred-MB band+distance blocks every churn event
    donate_argnums=(0, 1, 9),
)
def _ell_reconverge(srcs_t, ws_t, patch_ids_t, patch_src_t, patch_w_t,
                    inc_tail, inc_head, inc_w, overloaded, d_prev,
                    srcs, bands, n):
    """Fused churn executable: scatter the patched rows, derive the
    direct metrics on device, warm-seed the fixed point from d_prev
    (in a row an increase is tight in, the columns no in-edge supports
    any more restart from the cold init: _cone_seed), pack distances +
    first hops.
    Only the O(rows x K) patch + O(|delta|) increase edges cross
    host->device; only the packed [2B, N] view and the solve's two
    scalars (_solve_stats) cross back. Warm, cold (_FORCE_RESET_EDGE)
    and zero-metric inputs are one executable: which rows take the
    cone and which restart whole is decided from the arrays."""
    new_src, new_w = zip(*(
        _scatter_band_rows(s, w, ids, ps, pw)
        for s, w, ids, ps, pw in zip(
            srcs_t, ws_t, patch_ids_t, patch_src_t, patch_w_t
        )
    ))
    w_sv = _device_direct_metrics(new_src, new_w, srcs, bands)
    seed, _, support_passes, reset = _reconverge_seed(
        new_src, new_w, inc_tail, inc_head, inc_w, overloaded, d_prev,
        srcs, bands, n,
    )

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed, it < n)

    def body(state):
        d, _, it = state
        nxt = _ell_relax(d, bands, new_src, new_w, overloaded)
        return nxt, jnp.any(nxt < d), it + 1

    with jax.named_scope("ell.relax"):
        d, _, it = jax.lax.while_loop(
            cond, body, (seed, jnp.bool_(True), 0)
        )
    fh = _first_hops_from_rows(d, srcs, w_sv, overloaded, n)
    packed = jnp.concatenate([d, fh.astype(jnp.int32)], axis=0)
    return new_src, new_w, packed, d, _solve_stats(
        support_passes + it, jnp.sum(reset)
    )


def _batch_args(graph: EllGraph, srcs):
    srcs = np.asarray(srcs, dtype=np.int32)
    w_sv = direct_metrics(graph, int(srcs[0]), srcs)
    # the source itself is never its own neighbor
    w_sv[srcs == srcs[0]] = INF
    return jnp.asarray(srcs), jnp.asarray(w_sv)


def ell_view_batch_packed(graph: EllGraph, srcs):
    """Distances + first hops [2B, N_pad] (packed, one transfer) for a
    padded source batch over the sliced-ELL graph."""
    srcs_dev, w_sv = _batch_args(graph, srcs)
    return _ell_view_batch(
        tuple(jnp.asarray(s) for s in graph.src),
        tuple(jnp.asarray(w) for w in graph.w),
        jnp.asarray(graph.overloaded),
        srcs_dev, w_sv, graph.bands, graph.n_pad,
    )[0]


def ell_source_batch(graph: EllGraph, ls, src_name: str):
    """The hot-path source batch over an ELL graph: [src] + sorted
    unique up-neighbor ids, padded by repeating src to a power-of-two
    bucket (>= 8, capped at n_pad) — the ELL analogue of
    ops.spf.source_batch, and the one place this layout is defined for
    the sparse path."""
    sid = graph.node_index[src_name]
    nbrs = sorted(
        {
            graph.node_index[link.other_node(src_name)]
            for link in ls.links_from_node(src_name)
            if link.is_up() and link.other_node(src_name) in graph.node_index
        }
    )
    srcs = [sid] + nbrs
    bucket = 8
    while bucket < len(srcs):
        bucket *= 2
    bucket = min(bucket, graph.n_pad)
    return srcs + [sid] * (bucket - len(srcs))


def _ell_fixed_point(srcs_t, ws_t, overloaded, src_ids, bands, n,
                     vote=None, warm=None):
    """Shared ELL relaxation fixed-point: distances [S, N] from unit
    init. ``vote`` turns the local convergence bit into the global
    stop condition (identity when None; a psum over the mesh axis for
    the sharded variant — every device iterates until ALL shards
    converge; the relaxation is idempotent past the fixed point).
    Init rows are one UNMASKED relax so overloaded sources still
    originate (reference: LinkState.cpp:831-838). ``warm`` is an
    optional (d_prev, inc_tail, inc_head, inc_w) tuple: seed from the
    previous distances via _warm_seed (bit-identical fixed point,
    fewer iterations under churn). Returns ``(d, passes)``: the
    distances and the loop's own counter, the relax passes it ran (the
    pass that builds the init is not one of them), as _ell_reconverge
    carries its own."""
    s = src_ids.shape[0]
    unit = jnp.full((s, n), INF, dtype=jnp.int32)
    unit = unit.at[jnp.arange(s), src_ids].set(0)
    d0 = _ell_relax(unit, bands, srcs_t, ws_t, None)
    if warm is not None:
        d_prev, inc_tail, inc_head, inc_w = warm
        d0, _ = _warm_seed(d_prev, inc_tail, inc_head, inc_w, d0)

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed > 0, it < n)

    def body(state):
        d, _, it = state
        nxt = _ell_relax(d, bands, srcs_t, ws_t, overloaded)
        local = jnp.any(nxt < d).astype(jnp.int32)
        return nxt, local if vote is None else vote(local), it + 1

    d, _, it = jax.lax.while_loop(cond, body, (d0, jnp.int32(1), 0))
    return d, jnp.asarray(it, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("bands", "n"))
def _ell_from_sources(srcs_t, ws_t, overloaded, src_ids, bands, n):
    """Distances [S, N] from a batch of sources over the sliced-ELL
    bands — pure gather + K-reduce per band, NO segment-min scatter
    anywhere. This is the all-sources workhorse: the flat-edge-list
    formulation (_sparse_from_sources) spends its time in
    ``jax.ops.segment_min``, which lowers to serialized scatters on
    TPU; this one vectorizes."""
    return _ell_fixed_point(srcs_t, ws_t, overloaded, src_ids, bands, n)[0]


def ell_distances_from_sources(graph: EllGraph, src_ids,
                               state: "EllState" = None):
    """Distances [S, N_pad] from a batch of sources over the ELL graph.
    Pass ``state`` to reuse device-resident bands (no re-upload).
    Fixed-point-equal to ``sparse_distances_from_sources`` (and the
    host Dijkstra) on the same topology."""
    srcs_t = state.src if state is not None else tuple(
        jnp.asarray(s) for s in graph.src
    )
    ws_t = state.w if state is not None else tuple(
        jnp.asarray(w) for w in graph.w
    )
    ov = (
        state.overloaded
        if state is not None
        else jnp.asarray(graph.overloaded)
    )
    return _ell_from_sources(
        srcs_t, ws_t, ov,
        _as_device_ids(src_ids),
        graph.bands, graph.n_pad,
    )


def iter_ell_all_sources(graph: EllGraph, block: int = 2048):
    """All-sources distances, yielded as (start, [block, N_pad] host
    array) source blocks — the caller streams them so the full
    [N, N] product never has to exist on host (at 100k that is 40 GB).
    The resident bands upload once (EllState) and each block is one
    dispatch + one readback."""
    state = EllState(graph)
    n = graph.n_pad
    # all block id vectors go up front in one async burst: uploading per
    # block would serialize a host round trip between blocks
    id_blocks = []
    for start in range(0, n, block):
        ids = np.arange(start, min(start + block, n), dtype=np.int32)
        if len(ids) < block:  # keep one compiled shape
            ids = np.concatenate(
                [ids, np.full(block - len(ids), ids[-1], np.int32)]
            )
        id_blocks.append((start, jnp.asarray(ids)))
    for start, ids in id_blocks:
        yield start, np.asarray(
            ell_distances_from_sources(graph, ids, state=state)
        )


def ell_all_sources(graph: EllGraph, block: int = 2048) -> np.ndarray:
    """Materialized all-sources distances [N_pad, N_pad] (moderate N
    only — use iter_ell_all_sources past ~16k nodes)."""
    n = graph.n_pad
    out = np.empty((n, n), dtype=np.int32)
    for start, d_blk in iter_ell_all_sources(graph, block=block):
        take = min(block, n - start)
        out[start : start + take] = d_blk[:take]
    return out


def _ell_relax_masked(d, bands, srcs_t, ws_t, masks_t, overloaded):
    """One relaxation with a PER-BATCH edge mask: [B, N] -> [B, N].
    masks_t[bi] is [B, rows, k] bool — True == edge excluded for that
    batch element (the KSP2 edge-disjoint second-path graphs). The
    overload mask sits on the distance columns, as in _ell_relax."""
    d_t = _mask_transit_cols(d, overloaded)
    parts = []
    pos = 0
    for band, s_b, w_b, m_b in zip(bands, srcs_t, ws_t, masks_t):
        assert band.start == pos, (band, pos)
        w_batched = jnp.where(m_b, INF, w_b[None, :, :])  # [B, rows, k]
        gathered = d_t[:, s_b]  # [B, rows, k]
        relaxed = jnp.min(
            jnp.minimum(gathered + w_batched, INF), axis=2
        )
        parts.append(
            jnp.minimum(d[:, pos : pos + band.rows], relaxed.astype(jnp.int32))
        )
        pos += band.rows
    parts.append(d[:, pos:])
    return jnp.concatenate(parts, axis=1)


def _ell_masked_fixed_point(srcs_t, ws_t, masks_t, overloaded, src_id,
                            bands, n, vote=None):
    """Single-source distances over B differently-masked graphs:
    [B, N] — the device half of batched KSP2 second-path computation
    (reference semantics: LinkState.cpp:763 getKthPaths' runSpf with
    linksToIgnore, one per destination). Init is an unmasked-overload
    relax so an overloaded SOURCE still originates (mirrors
    _ell_view_batch). ``vote`` turns the local convergence bit into the
    global stop condition (identity when None; a psum for the sharded
    variant) — the SAME parameterization as _ell_fixed_point, and the
    ONE home of this loop (three call sites share it). Returns
    ``(d, passes)`` as _ell_fixed_point does: every row starts cold,
    so the passes are the source's hop eccentricity in the deepest of
    the batch's masked graphs (the init pass reaches one hop and is not
    counted; the pass that finds nothing left is)."""
    b = masks_t[0].shape[0]
    unit = jnp.full((b, n), INF, dtype=jnp.int32)
    unit = unit.at[:, src_id].set(0)
    d0 = _ell_relax_masked(unit, bands, srcs_t, ws_t, masks_t, None)

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed > 0, it < n)

    def body(state):
        d, _, it = state
        nxt = _ell_relax_masked(
            d, bands, srcs_t, ws_t, masks_t, overloaded
        )
        local = jnp.any(nxt < d).astype(jnp.int32)
        return nxt, local if vote is None else vote(local), it + 1

    d, _, it = jax.lax.while_loop(cond, body, (d0, jnp.int32(1), 0))
    return d, jnp.asarray(it, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("bands", "n"))
def _ell_masked_source_batch(srcs_t, ws_t, masks_t, overloaded, src_id,
                             bands, n):
    """The masked batch as one program: ``(rows [B, N], passes)``, two
    outputs ready together, so the pass count costs the host no
    program and no sync of its own."""
    return _ell_masked_fixed_point(
        srcs_t, ws_t, masks_t, overloaded, src_id, bands, n
    )


def excluded_slots(graph: EllGraph, links, parallel_pairs=None, memo=None):
    """One batch element's excluded links as the slots they hold:
    ``(flat, ok)``, ``flat`` the int64 offsets into the graph's slots
    laid end to end, band after band, each band row-major (a band's
    ``[rows, k]`` flattened). On a per-link-slot graph (compile_ell
    direction="in") every link, parallel group members included, maps
    to its OWN slot via ``graph.slot_of``, so ``ok`` is False only when
    an exclusion references a node outside the graph (reference
    semantics: LinkState.cpp:763 getKthPaths' linksToIgnore treats each
    Link as first-class, LinkState.h:82). The offsets are good for as
    long as the slots of the links' two end rows stay where they are
    (ell_patch re-packs a row when one of its links comes or goes);
    ``memo``, the caller's dict, keeps each link's offsets from one
    element to the next for as long as the caller knows that holds.

    Collapsed graphs (no slot_of) keep the legacy behavior:
    ``parallel_pairs`` elements are unrepresentable and flag ok=False."""
    per_link = graph.slot_of is not None
    offsets = _band_offsets(graph.bands)
    flat: List[int] = []
    for link in links:
        if memo is not None and link in memo:
            flat.extend(memo[link])
            continue
        if not per_link and parallel_pairs and (
            frozenset((link.n1, link.n2)) in parallel_pairs
        ):
            return np.zeros(0, dtype=np.int64), False
        key = link_key(link) if per_link else None
        first = len(flat)
        for head in (link.n1, link.n2):
            tail = link.other_node(head)
            hid = graph.node_index.get(head)
            tid = graph.node_index.get(tail)
            if hid is None or tid is None:
                return np.zeros(0, dtype=np.int64), False
            if per_link:
                hit = graph.slot_of.get(hid, _EMPTY_SLOTS).get(key)
                if hit is None:
                    # link not in the ELL (e.g. went down after
                    # compile): nothing to mask
                    continue
                bi, r, slot = hit
            else:
                bi, band = _band_of(graph, hid)
                r = hid - band.start
                hits = np.flatnonzero(graph.src[bi][r] == tid)
                if len(hits) == 0:
                    continue
                slot = int(hits[0])
            flat.append(offsets[bi] + r * graph.bands[bi].k + slot)
        if memo is not None:
            memo[link] = tuple(flat[first:])
    return np.asarray(flat, dtype=np.int64), True


@functools.lru_cache(maxsize=64)
def _band_offsets(bands: Tuple[EllBand, ...]) -> Tuple[int, ...]:
    """Where each band's slots begin when the bands' ``[rows, k]`` are
    laid end to end; the total last."""
    offsets = [0]
    for band in bands:
        offsets.append(offsets[-1] + band.rows * band.k)
    return tuple(offsets)


def masks_from_slots(graph: EllGraph, slots, rows: int):
    """Per-band ``[rows, band.rows, band.k]`` bool masks with element
    x's ``slots[x]`` (excluded_slots' offsets) set; elements past
    ``len(slots)`` exclude nothing (the padding of a batch)."""
    offsets = _band_offsets(graph.bands)
    flat = np.zeros((rows, offsets[-1]), dtype=bool)
    if len(slots):
        flat[
            np.repeat(np.arange(len(slots)), [len(s) for s in slots]),
            np.concatenate(slots),
        ] = True
    return [
        flat[:, lo:hi].reshape(rows, band.rows, band.k)
        for band, lo, hi in zip(graph.bands, offsets, offsets[1:])
    ]


def build_edge_masks(graph: EllGraph, exclusion_sets, parallel_pairs=None):
    """Per-band [B, rows, k] bool masks from per-batch-element link
    sets, and which elements the slots could carry (excluded_slots);
    one that they could not excludes nothing."""
    slots, ok = [], np.ones(len(exclusion_sets), dtype=bool)
    for x, links in enumerate(exclusion_sets):
        flat, ok[x] = excluded_slots(graph, links, parallel_pairs)
        slots.append(flat)
    return masks_from_slots(graph, slots, len(exclusion_sets)), ok


def ell_masked_distances(graph: EllGraph, src_id: int, masks):
    """Run the batched masked solve; returns host [B, n_pad] int32.
    Rides the committed AOT executable cache — the host-graph twin of
    ``ell_masked_distances_resident`` (the serve plane's per-tenant
    KSP2 view dispatches here, so its warm waves must not retrace)."""
    d, _passes = _aot_call(
        "ksp2_masked_host", _ell_masked_source_batch,
        (
            tuple(jnp.asarray(s) for s in graph.src),
            tuple(jnp.asarray(w) for w in graph.w),
            tuple(jnp.asarray(m) for m in masks),
            jnp.asarray(graph.overloaded),
            src_id,
        ),
        dict(bands=graph.bands, n=graph.n_pad),
    )
    return np.asarray(d)


def ell_masked_distances_resident(
    state: "EllState", src_id: int, masks, defer: bool = False
):
    """Masked solve over an EllState's device-RESIDENT bands — only the
    masks cross host->device per dispatch. Dispatches through the AOT
    executable cache (``ksp2_masked_resident``) so a warm churn event
    costs a dict lookup, not a jit signature re-derivation. Returns
    ``(rows [B, n_pad], passes)``, the program's two outputs, on the
    host and the count booked (note_ksp2_passes). With ``defer=True``
    both stay ON DEVICE with their readback kicked on the async lane —
    the caller reaps the pair via ONE
    ``dispatch_accounting.reap_read(pair, kicked=True)`` inside its
    event window (the KSP2 committed-dispatch chain) and books the
    count itself."""
    out = _aot_call(
        "ksp2_masked_resident", _ell_masked_source_batch,
        (
            state.src,
            state.w,
            tuple(jnp.asarray(m) for m in masks),
            state.overloaded,
            src_id,
        ),
        dict(bands=state.graph.bands, n=state.graph.n_pad),
    )
    if defer:
        for arr in out:
            _da.kick_async(arr)
        return out
    rows, passes = jax.device_get(out)
    return rows, note_ksp2_passes("masked", passes)


def note_ksp2_passes(program: str, passes) -> int:
    """The relax passes a KSP2 program ran (``masked``: one batch of
    _ell_masked_source_batch; ``all_pairs``: the fixed point the sync
    waits for its distances in, _ell_view_ep_rows on one chip and the
    fused _sharded_ell_all_view_rows on a mesh; ``matrix``: the
    all-sources fixed point of _ell_all_view_rows, behind the window),
    known where its outputs reach the host: summed into the counter
    ``ops.ksp2.<program>_passes`` and returned for the span that covers
    the dispatch."""
    passes = int(passes)
    _get_registry().counter_bump(f"ops.ksp2.{program}_passes", passes)
    return passes


def warm_masked_distances_resident(
    state: "EllState", src_id: int, rows: int
) -> bool:
    """Compile (or find compiled) ``ell_masked_distances_resident``'s
    executable for a batch of ``rows`` masked graphs, without running
    it: the same key the dispatch looks up."""
    from openr_tpu.ops.aot_cache import get_aot_cache

    return get_aot_cache().warm(
        "ksp2_masked_resident", _ell_masked_source_batch,
        (
            state.src,
            state.w,
            tuple(
                jnp.zeros((rows, band.rows, band.k), dtype=jnp.bool_)
                for band in state.graph.bands
            ),
            state.overloaded,
            src_id,
        ),
        dict(bands=state.graph.bands, n=state.graph.n_pad),
    )


def _band_patch_rows(patched: EllGraph):
    """Host half of the band patch discipline, the ONE implementation
    every resident-band consumer shares: per band, ``(widened, ids)``.
    A WIDENED band changed tensor SHAPE and is re-uploaded wholesale
    (ids None); otherwise ids is the bucketed changed-row vector
    (pad_patch_rows shapes; every row once a change outgrows the
    largest bucket), or None when nothing changed."""
    changed: Dict[int, np.ndarray] = patched.changed or {}
    widened = patched.widened or frozenset()
    for bi, band in enumerate(patched.bands):
        rows = None if bi in widened else changed.get(bi)
        if rows is None or len(rows) == 0:
            yield bi in widened, None
            continue
        padded = pad_patch_rows(np.asarray(rows, dtype=np.int32))
        yield False, (
            padded
            if padded is not None
            else np.arange(band.rows, dtype=np.int32)
        )


def band_patch_inputs(resident_src, resident_w, patched: EllGraph):
    """The band patch as inputs of a FUSED dispatch, whose signature
    carries a scatter triple for every band (EllState.reconverge and
    the route engine's churn prep): _band_patch_rows' ids, a zeros(1)
    no-op where a band has nothing to scatter, and a widened band's
    re-upload in place of the resident tensor. Returns
    (in_src, in_w, patch_ids, patch_src, patch_w) as tuples of device
    arrays: dispatch inputs plus the scatter triples."""
    in_src = list(resident_src)
    in_w = list(resident_w)
    patch_ids, patch_src, patch_w = [], [], []
    for bi, (widened, rows) in enumerate(_band_patch_rows(patched)):
        if widened:
            in_src[bi] = jnp.asarray(patched.src[bi])
            in_w[bi] = jnp.asarray(patched.w[bi])
        if rows is None:
            rows = np.zeros(1, dtype=np.int32)  # no-op scatter
        patch_ids.append(jnp.asarray(rows))
        patch_src.append(jnp.asarray(patched.src[bi][rows]))
        patch_w.append(jnp.asarray(patched.w[bi][rows]))
    return (
        tuple(in_src), tuple(in_w),
        tuple(patch_ids), tuple(patch_src), tuple(patch_w),
    )


class EllState:
    """Caller-owned resident device bands for the churn loop.

    Everything a dispatch consumes lives on the device: the bands, and
    the overloaded mask (re-uploaded only when it actually changes, so
    a steady-state dispatch carries no host->device transfer for it)."""

    def __init__(self, graph: EllGraph):
        self.graph = graph
        self.src = tuple(jnp.asarray(s) for s in graph.src)
        self.w = tuple(jnp.asarray(w) for w in graph.w)
        self.overloaded = jnp.asarray(graph.overloaded)
        # warm-start state: the previous solve's distance rows plus the
        # source batch they belong to, and a MERGEABLE journal of every
        # un-solved patch's edge changes. Each journal entry keys
        # (tail, head) -> (w_snapshot, w_current): the snapshot is the
        # collapsed weight the RESIDENT DISTANCES were solved under
        # (first touch wins — an edge changed twice inside one debounce
        # window keeps its original snapshot), the current side tracks
        # the latest patch. At solve time the increase delta is emitted
        # against the snapshots, which is exactly what the tight test
        # is sound against — so stacked patches coalesce into one warm
        # solve instead of degrading to a forced cold seed.
        #
        # STRUCTURAL events (overload-mask flips) stay warm too: the
        # mask at the last solve is kept (_ov_solved), a flipped
        # node's out-edges are journaled at their raw weights, and the
        # solve-time emission compares EFFECTIVE weights (raw, or INF
        # when the tail was/is masked) so a drain reads as an increase
        # delta and an undrain as a plain decrease — no forced cold
        # seed on either.
        self._d_dev = None
        # the last solve's _solve_stats, still on the device: fetch_view
        # brings it to the host in the read that brings the packed view
        self._stats_dev = None
        self._warm_key: Optional[Tuple[int, ...]] = None
        self._pending_edges: Dict[
            Tuple[int, int], Tuple[int, int]
        ] = {}
        self._ov_solved = np.array(graph.overloaded, copy=True)
        self._pending_structural = False

    def _sync_overloaded(self, patched: EllGraph) -> bool:
        changed = not np.array_equal(
            self.graph.overloaded, patched.overloaded
        )
        if changed:
            self.overloaded = jnp.asarray(patched.overloaded)
        return changed

    def _note_patch(self, patched: EllGraph, ov_changed: bool) -> None:
        """Fold one patch's delta into the warm-start journal. Stacked
        patches MERGE: an edge already journaled keeps its weight
        snapshot (taken from the last-solved graph) and only advances
        its current side, so a burst of patches inside one debounce
        window still emits a single sound increase delta at solve
        time.

        Overload-mask flips are journaled rather than forcing a cold
        seed: every out-edge of a flipped node enters the journal at
        its raw collapsed weight, and the emission in reconverge
        applies the mask per side (see _emit_increases) — a drain
        becomes an ordinary increase delta, an undrain a decrease.
        Link up/down (a row removal/addition in the patch) already
        reads as a w <-> INF transition through band_row_edge_changes,
        so the same journal carries it."""
        if patched.changed:
            ELL_COUNTERS["ell_incremental_syncs"] += 1
        if patched.widened:
            ELL_COUNTERS["ell_widen_events"] += len(patched.widened)
        if self._d_dev is None:
            return
        if ov_changed:
            # journal the flipped nodes' out-edges from the PRE-patch
            # resident graph (self.graph — replaced only after the
            # patch lands): the effective weight of every such edge
            # moves with the mask even though its raw weight did not.
            # O(E) host scan, vectorized; flips are rare events.
            self._pending_structural = True
            flipped = np.nonzero(
                np.asarray(self.graph.overloaded)
                != np.asarray(patched.overloaded)
            )[0]
            collapsed: Dict[Tuple[int, int], int] = {}
            pos = 0
            for src_b, w_b in zip(self.graph.src, self.graph.w):
                src_h = np.asarray(src_b)
                w_h = np.asarray(w_b)
                hit = np.isin(src_h, flipped) & (w_h < INF)
                for r, sl in zip(*np.nonzero(hit)):
                    key = (int(src_h[r, sl]), pos + int(r))
                    w = int(w_h[r, sl])
                    if w < collapsed.get(key, INF):
                        collapsed[key] = w
                pos += src_h.shape[0]
            for key, w in collapsed.items():
                self._pending_edges.setdefault(key, (w, w))
        if not patched.changed:
            return  # mask-only / no-op sync: raw journal untouched
        if self._pending_edges:
            ELL_COUNTERS["ell_patch_merges"] += 1
        structural = False
        for s, h, wo, wn in band_row_edge_changes(self.graph, patched):
            snap, _cur = self._pending_edges.get((s, h), (wo, wo))
            self._pending_edges[(s, h)] = (snap, wn)
            structural = structural or wo >= INF or wn >= INF
        if structural:
            self._pending_structural = True

    def _emit_increases(self, ov_now: np.ndarray):
        """The journal's increase delta, EFFECTIVE-weight aware: an
        entry is emitted when its raw weight rose (covers the
        origination row — an overloaded source still uses its own
        out-edges) or when its masked weight rose (covers transit
        rows across a drain flip). The emitted weight is the raw
        snapshot: every realized tight step in d_prev used the raw
        value, so the tight test stays sound; rows reset through a
        masked coincidence are merely extra work, never wrong."""
        inc = []
        for (s, h), (snap, cur) in self._pending_edges.items():
            if snap >= INF:
                continue  # edge unusable at solve time: can't tighten
            snap_eff = INF if self._ov_solved[s] else snap
            cur_eff = INF if ov_now[s] else cur
            if cur > snap or cur_eff > snap_eff:
                inc.append((s, h, snap))
        return inc

    def apply_patch(self, patched: EllGraph) -> None:
        """Scatter a patched graph's changed rows into the resident
        bands WITHOUT solving (for consumers that only need synced
        device bands, e.g. the KSP2 masked batches, and the decision
        module's publication-time prewarm). A WIDENED band
        (ell_patch(widen=True) grew its k — a row outgrew its slot
        class) changed tensor SHAPE and is re-uploaded wholesale; node
        ids are unchanged, so every id-keyed resident consumer stays
        valid. The increase delta is journaled so a later reconverge
        can still warm-start across the un-solved patch."""
        ov_changed = self._sync_overloaded(patched)
        self._note_patch(patched, ov_changed)
        # one jitted scatter per band that has changed rows (one
        # compiled shape per band x bucket), fed the host row blocks
        # directly; a band with nothing to scatter launches nothing
        src, w = list(self.src), list(self.w)
        for bi, (widened, rows) in enumerate(_band_patch_rows(patched)):
            if widened:
                src[bi] = jnp.asarray(patched.src[bi])
                w[bi] = jnp.asarray(patched.w[bi])
            elif rows is not None:
                src[bi], w[bi] = _patch_band(
                    src[bi], w[bi], rows,
                    patched.src[bi][rows], patched.w[bi][rows],
                )
        self.src, self.w = tuple(src), tuple(w)
        self.graph = _replace(patched, changed=None)

    @solve_window
    def reconverge(self, patched: EllGraph, srcs):
        """Fused churn step: scatter the patched rows into the resident
        bands, solve the batched view warm-started from the previous
        solve's distances (bit-identical to cold — see _warm_seed and
        _cone_seed),
        O(rows x K_class + |delta|) transfer in, O(B x N) out. Widened
        bands (shape changed) are re-uploaded wholesale as the dispatch
        inputs with a no-op scatter — same discipline as apply_patch;
        the new band shapes cost one jit recompile."""
        # span on the enclosing module's active trace (no-op outside a
        # traced churn event); attrs carry the warm/cold verdict plus
        # the device-dispatch vs host-overhead split
        _tracer = _get_tracer()
        _span = _tracer.span_active("ops.ell_reconverge")
        _t0 = time.perf_counter()
        ov_changed = self._sync_overloaded(patched)
        self._note_patch(patched, ov_changed)
        in_src, in_w, patch_ids, patch_src, patch_w = (
            band_patch_inputs(self.src, self.w, patched)
        )
        srcs_key = tuple(int(s) for s in srcs)
        b = len(srcs_key)
        warm = (
            self._d_dev is not None
            and self._warm_key == srcs_key
        )
        if warm:
            # increases vs the SNAPSHOT weights the resident distances
            # were solved under (edges that moved and came back to or
            # below their snapshot need no reset: the old rows are
            # still valid upper bounds); effective-weight aware, so
            # drain flips and link removals ride the same warm seed
            # openr-lint: disable=host-sync-in-window -- overloaded is
            # a host ndarray on EllGraph; no device transfer happens
            ov_now = np.asarray(patched.overloaded)
            inc = self._emit_increases(ov_now)
            d_prev = self._d_dev
            ELL_COUNTERS["ell_warm_solves"] += 1
            if self._pending_structural:
                ELL_COUNTERS["ell_structural_warm_solves"] += 1
        else:
            inc = [_FORCE_RESET_EDGE]
            d_prev = (
                self._d_dev
                if self._d_dev is not None
                and self._d_dev.shape == (b, patched.n_pad)
                else jnp.zeros((b, patched.n_pad), dtype=jnp.int32)
            )
            ELL_COUNTERS["ell_cold_solves"] += 1
        inc_t, inc_h, inc_w = pad_increase_edges(inc)
        # openr-lint: disable=host-sync-in-window -- srcs is a host
        # list of sample ids, not a device array; no transfer happens
        srcs_dev = jnp.asarray(np.asarray(srcs, dtype=np.int32))
        _t_dispatch = time.perf_counter()
        # openr-lint: disable=donation-hazard -- intentional: the warm
        # path CONSUMES the previous resident distances (d_prev is dead
        # after this dispatch) and self._d_dev is rebound to the fresh
        # output below; no retry ladder re-reads the donated buffer
        # openr-lint: disable=sharding-spec -- single-chip resident
        # reconvergence (mesh callers go through the sharded_ell_*
        # shard_map wrappers): no mesh axis to spec
        self.src, self.w, packed, d, self._stats_dev = _ell_reconverge(
            in_src, in_w, patch_ids, patch_src, patch_w,
            jnp.asarray(inc_t), jnp.asarray(inc_h), jnp.asarray(inc_w),
            self.overloaded, d_prev, srcs_dev,
            patched.bands, patched.n_pad,
        )
        _t_end = time.perf_counter()
        self._d_dev = d
        self._warm_key = srcs_key
        self._pending_edges = {}
        # openr-lint: disable=host-sync-in-window -- host ndarray copy
        # (the overload mask the resident distances were solved under)
        self._ov_solved = np.array(patched.overloaded, copy=True)
        self._pending_structural = False
        self.graph = _replace(patched, changed=None)
        _total_ms = (_t_end - _t0) * 1000.0
        _dispatch_ms = (_t_end - _t_dispatch) * 1000.0
        _reg = _get_registry()
        _reg.observe("ops.ell.reconverge_ms", _total_ms)
        _reg.observe(
            "ops.ell.host_overhead_ms", _total_ms - _dispatch_ms
        )
        _tracer.end_span_active(
            _span,
            warm=warm,
            dispatch_ms=round(_dispatch_ms, 4),
            host_overhead_ms=round(_total_ms - _dispatch_ms, 4),
            # what every pass streams against what it needs: the
            # bands' slots, padding included, and the filled ones
            slots=sum(band.rows * band.k for band in patched.bands),
            edges=patched.edges,
        )
        return packed

    def fetch_view(self, packed):
        """The packed view ``reconverge`` just returned, on the host,
        with that solve's two scalars (_solve_stats): outputs of one
        program, ready together and brought over by ONE ``device_get``,
        so the scalars cost no program and no sync of their own. They
        are only known here, so this is where the registry learns them:
        the observation ``ops.ell.relax_passes`` once per solve (every
        pass over the bands the solve ran: _cone_seed's support passes
        and the relax passes after them, so it is what the mechanism
        achieved), and ``decision.ell_reset_solves`` for a solve in
        which a tight increased edge flagged at least one row (how
        often the mechanism engaged; a cold solve flags every row).
        Returns (packed host array, passes, reset_rows)."""
        packed_host, stats = jax.device_get((packed, self._stats_dev))
        passes, reset_rows = int(stats[0]), int(stats[1])
        _get_registry().observe("ops.ell.relax_passes", passes)
        if reset_rows:
            ELL_COUNTERS["ell_reset_solves"] += 1
        return packed_host, passes, reset_rows


def ell_reconverge_step(state: EllState, patched: EllGraph, srcs):
    """Convenience wrapper around EllState.reconverge."""
    return state.reconverge(patched, srcs)


def _pack_view_rows(view_d, view_srcs, w_sv, overloaded, n,
                    rows_new, rows_old):
    """The packet a KSP2 sync reads back, in the one layout its host
    side unpacks: [view_d | view_fh | rows_new | rows_old], the view's
    first hops by the algebra _ell_view_batch shares."""
    fh = _first_hops_from_rows(view_d, view_srcs, w_sv, overloaded, n)
    return jnp.concatenate(
        [view_d, fh.astype(jnp.int32), rows_new, rows_old], axis=0
    )


@functools.partial(jax.jit, static_argnames=("bands", "n"))
def _ell_view_ep_rows(
    srcs_t, ws_t, overloaded, view_srcs, w_sv, ep_ids, d_prev,
    inc_tail, inc_head, inc_w, bands, n,
):
    """The rows an incremental-KSP2 sync READS, and nothing else: the
    program the sync waits for (ksp2_engine._sync_window, the span
    ops.ksp2_all_pairs).

      1. distances from ``view_srcs ++ ep_ids`` only (the root's view
         batch and the window's changed-edge endpoints: 40 rows on the
         31x31 grid, 48 on the 1k fabric) over the resident bands,
         warm-seeded from THEIR rows of ``d_prev`` (the previous
         epoch's resident all-pairs matrix) with the increase-edge
         delta (inc_tail/inc_head/inc_w: see _warm_seed; the
         _FORCE_RESET_EDGE sentinel for cold semantics),
      2. the view's packed first hops (same algebra as _ell_view_batch)
         from the view rows,

    returning (packed, passes): packed = [view_d | view_fh | rows_new |
    rows_old] with rows_old = ``d_prev[ep_ids]``, and the fixed point's
    loop counter. Rows of an all-sources solve are independent
    single-source fixed points over the same bands under the same
    overload mask, and int32 min-relaxation has one fixed point: these
    rows are bit-identical to the rows _ell_all_view_rows leaves in the
    matrix for the same epoch. Nothing is donated: ``d_prev`` stays the
    engine's live matrix until the matrix solve behind the window
    consumes it."""
    src_ids = jnp.concatenate([view_srcs, ep_ids])
    d, passes = _ell_fixed_point(
        srcs_t, ws_t, overloaded, src_ids, bands, n,
        warm=(d_prev[src_ids], inc_tail, inc_head, inc_w),
    )
    b = view_srcs.shape[0]
    packed = _pack_view_rows(
        d[:b], view_srcs, w_sv, overloaded, n, d[b:], d_prev[ep_ids]
    )
    return packed, passes


@functools.partial(
    jax.jit,
    static_argnames=("bands", "n"),
    donate_argnums=(3,),  # d_prev: dead after the call, relax in place
)
def _ell_all_view_rows(
    srcs_t, ws_t, overloaded, d_prev, inc_tail, inc_head, inc_w, bands, n,
):
    """The matrix an incremental-KSP2 engine KEEPS: all-sources
    distances D [n, n] over the resident bands at moderate N (n_pad <=
    ~4k, where a full all-sources block fits), warm-seeded from
    ``d_prev`` (the previous epoch's D, donated: relaxed in place) with
    the increase-edge delta, as _ell_view_ep_rows is. Returns (D,
    passes). No sync reads D on the host: it is the NEXT window's warm
    seed and the source of its ``rows_old``, so the engine dispatches
    this behind the window's last masked batch and blocks on nothing
    of it (ell_all_view_rows). The name is the benchmark's handle:
    chipbench/roofline_ksp2.ALL_PAIRS finds the compiled module by
    it."""
    return _ell_fixed_point(
        srcs_t, ws_t, overloaded,
        jnp.arange(n, dtype=jnp.int32), bands, n,
        warm=(d_prev, inc_tail, inc_head, inc_w),
    )


def _inc_args(inc, bucket: int):
    """Device increase-edge triple for the warm-seeded dispatches:
    ``inc=None`` means cold semantics (the reset sentinel flags every
    row); an (possibly empty) increase list warm-starts. ``bucket``:
    the length every list is padded to, which is the caller's bound on
    a list (ksp2_engine.ENGINE_MAX_CHANGED_PAIRS), so that an engine
    runs one compiled shape of each dispatch and not one per power
    of two a window of events happens to reach."""
    inc_t, inc_h, inc_w = pad_increase_edges(
        [_FORCE_RESET_EDGE] if inc is None else list(inc),
        bucket_min=bucket,
    )
    return jnp.asarray(inc_t), jnp.asarray(inc_h), jnp.asarray(inc_w)


def ell_view_ep_rows(state: EllState, view_srcs, w_sv, ep_ids, d_prev,
                     inc=None, inc_bucket: int = 4):
    """Dispatch the rows solve (_ell_view_ep_rows) on the resident
    bands: the program a KSP2 sync waits for. Returns ``(packed,
    passes, inc_dev)``: the first two ON DEVICE with their readback
    kicked async (the caller reaps the pair via ONE
    ``dispatch_accounting.reap_read((packed, passes), kicked=True)``
    inside its event window and books the count itself), and the
    increase triple as it went to the device, for the matrix solve of
    the same window (ell_all_view_rows), which then puts nothing
    there. ``inc`` is the increase-edge delta [(tail, head, old_w)]
    for warm seeding, padded to ``inc_bucket`` (None forces the cold
    seed); ``d_prev`` is read, not donated. Rides the committed AOT
    executable cache (``ksp2_rows``)."""
    inc_dev = _inc_args(inc, inc_bucket)
    packed, passes = _aot_call(
        "ksp2_rows", _ell_view_ep_rows,
        (
            state.src, state.w, state.overloaded,
            _as_device_ids(view_srcs),
            w_sv if isinstance(w_sv, jax.Array) else jnp.asarray(
                np.asarray(w_sv, dtype=np.int32)
            ),
            _as_device_ids(ep_ids),
            d_prev, *inc_dev,
        ),
        dict(bands=state.graph.bands, n=state.graph.n_pad),
    )
    _da.kick_async(packed)
    _da.kick_async(passes)
    return packed, passes, inc_dev


@donates("d_prev")
def ell_all_view_rows(state: EllState, d_prev, inc_dev):
    """Dispatch the matrix solve (_ell_all_view_rows) on the resident
    bands and wait for nothing: returns ``(d_all, passes)`` ON DEVICE,
    the count's readback kicked async for whoever reaps it later
    (``note_ksp2_passes("matrix", ...)``). ``inc_dev`` is the device
    increase triple of the window (ell_view_ep_rows returns the one it
    sent; ``_inc_args(None, bucket)`` forces the cold seed); d_prev is
    DONATED (invalid after the call). Rides the committed AOT
    executable cache (``ksp2_view_rows``)."""
    d_all, passes = _aot_call(
        "ksp2_view_rows", _ell_all_view_rows,
        (state.src, state.w, state.overloaded, d_prev, *inc_dev),
        dict(bands=state.graph.bands, n=state.graph.n_pad),
    )
    _da.kick_async(passes)
    return d_all, passes


SOURCES_AXIS = "sources"


@functools.partial(jax.jit, static_argnames=("n", "mesh"))
def _sharded_sparse(
    src_ids, full_src, full_dst, full_w, t_src, t_dst, t_w, n, mesh
):
    def shard_fn(ids_blk, fs, fd, fw, ts, td, tw):
        s = ids_blk.shape[0]
        unit = jnp.full((s, n), INF, dtype=jnp.int32)
        unit = unit.at[jnp.arange(s), ids_blk].set(0)
        d0 = _relax(unit, fs, fd, fw, n)

        def cond(state):
            _, changed, it = state
            return jnp.logical_and(changed > 0, it < n)

        def body(state):
            d, _, it = state
            nxt = _relax(d, ts, td, tw, n)
            local = jnp.any(nxt < d).astype(jnp.int32)
            return nxt, jax.lax.psum(local, SOURCES_AXIS), it + 1

        d, _, _ = jax.lax.while_loop(cond, body, (d0, jnp.int32(1), 0))
        return d

    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(SOURCES_AXIS),
            P(None), P(None), P(None),
            P(None), P(None), P(None),
        ),
        out_specs=P(SOURCES_AXIS, None),
    )(src_ids, full_src, full_dst, full_w, t_src, t_dst, t_w)


def sharded_sparse_all_sources(graph: SparseGraph, mesh: Mesh):
    """All-sources distances [N_pad, N_pad], source rows sharded over
    the mesh, graph as replicated edge lists. This is the 100k-node
    shape: per-device memory is O(N_pad/devices x N_pad + E) and the
    only collective is the convergence bit."""
    n = graph.n_pad
    assert n % mesh.devices.size == 0, (n, mesh.devices.size)
    src_ids = np.arange(n, dtype=np.int32)
    return _sharded_sparse(
        jnp.asarray(src_ids),
        jnp.asarray(graph.full_src),
        jnp.asarray(graph.full_dst),
        jnp.asarray(graph.full_w),
        jnp.asarray(graph.transit_src),
        jnp.asarray(graph.transit_dst),
        jnp.asarray(graph.transit_w),
        n,
        mesh,
    )


@functools.partial(jax.jit, static_argnames=("bands", "n", "mesh"))
def _sharded_ell(src_ids, srcs_t, ws_t, overloaded, bands, n, mesh):
    def shard_fn(ids_blk, srcs_r, ws_r, ov_r):
        return _ell_fixed_point(
            srcs_r, ws_r, ov_r, ids_blk, bands, n,
            vote=lambda bit: jax.lax.psum(bit, SOURCES_AXIS),
        )[0]

    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(SOURCES_AXIS), P(None), P(None), P(None)),
        out_specs=P(SOURCES_AXIS, None),
    )(src_ids, srcs_t, ws_t, overloaded)


@functools.partial(jax.jit, static_argnames=("bands", "n", "mesh"))
def _sharded_ell_masked(
    srcs_t, ws_t, masks_t, overloaded, src_id, bands, n, mesh
):
    def shard_fn(*args):
        masks_blk = args[: len(masks_t)]
        srcs_r = args[len(masks_t) : 2 * len(masks_t)]
        ws_r = args[2 * len(masks_t) : 3 * len(masks_t)]
        ov_r = args[-1]
        d, passes = _ell_masked_fixed_point(
            srcs_r, ws_r, masks_blk, ov_r, src_id, bands, n,
            vote=lambda bit: jax.lax.psum(bit, SOURCES_AXIS),
        )
        # the vote is global, so every shard counts the same passes
        return d, jax.lax.pmax(passes, SOURCES_AXIS)

    nb = len(masks_t)
    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=tuple(
            [P(SOURCES_AXIS, None, None)] * nb  # masks: batch-sharded
            + [P(None, None)] * nb  # bands replicated
            + [P(None, None)] * nb
            + [P(None)]
        ),
        out_specs=(P(SOURCES_AXIS, None), P()),
    )(*masks_t, *srcs_t, *ws_t, overloaded)


def sharded_ell_masked_distances(
    graph: EllGraph, src_id: int, masks, mesh: Mesh
) -> np.ndarray:
    """The KSP2 masked batch sharded over the mesh: each device owns a
    block of DESTINATIONS (batch elements of the per-destination
    edge-masked solve, reference semantics LinkState.cpp:763
    getKthPaths); bands are replicated (O(E)), the only collective is
    the 1-bit convergence psum. This is how the KSP2 second-path
    product scales past one chip's mask-memory budget: B x slots bool
    masks divide by the mesh size. The mesh size must divide the
    batch size."""
    b = masks[0].shape[0]
    assert b % mesh.devices.size == 0, (b, mesh.devices.size)
    return np.asarray(
        _sharded_ell_masked(
            tuple(jnp.asarray(s) for s in graph.src),
            tuple(jnp.asarray(w) for w in graph.w),
            tuple(jnp.asarray(m) for m in masks),
            jnp.asarray(graph.overloaded),
            src_id,
            graph.bands,
            graph.n_pad,
            mesh,
        )[0]
    )


def sharded_ell_all_sources(graph: EllGraph, mesh: Mesh):
    """All-sources distances [N_pad, N_pad] over the sliced-ELL bands,
    source rows sharded over the mesh, bands replicated (O(E) each —
    tiny next to the distance block). The gather+K-reduce relaxation
    runs entirely shard-local; the only collective is the 1-bit
    convergence psum per iteration, so scaling to a v4-32 mesh is
    bandwidth-trivial. Per-device memory at 100k nodes on 32 devices:
    100096/32 x 100096 x 4 B ~= 1.25 GB of distance rows."""
    n = graph.n_pad
    assert n % mesh.devices.size == 0, (n, mesh.devices.size)
    return _sharded_ell(
        jnp.asarray(np.arange(n, dtype=np.int32)),
        tuple(jnp.asarray(s) for s in graph.src),
        tuple(jnp.asarray(w) for w in graph.w),
        jnp.asarray(graph.overloaded),
        graph.bands,
        n,
        mesh,
    )


def _sharded_warm_all_pairs(
    srcs_t, ws_t, overloaded, d_prev, inc_tail, inc_head, inc_w,
    bands, n, mesh,
):
    """Warm-seeded all-pairs fixed point with source rows sharded over
    the mesh. The warm seed (_warm_seed) is row-local — its tight test
    reads whole COLUMNS of d_prev at the increase tails/heads, which
    every shard's [rows, n] block carries — so d_prev shards along the
    same axis as the solve and never moves. d_prev is NOT donated on
    this path (the sharded buffer may still back a caller-held ref;
    the single-chip dispatch keeps its donation win)."""
    nb = len(srcs_t)

    def shard_fn(ids_blk, d_prev_blk, it, ih, iw, *rest):
        srcs_r = rest[:nb]
        ws_r = rest[nb : 2 * nb]
        ov_r = rest[-1]
        d, passes = _ell_fixed_point(
            srcs_r, ws_r, ov_r, ids_blk, bands, n,
            vote=lambda bit: jax.lax.psum(bit, SOURCES_AXIS),
            warm=(d_prev_blk, it, ih, iw),
        )
        return d, jax.lax.pmax(passes, SOURCES_AXIS)

    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=tuple(
            [P(SOURCES_AXIS), P(SOURCES_AXIS, None)]
            + [P(None)] * 3
            + [P(None, None)] * (2 * nb)
            + [P(None)]
        ),
        out_specs=(P(SOURCES_AXIS, None), P()),
    )(
        jnp.arange(n, dtype=jnp.int32), d_prev,
        inc_tail, inc_head, inc_w,
        *srcs_t, *ws_t, overloaded,
    )


@functools.partial(jax.jit, static_argnames=("bands", "n", "mesh"))
def _sharded_ell_all_view_rows(
    srcs_t, ws_t, overloaded, view_srcs, w_sv, ep_ids, d_prev,
    inc_tail, inc_head, inc_w, bands, n, mesh,
):
    """The mesh engine's ONE fused dispatch (the single-chip engine
    splits it in two, _ell_view_ep_rows and _ell_all_view_rows; here
    the rows come back on the host with the call and no cell runs it):
    the all-pairs fixed point runs with source rows sharded over the
    mesh (1-bit psum vote), WARM-SEEDED from the row-sharded previous
    distances, and the view/endpoint row gathers run as global-view
    ops on the sharded matrix (XLA inserts the row collectives).
    Returns (d_all, packed = [view_d | view_fh | rows_new | rows_old],
    passes). d_all comes back SHARDED — the resident footprint per
    device is n^2/ndev, which is what lifts the KSP2 engine past the
    single-chip bound."""
    d_all, passes = _sharded_warm_all_pairs(
        srcs_t, ws_t, overloaded, d_prev, inc_tail, inc_head, inc_w,
        bands, n, mesh,
    )
    packed = _pack_view_rows(
        d_all[view_srcs], view_srcs, w_sv, overloaded, n,
        d_all[ep_ids], d_prev[ep_ids],
    )
    return d_all, packed, passes


def sharded_ell_all_view_rows(
    state: "EllState", view_srcs, w_sv, ep_ids, d_prev, mesh: Mesh,
    inc=None, inc_bucket: int = 4,
):
    """Run the sharded all-sources + view + invalidation-rows dispatch
    on the resident bands. Returns (d_all_dev SHARDED, packed_host,
    passes), the count booked (note_ksp2_passes, ``all_pairs``).
    ``inc`` is the increase-edge delta for warm seeding (None forces
    the cold seed — same contract as ell_view_ep_rows); d_prev is NOT
    donated. n_pad must divide by the mesh size (the engine gates on
    this and falls back to the single-chip dispatch otherwise)."""
    assert state.graph.n_pad % mesh.devices.size == 0, (
        state.graph.n_pad, mesh.devices.size,
    )
    inc_t, inc_h, inc_w = _inc_args(inc, inc_bucket)
    d_all, packed, passes = _sharded_ell_all_view_rows(
        state.src, state.w, state.overloaded,
        _as_device_ids(view_srcs),
        w_sv if isinstance(w_sv, jax.Array) else jnp.asarray(
            np.asarray(w_sv, dtype=np.int32)
        ),
        _as_device_ids(ep_ids),
        d_prev, inc_t, inc_h, inc_w,
        state.graph.bands, state.graph.n_pad, mesh,
    )
    packed, passes = jax.device_get((packed, passes))
    return d_all, packed, note_ksp2_passes("all_pairs", passes)


def sharded_ell_masked_distances_resident(
    state: "EllState", src_id: int, masks, mesh: Mesh
):
    """Mesh-sharded twin of ell_masked_distances_resident: the KSP2
    masked batch over the RESIDENT bands with destinations sharded
    (each device owns batch/ndev masked solves). The batch size must
    divide by the mesh size (callers pad their pow2 buckets up).
    Dispatches through the same jitted _sharded_ell_masked the
    graph-argument wrapper uses — the resident tensors pass straight
    through. Returns ``(rows, passes)`` on the host, the count booked,
    as ell_masked_distances_resident does."""
    b = masks[0].shape[0]
    assert b % mesh.devices.size == 0, (b, mesh.devices.size)
    rows, passes = jax.device_get(
        _sharded_ell_masked(
            state.src, state.w,
            tuple(jnp.asarray(m) for m in masks),
            state.overloaded, src_id,
            state.graph.bands, state.graph.n_pad, mesh,
        )
    )
    return rows, note_ksp2_passes("masked", passes)


# ---------------------------------------------------------------------------
# Batched multi-tenant worlds: uniform-ELL packing + leading-axis kernels
# ---------------------------------------------------------------------------
#
# The sliced-ELL layout above specializes its executables on the band
# structure (``bands`` is a static jit argument) — optimal for ONE
# resident graph, hostile to batching: two topologies almost never share
# a band tuple, so a [B, ...] dispatch over banded tensors would retrace
# per tenant set. The tenant plane (ops.world_batch) therefore packs
# each tenant into a UNIFORM [n_slot, k_slot] ELL block — every row
# padded to one shared slot width, the node axis padded to one shared
# count — so a whole shape bucket of tenants runs one
# [B, n_slot, k_slot] executable regardless of which tenants occupy it.
# The padding is inert by construction (self-loop src ids with w = INF,
# the same trick the banded layout uses inside a slot class), so the
# per-tenant result is bit-identical to the banded single-graph solve:
# the int32 min-relaxation has a unique fixed point and the uniform
# relax computes the same monotone map, just with more (INF) slots.


def ell_pack_uniform(
    graph: EllGraph, n_slot: int, k_slot: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Flatten a sliced-ELL graph into one uniform [n_slot, k_slot]
    block: (src, w, overloaded). Rows keep their banded ids (node
    numbering is unchanged); slots past a row's band k and rows past
    n_pad are self-loop/INF padding, inert in every relax."""
    assert n_slot >= graph.n_pad, (n_slot, graph.n_pad)
    assert k_slot >= max(b.k for b in graph.bands), k_slot
    src = np.tile(
        np.arange(n_slot, dtype=np.int32)[:, None], (1, k_slot)
    )
    w = np.full((n_slot, k_slot), INF, dtype=np.int32)
    for band, s_b, w_b in zip(graph.bands, graph.src, graph.w):
        src[band.start : band.start + band.rows, : band.k] = s_b
        w[band.start : band.start + band.rows, : band.k] = w_b
    overloaded = np.zeros(n_slot, dtype=bool)
    overloaded[: len(graph.overloaded)] = graph.overloaded
    return src, w, overloaded


def ell_uniform_rows(
    graph: EllGraph, ids, k_slot: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Uniform-layout (src, w) rows for a set of global node ids — the
    O(rows x k) host prep for scattering a patch into a resident
    uniform block (ops.world_batch's analogue of band_patch_inputs)."""
    ids = np.asarray(ids, dtype=np.int32)
    src = np.tile(ids[:, None], (1, k_slot))
    w = np.full((len(ids), k_slot), INF, dtype=np.int32)
    for x, j in enumerate(ids):
        bi, band = _band_of(graph, int(j))
        r = int(j) - band.start
        src[x, : band.k] = graph.src[bi][r]
        w[x, : band.k] = graph.w[bi][r]
    return src, w


def _uniform_relax(d, src, w, overloaded):
    """One masked relaxation over a uniform ELL block: [S, N] -> [S, N]
    as one gather + K-reduce (the single-band special case of
    _ell_relax — identical algebra, so fixed points agree bit-for-bit).
    Edges originating at overloaded nodes never extend paths."""
    gathered = _mask_transit_cols(d, overloaded)[:, src]  # [S, n, k]
    relaxed = jnp.min(
        jnp.minimum(gathered + w[None, :, :], INF), axis=2
    )
    return jnp.minimum(d, relaxed.astype(jnp.int32))


def _uniform_direct(src, w, srcs):
    """On-device direct min-metric srcs[0] -> each batch node over a
    uniform block (INF when not adjacent, and for the source itself) —
    the uniform twin of _device_direct_metrics, so the batched dispatch
    needs no host band reads."""
    src_id = srcs[0]
    direct = jnp.min(jnp.where(src == src_id, w, INF), axis=1)  # [n]
    w_sv = direct[srcs]
    return jnp.where(srcs == src_id, INF, w_sv).astype(jnp.int32)


def _tenant_view_solve(src, w, overloaded, srcs, p_rows, p_src, p_w,
                       inc_t, inc_h, inc_w, d_prev):
    """One tenant's fused view solve over its uniform block: scatter
    the pending patch rows into the resident block (p_rows carries
    global row ids padded with the out-of-bounds id ``n`` — mode="drop"
    makes padding and idle tenants zero-cost no-ops, so patch
    application costs no extra dispatch and no extra executable),
    derive the direct metrics on device, warm-seed the fixed point
    from d_prev (reset only the increase cone — cold tenants pass the
    _FORCE_RESET_EDGE sentinel, so warm and cold share ONE executable,
    exactly like _ell_reconverge), iterate to the fixed point, pack
    distances + first hops. Shapes only — no static arguments — so
    jax.vmap lifts it to the [B, ...] tenant axis without retracing.
    Returns the post-patch (src, w) too: the caller rebinds them as
    the new resident block, keeping device and host graphs coherent
    with ONE device round trip per bucket."""
    n = src.shape[0]
    s = srcs.shape[0]
    src = src.at[p_rows].set(p_src, mode="drop")
    w = w.at[p_rows].set(p_w, mode="drop")
    w_sv = _uniform_direct(src, w, srcs)
    unit = jnp.full((s, n), INF, dtype=jnp.int32)
    unit = unit.at[jnp.arange(s), srcs].set(0)
    # init rows: one UNMASKED relax (overloaded sources still originate)
    d0 = _uniform_relax(unit, src, w, None)
    seed, _ = _warm_seed(d_prev, inc_t, inc_h, inc_w, d0)

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed, it < n)

    def body(state):
        d, _, it = state
        nxt = _uniform_relax(d, src, w, overloaded)
        return nxt, jnp.any(nxt < d), it + 1

    d, _, _ = jax.lax.while_loop(cond, body, (seed, jnp.bool_(True), 0))
    fh = _first_hops_from_rows(d, srcs, w_sv, overloaded, n)
    packed = jnp.concatenate([d, fh.astype(jnp.int32)], axis=0)
    return packed, d, src, w


# The batch-lifted solve: every argument carries a leading tenant axis
# ([B, n, k] blocks, [B, R] patch rows (+[B, R, k] values), [B, S]
# source batches, [B, E] increase deltas, [B, S, n] previous
# distances). Under vmap the while_loop iterates until EVERY tenant's
# lanes converge; extra iterations past a tenant's own fixed point are
# identity (min-relax is idempotent there), so per-tenant results never
# depend on batch composition — the padding-masking contract
# tests/test_world_batch.py enforces. Inactive slots ride along as
# all-INF blocks that converge in zero iterations. Resident inputs are
# NOT donated: the delta-readback retry (overflow -> full fallback) and
# the arbiter's rehydration path both re-read them (the same
# double-buffer hazard rule _churn_step follows). The production entry
# is route_engine.world_dispatch, which fuses this with the tenant-id
# delta compaction into one executable per shape bucket; this unfused
# alias exists for kernel-level tests.
world_view_solve = jax.jit(jax.vmap(_tenant_view_solve))
