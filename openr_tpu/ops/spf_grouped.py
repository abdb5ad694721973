"""Block-bipartite grouped SPF kernels: gather-free relaxation on
structured fabrics.

The sliced-ELL kernels (ops.spf_sparse) spend their device time in the
per-edge gather ``d[:, src[r, k]]`` — an irregular lane-gather that TPUs
execute at a few elements per cycle, single-digit percent of the VPU
roof (the round-3 measurement: 188 ms for a 1024x100k block over 800k
edges).

This module removes the big gather. Observation: in a multi-tier
fabric, nodes overwhelmingly share in-neighbor SETS — every rack in a
pod sees the same fabric switches, every plane-k fabric switch sees the
same spines (reference fabric generator:
/root/reference/openr/decision/tests/RoutingBenchmarkUtils.h:53-58).
Nodes sharing a source set form a COMPLETE BIPARTITE BLOCK with their
common sources, and relaxation over such a block is a small dense
min-plus contraction:

    c[b, g, r] = min_s ( d[b, src[g, s]] + w[g, s, r] )

— one tiny gather per GROUP (not per node) to pull the [B, G, S] source
table, then pure broadcast-add-min, which the VPU runs at full lane
utilization. Per-edge work is identical (E x B adds); the irregular
part shrinks by the group fanout (12-6000x on fat-trees).

Compilation (host, O(E log E)): nodes are classed by degree (as in the
sliced ELL), then each class band is structured by hashing every node's
per-source-class neighbor signature:

  - one source class, equal group sizes  -> grid [G, R], one segment;
  - two source classes, both regular and their groupings form a full
    G1 x G2 product -> grid [G1, G2], two segments (the second writes
    transposed);
  - anything else -> the band degrades to singleton groups (G = rows,
    R = 1), which is exactly the ELL gather shape — unstructured graphs
    pay what they paid before, never more.

Node ids are renumbered (class, group, member) so every segment's
output is a contiguous [B, G, R] reshape — no scatter anywhere.

Both relaxation directions are provided: forward (in-edge bands,
transit mask = edge ORIGIN overloaded — LinkState.cpp:809 runSpf with
the :831-838 originate exception handled by an unmasked init relax) and
reverse (out-edge bands for the destination-major route sweep, mask =
``overloaded[v] & (v != t)`` — see ops.route_sweep).

Equality with the ELL kernels is witnessed by the canonical route-sweep
digest (route_sweep.canonical_pos_weights): same node set, same uint32
per destination, bit-exactly, regardless of either layout's internal
renumbering.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from jax import shard_map
import numpy as np

from openr_tpu.ops.spf import INF
from openr_tpu.ops.spf_sparse import (
    _as_device_ids,
    _in_edges,
    _out_edges,
    _pad_up,
)

def _contract(gath, w):
    """c[b, g, r] = min_s gath[b, g, s] + w[g, s, r] (INF-saturating).
    The broadcast+min-reduce is left to XLA's fuser."""
    return jnp.min(
        jnp.minimum(gath[:, :, :, None] + w[None], INF), axis=2
    )


@dataclass(frozen=True)
class Segment:
    """One bipartite block family of a band: groups of ``R`` nodes
    sharing ``S`` sources. ``axis=1``: group index is the grid's major
    axis (contribution lands as [B, G1, G2] directly); ``axis=2``:
    group index is the minor axis (contribution transposes in)."""

    axis: int
    src: np.ndarray  # [G, S] int32 source ids (pad: self-ids, w=INF)
    w: np.ndarray  # [G, S, R] int32 edge metrics, INF padding


@dataclass(frozen=True)
class GridBand:
    start: int  # first node id of the band
    g1: int
    g2: int  # band rows = g1 * g2; id = start + a * g2 + b
    segments: Tuple[Segment, ...]


@dataclass(frozen=True)
class GroupedGraph:
    node_names: Tuple[str, ...]  # index == node id (grid-grouped order)
    node_index: Dict[str, int]
    n: int
    n_pad: int
    bands: Tuple[GridBand, ...]
    overloaded: np.ndarray  # [n_pad] bool
    direction: str  # "in" (forward relax) | "out" (reverse relax)

    def out_slots(self, node_id: int) -> Tuple[np.ndarray, np.ndarray]:
        """(neighbor ids, metrics) of this node's band row — for an
        "out" graph these are the node's forward out-edges, the slot
        list the route sweep's sample masks are defined over."""
        for band in self.bands:
            rows = band.g1 * band.g2
            if not (band.start <= node_id < band.start + rows):
                continue
            local = node_id - band.start
            a, b = divmod(local, band.g2)
            vs: List[int] = []
            ws: List[int] = []
            for seg in band.segments:
                g, r = (a, b) if seg.axis == 1 else (b, a)
                for s in range(seg.src.shape[1]):
                    if seg.w[g, s, r] < INF:
                        vs.append(int(seg.src[g, s]))
                        ws.append(int(seg.w[g, s, r]))
            return np.asarray(vs, np.int32), np.asarray(ws, np.int32)
        raise KeyError(node_id)


def _signature_groups(rows: List[str], srcs_by_class, cls):
    """Group band rows by their class-``cls`` source-set signature.
    Returns (groups: list of lists of row names, regular: bool)."""
    sig_map: Dict[Tuple[str, ...], List[str]] = {}
    for nm in rows:
        sig = tuple(sorted(srcs_by_class[nm].get(cls, {})))
        sig_map.setdefault(sig, []).append(nm)
    groups = [sorted(v) for v in sig_map.values()]
    groups.sort(key=lambda g: g[0])
    sizes = {len(g) for g in groups}
    regular = len(sizes) == 1 and () not in sig_map
    return groups, regular


def compile_grouped(
    ls, align: int = 128, direction: str = "in"
) -> GroupedGraph:
    """Structure-detecting compilation from the LinkState. O(E log E)
    host work; no dense matrix anywhere."""
    edges_of = _in_edges if direction == "in" else _out_edges
    raw_names = sorted(ls.get_adjacency_databases().keys())
    raw_index = {nm: i for i, nm in enumerate(raw_names)}
    # per node: src name -> metric (direction-appropriate)
    edges: Dict[str, Dict[str, int]] = {}
    for nm in raw_names:
        by_id = edges_of(ls, nm, raw_index)
        edges[nm] = {raw_names[i]: w for i, w in by_id.items()}
    # class = EXACT degree: finer than the ELL's pow2 classes, so that
    # fabric tiers land in distinct bands even when their degrees share
    # a pow2 bucket (a 3-tier fat-tree with degrees 2/3/6 must become
    # three bands for the signature grouping to see the structure).
    # Irregular graphs get at most O(distinct degrees) bands.
    degree = {nm: max(1, len(edges[nm])) for nm in raw_names}
    node_class = dict(degree)
    # per node: src class -> {src name: metric}
    srcs_by_class: Dict[str, Dict[int, Dict[str, int]]] = {}
    for nm in raw_names:
        per: Dict[int, Dict[str, int]] = {}
        for src, w in edges[nm].items():
            per.setdefault(node_class[src], {})[src] = w
        srcs_by_class[nm] = per

    # ---- band structuring ------------------------------------------------
    classes = sorted({node_class[nm] for nm in raw_names})
    band_plans = []  # (class_k, grid_names [G1][G2], seg plans)
    for ck in classes:
        rows = sorted(nm for nm in raw_names if node_class[nm] == ck)
        src_classes = sorted(
            {c for nm in rows for c in srcs_by_class[nm]}
        )
        plan = None
        if len(src_classes) == 1:
            groups, regular = _signature_groups(
                rows, srcs_by_class, src_classes[0]
            )
            if regular:
                grid = groups  # [G][R]
                plan = (grid, [(src_classes[0], 1)])
        elif len(src_classes) == 2:
            c1, c2 = src_classes
            gr1, reg1 = _signature_groups(rows, srcs_by_class, c1)
            gr2, reg2 = _signature_groups(rows, srcs_by_class, c2)
            if reg1 and reg2 and len(gr1) * len(gr2) == len(rows):
                # product check: every (group1, group2) cell holds
                # exactly one row
                pos1 = {nm: i for i, g in enumerate(gr1) for nm in g}
                pos2 = {nm: j for j, g in enumerate(gr2) for nm in g}
                cells = {(pos1[nm], pos2[nm]) for nm in rows}
                if len(cells) == len(rows):
                    grid = [
                        [None] * len(gr2) for _ in range(len(gr1))
                    ]
                    for nm in rows:
                        grid[pos1[nm]][pos2[nm]] = nm
                    plan = (grid, [(c1, 1), (c2, 2)])
        if plan is None:
            # unstructured: singleton groups, R=1 — the ELL shape
            grid = [[nm] for nm in rows]
            plan = (grid, None)
        band_plans.append((ck, plan))

    # ---- numbering: (class, grid-major) ---------------------------------
    names: List[str] = []
    for ck, (grid, _segs) in band_plans:
        for row in grid:
            names.extend(row)
    names_t = tuple(names)
    index = {nm: i for i, nm in enumerate(names_t)}
    n = len(names_t)
    n_pad = _pad_up(n, align)

    # ---- materialize segments -------------------------------------------
    bands: List[GridBand] = []
    start = 0
    for ck, (grid, seg_plan) in band_plans:
        g1 = len(grid)
        g2 = len(grid[0])
        segments: List[Segment] = []
        if seg_plan is None:
            # one generic segment: per-node source table, R = 1
            s_max = max(1, max(len(edges[r[0]]) for r in grid))
            src = np.zeros((g1, s_max), dtype=np.int32)
            w = np.full((g1, s_max, 1), INF, dtype=np.int32)
            for g, row in enumerate(grid):
                nm = row[0]
                src[g, :] = index[nm]  # inert self-pad
                for s, (sn, sw) in enumerate(
                    sorted(edges[nm].items())
                ):
                    src[g, s] = index[sn]
                    w[g, s, 0] = min(int(sw), int(INF) - 1)
            segments.append(Segment(axis=1, src=src, w=w))
        else:
            for cls, axis in seg_plan:
                if axis == 1:
                    groups = grid  # member r at grid[g][r]
                else:
                    groups = [
                        [grid[a][b] for a in range(g1)]
                        for b in range(g2)
                    ]
                g_count = len(groups)
                r_count = len(groups[0])
                src_names = [
                    sorted(srcs_by_class[groups[g][0]].get(cls, {}))
                    for g in range(g_count)
                ]
                s_max = max(1, max(len(s) for s in src_names))
                src = np.zeros((g_count, s_max), dtype=np.int32)
                w = np.full(
                    (g_count, s_max, r_count), INF, dtype=np.int32
                )
                for g in range(g_count):
                    base = index[groups[g][0]]
                    src[g, :] = base  # inert pad
                    for s, sn in enumerate(src_names[g]):
                        src[g, s] = index[sn]
                        for r, nm in enumerate(groups[g]):
                            w[g, s, r] = min(
                                int(srcs_by_class[nm][cls][sn]),
                                int(INF) - 1,
                            )
                segments.append(Segment(axis=axis, src=src, w=w))
        bands.append(
            GridBand(
                start=start, g1=g1, g2=g2, segments=tuple(segments)
            )
        )
        start += g1 * g2
    assert start == n, (start, n)

    overloaded = np.zeros(n_pad, dtype=bool)
    for nm in names_t:
        overloaded[index[nm]] = ls.is_node_overloaded(nm)
    return GroupedGraph(
        node_names=names_t,
        node_index=index,
        n=n,
        n_pad=n_pad,
        bands=tuple(bands),
        overloaded=overloaded,
        direction=direction,
    )


# ---- device tensors ------------------------------------------------------


@dataclass(frozen=True)
class _BandMeta:
    """Static (hashable) shape info for jit specialization."""

    start: int
    g1: int
    g2: int
    seg_axes: Tuple[int, ...]


def band_meta(graph: GroupedGraph) -> Tuple[_BandMeta, ...]:
    return tuple(
        _BandMeta(
            start=b.start,
            g1=b.g1,
            g2=b.g2,
            seg_axes=tuple(s.axis for s in b.segments),
        )
        for b in graph.bands
    )


def device_tensors(graph: GroupedGraph):
    """Flat tuples of per-segment (src, w) device arrays, in band/seg
    order — the resident state a caller uploads once."""
    srcs = []
    ws = []
    for band in graph.bands:
        for seg in band.segments:
            srcs.append(jnp.asarray(seg.src))
            ws.append(jnp.asarray(seg.w))
    return tuple(srcs), tuple(ws)


def _grouped_relax(d, meta, srcs_t, ws_t, overloaded, t_ids):
    """One relaxation [B, N] -> [B, N] over the grouped bands as dense
    per-segment contractions. ``t_ids`` None => forward transit mask
    (edge origin overloaded); else the reverse row-dependent mask
    ``overloaded[v] & (v != t)``."""
    parts = []
    pos = 0
    si = 0
    for band in meta:
        assert band.start == pos, (band, pos)
        rows = band.g1 * band.g2
        acc = d[:, pos : pos + rows]
        for axis in band.seg_axes:
            src = srcs_t[si]
            w = ws_t[si]
            si += 1
            gath = d[:, src]  # [B, G, S] — the only gather, G-sized
            if t_ids is None:
                blocked = overloaded[src][None, :, :]
            else:
                blocked = overloaded[src][None, :, :] & (
                    src[None, :, :] != t_ids[:, None, None]
                )
            gath = jnp.where(blocked, INF, gath)
            c = _contract(gath, w)  # [B, G, R]
            if axis == 2:
                c = jnp.transpose(c, (0, 2, 1))  # -> [B, G1, G2]
            acc = jnp.minimum(acc, c.reshape(c.shape[0], rows))
        parts.append(acc.astype(jnp.int32))
        pos += rows
    parts.append(d[:, pos:])  # padding columns
    return jnp.concatenate(parts, axis=1)


def _grouped_fixed_point(
    meta, srcs_t, ws_t, overloaded, ids, n, reverse, vote=None,
    init=None,
):
    """Distance fixed point from unit init. ``reverse=False``: rows are
    SOURCES (forward all-sources; init = one unmasked relax so an
    overloaded source still originates). ``reverse=True``: rows are
    DESTINATIONS (route-sweep orientation; the per-row mask needs no
    init special case). ``init`` (reverse only) warm-seeds rows with a
    pointwise upper bound on the new fixed point — the unit anchor is
    min-ed in, and the int32 min-relaxation's unique fixed point keeps
    the result bit-identical to the cold solve (the same contract as
    route_sweep._rev_fixed_point)."""
    b = ids.shape[0]
    unit = jnp.full((b, n), INF, dtype=jnp.int32)
    unit = unit.at[jnp.arange(b), ids].set(0)
    if reverse:
        d0 = unit if init is None else jnp.minimum(init, unit)
    else:
        assert init is None, "warm seed is a reverse-sweep contract"
        no_overload = jnp.zeros_like(overloaded)
        d0 = _grouped_relax(unit, meta, srcs_t, ws_t, no_overload, None)

    t_ids = ids if reverse else None

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed > 0, it < n)

    def body(state):
        d, _, it = state
        nxt = _grouped_relax(d, meta, srcs_t, ws_t, overloaded, t_ids)
        local = jnp.any(nxt < d).astype(jnp.int32)
        return nxt, local if vote is None else vote(local), it + 1

    d, _, _ = jax.lax.while_loop(cond, body, (d0, jnp.int32(1), 0))
    return d


@functools.partial(jax.jit, static_argnames=("meta", "n"))
def _grouped_from_sources(srcs_t, ws_t, overloaded, ids, meta, n):
    return _grouped_fixed_point(
        meta, srcs_t, ws_t, overloaded, ids, n, reverse=False
    )


class GroupedState:
    """Caller-owned resident device tensors (upload once)."""

    def __init__(self, graph: GroupedGraph):
        self.graph = graph
        self.meta = band_meta(graph)
        self.src, self.w = device_tensors(graph)
        self.overloaded = jnp.asarray(graph.overloaded)


def grouped_distances_from_sources(
    graph: GroupedGraph, src_ids, state: Optional[GroupedState] = None
):
    """Forward distances [S, N_pad] from a batch of sources — the
    grouped mirror of spf_sparse.ell_distances_from_sources."""
    st = state if state is not None else GroupedState(graph)
    return _grouped_from_sources(
        st.src, st.w, st.overloaded,
        _as_device_ids(src_ids), st.meta, graph.n_pad,
    )


# ---- destination-major route sweep over grouped bands --------------------


def _grouped_nh_counts(dr, meta, srcs_t, ws_t, overloaded, t_ids):
    """Per-node ECMP next-hop slot counts [B, N] over the grouped
    segments — the dense mirror of route_sweep._nh_counts (same
    algebra: v is a next hop of s toward t iff
    w(s, v) + DR[t, v] == DR[t, s], v not transit-blocked)."""
    b = dr.shape[0]
    parts = []
    pos = 0
    si = 0
    for band in meta:
        rows = band.g1 * band.g2
        acc = jnp.zeros((b, rows), dtype=jnp.int32)
        d_grid = dr[:, pos : pos + rows].reshape(b, band.g1, band.g2)
        for axis in band.seg_axes:
            src = srcs_t[si]
            w = ws_t[si]
            si += 1
            d_g = d_grid if axis == 1 else jnp.transpose(
                d_grid, (0, 2, 1)
            )  # [B, G, R]
            gath = dr[:, src]  # [B, G, S]
            blocked = overloaded[src][None, :, :] & (
                src[None, :, :] != t_ids[:, None, None]
            )
            total = jnp.minimum(
                jnp.where(blocked, INF, gath)[:, :, :, None] + w[None],
                INF,
            )  # [B, G, S, R]
            cond = (
                (total == d_g[:, :, None, :])
                & (d_g < INF)[:, :, None, :]
                & (w < INF)[None]
            )
            c = jnp.sum(cond, axis=2, dtype=jnp.int32)  # [B, G, R]
            if axis == 2:
                c = jnp.transpose(c, (0, 2, 1))
            acc = acc + c.reshape(b, rows)
        parts.append(acc)
        pos += rows
    parts.append(jnp.zeros_like(dr[:, pos:]))
    return jnp.concatenate(parts, axis=1)


def _grouped_cone_expand(sel_dr, meta, srcs_t, ws_t, e_u, e_v, e_w_old,
                         e_w_new, max_jumps, vote=None, cell_limit=None):
    """Affected-cone mask for a weight-increase delta over the GROUPED
    segment slabs — the dense mirror of route_sweep._cone_expand (same
    seed, same growth semantics, same counters), walking each band's
    ``[G, S, R]`` segments instead of per-row ELL slots. Seed: cells u
    where an increased edge (u -> v, w_old) was tight (edge-list based,
    layout-independent). Grow: cell j joins when any RAW-tight segment
    slot of j (old weights, resident distances) reaches a cone cell —
    the per-segment tight test is the same [B, G, S, R] algebra as
    _grouped_nh_counts, joined against ``cone[:, src]`` and landed back
    on the band grid (axis-2 segments transpose in, exactly like
    _grouped_relax). Tightness on RAW weights over-approximates — extra
    resets stay bit-identical by the unique-fixed-point squeeze. INF
    cells can never rise and are excluded.

    Returns ``(cone [B, N] bool, rows, cells, jumps, converged)`` with
    the identical contract as the ELL kernel: ``converged`` False on a
    ``max_jumps`` cutoff or ``cell_limit`` overflow (the cone is then
    an under-approximation and the caller must fall back), and
    ``vote`` psum-lifts the counters/growth bit for sharded callers."""
    b = sel_dr.shape[0]
    live = sel_dr < INF
    inc_e = (e_w_new > e_w_old) & (e_w_old < INF)
    seed_tight = (
        (sel_dr[:, e_u]
         == jnp.minimum(e_w_old[None, :] + sel_dr[:, e_v], INF))
        & inc_e[None, :]
        & live[:, e_u]
    )  # [B, E]
    cone0 = (
        jnp.zeros(sel_dr.shape, dtype=jnp.int32)
        .at[:, e_u].max(seed_tight.astype(jnp.int32))
    ) > 0

    def count(cone):
        rows = jnp.sum(jnp.any(cone, axis=1), dtype=jnp.int32)
        cells = jnp.sum(cone, dtype=jnp.float32)
        if vote is None:
            return rows, cells
        return vote(rows), vote(cells)

    def grow(cone):
        parts = []
        pos = 0
        si = 0
        for band in meta:
            rows = band.g1 * band.g2
            joined = jnp.zeros((b, rows), dtype=bool)
            d_grid = sel_dr[:, pos : pos + rows].reshape(
                b, band.g1, band.g2
            )
            for axis in band.seg_axes:
                src = srcs_t[si]
                w = ws_t[si]
                si += 1
                d_g = d_grid if axis == 1 else jnp.transpose(
                    d_grid, (0, 2, 1)
                )  # [B, G, R]
                gath = sel_dr[:, src]  # [B, G, S]
                total = jnp.minimum(
                    gath[:, :, :, None] + w[None], INF
                )  # [B, G, S, R]
                tight = (
                    (total == d_g[:, :, None, :])
                    & (d_g < INF)[:, :, None, :]
                    & (w < INF)[None]
                )
                j = jnp.any(
                    tight & cone[:, src][:, :, :, None], axis=2
                )  # [B, G, R]
                if axis == 2:
                    j = jnp.transpose(j, (0, 2, 1))
                joined = joined | j.reshape(b, rows)
            parts.append(joined)
            pos += rows
        parts.append(jnp.zeros_like(cone[:, pos:]))
        return cone | jnp.concatenate(parts, axis=1)

    def cond(state):
        _, _, cells, it, grew = state
        keep = jnp.logical_and(grew > 0, it < max_jumps)
        if cell_limit is not None:
            keep = jnp.logical_and(keep, cells <= cell_limit)
        return keep

    def body(state):
        cone, _, _, it, _ = state
        nxt = grow(cone)
        grew_local = jnp.any(nxt & ~cone).astype(jnp.int32)
        grew = grew_local if vote is None else vote(grew_local)
        rows, cells = count(nxt)
        return nxt, rows, cells, it + 1, grew

    rows0, cells0 = count(cone0)
    cone, rows, cells, jumps, grew = jax.lax.while_loop(
        cond, body,
        (cone0, rows0, cells0, jnp.int32(0),
         (cells0 > 0).astype(jnp.int32)),
    )
    converged = grew == 0
    if cell_limit is not None:
        converged = jnp.logical_and(converged, cells <= cell_limit)
    return cone, rows, cells, jumps, converged


def _grouped_route_block_body(
    srcs_t, ws_t, overloaded, t_ids, samp_ids, samp_v, samp_w, pos_w,
    meta, n, vote=None,
):
    """Grouped twin of route_sweep._route_block_body: same packed
    layout, same digest algebra — only the relaxation backend differs,
    so the canonical digest must agree bit-exactly with the ELL sweep."""
    from openr_tpu.ops import route_sweep as rs

    dr = _grouped_fixed_point(
        meta, srcs_t, ws_t, overloaded, t_ids, n, reverse=True,
        vote=vote,
    )
    nh_count = _grouped_nh_counts(
        dr, meta, srcs_t, ws_t, overloaded, t_ids
    )
    digest = rs._digest_rows(dr, nh_count, pos_w)
    nh_total = jnp.sum(nh_count, axis=1, dtype=jnp.int32)
    d_s, packed_mask = rs._sample_stats(
        dr, samp_ids, samp_v, samp_w, overloaded, t_ids
    )
    b = t_ids.shape[0]
    return jnp.concatenate(
        [
            jax.lax.bitcast_convert_type(digest, jnp.int32)[:, None],
            nh_total[:, None],
            d_s,
            jax.lax.bitcast_convert_type(
                packed_mask, jnp.int32
            ).reshape(b, -1),
        ],
        axis=1,
    )


@functools.partial(jax.jit, static_argnames=("meta", "n"))
def _grouped_route_block(
    srcs_t, ws_t, overloaded, t_ids, samp_ids, samp_v, samp_w, pos_w,
    meta, n,
):
    return _grouped_route_block_body(
        srcs_t, ws_t, overloaded, t_ids, samp_ids, samp_v, samp_w,
        pos_w, meta, n,
    )


class GroupedRouteSweeper:
    """Destination-major route sweeper over the grouped (out-edge)
    graph — the gather-free backend of ops.route_sweep.RouteSweeper,
    producing the identical RouteSweepResult (canonical digests are
    bit-comparable across the two backends)."""

    def __init__(self, graph: GroupedGraph, sample_names: Sequence[str],
                 plan=None):
        from openr_tpu.ops import route_sweep as rs

        assert graph.direction == "out", "route sweep needs out-edges"
        # replicated build-time placement under a mesh, mirroring
        # RouteSweeper (see parallel.mesh.ShardingPlan)
        up = plan.replicate if plan is not None else jnp.asarray
        self.graph = graph
        self.plan = plan
        self.meta = band_meta(graph)
        self.v_t, self.w_t = (
            tuple(up(seg) for seg in t) for t in device_tensors(graph)
        )
        self.overloaded = up(graph.overloaded)
        self.sample_names = tuple(sample_names)
        self.sample_ids = np.asarray(
            [graph.node_index[nm] for nm in self.sample_names],
            dtype=np.int32,
        )
        rows = [graph.out_slots(int(sid)) for sid in self.sample_ids]
        self.samp_v, self.samp_w = rs.pack_sample_rows(
            rows, self.sample_ids
        )
        self._samp_ids_dev = up(self.sample_ids)
        self._samp_v_dev = up(self.samp_v)
        self._samp_w_dev = up(self.samp_w)
        self._pos_w_dev = up(rs.canonical_pos_weights(graph))

    def solve_block(self, t_ids):
        # openr-lint: disable=sharding-spec -- single-chip block solve
        # (mesh engines dispatch their sharded full-resident twin)
        return _grouped_route_block(
            self.v_t, self.w_t, self.overloaded,
            _as_device_ids(t_ids),
            self._samp_ids_dev, self._samp_v_dev, self._samp_w_dev,
            self._pos_w_dev, self.meta, self.graph.n_pad,
        )

    # the block loop and result assembly are layout-independent —
    # reuse RouteSweeper's implementation verbatim
    from openr_tpu.ops.route_sweep import RouteSweeper as _RS

    sweep = _RS.sweep
    del _RS


def compile_out_grouped(ls, align: int = 128) -> GroupedGraph:
    """Out-edge grouped graph for the destination-major route sweep."""
    return compile_grouped(ls, align=align, direction="out")


# ---- incremental weight patching -----------------------------------------


def slot_table(graph: GroupedGraph) -> Dict[int, List[Tuple]]:
    """node id -> [(segment flat index, g, s, r, src id)] for every
    REAL edge slot of the node's band row, in device_tensors order.

    Captured at compile time (a real slot has w < INF in the fresh
    layout), so later in-place removals (slot INF'd by grouped_patch)
    stay in the table and remain RESTORABLE — the inert self-pad slots
    are never in it, which is what keeps a patch from double-counting
    a pad whose id coincides with a real neighbor (nh counts sum over
    slots; a duplicated edge would corrupt the digest)."""
    out: Dict[int, List[Tuple]] = {}
    si = 0
    for band in graph.bands:
        for seg in band.segments:
            # vectorized over the dense [G, S, R] weight tensor: a
            # python triple loop here costs seconds of host time per
            # cold build at engine scale (millions of cells at 10k+)
            gg, ss, rr = np.nonzero(seg.w < INF)
            if seg.axis == 1:
                nodes = band.start + gg * band.g2 + rr
            else:
                nodes = band.start + rr * band.g2 + gg
            sids = seg.src[gg, ss]
            for x in range(len(gg)):
                out.setdefault(int(nodes[x]), []).append(
                    (si, int(gg[x]), int(ss[x]), int(rr[x]),
                     int(sids[x]))
                )
            si += 1
    return out


def grouped_patch(
    graph: GroupedGraph, ls, affected, slots: Dict[int, List[Tuple]]
):
    """In-place weight patch for churn on an existing grouped layout:
    returns (patched GroupedGraph, per-segment update lists
    {seg flat idx: [(g, s, r, new_w)]}) or None when the event breaks
    the layout's structure (unknown node, or an edge toward a neighbor
    the node's slot signature does not carry — a NEW adjacency needs a
    recompile; the signature grouping is what makes the segments
    dense).

    Metric changes and edge REMOVALS (slot set to INF — inert in every
    relaxation) always patch in place: node ids are untouched, so a
    resident DR keyed by them stays valid. A removed slot stays in the
    slot table and is restored by a later patch when the edge returns.
    The patched layout may no longer be what a fresh compile would
    produce (a removal changes the node's degree class) — stale as a
    CANONICAL layout, but exact as a relaxation structure."""
    edges_of = _in_edges if graph.direction == "in" else _out_edges
    names = tuple(sorted(ls.get_adjacency_databases().keys()))
    if len(names) != graph.n or any(
        nm not in graph.node_index for nm in names
    ):
        # node set changed — including a same-count SWAP (one node
        # out, another in), which a bare length check would miss and
        # silently serve routes for a topology that no longer exists
        return None
    updates: Dict[int, List[Tuple[int, int, int, int]]] = {}
    overloaded = graph.overloaded.copy()
    for nm in affected:
        i = graph.node_index.get(nm)
        if i is None:
            return None
        new_edges = edges_of(ls, nm, graph.node_index)
        my_slots = slots.get(i, [])
        slot_srcs = {sid for (_si, _g, _s, _r, sid) in my_slots}
        if set(new_edges) - slot_srcs:
            return None  # new neighbor: structure change
        for (si, g, s, r, sid) in my_slots:
            # real metrics arrive capped at INF-1 by edges_of (the
            # same cap compile_grouped applies); INF is exclusively
            # the removed/pad sentinel. The ONE update list feeds both
            # the host copy below and the device scatter tensors, so
            # the two representations cannot diverge.
            updates.setdefault(si, []).append(
                (g, s, r, int(new_edges.get(sid, INF)))
            )
        overloaded[i] = ls.is_node_overloaded(nm)
    # copy-on-write the touched segments' host arrays
    seg_list: List[Segment] = []
    for band in graph.bands:
        seg_list.extend(band.segments)
    patched_segs = list(seg_list)
    for si, ups in updates.items():
        w = seg_list[si].w.copy()
        for (g, s, r, wv) in ups:
            w[g, s, r] = wv
        patched_segs[si] = Segment(
            axis=seg_list[si].axis, src=seg_list[si].src, w=w
        )
    bands: List[GridBand] = []
    si = 0
    for band in graph.bands:
        k = len(band.segments)
        bands.append(
            GridBand(
                start=band.start, g1=band.g1, g2=band.g2,
                segments=tuple(patched_segs[si : si + k]),
            )
        )
        si += k
    patched = GroupedGraph(
        node_names=graph.node_names, node_index=graph.node_index,
        n=graph.n, n_pad=graph.n_pad, bands=tuple(bands),
        overloaded=overloaded, direction=graph.direction,
    )
    return patched, updates


@functools.partial(jax.jit, static_argnames=("meta", "n", "mesh"))
def _sharded_grouped_route_blocks(
    srcs_t, ws_t, overloaded, t_ids, samp_ids, samp_v, samp_w, pos_w,
    meta, n, mesh,
):
    from jax.sharding import PartitionSpec as P

    from openr_tpu.ops.spf_sparse import SOURCES_AXIS

    def shard_fn(t_blk, *rest):
        ns = len(srcs_t)
        s_r = rest[:ns]
        w_r = rest[ns : 2 * ns]
        ov_r, sid_r, sv_r, sw_r, pw_r = rest[2 * ns :]
        return _grouped_route_block_body(
            s_r, w_r, ov_r, t_blk, sid_r, sv_r, sw_r, pw_r, meta, n,
            vote=lambda bit: jax.lax.psum(bit, SOURCES_AXIS),
        )

    ns = len(srcs_t)
    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=tuple(
            [P(SOURCES_AXIS)]
            + [P(None, None)] * ns  # src tables [G, S], replicated
            + [P(None, None, None)] * ns  # w tensors [G, S, R]
            + [P(None), P(None), P(None, None), P(None, None), P(None)]
        ),
        out_specs=P(SOURCES_AXIS, None),
    )(t_ids, *srcs_t, *ws_t, overloaded, samp_ids, samp_v, samp_w,
      pos_w)


def sharded_grouped_route_sweep(graph: GroupedGraph, sample_names, mesh):
    """The grouped route sweep in ONE sharded dispatch: destination
    rows sharded over the mesh, segment tables replicated (O(E)), the
    1-bit convergence psum the only collective — the grouped twin of
    route_sweep.sharded_route_sweep, producing the identical
    RouteSweepResult (canonical digests bit-comparable)."""
    from openr_tpu.ops import route_sweep as rs

    sweeper = GroupedRouteSweeper(graph, sample_names)
    n = graph.n_pad
    assert n % mesh.devices.size == 0, (n, mesh.devices.size)
    packed = np.asarray(
        _sharded_grouped_route_blocks(
            sweeper.v_t, sweeper.w_t, sweeper.overloaded,
            jnp.asarray(np.arange(n, dtype=np.int32)),
            sweeper._samp_ids_dev, sweeper._samp_v_dev,
            sweeper._samp_w_dev, sweeper._pos_w_dev,
            sweeper.meta, n, mesh,
        )
    )
    return rs.assemble_result(sweeper, packed)


def structure_report(graph: GroupedGraph) -> dict:
    """How much of the edge volume the structure detection captured:
    per band (g1, g2, segments, slots) + the total gather shrink
    factor vs per-node ELL slots."""
    bands = []
    grouped_slots = 0
    row_slots = 0
    for band in graph.bands:
        rows = band.g1 * band.g2
        seg_info = []
        for seg in band.segments:
            g, s, r = seg.w.shape
            seg_info.append({"axis": seg.axis, "g": g, "s": s, "r": r})
            grouped_slots += g * s
            row_slots += g * s * r
        bands.append(
            {"rows": rows, "g1": band.g1, "g2": band.g2,
             "segments": seg_info}
        )
    return {
        "bands": bands,
        "gather_slots": grouped_slots,
        "ell_equivalent_slots": row_slots,
        "gather_shrink": round(row_slots / max(1, grouped_slots), 1),
    }
