"""Destination-major all-sources route sweep: route selection consumed
ON-DEVICE, so the all-sources product never crosses host<->device.

The source-major sweep (ops.spf_sparse.iter_ell_all_sources) computes
d(s, .) row blocks — but ECMP first-hop extraction for source s needs
its NEIGHBORS' rows, which live in other blocks, so the only way to
finish route selection was to read the whole [N, N] matrix back to the
host: 414 MB at 10k nodes, 40 GB at 100k — the e2e was transfer-bound
(13.5 s against 143 ms of device compute at 10k).

This module flips the major axis. Sweeping the REVERSED graph (an
out-edge ELL: row s holds (v, w(s->v)) for every forward edge s->v, see
spf_sparse.compile_ell(direction="out")) makes each block row a
destination column of the forward problem:

    DR[t, s] = d(s -> t)

and within that single row EVERY node's ECMP next-hop test is local:

    v in nh(s -> t)  iff  w(s, v) + DR[t, v] == DR[t, s]

(reference semantics: SpfSolver::getNextHopsWithMetric,
/root/reference/openr/decision/Decision.cpp:1124, consumed by
buildRouteDb, Decision.cpp:569-734). So per destination block the
device computes, with one extra relax-shaped pass:

  - per-node ECMP next-hop slot masks and counts (all N sources),
  - a position-sensitive uint32 digest of (distances, nh counts) per
    destination — the proof that route selection for EVERY source was
    computed, readable back in 4 bytes per destination,
  - full route rows (metric + packed next-hop slot mask) for a small
    set of SAMPLE nodes — enough to assemble a complete RouteDb for
    this node (and oracle-check others) on the host.

Readback per block is O(B) + O(B x samples), not O(B x N): the 10k
sweep returns ~200 KB instead of 414 MB, which is what lets e2e track
device-only time instead of transfer time.

Transit/overload semantics match the forward kernels exactly, but the
reversed formulation needs no special init step: a forward path
s -> v1 -> ... -> t is blocked iff some INTERMEDIATE v_i is overloaded
(the source may originate, the destination may terminate — reference
LinkState.cpp:831-838). Relaxing DR[t, s] over edge (s -> v) prepends s
to a v ~> t path, in which v is intermediate unless v == t, so the edge
mask is simply  blocked = overloaded[v] & (v != t)  — row-dependent,
never source-dependent.

The digest doubles as a cross-kernel equivalence check: any alternative
relaxation backend (e.g. the grouped block contraction) must reproduce
the same uint32 per destination, bit-exactly.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from openr_tpu.ops.spf import INF
from openr_tpu.ops.spf_sparse import (
    EllGraph,
    _as_device_ids,
    compile_ell,
)

__all__ = [
    "RouteSweepResult",
    "RouteSweeper",
    "all_sources_route_sweep",
    "compile_out_ell",
    "host_digest",
]

_DIGEST_MULT_D = np.uint32(2654435761)  # Knuth multiplicative
_DIGEST_MULT_C = np.uint32(40503)
_DIGEST_POS_A = np.uint32(2246822519)  # xxhash prime
_DIGEST_POS_B = np.uint32(0x9E3779B9)


def compile_out_ell(ls, align: int = 128) -> EllGraph:
    """Out-edge (reversed-graph) sliced-ELL bands for the route sweep."""
    return compile_ell(ls, align=align, direction="out")


def _rev_relax(dr, bands, v_t, w_t, overloaded, t_ids):
    """One reversed-graph relaxation [B, N] -> [B, N] with the
    row-dependent transit mask: edge (s -> v) may extend a v ~> t path
    unless v is overloaded and v != t."""
    parts = []
    pos = 0
    for band, v_b, w_b in zip(bands, v_t, w_t):
        assert band.start == pos, (band, pos)
        blocked = overloaded[v_b][None, :, :] & (
            v_b[None, :, :] != t_ids[:, None, None]
        )  # [B, rows, k]
        w_eff = jnp.where(blocked, INF, w_b[None, :, :])
        gathered = dr[:, v_b]  # [B, rows, k]
        relaxed = jnp.min(jnp.minimum(gathered + w_eff, INF), axis=2)
        parts.append(
            jnp.minimum(dr[:, pos : pos + band.rows], relaxed.astype(jnp.int32))
        )
        pos += band.rows
    parts.append(dr[:, pos:])  # padding columns: unchanged
    return jnp.concatenate(parts, axis=1)


def _rev_fixed_point(bands, v_t, w_t, overloaded, t_ids, n, vote=None,
                     init=None):
    """DR rows [B, N] for destination batch ``t_ids`` from unit init.
    ``vote`` lifts the local convergence bit to a global one (psum) for
    the sharded variant, mirroring spf_sparse._ell_fixed_point.
    ``init`` optionally warm-seeds rows with a pointwise upper bound on
    the new fixed point (e.g. the pre-patch resident rows outside the
    increase-affected cone); the unit anchor is min-ed in, and the
    int32 min-relaxation's unique fixed point keeps the result
    bit-identical to the cold solve."""
    b = t_ids.shape[0]
    unit = jnp.full((b, n), INF, dtype=jnp.int32)
    unit = unit.at[jnp.arange(b), t_ids].set(0)
    d0 = unit if init is None else jnp.minimum(init, unit)

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed > 0, it < n)

    def body(state):
        dr, _, it = state
        nxt = _rev_relax(dr, bands, v_t, w_t, overloaded, t_ids)
        local = jnp.any(nxt < dr).astype(jnp.int32)
        return nxt, local if vote is None else vote(local), it + 1

    dr, _, _ = jax.lax.while_loop(cond, body, (d0, jnp.int32(1), 0))
    return dr


def _cone_expand(sel_dr, bands, v_t, w_t, e_u, e_v, e_w_old, e_w_new,
                 max_jumps, vote=None, cell_limit=None):
    """Affected-cone mask for a weight-increase delta (the frontier
    kernel). Given resident distance rows ``sel_dr`` [B, N] and the
    PRE-patch bands, mark every cell whose tight shortest path crosses
    an increased edge — exactly the cells whose distance may RISE, i.e.
    the cells a warm seed must reset. Seed: cells u where an increased
    edge (u -> v, w_old) was tight; expand by frontier jumps: cell j
    joins when any tight band slot of j (old weights, old distances)
    reaches a cone cell; a jump per `lax.while_loop` iteration until
    the cone stops growing. Tightness is tested on RAW weights (no
    overload mask): every realized tight step is raw-tight, so the
    cone only over-approximates — extra resets stay bit-identical by
    the unique-fixed-point squeeze. Cells already at INF can never
    rise and are excluded (keeps unreachable regions from chaining
    into the cone).

    Returns ``(cone [B, N] bool, rows, cells, jumps, converged)``.
    ``rows`` counts rows with a nonempty cone; ``cells`` the total
    cone population — the re-solve work measure the overflow policy
    thresholds on (a single link down puts ONE cell in nearly every
    row, so a row count saturates while the cone stays tiny).
    ``vote``/psum lifts both globally for the sharded variant, same
    contract as _rev_fixed_point. ``converged`` is False when the
    expansion was cut off by ``max_jumps`` or overflowed
    ``cell_limit`` — the cone is then an UNDER-approximation and the
    caller must fall back to a coarser reset (whole-row, or the
    full-width refresh)."""
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
        # float32: the population can reach B*N (1e10 at 100k nodes),
        # past int32; a policy threshold tolerates float rounding
        cells = jnp.sum(cone, dtype=jnp.float32)
        if vote is None:
            return rows, cells
        return vote(rows), vote(cells)

    def grow(cone):
        parts = []
        pos = 0
        for band, v_b, w_b in zip(bands, v_t, w_t):
            d_band = sel_dr[:, pos : pos + band.rows]  # [B, rows]
            total = jnp.minimum(sel_dr[:, v_b] + w_b[None, :, :], INF)
            tight = (
                (total == d_band[:, :, None])
                & (d_band < INF)[:, :, None]
                & (w_b < INF)[None, :, :]
            )  # [B, rows, k]
            parts.append(jnp.any(tight & cone[:, v_b], axis=2))
            pos += band.rows
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
    # rows/cells: int32 / float32; jumps int32; converged bool
    converged = grew == 0
    if cell_limit is not None:
        converged = jnp.logical_and(converged, cells <= cell_limit)
    return cone, rows, cells, jumps, converged


def _nh_counts(dr, bands, v_t, w_t, overloaded, t_ids):
    """Per-node ECMP next-hop slot counts [B, N] — route selection for
    every source, evaluated against its own destination row."""
    parts = []
    pos = 0
    for band, v_b, w_b in zip(bands, v_t, w_t):
        blocked = overloaded[v_b][None, :, :] & (
            v_b[None, :, :] != t_ids[:, None, None]
        )
        total = jnp.minimum(
            dr[:, v_b] + jnp.where(blocked, INF, w_b[None, :, :]), INF
        )  # [B, rows, k]
        d_row = dr[:, pos : pos + band.rows]  # [B, rows]
        cond = (
            (total == d_row[:, :, None])
            & (d_row < INF)[:, :, None]
            & (w_b < INF)[None, :, :]
        )
        parts.append(jnp.sum(cond, axis=2, dtype=jnp.int32))
        pos += band.rows
    parts.append(jnp.zeros_like(dr[:, pos:]))
    return jnp.concatenate(parts, axis=1)


def canonical_pos_weights(graph: EllGraph) -> np.ndarray:
    """Per-column digest weights keyed by CANONICAL (name-rank) node
    order, so two graphs over the same node set produce comparable
    digests regardless of their internal band renumbering — the digest
    is a cross-kernel/cross-layout equality witness. Padding columns
    get weight 0 (their content is layout-specific)."""
    n_pad = graph.n_pad
    order = np.argsort(np.asarray(graph.node_names))
    ranks = np.empty(len(order), dtype=np.uint32)
    ranks[order] = np.arange(len(order), dtype=np.uint32)
    pos = np.zeros(n_pad, dtype=np.uint32)
    with np.errstate(over="ignore"):
        pos[: len(ranks)] = (
            ranks * _DIGEST_MULT_C + np.uint32(1)
        ) * _DIGEST_POS_A ^ _DIGEST_POS_B
    return pos


def _digest_rows(dr, nh_count, pos_w):
    """Position-sensitive uint32 fold of (distance, nh count) per row.
    Pure int mixing — wraparound adds/multiplies are deterministic on
    every backend. ``pos_w`` carries the canonical column weights."""
    v = dr.astype(jnp.uint32) * _DIGEST_MULT_D + (
        nh_count.astype(jnp.uint32) + jnp.uint32(0x85EBCA6B)
    )
    return jnp.sum(v * pos_w[None, :], axis=1, dtype=jnp.uint32)


def host_digest(
    d_rows: np.ndarray, nh_counts: np.ndarray,
    pos_w: Optional[np.ndarray] = None,
) -> np.ndarray:
    """NumPy replica of the device digest (oracle for tests). When
    ``pos_w`` is omitted the columns are assumed to already be in
    canonical name-rank order."""
    n = d_rows.shape[1]
    with np.errstate(over="ignore"):
        if pos_w is None:
            pos_w = (
                np.arange(n, dtype=np.uint32) * _DIGEST_MULT_C
                + np.uint32(1)
            ) * _DIGEST_POS_A ^ _DIGEST_POS_B
        v = d_rows.astype(np.uint32) * _DIGEST_MULT_D + (
            nh_counts.astype(np.uint32) + np.uint32(0x85EBCA6B)
        )
        acc = np.zeros(d_rows.shape[0], dtype=np.uint32)
        for j in range(n):
            acc += v[:, j] * pos_w[j]
    return acc


def _sample_stats(dr, samp_ids, samp_v, samp_w, overloaded, t_ids):
    """Metrics + packed next-hop slot masks for the sample nodes:
    ([B, S] int32, [B, S, K/32] uint32). K is a multiple of 32."""
    blocked = overloaded[samp_v][None, :, :] & (
        samp_v[None, :, :] != t_ids[:, None, None]
    )  # [B, S, K]
    total = jnp.minimum(
        dr[:, samp_v] + jnp.where(blocked, INF, samp_w[None, :, :]), INF
    )
    d_s = dr[:, samp_ids]  # [B, S]
    cond = (
        (total == d_s[:, :, None])
        & (d_s < INF)[:, :, None]
        & (samp_w < INF)[None, :, :]
    )
    b, s, k = cond.shape
    bits = cond.reshape(b, s, k // 32, 32).astype(jnp.uint32)
    weights = jnp.left_shift(
        jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32)
    )
    packed = jnp.sum(bits * weights[None, None, None, :], axis=3,
                     dtype=jnp.uint32)
    return d_s, packed


def _route_block_body(v_t, w_t, overloaded, t_ids, samp_ids, samp_v,
                      samp_w, pos_w, bands, n, vote=None):
    """Fixed point + on-device route selection for one destination
    block, packed into a single int32 array [B, W] so the block costs
    exactly ONE device->host transfer:
      col 0                digest (uint32 bitcast)
      col 1                per-destination total ECMP next-hop count
      cols 2 .. 2+S        sample metrics
      cols 2+S ..          sample packed nh masks (uint32 bitcast)
    (decoded by _unpack_blocks — the one other place that knows this
    layout). Shared verbatim by the single-chip and sharded dispatches;
    ``vote`` lifts the convergence bit for the sharded variant."""
    dr = _rev_fixed_point(bands, v_t, w_t, overloaded, t_ids, n, vote=vote)
    nh_count = _nh_counts(dr, bands, v_t, w_t, overloaded, t_ids)
    digest = _digest_rows(dr, nh_count, pos_w)
    nh_total = jnp.sum(nh_count, axis=1, dtype=jnp.int32)
    d_s, packed_mask = _sample_stats(
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


@functools.partial(jax.jit, static_argnames=("bands", "n"))
def _route_block(v_t, w_t, overloaded, t_ids, samp_ids, samp_v, samp_w,
                 pos_w, bands, n):
    return _route_block_body(
        v_t, w_t, overloaded, t_ids, samp_ids, samp_v, samp_w, pos_w,
        bands, n
    )


def _unpack_blocks(packed: np.ndarray, s: int, kw: int):
    """Decode the _route_block_body column layout for ``T`` packed rows:
    (digests [T] uint32, nh_totals [T] int32, metrics [T, S] int32,
    masks [T, S, kw] uint32)."""
    t = packed.shape[0]
    return (
        packed[:, 0].view(np.uint32).copy(),
        packed[:, 1].copy(),
        packed[:, 2 : 2 + s].copy(),
        packed[:, 2 + s :].view(np.uint32).reshape(t, s, kw).copy(),
    )


def assemble_result(
    sweeper, packed: np.ndarray, into: "RouteSweepResult" = None
) -> "RouteSweepResult":
    """Build a RouteSweepResult from a full [n_pad, W] packed array —
    the ONE assembly site shared by every one-dispatch sweep (the ELL
    and grouped sharded variants).

    Delta mode (``into=``): ``packed`` is a COMPACTED [m, 1+W] delta —
    each row a destination id followed by that row's fresh product —
    and the decoded fields are scattered in place into the existing
    result. O(m) host work instead of O(n_pad): this is how the
    engine's delta-compacted readbacks land without re-assembling the
    whole product (ids must be in-range; the engine filters padding
    rows before calling)."""
    s = len(sweeper.sample_ids)
    kw = sweeper.samp_v.shape[1] // 32
    if into is not None:
        ids = packed[:, 0]
        dg, nt, sm, sk = _unpack_blocks(
            np.ascontiguousarray(packed[:, 1:]), s, kw
        )
        into.digests[ids] = dg
        into.nh_totals[ids] = nt
        into.sample_metrics[ids] = sm
        into.sample_masks[ids] = sk
        return into
    dg, nt, sm, sk = _unpack_blocks(packed, s, kw)
    return RouteSweepResult(
        graph=sweeper.graph,
        sample_names=sweeper.sample_names,
        sample_ids=sweeper.sample_ids,
        samp_v=sweeper.samp_v,
        samp_w=sweeper.samp_w,
        digests=dg,
        nh_totals=nt,
        sample_metrics=sm,
        sample_masks=sk,
    )


def digests_by_name(result: "RouteSweepResult"):
    """Name-keyed canonical digests — the cross-backend comparison
    view (two layouts number nodes differently; names do not)."""
    idx = result.graph.node_index
    return {
        nm: result.digests[idx[nm]] for nm in result.graph.node_names
    }


@dataclass
class RouteSweepResult:
    """Host-side product of a full destination sweep."""

    graph: EllGraph  # out-direction ELL (its node order names the axes)
    sample_names: Tuple[str, ...]
    sample_ids: np.ndarray  # [S]
    samp_v: np.ndarray  # [S, K] out-edge dst ids (self-pad)
    samp_w: np.ndarray  # [S, K] out-edge metrics (INF pad)
    digests: np.ndarray  # [n] uint32 per-destination route digest
    nh_totals: np.ndarray  # [n] int32 sum of all sources' ECMP fanout
    sample_metrics: np.ndarray  # [n, S] d(sample -> t) for every t
    sample_masks: np.ndarray  # [n, S, K/32] uint32 packed nh slots

    def routes_from(self, sample_name: str) -> Dict[str, Tuple[int, Set[str]]]:
        """Full route table of one sample node, assembled from the
        sweep: destination name -> (metric, ECMP next-hop node names).
        Unreachable destinations are omitted; the self row is omitted
        (a node has no route to itself)."""
        s = self.sample_names.index(sample_name)
        names = self.graph.node_names
        sid = int(self.sample_ids[s])
        out: Dict[str, Tuple[int, Set[str]]] = {}
        k = self.samp_v.shape[1]
        words = self.sample_masks[:, s, :]  # [n, K/32]
        for t in range(self.graph.n):
            if t == sid:
                continue
            metric = int(self.sample_metrics[t, s])
            if metric >= INF:
                continue
            nhs: Set[str] = set()
            for slot in range(k):
                if words[t, slot // 32] >> np.uint32(slot % 32) & 1:
                    nhs.add(names[int(self.samp_v[s, slot])])
            out[names[t]] = (metric, nhs)
        return out


def pack_sample_rows(rows, sample_ids):
    """Pack per-sample (neighbor ids, metrics) rows into one [S, K]
    pair, K padded to a multiple of 32 (the nh masks pack into uint32
    words; RouteSweepResult.routes_from decodes this exact layout).
    Shared by every sweep backend so the packing contract has one
    home."""
    k_max = max(1, max(len(v) for v, _ in rows))
    k_pad = max(32, ((k_max + 31) // 32) * 32)
    s = len(rows)
    samp_v = np.zeros((s, k_pad), dtype=np.int32)
    samp_w = np.full((s, k_pad), INF, dtype=np.int32)
    for x, (v, w) in enumerate(rows):
        samp_v[x, : len(v)] = v
        samp_v[x, len(v):] = sample_ids[x]  # inert self-pad
        samp_w[x, : len(w)] = w
    return samp_v, samp_w


def _sample_bands(graph: EllGraph, sample_ids: Sequence[int]):
    """Sample nodes' out-edge rows from the ELL bands, packed."""
    from openr_tpu.ops.spf_sparse import _band_of

    rows = []
    for sid in sample_ids:
        bi, band = _band_of(graph, int(sid))
        r = int(sid) - band.start
        v_row = graph.src[bi][r]
        w_row = graph.w[bi][r]
        keep = w_row < INF
        rows.append((v_row[keep], w_row[keep]))
    return pack_sample_rows(rows, sample_ids)


class RouteSweeper:
    """Resident-band driver for the destination-major route sweep.

    Bands upload once; every block is one dispatch + ONE small
    readback. Mirrors spf_sparse.EllState's residency discipline (a
    per-block re-upload is a host round trip per block)."""

    def __init__(self, graph: EllGraph, sample_names: Sequence[str],
                 plan=None):
        assert graph.direction == "out", "route sweep needs out-edge ELL"
        # every resident the sharded dispatches read is committed
        # replicated at build time (parallel.mesh.ShardingPlan) — under
        # a mesh an unplaced band tensor makes XLA insert a replication
        # copy on every churn dispatch
        up = plan.replicate if plan is not None else jnp.asarray
        self.graph = graph
        self.plan = plan
        self.v_t = tuple(up(s) for s in graph.src)
        self.w_t = tuple(up(w) for w in graph.w)
        self.overloaded = up(graph.overloaded)
        self.sample_names = tuple(sample_names)
        self.sample_ids = np.asarray(
            [graph.node_index[nm] for nm in self.sample_names],
            dtype=np.int32,
        )
        self.samp_v, self.samp_w = _sample_bands(graph, self.sample_ids)
        self._samp_ids_dev = up(self.sample_ids)
        self._samp_v_dev = up(self.samp_v)
        self._samp_w_dev = up(self.samp_w)
        self._pos_w_dev = up(canonical_pos_weights(graph))

    def solve_block(self, t_ids) -> jnp.ndarray:
        """One destination block -> packed [B, W] int32 (still on
        device; the caller reads it back or chains on it)."""
        # openr-lint: disable=sharding-spec -- single-chip block solve
        # (mesh engines dispatch _sharded_full_resident instead)
        return _route_block(
            self.v_t, self.w_t, self.overloaded,
            _as_device_ids(t_ids),
            self._samp_ids_dev, self._samp_v_dev, self._samp_w_dev,
            self._pos_w_dev,
            self.graph.bands, self.graph.n_pad,
        )

    def sweep(self, block: int = 1024) -> RouteSweepResult:
        n = self.graph.n_pad
        s = len(self.sample_ids)
        kw = self.samp_v.shape[1] // 32
        digests = np.zeros(n, dtype=np.uint32)
        nh_totals = np.zeros(n, dtype=np.int32)
        sample_metrics = np.zeros((n, s), dtype=np.int32)
        sample_masks = np.zeros((n, s, kw), dtype=np.uint32)
        # all block id vectors up front (async upload burst; uploading
        # per block would serialize a host round trip between blocks)
        id_blocks = []
        for start in range(0, n, block):
            ids = np.arange(start, min(start + block, n), dtype=np.int32)
            if len(ids) < block:  # keep one compiled shape
                ids = np.concatenate(
                    [ids, np.full(block - len(ids), ids[-1], np.int32)]
                )
            id_blocks.append((start, jnp.asarray(ids)))
        for start, ids in id_blocks:
            packed = np.asarray(self.solve_block(ids))
            take = min(block, n - start)
            dg, nt, sm, sk = _unpack_blocks(packed[:take], s, kw)
            digests[start : start + take] = dg
            nh_totals[start : start + take] = nt
            sample_metrics[start : start + take] = sm
            sample_masks[start : start + take] = sk
        return RouteSweepResult(
            graph=self.graph,
            sample_names=self.sample_names,
            sample_ids=self.sample_ids,
            samp_v=self.samp_v,
            samp_w=self.samp_w,
            digests=digests,
            nh_totals=nh_totals,
            sample_metrics=sample_metrics,
            sample_masks=sample_masks,
        )


def all_sources_route_sweep(
    ls, sample_names: Sequence[str], block: int = 1024
) -> RouteSweepResult:
    """Convenience: compile the out-ELL from a LinkState and run the
    full destination sweep with on-device route selection."""
    graph = compile_out_ell(ls)
    return RouteSweeper(graph, sample_names).sweep(block=block)


# -- mesh-sharded variant -------------------------------------------------

from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from jax import shard_map

from openr_tpu.ops.spf_sparse import SOURCES_AXIS  # noqa: E402


@functools.partial(jax.jit, static_argnames=("bands", "n", "mesh"))
def _sharded_route_blocks(
    v_t, w_t, overloaded, t_ids, samp_ids, samp_v, samp_w, pos_w, bands,
    n, mesh
):
    def shard_fn(t_blk, *rest):
        nb = len(v_t)
        v_r = rest[:nb]
        w_r = rest[nb : 2 * nb]
        ov_r, sid_r, sv_r, sw_r, pw_r = rest[2 * nb :]
        return _route_block_body(
            v_r, w_r, ov_r, t_blk, sid_r, sv_r, sw_r, pw_r, bands, n,
            vote=lambda bit: jax.lax.psum(bit, SOURCES_AXIS),
        )

    nb = len(v_t)
    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=tuple(
            [P(SOURCES_AXIS)]
            + [P(None, None)] * (2 * nb)
            + [P(None), P(None), P(None, None), P(None, None), P(None)]
        ),
        out_specs=P(SOURCES_AXIS, None),
    )(t_ids, *v_t, *w_t, overloaded, samp_ids, samp_v, samp_w, pos_w)


def sharded_route_sweep(
    graph: EllGraph, sample_names: Sequence[str], mesh: Mesh
) -> RouteSweepResult:
    """The full destination sweep in ONE sharded dispatch: each device
    owns a block of destination rows (the same axis the single-chip
    sweep iterates), bands are replicated (O(E)), and the only
    collective is the 1-bit convergence psum — identical scaling shape
    to spf_sparse.sharded_ell_all_sources, but the result crossing the
    mesh boundary is the O(N) route product, not the O(N^2) matrix.
    The mesh size must divide n_pad."""
    sweeper = RouteSweeper(graph, sample_names)
    n = graph.n_pad
    assert n % mesh.devices.size == 0, (n, mesh.devices.size)
    packed = np.asarray(
        _sharded_route_blocks(
            sweeper.v_t, sweeper.w_t, sweeper.overloaded,
            jnp.asarray(np.arange(n, dtype=np.int32)),
            sweeper._samp_ids_dev, sweeper._samp_v_dev,
            sweeper._samp_w_dev, sweeper._pos_w_dev,
            graph.bands, n, mesh,
        )
    )
    return assemble_result(sweeper, packed)
