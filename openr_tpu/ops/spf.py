"""Batched shortest-path kernels.

The TPU-native replacement for the reference's per-source Dijkstra
(reference: openr/decision/LinkState.cpp:809-882 runSpf). Instead of a
heap walk per source, shortest paths are computed *algebraically* over the
snapshot's dense int32 metric matrix:

- ``all_pairs_distances``: min-plus matrix "squaring" — doubles the covered
  path length each iteration, so it converges in ceil(log2(diameter))
  fixed-point steps inside a ``lax.while_loop``.
- ``distances_from_sources``: Bellman-Ford relaxation for a (small) batch of
  sources — S x N x N work per step, diameter steps; used by the daemon
  path where only this node + its neighbors are needed.
- ``first_hop_matrix``: ECMP first-hop set reconstruction. A neighbor ``v``
  of source ``s`` is a valid first hop toward ``j`` iff

      W[s,v] + D[v,j] == D[s,j]      (v not overloaded, transit case)
      W[s,v] == D[s,j] and v == j    (directly-connected case)

  which reproduces exactly the Dijkstra ECMP accumulation semantics of the
  reference (nextHops union over equal-cost predecessors, directly-connected
  nodes contributing themselves; reference LinkState.cpp:857-873), including
  overloaded-node transit exclusion (reference: LinkState.cpp:831-838).

Transit exclusion is encoded by masking *rows* of the one-hop matrix: an
overloaded node's outgoing edges never extend a path, while paths may still
start at (source exemption: initial D rows are direct edges) or terminate
on (columns stay intact) an overloaded node.

All kernels are jit-compiled with static padded shapes; distances saturate
at INF = 2**30 - 1 (int32-safe: INF + INF == 2**31 - 2 < 2**31 - 1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

INF = np.int32((1 << 30) - 1)


def _mask_transit_rows(d: jnp.ndarray, overloaded: jnp.ndarray) -> jnp.ndarray:
    """Replace rows of overloaded nodes with the min-plus identity row
    (0 on the diagonal, INF elsewhere): their paths never extend others."""
    n = d.shape[0]
    ident_row = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1),
        jnp.int32(0),
        INF,
    )
    return jnp.where(overloaded[:, None], ident_row, d)


# The one value ``_spf_view_batch``'s ``impl`` keyword accepts. Both exist
# for tests/chipbench/test_tpu_lowering.py, which lowers that program with
# ``impl=spf.JNP`` and is the benchmark's file; they go with ROADMAP B18.
JNP = "jnp"


def _minplus(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(a (x) b)[s, j] = min_k a[s, k] + b[k, j], saturating at INF.

    XLA fuses the broadcast-add into the min-reduction, so the
    [S, N, N] intermediate is never materialized in HBM.
    """
    return jnp.minimum(
        jnp.min(a[:, :, None] + b[None, :, :], axis=1), INF
    ).astype(jnp.int32)


@jax.jit
def _all_pairs_distances(
    w: jnp.ndarray, overloaded: jnp.ndarray
) -> jnp.ndarray:
    n = w.shape[0]
    eye = (
        jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    )
    d0 = jnp.where(eye, jnp.int32(0), w)

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed, it < n)

    def body(state):
        d, _, it = state
        d_transit = _mask_transit_rows(d, overloaded)
        nxt = jnp.minimum(d, _minplus(d, d_transit))
        return nxt, jnp.any(nxt < d), it + 1

    d, _, _ = jax.lax.while_loop(cond, body, (d0, jnp.bool_(True), 0))
    return d


def all_pairs_distances(
    w: jnp.ndarray, overloaded: jnp.ndarray
) -> jnp.ndarray:
    """All-sources shortest path distances, [N, N] int32.

    w: [N, N] one-hop metric matrix (INF = no edge). Diagonal is forced
    to 0. overloaded: [N] bool transit-exclusion mask.
    """
    return _all_pairs_distances(w, overloaded)


@jax.jit
def _distances_from_sources(
    w: jnp.ndarray,
    overloaded: jnp.ndarray,
    src_ids: jnp.ndarray,
) -> jnp.ndarray:
    n = w.shape[0]
    t = _mask_transit_rows(w, overloaded)
    d0 = w[src_ids, :]
    d0 = d0.at[jnp.arange(src_ids.shape[0]), src_ids].set(0)

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed, it < n)

    def body(state):
        d, _, it = state
        nxt = jnp.minimum(d, _minplus(d, t))
        return nxt, jnp.any(nxt < d), it + 1

    d, _, _ = jax.lax.while_loop(cond, body, (d0, jnp.bool_(True), 0))
    return d


def distances_from_sources(
    w: jnp.ndarray, overloaded: jnp.ndarray, src_ids: jnp.ndarray
) -> jnp.ndarray:
    """Shortest-path distances from a batch of sources, [S, N] int32.

    Bellman-Ford over the transit-masked one-hop matrix. Initial rows are
    the sources' direct edges (so an overloaded source still originates).
    """
    return _distances_from_sources(w, overloaded, src_ids)


@jax.jit
def first_hop_matrix(
    w: jnp.ndarray,
    overloaded: jnp.ndarray,
    src_id: jnp.ndarray,
    d_src: jnp.ndarray,
    d_all: jnp.ndarray,
) -> jnp.ndarray:
    """ECMP first-hop membership, [N, N] bool: out[v, j] == True iff
    neighbor v of the source lies on an equal-cost shortest path to j.

    d_src: [N] distances from the source. d_all: [N, N] distances from
    every node (rows for non-neighbors are ignored).
    """
    n = w.shape[0]
    w_sv = w[src_id, :]  # [N] direct metric source -> v
    is_neighbor = w_sv < INF
    reachable = d_src < INF

    # transit case: s -> v -> ... -> j, v must not be overloaded
    total = jnp.minimum(w_sv[:, None] + d_all, INF)
    transit_ok = (
        is_neighbor[:, None]
        & (~overloaded)[:, None]
        & (total == d_src[None, :])
    )
    # direct case: v == j and the direct edge achieves the shortest metric
    eye = (
        jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    )
    direct_ok = eye & (is_neighbor & (w_sv == d_src))[:, None]

    mask = (transit_ok | direct_ok) & reachable[None, :]
    # the source is never its own first hop
    mask = mask.at[src_id, :].set(False)
    return mask


def source_batch(snap, sid: int):
    """Build the hot-path source batch for ``spf_view_batch``: the source
    followed by its sorted unique neighbor ids, padded by repeating the
    source up to a power-of-two bucket (>= 8, capped at the snapshot's
    padded dimension). Padding rows are inert: the source is never its
    own neighbor, so their first-hop rows are all False.

    Returns (real_srcs, padded_device_ids); row i of the kernel output
    corresponds to real_srcs[i] for i < len(real_srcs). This is the one
    place the batch layout is defined — the solver and the tests share
    it.
    """
    nbrs = sorted({dl.dst_id for dl in snap.links_from[sid]})
    srcs = [sid] + nbrs
    bucket = 8
    while bucket < len(srcs):
        bucket *= 2
    bucket = min(bucket, snap.n_pad)
    padded = srcs + [sid] * (bucket - len(srcs))
    return srcs, jnp.asarray(np.asarray(padded, dtype=np.int32))


@functools.partial(jax.jit, static_argnames=("use_link_metric", "impl"))
def _spf_view_batch(
    metric: jnp.ndarray,
    overloaded: jnp.ndarray,
    srcs: jnp.ndarray,
    use_link_metric: bool,
    impl: str = JNP,
):
    assert impl == JNP, impl
    n = metric.shape[0]
    b = srcs.shape[0]
    w = metric if use_link_metric else jnp.where(metric < INF, jnp.int32(1), INF)
    t = _mask_transit_rows(w, overloaded)
    d0 = w[srcs, :]
    d0 = d0.at[jnp.arange(b), srcs].set(0)

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed, it < n)

    def body(state):
        d, _, it = state
        nxt = jnp.minimum(d, _minplus(d, t))
        return nxt, jnp.any(nxt < d), it + 1

    d, _, _ = jax.lax.while_loop(cond, body, (d0, jnp.bool_(True), 0))

    # ECMP first-hop membership for the batch rows. Row 0 is the source
    # itself (w[src, src] == INF => never a neighbor => all False); padding
    # rows that repeat the source behave identically.
    src_id = srcs[0]
    d_src = d[0]
    w_sv = w[src_id, srcs]  # [B] direct metric source -> batch node
    is_neighbor = w_sv < INF
    reachable = d_src < INF
    total = jnp.minimum(w_sv[:, None] + d, INF)
    transit_ok = (
        is_neighbor[:, None]
        & (~overloaded[srcs])[:, None]
        & (total == d_src[None, :])
    )
    # direct case: batch node v == destination j and the direct edge
    # achieves the shortest metric
    col_is_self = srcs[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (b, n), 1
    )
    direct_ok = col_is_self & (is_neighbor & (w_sv == d_src[srcs]))[:, None]
    fh = (transit_ok | direct_ok) & reachable[None, :]
    # pack into one output buffer: a single device->host fetch returns
    # both
    return jnp.concatenate([d, fh.astype(jnp.int32)], axis=0)


def spf_view_batch(
    metric: jnp.ndarray,
    overloaded: jnp.ndarray,
    srcs: jnp.ndarray,
    use_link_metric: bool = True,
):
    """Daemon hot-path kernel: distances + ECMP first hops for a batch of
    sources ``srcs = [src, neighbor_0, neighbor_1, ...]`` (padded by
    repeating ``src``).

    This is what one route rebuild actually consumes (reference:
    openr/decision/Decision.cpp:1124 getNextHopsWithMetric needs the
    source's distance vector plus each neighbor's, and LFA at :1192 needs
    neighbor rows only) — S x N x N work instead of the N x N x N
    all-pairs product. Returns (d [B, N], fh [B, N] bool) where fh[i, j]
    is True iff batch node i is a valid ECMP first hop from the source
    toward j.
    """
    packed = _spf_view_batch(metric, overloaded, srcs, use_link_metric)
    b = srcs.shape[0]
    return packed[:b], packed[b:].astype(jnp.bool_)


def spf_view_batch_packed(
    metric: jnp.ndarray,
    overloaded: jnp.ndarray,
    srcs: jnp.ndarray,
    use_link_metric: bool = True,
):
    """Single-buffer variant of ``spf_view_batch``: returns [2B, N] int32
    (rows [0, B) distances, rows [B, 2B) first-hop 0/1) so the host pays
    exactly one device->host transfer."""
    return _spf_view_batch(metric, overloaded, srcs, use_link_metric)


@functools.partial(jax.jit, static_argnames=("use_link_metric",))
def _reconverge_step(
    metric: jnp.ndarray,
    patch_ids: jnp.ndarray,
    patch_vals: jnp.ndarray,
    overloaded: jnp.ndarray,
    srcs: jnp.ndarray,
    use_link_metric: bool,
):
    m = metric.at[patch_ids, :].set(patch_vals)
    packed = _spf_view_batch(m, overloaded, srcs, use_link_metric)
    return m, packed


def reconverge_step(
    metric: jnp.ndarray,
    patch_ids: jnp.ndarray,
    patch_vals: jnp.ndarray,
    overloaded: jnp.ndarray,
    srcs: jnp.ndarray,
    use_link_metric: bool = True,
):
    """Fused churn step, one dispatch: scatter changed metric rows into
    the resident matrix, then run the batched SPF view from it.

    Returns (patched metric [N, N], packed [2B, N] int32: distances then
    first-hop 0/1 rows). The patched matrix becomes the new resident
    snapshot array — the host never re-uploads O(N^2) state on
    steady-state churn — and the packed result costs one transfer.
    """
    return _reconverge_step(
        metric, patch_ids, patch_vals, overloaded, srcs, use_link_metric
    )


@functools.partial(jax.jit, static_argnames=("use_link_metric",))
def _spf_from_source_with_first_hops(
    metric: jnp.ndarray,
    hop: jnp.ndarray,
    overloaded: jnp.ndarray,
    src_id: jnp.ndarray,
    use_link_metric: bool,
):
    w = metric if use_link_metric else hop
    d_all = _all_pairs_distances(w, overloaded)
    d_src = d_all[src_id, :]
    fh = first_hop_matrix(w, overloaded, src_id, d_src, d_all)
    return d_src, d_all, fh


def spf_from_source_with_first_hops(
    metric: jnp.ndarray,
    hop: jnp.ndarray,
    overloaded: jnp.ndarray,
    src_id: jnp.ndarray,
    use_link_metric: bool = True,
):
    """One fused device step for the daemon hot path: distances from the
    source and from all nodes, plus the ECMP first-hop matrix.

    Returns (d_src [N], d_all [N, N], first_hops [N, N] bool).
    """
    return _spf_from_source_with_first_hops(
        metric, hop, overloaded, src_id, use_link_metric
    )
