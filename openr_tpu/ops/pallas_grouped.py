"""Pallas TPU kernel for the BATCHED min-plus contraction of the
grouped SPF backend (ops.spf_grouped).

Per segment the relaxation computes, for every bipartite group g:

    c[g, b, r] = min_s ( gath[g, b, s] + w[g, s, r] )

— G independent small min-plus matmuls. The jnp formulation leaves the
[B, G, S, R] broadcast to XLA's fuser; this kernel tiles it explicitly
so the broadcast temporary lives in VMEM.

Tiling is GROUP-BLOCKED, sized from on-chip measurement of the actual
fat-tree segment shapes (e.g. 10k nodes: G=624, S=4, R=12 with
B=1024): the first kernel generation iterated the grid per group with
an 8-row batch tile, which at those shapes meant ~165k grid steps of a
few hundred min-adds each — pure grid-step overhead (measured 227 ms
vs 8 ms for jnp per 1024-source block). This generation processes TG
whole groups x TB=128 batch rows per grid step, with TG chosen to
bound the VMEM broadcast temporary, collapsing the same segment to a
few hundred steps. The s dimension is chunked inside the kernel (8 at
a time) so the temporary is (TG, TB, 8, TR) regardless of S; segments
with S beyond the block cap revisit the output tile across an s grid
dimension, accumulated with minimum (INF-initialized at s == 0),
exactly the proven dense-kernel discipline (ops.pallas_minplus).

Block legality (Mosaic): every block's last-two (sublane, lane) dims
are either multiples of (8, 128) or equal to the full array extent;
leading block dims are unconstrained. Padding rows/cols are inert
(weights pad with INF; min ignores them).

Like the dense kernel, selection is explicit
(spf_grouped.set_grouped_impl; ``"auto"`` measures through
ops.autotune). ``interpret`` is always passed by the
caller — True in CPU tests, False on the chip, where ``chip_smoke.py``
compiles both layouts at the 10k fabric's segment shapes and compares
them with the jnp contraction bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

INF = np.int32((1 << 30) - 1)

_S_CAP = 512  # s block cap; beyond this the grid revisits over s
_TEMP_BUDGET = 1 << 20  # int32 elements of per-step VMEM (blocks + temp)


def _pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _s_plan(s: int):
    """(s_pad, TS): full-extent block up to the cap, 128-aligned
    revisit grid past it. Shared by both tile planners."""
    if s <= _S_CAP:
        s_pad = _pad_to(s, 8)
        return s_pad, s_pad
    s_pad, ts = _pad_to(s, 128), _S_CAP
    while s_pad % ts:
        ts //= 2
    return s_pad, ts


def _group_plan(g: int, per_group: int):
    """(TG, g_pad) under the shared VMEM element budget."""
    tg = max(1, _TEMP_BUDGET // per_group)
    tg = min(tg, g)
    return tg, _pad_to(g, tg)


def _per_group(tb: int, ts: int, tr: int) -> int:
    """int32 elements of per-group VMEM at one grid step (standard
    layout): the gath (TB,TS) and weight (TS,TR) input blocks, the
    output (TB,TR), and the (TB,8,TR) broadcast temporary. Count
    TILED sizes: VMEM lays the last-two dims out in (8, 128) tiles,
    so a tiny trailing dim still occupies full lanes — raw element
    counts under-estimated a TR=4 segment 32x and blew the 16 MB
    scoped-vmem limit on-chip (measured on v5e at 1008)."""
    lanes_s = _pad_to(ts, 128)
    lanes_r = _pad_to(tr, 128)
    return (
        tb * lanes_s  # gath block (tb, ts)
        + _pad_to(ts, 8) * lanes_r  # weight block (ts, tr)
        + tb * lanes_r  # output block (tb, tr)
        + tb * 8 * lanes_r  # broadcast temp (tb, 8, tr)
    )


def _per_group_t(tb: int, ts: int, tr: int) -> int:
    """Per-group VMEM elements for the TRANSPOSED layout (lanes =
    batch): b rides the lane axis, r rides sublanes."""
    lanes_b = _pad_to(tb, 128)
    return (
        _pad_to(ts, 8) * lanes_b  # gath block (ts, tb)
        + _pad_to(ts, 8) * _pad_to(tr, 128)  # weight block (ts, tr)
        + _pad_to(tr, 8) * lanes_b  # output block (tr, tb)
        + 8 * _pad_to(tr, 8) * lanes_b  # broadcast temp (8, tr, tb)
    )


def vmem_bytes(g: int, b_pad: int, s: int, r: int,
               transposed: bool = False) -> int:
    """Planned per-grid-step VMEM residency in bytes for the [B,G,S] x
    [G,S,R] contraction at this shape — TG groups times the per-group
    blocks+temporary the planner budgeted under ``_TEMP_BUDGET``. The
    planner guarantees TG * per_group <= _TEMP_BUDGET elements (4 MB)
    unless a single group alone exceeds the budget (TG floors at 1)."""
    if transposed:
        tg, _, tb, _, _, ts, _, tr = _pick_tiles_t(g, b_pad, s, r)
        return tg * _per_group_t(tb, ts, tr) * 4
    tg, _, tb, _, _, ts, _, tr = _pick_tiles(g, b_pad, s, r)
    return tg * _per_group(tb, ts, tr) * 4


def _accumulate(o_ref, acc, s_idx):
    """INF-clamp + s-grid revisit discipline shared by both kernels:
    the output tile is INF-initialized on the first s step and
    min-accumulated on every revisit."""
    acc = jnp.minimum(acc, INF).astype(jnp.int32)

    @pl.when(s_idx == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref[...], INF)

    o_ref[...] = jnp.minimum(o_ref[...], acc)


def _pick_tiles(g: int, b_pad: int, s: int, r: int):
    """(TG, g_pad, TB, b_pad, s_pad, TS, r_pad, TR) under Mosaic
    legality and the VMEM temp budget. Incoming b_pad is a multiple of
    8; it is re-padded to a TB multiple."""
    tb = 128 if b_pad >= 128 else b_pad
    b_ok = _pad_to(b_pad, tb)
    # lane dim: full extent is legal at any size; tiled needs 128-mult
    if r <= _S_CAP:
        r_pad, tr = r, r
    else:
        r_pad, tr = _pad_to(r, 128), 128
    # s is chunked by 8 inside the kernel -> 8-mult; block cap _S_CAP
    s_pad, ts = _s_plan(s)
    # groups per step: bound TOTAL per-step VMEM (blocks + broadcast
    # temporary; tiled sizes — see _per_group)
    tg, g_pad = _group_plan(g, _per_group(tb, ts, tr))
    return tg, g_pad, tb, b_ok, s_pad, ts, r_pad, tr


def _kernel(g_ref, w_ref, o_ref):
    s_idx = pl.program_id(3)
    a = g_ref[...]  # (TG, TB, TS)
    w = w_ref[...]  # (TG, TS, TR)
    nchunk = a.shape[2] // 8

    # static unroll: a fori_loop carrying dynamic_slice over register
    # values does not lower on Mosaic (measured on v5e: KernelType.TC
    # "Unimplemented primitive: dynamic_slice"); TS is static and
    # 8-aligned, so static slices compile — nchunk is at most
    # _S_CAP // 8 = 64 and 1-2 at the real fat-tree segment shapes
    acc = jnp.full(o_ref.shape, INF, jnp.int32)
    for i in range(nchunk):
        ac = jax.lax.slice_in_dim(a, i * 8, (i + 1) * 8, axis=2)
        wc = jax.lax.slice_in_dim(w, i * 8, (i + 1) * 8, axis=1)
        cand = jnp.min(ac[:, :, :, None] + wc[:, None, :, :], axis=2)
        acc = jnp.minimum(acc, cand)
    _accumulate(o_ref, acc, s_idx)


def _pick_tiles_t(g: int, b_pad: int, s: int, r: int):
    """Tile plan for the TRANSPOSED layout (lanes = batch): returns
    (TG, g_pad, TB, b_pad, s_pad, TS, r_pad, TR). b rides the lane
    axis (128-tiled), r rides sublanes (8-tiled) — so a small R costs
    8 sublanes instead of 128 lanes, shrinking the broadcast temp 8x
    at the real fat-tree segment shapes (R = 4..16)."""
    tb = 128 if b_pad >= 128 else b_pad
    b_ok = _pad_to(b_pad, tb)
    # r rides SUBLANES here: 8-aligned, same cap/revisit shape as s
    r_pad, tr = _s_plan(r)
    s_pad, ts = _s_plan(s)
    tg, g_pad = _group_plan(g, _per_group_t(tb, ts, tr))
    return tg, g_pad, tb, b_ok, s_pad, ts, r_pad, tr


def _kernel_t(g_ref, w_ref, o_ref):
    s_idx = pl.program_id(3)
    a = g_ref[...]  # (TG, TS, TB)
    w = w_ref[...]  # (TG, TS, TR)
    nchunk = a.shape[1] // 8

    acc = jnp.full(o_ref.shape, INF, jnp.int32)  # (TG, TR, TB)
    for i in range(nchunk):  # static unroll (see _kernel)
        ac = jax.lax.slice_in_dim(a, i * 8, (i + 1) * 8, axis=1)
        wc = jax.lax.slice_in_dim(w, i * 8, (i + 1) * 8, axis=1)
        cand = jnp.min(
            ac[:, :, None, :] + wc[:, :, :, None], axis=1
        )  # (TG, TR, TB)
        acc = jnp.minimum(acc, cand)
    _accumulate(o_ref, acc, s_idx)


@functools.partial(jax.jit, static_argnames=("interpret",))
def batched_minplus_t(
    gath_t: jnp.ndarray, w: jnp.ndarray, *, interpret: bool
) -> jnp.ndarray:
    """[G, S, B] (x) [G, S, R] -> [G, R, B] over (min, +): the
    lane-efficient layout for small R. Padding discipline matches
    batched_minplus (weights pad INF; padded gath rows compute garbage
    the caller's slice discards)."""
    g, s, b = gath_t.shape
    g2, s2, r = w.shape
    assert g == g2 and s == s2, (gath_t.shape, w.shape)
    b_pad = _pad_to(b, 8)
    tg, g_pad, tb, b_pad, s_pad, ts, r_pad, tr = _pick_tiles_t(
        g, b_pad, s, r
    )
    gath_t = jnp.pad(
        gath_t, ((0, g_pad - g), (0, s_pad - s), (0, b_pad - b))
    )
    w = jnp.pad(
        w,
        ((0, g_pad - g), (0, s_pad - s), (0, r_pad - r)),
        constant_values=INF,
    )
    grid = (g_pad // tg, b_pad // tb, r_pad // tr, s_pad // ts)
    out = pl.pallas_call(
        _kernel_t,
        out_shape=jax.ShapeDtypeStruct((g_pad, r_pad, b_pad), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (tg, ts, tb), lambda gg, i, rr, ss: (gg, ss, i)
            ),
            pl.BlockSpec(
                (tg, ts, tr), lambda gg, i, rr, ss: (gg, ss, rr)
            ),
        ],
        out_specs=pl.BlockSpec(
            (tg, tr, tb), lambda gg, i, rr, ss: (gg, rr, i)
        ),
        interpret=interpret,
    )(gath_t, w)
    return out[:g, :r, :b]


@functools.partial(jax.jit, static_argnames=("interpret",))
def batched_minplus(
    gath: jnp.ndarray, w: jnp.ndarray, *, interpret: bool
) -> jnp.ndarray:
    """[G, B, S] (x) [G, S, R] -> [G, B, R] over (min, +), saturating
    at INF. Inputs are padded here; INF weight padding keeps padded
    s/r/g slots inert (gath pads with 0 — its padded g/b rows compute
    garbage that the final slice discards)."""
    g, b, s = gath.shape
    g2, s2, r = w.shape
    assert g == g2 and s == s2, (gath.shape, w.shape)
    b_pad = _pad_to(b, 8)
    tg, g_pad, tb, b_pad, s_pad, ts, r_pad, tr = _pick_tiles(
        g, b_pad, s, r
    )
    gath = jnp.pad(
        gath, ((0, g_pad - g), (0, b_pad - b), (0, s_pad - s))
    )
    w = jnp.pad(
        w,
        ((0, g_pad - g), (0, s_pad - s), (0, r_pad - r)),
        constant_values=INF,
    )
    grid = (g_pad // tg, b_pad // tb, r_pad // tr, s_pad // ts)
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((g_pad, b_pad, r_pad), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (tg, tb, ts), lambda gg, i, rr, ss: (gg, i, ss)
            ),
            pl.BlockSpec(
                (tg, ts, tr), lambda gg, i, rr, ss: (gg, ss, rr)
            ),
        ],
        out_specs=pl.BlockSpec(
            (tg, tb, tr), lambda gg, i, rr, ss: (gg, i, rr)
        ),
        interpret=interpret,
    )(gath, w)
    return out[:g, :b, :r]
