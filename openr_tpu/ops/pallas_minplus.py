"""Pallas TPU kernel for the min-plus product (tropical matmul).

The relaxation step of the batched SPF is ``out[s, j] = min_k a[s, k] +
b[k, j]`` — a matmul over the (min, +) semiring. XLA's fused
broadcast+reduce handles it well for moderate N, but tiling it explicitly
keeps the k-panel resident in VMEM and bounds the broadcast temporary to
(TS, TK, TN) regardless of N, which matters once N is in the thousands.

Layout: TPU Mosaic requires every VMEM block's (sublane, lane) dims to be
multiples of (8, 128) (or equal to the full array dims). Blocks of ``a``
are therefore (TILE_S, TILE_K) = (8, 128) — tall-K, short-S — so both
operands are consumed untransposed with legal tiles, and the broadcast
temporary is (TS, TK, TN) = (8, 128, 128) int32 ≈ 0.5 MB of VMEM.

Tiling: grid (S/TS, N/TN, K/TK) with k innermost; the output tile is
revisited across k and accumulated with minimum (initialized to INF at
k == 0 via pl.when).

Enable through ``openr_tpu.ops.spf.set_minplus_impl("pallas")``.
``interpret`` is always passed by the caller — True in CPU correctness
tests, False on the chip, where ``chip_smoke.py`` compiles the kernel at
the fabric-1008 shape and compares it with the jnp formulation bit for
bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

INF = np.int32((1 << 30) - 1)

TILE_S = 8
TILE_N = 128
TILE_K = 128


def vmem_bytes() -> int:
    """Per-grid-step VMEM residency in bytes: the a/b input blocks,
    the revisited output tile, and the (TS, TK, TN) broadcast
    temporary — all int32. The tile sizes are static, so the budget
    is a constant (~0.6 MB), independent of N."""
    elems = (
        TILE_S * TILE_K  # a block
        + TILE_K * TILE_N  # b block
        + TILE_S * TILE_N  # output tile
        + TILE_S * TILE_K * TILE_N  # broadcast temporary
    )
    return elems * 4


def _minplus_kernel(a_ref, b_ref, o_ref):
    k = pl.program_id(2)
    a = a_ref[...]  # (TILE_S, TILE_K)
    b = b_ref[...]  # (TILE_K, TILE_N)
    cand = jnp.min(a[:, :, None] + b[None, :, :], axis=1)
    cand = jnp.minimum(cand, INF).astype(jnp.int32)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, INF)

    o_ref[...] = jnp.minimum(o_ref[...], cand)


@functools.partial(jax.jit, static_argnames=("interpret",))
def minplus(a: jnp.ndarray, b: jnp.ndarray, *, interpret: bool):
    """(a (x) b) over (min, +): [S, K] x [K, N] -> [S, N] int32.

    Shapes must be multiples of the tile sizes (the snapshot layer pads
    to 128, which satisfies this).
    """
    s, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert s % TILE_S == 0 and n % TILE_N == 0 and k % TILE_K == 0, (
        a.shape,
        b.shape,
    )
    grid = (s // TILE_S, n // TILE_N, k // TILE_K)
    return pl.pallas_call(
        _minplus_kernel,
        out_shape=jax.ShapeDtypeStruct((s, n), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_S, TILE_K), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((TILE_K, TILE_N), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((TILE_S, TILE_N), lambda i, j, kk: (i, j)),
        interpret=interpret,
    )(a, b)
