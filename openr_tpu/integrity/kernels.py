"""Fused audit kernels for the integrity plane — one dispatch, scalar
readback, per tier and backend.

Tier 1 (fixed-point residual): the resident distance product is the
unique fixed point of its backend's min-plus relaxation, so ONE extra
relax pass must be the identity. The kernels reuse the exact relax
bodies the solvers run (``route_sweep._rev_relax``,
``spf_grouped._grouped_relax``, ``spf_sparse._uniform_relax``) — any
divergence between audit and solve semantics would alarm on healthy
state. Cost O(nnz); readback is one int32 violation count.

Blind spot (documented, covered by tier 2): min-relax only LOWERS, so a
corrupted cell that was RAISED is caught (an uncorrupted neighbor
re-derives the shorter true value), but a cell LOWERED to a value that
enables no shorter neighbor path — or a raised diagonal still below the
shortest cycle — survives one relax pass. The ``device.corrupt_resident``
seam therefore always flips a bit in the packed product too, which
tier 2 catches unconditionally.

Tier 2 (mirror digest): per-row FNV-1a-32 over the raw uint32 words of
the packed product, folded with a WRAPAROUND uint32 SUM over rows. The
row fold is order-independent on purpose: shard order and slot order
then cannot perturb the digest, so device (sharded or not) and host
mirror agree bit-for-bit or the state diverged. Readback is one uint32.

Tier 3 (sampled row oracle): the seeded row subset re-solved COLD from
unit init through the backend's own fixed-point driver and bit-compared
against the resident rows — end-to-end ground truth at O(sample) cost.

This package is intentionally OUTSIDE the sharding-spec lint scope
(``openr_tpu/ops/``, ``openr_tpu/decision/``): audit dispatches are
read-only probes off the churn path; bare ``jit`` under GSPMD keeps
them placement-agnostic across the single-chip and mesh engines.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from openr_tpu.ops import route_sweep as rs
from openr_tpu.ops import spf_grouped as sg
from openr_tpu.ops import spf_sparse
from openr_tpu.ops.spf import INF

__all__ = [
    "fnv_device",
    "fnv_host",
    "fnv_slots",
    "ell_residual",
    "ell_sample_oracle",
    "grouped_residual",
    "grouped_sample_oracle",
    "world_residual",
    "world_cold_slot",
]

_FNV_OFFSET = 0x811C9DC5
_FNV_PRIME = 0x01000193


def _fnv_rows(words):
    """Per-row FNV-1a-32 over uint32 words: [R, W] -> [R]."""
    h0 = jnp.full((words.shape[0],), _FNV_OFFSET, dtype=jnp.uint32)

    def step(h, col):
        return (h ^ col) * jnp.uint32(_FNV_PRIME), None

    h, _ = jax.lax.scan(step, h0, jnp.transpose(words))
    return h


@jax.jit
def fnv_device(arr):
    """Order-independent digest of a resident int32 [R, W] array: sum
    (mod 2^32) of per-row FNV-1a digests. One uint32 readback."""
    words = jax.lax.bitcast_convert_type(arr, jnp.uint32)
    return jnp.sum(_fnv_rows(words), dtype=jnp.uint32)


@jax.jit
def fnv_slots(arr3):
    """Per-slot digests of a [slots, R, W] world block: vmapped row
    fold, [slots] uint32 out. The host folds occupied slots only."""
    words = jax.lax.bitcast_convert_type(arr3, jnp.uint32)
    return jax.vmap(
        lambda w2: jnp.sum(_fnv_rows(w2), dtype=jnp.uint32)
    )(words)


def fnv_host(arr: np.ndarray) -> int:
    """NumPy replica of ``fnv_device`` over a host mirror (bit-exact:
    same per-row FNV-1a, same wraparound row sum)."""
    words = np.ascontiguousarray(
        np.asarray(arr, dtype=np.int32)
    ).view(np.uint32)
    h = np.full(words.shape[0], _FNV_OFFSET, dtype=np.uint32)
    prime = np.uint32(_FNV_PRIME)
    for j in range(words.shape[1]):
        h = (h ^ words[:, j]) * prime
    return int(np.sum(h, dtype=np.uint32))


# -- tier 1: residual ---------------------------------------------------


@functools.partial(jax.jit, static_argnames=("bands",))
def ell_residual(dr, v_t, w_t, overloaded, bands):
    """ELL backends: violation count of one extra reversed relax over
    ALL resident destination rows (padding rows included — they were
    solved to fixed points too)."""
    t_ids = jnp.arange(dr.shape[0], dtype=jnp.int32)
    nxt = rs._rev_relax(dr, bands, v_t, w_t, overloaded, t_ids)
    return jnp.sum((nxt != dr).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("meta",))
def grouped_residual(dr, v_t, w_t, overloaded, meta):
    """Grouped backend: same identity check through the per-segment
    dense contraction the grouped solver runs."""
    t_ids = jnp.arange(dr.shape[0], dtype=jnp.int32)
    nxt = sg._grouped_relax(dr, meta, v_t, w_t, overloaded, t_ids)
    return jnp.sum((nxt != dr).astype(jnp.int32))


@jax.jit
def world_residual(src3, w3, ov2, d3):
    """World block: vmapped uniform-ELL relax identity over EVERY slot
    of a bucket. Vacated slots hold their last (stale but coherent)
    fixed point and never-occupied slots are all-zero — both are relax
    fixed points, so auditing the full block needs no occupancy mask."""

    def one(src, w, ov, d):
        nxt = spf_sparse._uniform_relax(d, src, w, ov)
        return jnp.sum((nxt != d).astype(jnp.int32))

    return jnp.sum(jax.vmap(one)(src3, w3, ov2, d3))


# -- tier 3: sampled cold oracle ---------------------------------------


@functools.partial(jax.jit, static_argnames=("bands", "n"))
def ell_sample_oracle(dr, ids, v_t, w_t, overloaded, bands, n):
    """Rows ``ids`` re-solved cold through the ELL fixed-point driver;
    returns how many differ from the resident rows anywhere."""
    cold = rs._rev_fixed_point(bands, v_t, w_t, overloaded, ids, n)
    return jnp.sum(jnp.any(cold != dr[ids], axis=1).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("meta", "n"))
def grouped_sample_oracle(dr, ids, v_t, w_t, overloaded, meta, n):
    cold = sg._grouped_fixed_point(
        meta, v_t, w_t, overloaded, ids, n, reverse=True
    )
    return jnp.sum(jnp.any(cold != dr[ids], axis=1).astype(jnp.int32))


@jax.jit
def world_cold_slot(src, w, overloaded, srcs):
    """Cold re-solve of ONE world slot's distance plane, replicating
    ``spf_sparse._tenant_view_solve``'s cold path exactly (unit init,
    unmasked first relax so overloaded sources originate, masked relax
    to the fixed point) — bit-identical by the unique-fixed-point
    argument."""
    s = srcs.shape[0]
    n = src.shape[0]
    unit = jnp.full((s, n), INF, dtype=jnp.int32)
    unit = unit.at[jnp.arange(s), srcs].set(0)
    d0 = spf_sparse._uniform_relax(unit, src, w, None)

    def cond(state):
        _, changed, it = state
        return changed & (it < n)

    def body(state):
        d, _, it = state
        nxt = spf_sparse._uniform_relax(d, src, w, overloaded)
        return nxt, jnp.any(nxt < d), it + 1

    d, _, _ = jax.lax.while_loop(
        cond, body, (d0, jnp.bool_(True), jnp.int32(0))
    )
    return d
