"""Device-mesh sharding of the all-sources SPF.

The scaling axis of this framework is the *source* dimension of the
batched shortest-path computation: every device owns a contiguous block of
source rows of the distance matrix while the (transit-masked) one-hop
metric matrix is replicated. Relaxation steps are purely local; the only
cross-device communication is a 1-bit "any row changed" OR (``psum``) per
iteration to agree on the fixed point — so the kernel scales linearly
across ICI with no distance-matrix traffic at all.

This is the TPU-native analogue of the reference's scale story (per-source
Dijkstra memoization + multi-area partitioning, reference:
openr/decision/LinkState.cpp:794); instead of memoizing per source we
recompute all sources in parallel from the HBM-resident snapshot.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from openr_tpu.ops.spf import INF, _mask_transit_rows, _minplus

SOURCES_AXIS = "sources"


def make_mesh(devices=None, axis_name: str = SOURCES_AXIS) -> Mesh:
    """1-D mesh over all (or the given) devices, sharding the source axis."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (axis_name,))


def pad_for_mesh(n: int, mesh: Mesh, align: int = 128) -> int:
    """Rows must divide evenly across mesh devices and stay lane-aligned
    (128 on TPU; tests on virtual CPU meshes may pass a smaller align)."""
    devs = mesh.devices.size
    block = align * devs
    return max(block, ((n + block - 1) // block) * block)


class ShardingPlan:
    """Build-time placement contract for the mesh-sharded engines.

    Every resident buffer the sharded dispatches touch gets an explicit
    ``NamedSharding`` at creation so the steady-state churn path never
    pays an XLA-inserted reshard or replication copy: row-striped
    residents (`[n_pad, ...]` products, digests) live on the source
    axis, the band/segment topology tensors and small edge uploads are
    replicated to every device, and destination-batched KSP2 masks are
    striped over the same axis by batch row.

    ``ensure`` is the churn-path tripwire: it verifies an operand is
    already committed to its planned placement, and when it is not it
    bumps ``ops.reshard_events`` and corrects the placement with an
    explicit ``device_put`` — so the acceptance gate
    (``ops.reshard_events == 0`` across a churn run) measures real
    placement discipline rather than hoping ``jax.transfer_guard``
    notices (device-to-device resharding is invisible to the guard).
    """

    __slots__ = ("mesh", "axis", "rows", "vec", "batch3", "replicated")

    def __init__(self, mesh: Mesh, axis: str = SOURCES_AXIS) -> None:
        self.mesh = mesh
        self.axis = axis
        # [n_pad, W]-shaped residents, striped by source row
        self.rows = NamedSharding(mesh, P(axis, None))
        # [n_pad] per-row vectors (digests)
        self.vec = NamedSharding(mesh, P(axis))
        # [B, slots, k] destination-batched mask stacks, striped by batch
        self.batch3 = NamedSharding(mesh, P(axis, None, None))
        # topology bands / edge uploads / overload vector: every device
        # reads all of it, so commit a replica per device up front
        self.replicated = NamedSharding(mesh, P())

    def place(self, x, sharding: NamedSharding) -> jnp.ndarray:
        """Explicit build-time placement (host->device; transfer-guard
        exempt because device_put is an explicit transfer)."""
        return jax.device_put(jnp.asarray(x), sharding)

    def shard_rows(self, x) -> jnp.ndarray:
        return self.place(x, self.rows if np.ndim(x) > 1 else self.vec)

    def replicate(self, x) -> jnp.ndarray:
        return self.place(x, self.replicated)

    def ensure(self, x: jnp.ndarray, sharding: NamedSharding,
               name: str = "") -> jnp.ndarray:
        """Churn-path placement check: already-committed-as-planned is a
        no-op; anything else is a reshard event (counted, then fixed)."""
        cur = getattr(x, "sharding", None)
        if cur is not None and cur.is_equivalent_to(sharding, x.ndim):
            return x
        from openr_tpu.telemetry import get_registry

        get_registry().counter_bump("ops.reshard_events")
        return jax.device_put(x, sharding)


@functools.lru_cache(maxsize=None)
def replicated_jit(fn, mesh: Mesh):
    """A jitted dispatch of ``fn`` whose every input and output is
    committed replicated across ``mesh``.

    Used for the small patch dispatches (`_patch_bands` /
    `_patch_segments`): their outputs feed the shard_map churn
    dispatches as replicated operands, so committing them replicated at
    the producer keeps XLA from inserting a broadcast copy at the
    consumer (SNIPPETS.md [2]: out specs of one dispatch must match the
    in specs of the next). A single NamedSharding broadcasts as a
    pytree prefix over every argument/result.
    """
    rep = NamedSharding(mesh, P())
    return jax.jit(fn, in_shardings=rep, out_shardings=rep)


@functools.partial(jax.jit, static_argnames=("mesh",))
def sharded_all_sources(
    w: jnp.ndarray, overloaded: jnp.ndarray, mesh: Mesh
) -> jnp.ndarray:
    """All-sources shortest-path distances [N, N], rows sharded over the
    mesh. ``w`` must be padded so N % mesh.devices.size == 0.

    Bellman-Ford over the replicated transit matrix; convergence agreed
    via a psum'd change flag so every shard exits the while_loop together.
    """
    n = w.shape[0]
    t = _mask_transit_rows(w, overloaded)

    def shard_fn(w_blk: jnp.ndarray, t_full: jnp.ndarray) -> jnp.ndarray:
        rows = w_blk.shape[0]
        shard_idx = jax.lax.axis_index(SOURCES_AXIS)
        row_ids = shard_idx * rows + jnp.arange(rows, dtype=jnp.int32)
        # initial distances: this shard's source rows, diagonal zeroed
        d0 = w_blk
        col_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1)
        d0 = jnp.where(col_ids == row_ids[:, None], jnp.int32(0), d0)

        def cond(state):
            _, changed, it = state
            return jnp.logical_and(changed > 0, it < n)

        def body(state):
            d, _, it = state
            nxt = jnp.minimum(d, _minplus(d, t_full))
            local_changed = jnp.any(nxt < d).astype(jnp.int32)
            global_changed = jax.lax.psum(local_changed, SOURCES_AXIS)
            return nxt, global_changed, it + 1

        d, _, _ = jax.lax.while_loop(cond, body, (d0, jnp.int32(1), 0))
        return d

    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(SOURCES_AXIS, None), P(None, None)),
        out_specs=P(SOURCES_AXIS, None),
    )(w, t)


@functools.partial(jax.jit, static_argnames=("mesh",))
def sharded_reconvergence_step(
    w: jnp.ndarray,
    overloaded: jnp.ndarray,
    dest_mask: jnp.ndarray,
    mesh: Mesh,
):
    """One full sharded "reconvergence" step: all-sources SPF plus a
    per-source nearest-advertiser reduction (the batched analogue of
    best-route selection's min-metric destination filter,
    reference: openr/decision/Decision.cpp:1099 getMinCostNodes).

    dest_mask: [P, N] bool — advertisers per prefix group.
    Returns (distances [N, N] row-sharded, best_metric [N, P]).
    """
    d = sharded_all_sources(w, overloaded, mesh)

    def reduce_fn(d_blk: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
        # min over advertisers of each prefix: [rows, N] x [P, N] -> [rows, P]
        masked = jnp.where(mask[None, :, :], d_blk[:, None, :], INF)
        return jnp.min(masked, axis=2)

    best = shard_map(
        reduce_fn,
        mesh=mesh,
        in_specs=(P(SOURCES_AXIS, None), P(None, None)),
        out_specs=P(SOURCES_AXIS, None),
    )(d, dest_mask)
    return d, best
