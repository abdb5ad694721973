"""Incremental destination-major route sweep: churn re-solves ONLY the
affected destinations, on device, in one dispatch.

The full route sweep (ops.route_sweep) computes the network-wide route
product — per-destination digests, next-hop structure for every
source — in N_pad/B blocks. Under churn that is wasteful: a metric
change touches few destinations' shortest-path structure.

The destination-major orientation makes incrementality EXACT and
simple: row t of DR is an independent single-destination problem
(reverse SPF to t) — rows never interact — so re-solving an arbitrary
subset of rows from scratch is correct regardless of what changed.
That sidesteps the monotonicity trap of in-place re-relaxation (weight
increases cannot be fixed by further min-relaxation).

Per churn event — metric changes, overload flips, AND link add/remove
between known nodes (the detection diffs the directed edge set, so a
removed edge that was tight or an added edge that improves/ties marks
the row; a row outgrowing its slot class widens its band in place,
ell_patch(widen=True), preserving node ids and the resident DR). Only
node add/remove — a renumbering event — cold-rebuilds:

1. host: diff the changed directed edges {(u, v): w_old -> w_new} and
   overload flips (an O(degree) LinkState journal read),
2. ONE fused device dispatch over the RESIDENT state:
   a. affected-row detection against the resident DR — row t is
      affected iff some changed edge was TIGHT in the old graph
      (DR[t, u] == w_old + DR[t, v], it may have carried a shortest
      path) or IMPROVES in the new one (w_new + DR[t, v] < DR[t, u]).
      Overload flips inject their incident edges with effective
      weights on both sides. The test is sound-conservative: it can
      only over-select (distances enter unchanged rows' relaxations
      never),
   b. scatter the patched band rows (O(degree) transfer),
   c. re-init + fixed-point the affected rows (a [K, N] solve,
      bucketed to a handful of compiled shapes),
   d. route extraction (nh counts, canonical digests, sample rows)
      for exactly those rows, scatter the fresh rows/digests into the
      resident state,
3. readback: DELTA-COMPACTED on device — the fresh product rows are
   diffed bit-for-bit against the resident previous packed product and
   prefix-sum-compacted, so only the rows that actually CHANGED cross
   the device->host boundary (plus a 2-int meta row carrying the
   affected and changed counts) — O(changed), not O(K) and never
   O(N^2); the caller sees which destinations moved and their fresh
   routes,
4. consume: the compacted readback stays an IN-FLIGHT device array.
   The device state commits immediately and the host applies event k's
   delta into the resident RouteSweepResult while event k+1's
   patch+solve dispatches (the double-buffer overlap window —
   ``churn(..., defer_consume=True)`` hands the caller the
   PendingDelta handle explicitly; the default consumes synchronously
   before returning, preserving the classic contract).

Memory: DR stays device-resident at [n_pad, n_pad] int32 — whole on a
single chip (~400 MB at 10k, 12k bound, the same envelope as the
incremental KSP2 engine), or ROW-SHARDED over a device mesh
(``mesh=`` at construction): each device owns n_pad/ndev destination
rows, detection + re-solve run per shard (rows never interact; the
only collective is the 1-bit convergence vote), and the bound scales
with sqrt(ndev) — ~100k on a 64-way mesh. The sharded event costs two
dispatches (band patch + detect/solve) instead of one.

Reference semantics: the product matches SpfSolver::buildRouteDb /
getNextHopsWithMetric (Decision.cpp:569-734, :1124) for every source
toward every destination; the incremental contract mirrors
Decision's debounced incremental rebuilds (Decision.cpp route rebuild
on delta) at the network-wide scale.
"""

from __future__ import annotations

import functools
import os
import random
import time
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from openr_tpu.ops.spf import INF
from openr_tpu.ops import host_sweep
from openr_tpu.ops import route_sweep as rs
from openr_tpu.ops.spf_sparse import (
    _out_edges,
    _tenant_view_solve,
    compile_ell,
    ell_patch,
    pad_patch_rows,
)
from openr_tpu.analysis.annotations import (
    committed_dispatch,
    fault_boundary,
    mirrored_by,
    requires_drain,
    resident_buffers,
    solve_window,
)
from openr_tpu.ops import dispatch_accounting as da
from openr_tpu.ops.aot_cache import aot_call, get_aot_cache
from openr_tpu.faults.injector import (
    consume_fault,
    fault_point,
    is_device_loss,
    register_fault_site,
)
from openr_tpu.faults.supervisor import DegradationSupervisor
from openr_tpu.integrity import ResidentEngineContract, get_auditor
from openr_tpu.integrity import kernels as integrity_kernels
from openr_tpu.telemetry import get_flight_recorder, get_registry, get_tracer

# degradation-ladder injection sites (armable by name; see
# openr_tpu.faults.injector)
FAULT_DISPATCH = register_fault_site("route_engine.dispatch")
FAULT_CONSUME = register_fault_site("route_engine.consume")
FAULT_COLD_BUILD = register_fault_site("route_engine.cold_build")
FAULT_FRONTIER = register_fault_site("route_engine.frontier_resolve")
# the accelerator itself dying under the residents (vs. a failed
# dispatch on a healthy device): fires at the same dispatch/consume
# crossings, recognized by faults.is_device_loss, recovered by the
# ladder's dedicated rung (_device_recover)
FAULT_DEVICE_LOST = register_fault_site("device.lost")
# silent corruption: a CONSUMED (non-raising) seam at the churn /
# solve_views entries that flips seeded bits in the live residents —
# the integrity plane's audit tiers must then detect within one
# cadence and heal bit-identically (tools/integrity_smoke.py)
FAULT_CORRUPT = register_fault_site("device.corrupt_resident")

ENGINE_MAX_NODES = 12288  # same residency envelope as ksp2_engine
# affected-row solve buckets: the dispatch runs at the hint bucket and
# RETRIES at a larger one on overflow (the jit is functional — nothing
# commits until the count fits, so a retry re-detects against the
# untouched resident state); beyond the largest bucket the event takes
# the FULL-WIDTH refresh — the patched resident layout is kept and
# every row re-solves in one cold-build-shaped dispatch, skipping the
# host layout recompile that makes a true cold build expensive (a
# fat-tree link up/down event affects every destination row through
# ECMP next-hop churn, so past 1024 nodes this is the common link-event
# path — first measured on-chip at 10k, where bucket overflow used to
# cold-rebuild 10/10 link events)
_ROW_BUCKETS = (32, 128, 512, 1024)
# frontier cone-expansion jump cap (static per compiled shape): each
# jump costs one relax-shaped pass, so past this the cone is deeper
# than re-deriving it is worth — the bucketed seed degrades to the
# whole-row reset and the overflow path to the full-width refresh,
# both still bit-identical (the cap only ever coarsens the reset)
_FRONTIER_MAX_JUMPS = 16
# fraction of rows past which a converged frontier still falls back to
# the full-width refresh (constructor-overridable): with most rows in
# the cone the warm seed saves nothing over the cold-shaped dispatch
# and the probe already paid its cost
_DEFAULT_FRONTIER_THRESHOLD = 0.5


def _pack_product(dr, nh_count, d_s, packed_mask, pos_w):
    """The ONE packing site for the engine's per-row route product:
    [digest, nh_total, sample metrics, sample masks] — shared by every
    cold build and churn dispatch of BOTH backends, which is what
    keeps the cross-backend digest contract a single definition.
    Returns (digests, packed [B, W])."""
    digests = rs._digest_rows(dr, nh_count, pos_w)
    nh_total = jnp.sum(nh_count, axis=1, dtype=jnp.int32)
    b = dr.shape[0]
    packed = jnp.concatenate(
        [
            jax.lax.bitcast_convert_type(digests, jnp.int32)[:, None],
            nh_total[:, None],
            d_s,
            jax.lax.bitcast_convert_type(
                packed_mask, jnp.int32
            ).reshape(b, -1),
        ],
        axis=1,
    )
    return digests, packed


@functools.partial(jax.jit, static_argnames=("bands", "n"))
def _full_resident_sweep(v_t, w_t, overloaded, samp_ids, samp_v,
                         samp_w, pos_w, bands, n):
    """Cold build: solve ALL destination rows, extract the route
    product, return (DR, digests, packed) with DR + digests staying
    resident. One dispatch at engine scale (n <= 12k)."""
    t_ids = jnp.arange(n, dtype=jnp.int32)
    dr = rs._rev_fixed_point(bands, v_t, w_t, overloaded, t_ids, n)
    nh_count = rs._nh_counts(dr, bands, v_t, w_t, overloaded, t_ids)
    d_s, packed_mask = rs._sample_stats(
        dr, samp_ids, samp_v, samp_w, overloaded, t_ids
    )
    digests, packed = _pack_product(
        dr, nh_count, d_s, packed_mask, pos_w
    )
    return dr, digests, packed


def _detect_rows(dr, e_u, e_v, e_w_old, e_w_new, k, row_start):
    """Affected-row detection against a (shard of the) RESIDENT
    pre-patch DR. Raw weights (not overload-effective) make the test
    conservative: coincidental tightness over-selects, never
    under-selects; overload flips arrive as INF transitions from the
    host.

    Old side: the edge was TIGHT (it may have carried a shortest path
    or an ECMP tie that the change breaks). New side is NON-strict: an
    edge landing exactly ON the current best creates new equal-cost
    next hops — distances unchanged, ECMP masks (and digests) changed
    (the undrain case).

    Returns (count, local row ids [k], global destination ids [k]);
    padding entries repeat the FIRST affected id so every duplicate
    scatter index writes an identical fresh row — deterministic and
    correct."""
    dr_u = dr[:, e_u]  # [rows, E]
    dr_v = dr[:, e_v]
    tight_old = dr_u == jnp.minimum(e_w_old[None, :] + dr_v, INF)
    ties_or_improves_new = (
        jnp.minimum(e_w_new[None, :] + dr_v, INF) <= dr_u
    )
    usable = (e_w_old[None, :] < INF) | (e_w_new[None, :] < INF)
    affected = jnp.any(
        (tight_old | ties_or_improves_new) & usable, axis=1
    )  # [rows]
    count = jnp.sum(affected.astype(jnp.int32))
    local = jnp.nonzero(affected, size=k, fill_value=0)[0].astype(
        jnp.int32
    )
    valid = jnp.arange(k) < count
    local = jnp.where(valid, local, local[0])
    return count, local, local + row_start


def _increase_rows(dr, e_u, e_v, e_w_old, e_w_new):
    """Rows whose resident DR may UNDERESTIMATE the post-patch
    distances: some edge whose weight went UP was tight under the old
    row. Every other affected row keeps its old row as a sound warm
    seed for the re-solve — same argument as spf_sparse._warm_seed,
    destination-major (old rows are valid upper bounds under pure
    decreases and equal-cost ties)."""
    tight_old = dr[:, e_u] == jnp.minimum(
        e_w_old[None, :] + dr[:, e_v], INF
    )
    return jnp.any(tight_old & (e_w_new > e_w_old)[None, :], axis=1)


def _resolve_and_pack(
    solve_rows, nh_counts, overloaded, ids, local_ids, count, dr,
    digests, packed_res, samp_ids, samp_v, samp_w, pos_w, n, k,
):
    """Re-init + fixed-point the affected rows (independent problems),
    extract their route product, scatter fresh rows/digests/product
    into the resident (shard of) DR. When count == 0 every id repeats
    one row and the write is that row's own fresh re-solve: a no-op by
    value. Returns (dr, digests, packed_res, out [k+1, 1+W]):

      out row 0: [affected_count, changed_count, 0, ...] — the TRUE
        affected count drives the overflow retry ladder; changed_count
        bounds the readback,
      out rows 1..changed_count: [dest id, product] for exactly the
        affected rows whose packed product CHANGED bit-for-bit against
        the resident previous product, prefix-sum-compacted in row
        order. Rows past changed_count are zero.

    The changed test compares FULL packed rows, not digests: a digest
    can survive a sample-mask flip (equal-cost slot swap keeps the
    distance and the fanout count while moving mask membership), so
    compacting on digests alone would drop real route changes.
    Detection padding repeats the first affected id; those duplicates
    fall outside the ``arange(k) < count`` live window and never reach
    the compaction, so compacted ids are unique.

    ``solve_rows(ids) -> [k, n]`` and ``nh_counts(rows, ids)`` are the
    relaxation-backend callables (ELL bands or grouped segments); the
    detection, scatter, digest and packing algebra is shared so the two
    backends stay bit-comparable."""
    rows = solve_rows(ids)
    nh_count = nh_counts(rows, ids)
    d_s, packed_mask = rs._sample_stats(
        rows, samp_ids, samp_v, samp_w, overloaded, ids
    )
    row_digests, product = _pack_product(
        rows, nh_count, d_s, packed_mask, pos_w
    )
    dr = dr.at[local_ids].set(rows)
    digests = digests.at[local_ids].set(row_digests)
    live = jnp.arange(k) < count
    changed = live & jnp.any(product != packed_res[local_ids], axis=1)
    ch_count = jnp.sum(changed.astype(jnp.int32))
    packed_res = packed_res.at[local_ids].set(product)
    body = jnp.concatenate([ids[:, None], product], axis=1)
    # prefix-sum compaction: changed rows scatter to 1..ch_count,
    # unchanged rows to the dropped out-of-bounds slot
    pos = jnp.cumsum(changed.astype(jnp.int32)) - 1
    dest = jnp.where(changed, pos + 1, k + 1)
    out = jnp.zeros((k + 1, body.shape[1]), dtype=jnp.int32)
    out = out.at[dest].set(body, mode="drop")
    out = out.at[0, 0].set(count)
    out = out.at[0, 1].set(ch_count)
    return dr, digests, packed_res, out


def _compact_changed_body(new_packed, prev_packed, n):
    """Full-width delta epilogue: diff the fresh [n_pad, W] packed
    product bit-for-bit against the resident previous one and
    prefix-sum-compact the changed rows to the front, each prefixed by
    its destination id. Returns (changed_count, out [n_pad, 1+W]) —
    the host reads the scalar, then slices out[:changed_count]: the
    full-width refresh pays an O(changed) readback like the bucketed
    path instead of hauling every row home. Padding destinations
    (t >= n) re-solve identically every time and are masked out.
    Traced body — shared by the standalone jit below and the fused
    overflow chains, so the compaction rides the same executable as
    the solve it diffs."""
    npad = new_packed.shape[0]
    ids = jnp.arange(npad, dtype=jnp.int32)
    changed = (ids < n) & jnp.any(new_packed != prev_packed, axis=1)
    ch_count = jnp.sum(changed.astype(jnp.int32))
    pos = jnp.cumsum(changed.astype(jnp.int32)) - 1
    dest = jnp.where(changed, pos, npad)
    body = jnp.concatenate([ids[:, None], new_packed], axis=1)
    out = jnp.zeros((npad, body.shape[1]), dtype=jnp.int32)
    out = out.at[dest].set(body, mode="drop")
    return ch_count, out


_compact_changed = functools.partial(
    jax.jit, static_argnames=("n",)
)(_compact_changed_body)


def _compact_rows_with_ids(new_packed, prev_packed, cap):
    """Traced body of compact_rows_with_ids — shared with the fused
    world_dispatch below so the delta epilogue rides the same
    executable as the solve it diffs."""
    bsz, rows, n = new_packed.shape
    changed = jnp.any(new_packed != prev_packed, axis=2).reshape(-1)
    ch_count = jnp.sum(changed.astype(jnp.int32))
    flat = new_packed.reshape(bsz * rows, n)
    ids = jnp.arange(bsz * rows, dtype=jnp.int32)
    body = jnp.concatenate(
        [(ids // rows)[:, None], (ids % rows)[:, None], flat], axis=1
    )
    pos = jnp.cumsum(changed.astype(jnp.int32)) - 1
    dest = jnp.where(changed, pos, cap)
    out = jnp.zeros((cap + 1, 2 + n), dtype=jnp.int32)
    out = out.at[dest].set(body, mode="drop")
    return ch_count, out


@functools.partial(jax.jit, static_argnames=("cap",))
def compact_rows_with_ids(new_packed, prev_packed, cap):
    """Tenant-batched delta epilogue (consumed by ops.world_batch):
    diff a [B, R, N] packed block bit-for-bit against the resident
    previous one and prefix-sum-compact the changed rows to the front,
    each prefixed by a [tenant, row] id column pair — the batched
    generalization of _compact_changed's single-graph delta readback,
    with the tenant id riding the compacted rows so one readback fans
    back out to B per-tenant host mirrors. Returns
    (changed_count, out [cap+1, 2+N]): the host reads the scalar, then
    slices out[:changed_count]; when the delta overflows ``cap`` the
    caller falls back to a full-block readback (counted, never silent).
    Unchanged rows scatter into the dropped slot at ``cap``; overflow
    positions land out of bounds and mode="drop" discards them, so the
    resident previous block is never torn by a too-small cap."""
    return _compact_rows_with_ids(new_packed, prev_packed, cap)


@functools.partial(jax.jit, static_argnames=("cap",))
def world_dispatch(
    src, w, ov, srcs, p_rows, p_src, p_w,
    inc_t, inc_h, inc_w, d_prev, packed_prev, cap,
):
    """The fused per-bucket tenant dispatch: patch scatter + batched
    view solve (spf_sparse._tenant_view_solve under vmap) + tenant-id
    delta compaction against the resident previous block — ONE device
    round trip per shape bucket per churn round, the tenant-plane twin
    of _churn_step. Returns (packed, d, src, w, changed_count, out):
    the first four rebind as the bucket's new resident block (inputs
    are NOT donated — the overflow fallback and rehydration re-read
    them, the double-buffer hazard rule), the last two drive the
    compacted readback exactly as compact_rows_with_ids documents."""
    packed, d, src, w = jax.vmap(_tenant_view_solve)(
        src, w, ov, srcs, p_rows, p_src, p_w,
        inc_t, inc_h, inc_w, d_prev,
    )
    ch_count, out = _compact_rows_with_ids(packed, packed_prev, cap)
    return packed, d, src, w, ch_count, out


@functools.partial(jax.jit, static_argnames=("bands", "n", "k"))
def _churn_step(
    v_t, w_t, patch_ids_t, patch_v_t, patch_w_t,
    dr, digests, packed_res,
    e_u, e_v, e_w_old, e_w_new,
    overloaded_new,
    samp_ids, samp_v, samp_w, pos_w,
    bands, n, k,
):
    """The fused single-chip incremental dispatch: detection against
    the resident DR, band-row patch scatter, affected-row re-solve and
    extraction — one device round trip per churn event. None of the
    resident inputs (dr/digests/packed_res) are donated: the overflow
    retry ladder re-dispatches at a larger bucket against the SAME
    untouched resident arrays (the double-buffer hazard rule)."""
    count, local_ids, ids = _detect_rows(
        dr, e_u, e_v, e_w_old, e_w_new, k, 0
    )
    # warm seed for the re-solve: pre-patch rows with the
    # increase-affected CONE reset cell-granular (rs._cone_expand, the
    # frontier kernel over the PRE-patch bands — XLA CSEs the shared
    # dr gathers with _detect_rows). If the expansion hit the jump cap
    # the cone is an under-approximation and the seed degrades to the
    # pre-frontier whole-row reset; either way the re-solve stays
    # bit-identical by the unique-fixed-point squeeze, the cone just
    # leaves already-final cells converged from iteration zero.
    sel = dr[local_ids]
    cone, _rows, _cells, _jumps, cone_ok = rs._cone_expand(
        sel, bands, v_t, w_t, e_u, e_v, e_w_old, e_w_new,
        _FRONTIER_MAX_JUMPS,
    )
    inc_row = _increase_rows(dr, e_u, e_v, e_w_old, e_w_new)
    warm0 = jnp.where(
        cone_ok,
        jnp.where(cone, INF, sel),
        jnp.where(inc_row[local_ids][:, None], INF, sel),
    )
    # scatter patched band rows (same bucketed shape discipline as
    # EllState.reconverge)
    new_v = tuple(
        s.at[pids, :].set(pv)
        for s, pids, pv in zip(v_t, patch_ids_t, patch_v_t)
    )
    new_w = tuple(
        w.at[pids, :].set(pw)
        for w, pids, pw in zip(w_t, patch_ids_t, patch_w_t)
    )
    dr, digests, packed_res, out = _resolve_and_pack(
        lambda t: rs._rev_fixed_point(
            bands, new_v, new_w, overloaded_new, t, n, init=warm0
        ),
        lambda rows, t: rs._nh_counts(
            rows, bands, new_v, new_w, overloaded_new, t
        ),
        overloaded_new, ids, local_ids, count,
        dr, digests, packed_res, samp_ids, samp_v, samp_w, pos_w, n, k,
    )
    return new_v, new_w, dr, digests, packed_res, out


@functools.partial(
    jax.jit, static_argnames=("bands", "n", "max_jumps")
)
def _frontier_probe(
    v_t, w_t, dr, e_u, e_v, e_w_old, e_w_new, cell_limit, bands, n,
    max_jumps,
):
    """Frontier probe dispatch: expand the increase-affected cone over
    the full resident DR and the PRE-patch bands (rs._cone_expand) and
    return it ON DEVICE plus a 4-int meta [frontier_rows,
    frontier_cells, jumps, converged]. The host reads only the meta to
    make the frontier-vs-full-refresh policy call; the cone itself
    stays resident as the follow-up _frontier_step's seed mask.
    ``cell_limit`` is a device scalar (shape [1]) so threshold changes
    never recompile; the expansion early-exits once the cone overflows
    it (the fallback is already decided, no point finishing the
    closure)."""
    cone, rows, cells, jumps, ok = rs._cone_expand(
        dr, bands, v_t, w_t, e_u, e_v, e_w_old, e_w_new, max_jumps,
        cell_limit=cell_limit[0],
    )
    # float32 meta: the cell count already is (int32 overflows at
    # 100k-node cone sizes), the rest are small ints cast losslessly
    meta = jnp.stack(
        [rows.astype(jnp.float32), cells,
         jumps.astype(jnp.float32), ok.astype(jnp.float32)]
    )
    return cone, meta


@functools.partial(jax.jit, static_argnames=("bands", "n"))
def _frontier_step(
    v_t, w_t, cone, dr, overloaded, samp_ids, samp_v, samp_w, pos_w,
    bands, n,
):
    """The frontier re-solve dispatch: full-width WARM fixed point over
    the PATCHED bands, seeded from the resident DR with only the cone
    cells reset to INF (+ the unit anchor inside _rev_fixed_point) —
    the masked min-plus relaxation then converges in ~cone-radius
    iterations instead of graph diameter, because every cell outside
    the cone is already at its fixed point (structural increases) or a
    sound upper bound (decreases / link up). Same extraction + packing
    as the cold-shaped _full_resident_sweep, so the product is
    bit-identical and the delta-compacted readback epilogue
    (_compact_changed) applies unchanged. The residents are NOT
    donated: a frontier failure falls back to _full_refresh against
    the same untouched arrays (the retry-ladder hazard rule)."""
    t_ids = jnp.arange(n, dtype=jnp.int32)
    warm0 = jnp.where(cone, INF, dr)
    dr2 = rs._rev_fixed_point(
        bands, v_t, w_t, overloaded, t_ids, n, init=warm0
    )
    nh_count = rs._nh_counts(dr2, bands, v_t, w_t, overloaded, t_ids)
    d_s, packed_mask = rs._sample_stats(
        dr2, samp_ids, samp_v, samp_w, overloaded, t_ids
    )
    digests, packed = _pack_product(
        dr2, nh_count, d_s, packed_mask, pos_w
    )
    return dr2, digests, packed


@functools.partial(
    jax.jit, static_argnames=("bands", "n", "n_real", "max_jumps")
)
def _overflow_chain(
    v_old_t, w_old_t, v_new_t, w_new_t, dr, packed_res,
    e_u, e_v, e_w_old, e_w_new, cell_limit, overloaded_new,
    samp_ids, samp_v, samp_w, pos_w, bands, n, n_real, max_jumps,
):
    """The fused overflow decision chain: probe + frontier-vs-full
    branch + re-solve + extraction + delta compaction in ONE
    executable, with the policy decision made ON DEVICE instead of a
    16-byte meta readback and a host ``if``.

    The branch reduces to a seed select: the full-width refresh is
    exactly the frontier re-solve with an all-True cone (an all-INF
    warm seed collapses to the cold unit init inside
    ``rs._rev_fixed_point``), so ``use_frontier`` only widens the
    reset mask — no ``lax.cond`` over differently-shaped programs, and
    the answer is bit-identical to whichever split-path dispatch the
    host branch would have picked. The probe runs over the PRE-patch
    tensors, the solve over the PATCHED ones (both passed in: patch
    scatter is its own tiny dispatch in the same submit phase). The
    meta row rides home on the async lane for post-hoc policy
    telemetry only — a warm multi-window burst never breaks the
    dispatch chain on it."""
    cone, rows, cells, jumps, ok = rs._cone_expand(
        dr, bands, v_old_t, w_old_t, e_u, e_v, e_w_old, e_w_new,
        max_jumps, cell_limit=cell_limit[0],
    )
    meta = jnp.stack(
        [rows.astype(jnp.float32), cells,
         jumps.astype(jnp.float32), ok.astype(jnp.float32)]
    )
    use_frontier = jnp.logical_and(ok, cells <= cell_limit[0])
    eff_cone = jnp.logical_or(cone, jnp.logical_not(use_frontier))
    t_ids = jnp.arange(n, dtype=jnp.int32)
    warm0 = jnp.where(eff_cone, INF, dr)
    dr2 = rs._rev_fixed_point(
        bands, v_new_t, w_new_t, overloaded_new, t_ids, n, init=warm0
    )
    nh_count = rs._nh_counts(
        dr2, bands, v_new_t, w_new_t, overloaded_new, t_ids
    )
    d_s, packed_mask = rs._sample_stats(
        dr2, samp_ids, samp_v, samp_w, overloaded_new, t_ids
    )
    digests, packed = _pack_product(
        dr2, nh_count, d_s, packed_mask, pos_w
    )
    ch_count, comp = _compact_changed_body(packed, packed_res, n_real)
    return dr2, digests, packed, ch_count, comp, meta


# -- mesh-sharded dispatches ----------------------------------------------

from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from jax import shard_map

from openr_tpu.ops.spf_sparse import SOURCES_AXIS  # noqa: E402
from openr_tpu.parallel.mesh import (  # noqa: E402
    ShardingPlan, replicated_jit,
)


def _patch_bands_fn(v_t, w_t, patch_ids_t, patch_v_t, patch_w_t):
    """Scatter patched band rows into the (replicated) resident band
    tensors — the sharded engine's band patch rides this one small
    dispatch instead of being fused into the churn step (replicated
    outputs from inside shard_map would need cross-shard replication
    bookkeeping for no bandwidth win; the patch is O(degree))."""
    new_v = tuple(
        s.at[pids, :].set(pv)
        for s, pids, pv in zip(v_t, patch_ids_t, patch_v_t)
    )
    new_w = tuple(
        w.at[pids, :].set(pw)
        for w, pids, pw in zip(w_t, patch_ids_t, patch_w_t)
    )
    return new_v, new_w


# single-chip dispatch of the band patch; the mesh engines instead ride
# parallel.mesh.replicated_jit(_patch_bands_fn, mesh) so the patched
# tensors come back COMMITTED replicated, matching the sharded churn
# step's replicated in_specs — otherwise XLA re-replicates the bands on
# every churn dispatch (the reshard storm the plan exists to prevent)
_patch_bands = jax.jit(_patch_bands_fn)


@functools.partial(jax.jit, static_argnames=("start", "size"))
def _rows_slice(seg, start, size):
    """Jitted static row slice of a device segment. Eager basic
    indexing (``seg[1:1+m]``) uploads its start indices host->device
    at every call — an IMPLICIT transfer the churn path's
    transfer_guard contract forbids; under jit the indices are
    compiled constants. One tiny executable per (shape, start, size),
    cached — the same per-(shape, m) executable cache the eager slice
    primitive was already paying for."""
    return jax.lax.slice_in_dim(seg, start, start + size)


@jax.jit
def _seg_meta(seg):
    """Jitted read of a segment's leading meta pair [affected,
    changed] — same implicit-index-upload avoidance as _rows_slice."""
    return jax.lax.slice(seg, (0, 0), (1, 2))[0]


@functools.partial(jax.jit, static_argnames=("bands", "n", "mesh"))
def _sharded_full_resident(
    v_t, w_t, overloaded, samp_ids, samp_v, samp_w, pos_w, bands, n,
    mesh,
):
    """Sharded cold build: every device solves its block of destination
    rows (the axis the single-chip engine holds whole); DR and digests
    come back SHARDED over the mesh — the resident footprint per device
    is n_pad^2/ndev, which is what breaks the single-chip 12k bound.
    Only collective: the 1-bit convergence vote per iteration."""
    nb = len(v_t)

    def shard_fn(t_blk, *rest):
        v_r = rest[:nb]
        w_r = rest[nb : 2 * nb]
        ov_r, sid_r, sv_r, sw_r, pw_r = rest[2 * nb :]
        vote = lambda bit: jax.lax.psum(bit, SOURCES_AXIS)  # noqa: E731
        dr = rs._rev_fixed_point(
            bands, v_r, w_r, ov_r, t_blk, n, vote=vote
        )
        nh_count = rs._nh_counts(dr, bands, v_r, w_r, ov_r, t_blk)
        digests = rs._digest_rows(dr, nh_count, pw_r)
        nh_total = jnp.sum(nh_count, axis=1, dtype=jnp.int32)
        d_s, packed_mask = rs._sample_stats(
            dr, sid_r, sv_r, sw_r, ov_r, t_blk
        )
        b = t_blk.shape[0]
        packed = jnp.concatenate(
            [
                jax.lax.bitcast_convert_type(digests, jnp.int32)[
                    :, None
                ],
                nh_total[:, None],
                d_s,
                jax.lax.bitcast_convert_type(
                    packed_mask, jnp.int32
                ).reshape(b, -1),
            ],
            axis=1,
        )
        return dr, digests, packed

    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=tuple(
            [P(SOURCES_AXIS)]
            + [P(None, None)] * (2 * nb)
            + [P(None), P(None), P(None, None), P(None, None), P(None)]
        ),
        out_specs=(
            P(SOURCES_AXIS, None),
            P(SOURCES_AXIS),
            P(SOURCES_AXIS, None),
        ),
    )(
        jnp.arange(n, dtype=jnp.int32),
        *v_t, *w_t, overloaded, samp_ids, samp_v, samp_w, pos_w,
    )


@functools.partial(jax.jit, static_argnames=("bands", "n", "k", "mesh"))
def _sharded_churn_step(
    v_t, w_t, dr, digests, packed_res,
    e_u, e_v, e_w_old, e_w_new,
    overloaded_new,
    samp_ids, samp_v, samp_w, pos_w,
    bands, n, k, mesh,
):
    """The sharded incremental dispatch: detection runs PER SHARD
    against its resident DR rows (destination rows never interact, so
    each shard's affected set is exactly its own rows' detection), the
    re-solve runs on each shard's affected rows with the convergence
    vote lifted over the mesh, and the delta-compacted readback comes
    back as ndev stacked [k+1, 1+W] segments (each shard's
    affected/changed counts in its meta row — the host reads each
    shard's changed rows from its OWN addressable shard, see
    _split_segments). Band tensors arrive ALREADY PATCHED
    (_patch_bands)."""
    nb = len(v_t)
    rows_per = n // mesh.devices.size

    def shard_fn(dr_s, dg_s, pk_s, *rest):
        v_r = rest[:nb]
        w_r = rest[nb : 2 * nb]
        (e_u_r, e_v_r, e_wo_r, e_wn_r, ov_r,
         sid_r, sv_r, sw_r, pw_r) = rest[2 * nb :]
        row_start = (
            jax.lax.axis_index(SOURCES_AXIS) * rows_per
        ).astype(jnp.int32)
        count, local_ids, ids = _detect_rows(
            dr_s, e_u_r, e_v_r, e_wo_r, e_wn_r, k, row_start
        )
        vote = lambda bit: jax.lax.psum(bit, SOURCES_AXIS)  # noqa: E731
        return _resolve_and_pack(
            lambda t: rs._rev_fixed_point(
                bands, v_r, w_r, ov_r, t, n, vote=vote
            ),
            lambda rows, t: rs._nh_counts(
                rows, bands, v_r, w_r, ov_r, t
            ),
            ov_r, ids, local_ids, count, dr_s, dg_s, pk_s,
            sid_r, sv_r, sw_r, pw_r, n, k,
        )

    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=tuple(
            [P(SOURCES_AXIS, None), P(SOURCES_AXIS),
             P(SOURCES_AXIS, None)]
            + [P(None, None)] * (2 * nb)
            + [P(None)] * 4
            + [P(None), P(None), P(None, None), P(None, None), P(None)]
        ),
        out_specs=(
            P(SOURCES_AXIS, None),
            P(SOURCES_AXIS),
            P(SOURCES_AXIS, None),
            P(SOURCES_AXIS, None),
        ),
    )(
        dr, digests, packed_res, *v_t, *w_t,
        e_u, e_v, e_w_old, e_w_new, overloaded_new,
        samp_ids, samp_v, samp_w, pos_w,
    )


@functools.partial(
    jax.jit, static_argnames=("bands", "n", "max_jumps", "mesh")
)
def _sharded_frontier_probe(
    v_t, w_t, dr, e_u, e_v, e_w_old, e_w_new, cell_limit, bands, n,
    max_jumps, mesh,
):
    """Sharded frontier probe: each shard expands the cone over its own
    resident DR rows (rows never interact), with the growth bit and the
    frontier row/cell counts psum-voted so every shard runs the same
    number of jumps. The meta row is device-invariant by construction
    (voted counts + shared iteration counter) and comes back
    replicated; the cone stays row-sharded for _sharded_frontier_step."""
    nb = len(v_t)

    def shard_fn(dr_s, *rest):
        v_r = rest[:nb]
        w_r = rest[nb : 2 * nb]
        e_u_r, e_v_r, e_wo_r, e_wn_r, lim_r = rest[2 * nb :]
        vote = lambda bit: jax.lax.psum(bit, SOURCES_AXIS)  # noqa: E731
        cone, rows, cells, jumps, ok = rs._cone_expand(
            dr_s, bands, v_r, w_r, e_u_r, e_v_r, e_wo_r, e_wn_r,
            max_jumps, vote=vote, cell_limit=lim_r[0],
        )
        meta = jnp.stack(
            [rows.astype(jnp.float32), cells,
             jumps.astype(jnp.float32), ok.astype(jnp.float32)]
        )
        return cone, meta

    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=tuple(
            [P(SOURCES_AXIS, None)]
            + [P(None, None)] * (2 * nb)
            + [P(None)] * 5
        ),
        out_specs=(P(SOURCES_AXIS, None), P(None)),
    )(dr, *v_t, *w_t, e_u, e_v, e_w_old, e_w_new, cell_limit)


@functools.partial(
    jax.jit, static_argnames=("bands", "n", "mesh")
)
def _sharded_frontier_step(
    v_t, w_t, cone, dr, overloaded, samp_ids, samp_v, samp_w, pos_w,
    bands, n, mesh,
):
    """Sharded frontier re-solve: the full-width warm dispatch over the
    PATCHED (replicated) bands with each shard seeding its own DR rows
    outside its cone shard — the convergence vote is the only
    collective, exactly like the sharded cold build it replaces."""
    nb = len(v_t)

    def shard_fn(t_blk, cone_s, dr_s, *rest):
        v_r = rest[:nb]
        w_r = rest[nb : 2 * nb]
        ov_r, sid_r, sv_r, sw_r, pw_r = rest[2 * nb :]
        vote = lambda bit: jax.lax.psum(bit, SOURCES_AXIS)  # noqa: E731
        warm0 = jnp.where(cone_s, INF, dr_s)
        dr2 = rs._rev_fixed_point(
            bands, v_r, w_r, ov_r, t_blk, n, vote=vote, init=warm0
        )
        nh_count = rs._nh_counts(dr2, bands, v_r, w_r, ov_r, t_blk)
        d_s, packed_mask = rs._sample_stats(
            dr2, sid_r, sv_r, sw_r, ov_r, t_blk
        )
        digests, packed = _pack_product(
            dr2, nh_count, d_s, packed_mask, pw_r
        )
        return dr2, digests, packed

    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=tuple(
            [P(SOURCES_AXIS), P(SOURCES_AXIS, None),
             P(SOURCES_AXIS, None)]
            + [P(None, None)] * (2 * nb)
            + [P(None), P(None), P(None, None), P(None, None), P(None)]
        ),
        out_specs=(
            P(SOURCES_AXIS, None),
            P(SOURCES_AXIS),
            P(SOURCES_AXIS, None),
        ),
    )(
        jnp.arange(n, dtype=jnp.int32), cone, dr, *v_t, *w_t,
        overloaded, samp_ids, samp_v, samp_w, pos_w,
    )


@functools.partial(
    jax.jit,
    static_argnames=("bands", "n", "n_real", "max_jumps", "mesh"),
)
def _sharded_overflow_chain(
    v_old_t, w_old_t, v_new_t, w_new_t, dr, packed_res,
    e_u, e_v, e_w_old, e_w_new, cell_limit, overloaded_new,
    samp_ids, samp_v, samp_w, pos_w, bands, n, n_real, max_jumps,
    mesh,
):
    """Sharded fused overflow chain: per-shard cone expansion with the
    counters/growth bit psum-voted (the policy inputs are
    device-invariant by construction, so every shard takes the SAME
    seed-select branch), warm re-solve over the patched replicated
    bands, per-shard extraction — one shard_map, no replicated policy
    readback in the middle. The delta compaction runs on the
    row-sharded packed product after the shard_map, inside the same
    executable; meta comes back replicated for post-hoc telemetry."""
    nb = len(v_old_t)

    def shard_fn(t_blk, dr_s, *rest):
        v_o = rest[:nb]
        w_o = rest[nb : 2 * nb]
        v_n = rest[2 * nb : 3 * nb]
        w_n = rest[3 * nb : 4 * nb]
        (e_u_r, e_v_r, e_wo_r, e_wn_r, lim_r, ov_r,
         sid_r, sv_r, sw_r, pw_r) = rest[4 * nb :]
        vote = lambda bit: jax.lax.psum(bit, SOURCES_AXIS)  # noqa: E731
        cone, rows, cells, jumps, ok = rs._cone_expand(
            dr_s, bands, v_o, w_o, e_u_r, e_v_r, e_wo_r, e_wn_r,
            max_jumps, vote=vote, cell_limit=lim_r[0],
        )
        meta = jnp.stack(
            [rows.astype(jnp.float32), cells,
             jumps.astype(jnp.float32), ok.astype(jnp.float32)]
        )
        use_frontier = jnp.logical_and(ok, cells <= lim_r[0])
        eff_cone = jnp.logical_or(
            cone, jnp.logical_not(use_frontier)
        )
        warm0 = jnp.where(eff_cone, INF, dr_s)
        dr2 = rs._rev_fixed_point(
            bands, v_n, w_n, ov_r, t_blk, n, vote=vote, init=warm0
        )
        nh_count = rs._nh_counts(dr2, bands, v_n, w_n, ov_r, t_blk)
        d_s, packed_mask = rs._sample_stats(
            dr2, sid_r, sv_r, sw_r, ov_r, t_blk
        )
        digests, packed = _pack_product(
            dr2, nh_count, d_s, packed_mask, pw_r
        )
        return dr2, digests, packed, meta

    dr2, digests, packed, meta = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=tuple(
            [P(SOURCES_AXIS), P(SOURCES_AXIS, None)]
            + [P(None, None)] * (4 * nb)
            + [P(None)] * 6
            + [P(None), P(None, None), P(None, None), P(None)]
        ),
        out_specs=(
            P(SOURCES_AXIS, None),
            P(SOURCES_AXIS),
            P(SOURCES_AXIS, None),
            P(None),
        ),
    )(
        jnp.arange(n, dtype=jnp.int32), dr,
        *v_old_t, *w_old_t, *v_new_t, *w_new_t,
        e_u, e_v, e_w_old, e_w_new, cell_limit, overloaded_new,
        samp_ids, samp_v, samp_w, pos_w,
    )
    ch_count, comp = _compact_changed_body(packed, packed_res, n_real)
    return dr2, digests, packed, ch_count, comp, meta


class _DeviceStateInvalid(RuntimeError):
    """The resident device state is stale (a host fallback bypassed
    it): the warm rung refuses to run and the ladder walks to the cold
    rebuild, which rederives everything."""


class PendingDelta:
    """Handle to ONE churn event's in-flight delta-compacted readback.

    The device state (bands, DR, digests, packed product) is already
    committed when the handle exists; only the HOST mirror
    (engine.result) lags until the delta is consumed. ``wait()``
    consumes (via the engine, which owns ordering) and returns the
    sorted moved destination names. The engine holds at most one
    pending delta: the next churn event consumes it inside its own
    dispatch window (the double-buffer overlap), so a pipelined caller
    pays zero dedicated host time for the readback."""

    __slots__ = (
        "_engine", "segs", "counts", "ch_counts", "k", "dslices",
        "fw_count", "consumed", "names", "delta_rows",
        "readback_bytes", "overlap_ms", "meta_dev", "meta_limit",
    )

    def __init__(self, engine, segs, counts, ch_counts, k,
                 fw_count=None, meta_dev=None, meta_limit=0.0):
        self._engine = engine
        self.segs = segs          # per-shard device [k+1, 1+W] arrays
        self.counts = counts      # per-shard affected counts
        self.ch_counts = ch_counts  # per-shard CHANGED counts
        self.k = k
        # FULL-WIDTH mode (fw_count is a device scalar): the segment is
        # a _compact_changed output [n_pad, 1+W] whose changed rows
        # start at ROW 0 and whose count has not crossed to host yet —
        # the count rides the async lane now and is reaped at consume
        # time, so even the overflow rungs keep the two-touch window
        self.fw_count = fw_count
        self.consumed = False
        self.names: List[str] = []
        self.delta_rows = 0
        self.readback_bytes = 0
        self.overlap_ms = 0.0
        # kick EVERY shard's changed-rows transfer now: each device
        # copies its own O(changed) slice to host concurrently while
        # the next event dispatches, so consume time is an apply, not a
        # serial per-device drain on the readback lane
        self.dslices = []
        for seg, m in zip(segs, ch_counts):
            sl = None
            if m:
                if isinstance(seg, jax.Array):
                    sl = _rows_slice(seg, 1, int(m))
                    da.kick_async(sl)
                else:  # host shim arrays
                    sl = seg[1 : 1 + m]
            self.dslices.append(sl)
        if fw_count is not None:
            da.kick_async(fw_count)
        # fused-overflow-chain mode: the probe meta rode the dispatch
        # and its policy classification (frontier vs full-width
        # counters) is settled at consume time, off the event window
        self.meta_dev = meta_dev
        self.meta_limit = meta_limit
        if meta_dev is not None:
            da.kick_async(meta_dev)

    def wait(self) -> List[str]:
        if not self.consumed:
            self._engine.flush()
        return self.names


class _Speculation:
    """One staged speculative churn dispatch (latest-wins guess at the
    debounce window's final composition). Everything here is
    FUNCTIONAL output of _run_bucket — the resident tensors are never
    donated (retry-ladder hazard rule), so cancelling a speculation is
    dropping this object: no device state to unwind, no readback to
    drain (the kicked meta copies land and are garbage-collected).
    ``dr_ref`` pins the exact resident DR the dispatch read; every
    commit path replaces the engine's ``_dr`` binding, so an
    identity mismatch at adoption time means another event committed
    underneath the speculation and it MUST cancel."""

    __slots__ = (
        "union", "version", "aversion", "dr_ref", "ctx", "segments",
        "counts", "ch_counts", "commit_state", "ov_new", "k",
        "new_out", "ov_flips", "structural",
    )


@mirrored_by(
    _dr="re-derived from the resident band tensors (integrity_heal) "
        "or the LinkState (_build)",
    _digests_dev="result.digests (delta-applied on every consume)",
    _packed_dev="_packed_host (settle-on-success row scatter)",
)
@resident_buffers("_dr", "_digests_dev", "_packed_dev")
class RouteSweepEngine(ResidentEngineContract):
    """Resident incremental network-wide route product.

    cold_build(ls) -> RouteSweepResult (full product)
    churn(ls, affected_nodes) -> (moved destination names, their
    fresh per-sample route rows refreshed in self.result) or None when
    the event needs a cold rebuild (node add/remove or a sample node's
    slot-table reshape). Link add/remove and band widening stay on the
    incremental path; affected-count overflow past the largest bucket
    takes the full-width refresh (patched layout kept, all rows
    re-solved in one dispatch — no host recompile) and still reports
    the moved names from the DEVICE product diff.

    Every event class reads back only the delta: the rows whose packed
    product changed bit-for-bit, compacted on device. With
    ``defer_consume=True`` churn returns a PendingDelta instead of
    names and the host-side apply overlaps the NEXT event's dispatch
    (call ``flush()`` — or ``PendingDelta.wait()`` — to drain).
    ``churn_coalesced`` folds a debounce window's worth of patches into
    one fused dispatch + one readback."""

    def __init__(self, ls, sample_names: Sequence[str],
                 align: int = 128, mesh: Optional[Mesh] = None,
                 frontier_threshold: float = _DEFAULT_FRONTIER_THRESHOLD):
        self.sample_names = tuple(sample_names)
        self.mesh = mesh
        # the build-time placement contract: under a mesh every
        # resident gets an explicit NamedSharding (rows striped,
        # bands/edges replicated) so churn dispatches never reshard
        self.plan = ShardingPlan(mesh) if mesh is not None else None
        # pre-mesh alignment, kept so a device-loss mesh shrink can
        # re-derive the per-shard row block for the surviving devices
        self._base_align = align
        if mesh is not None:
            # every shard must own an equal block of destination rows
            align = align * mesh.devices.size
        self._align = align
        self._k_hint = _ROW_BUCKETS[0]
        self._pending: Optional[PendingDelta] = None
        # at most one staged speculative dispatch (see speculate_churn)
        self._speculation: Optional[_Speculation] = None
        # service-plane visibility into the dispatch-level double
        # buffer: 1 while a delta-compacted readback is in flight
        # (consumed inside the next churn's dispatch window) — the same
        # overlap the Decision emit stage applies one layer up
        get_registry().gauge(
            "ops.pending_delta_inflight",
            lambda: float(self._pending is not None),
        )
        self.last_delta_rows = 0
        self.last_readback_bytes = 0
        self.last_overlap_ms = 0.0
        # overflow policy knob: a converged frontier covering more than
        # this fraction of the [n, n] route product still rides the
        # full-width refresh
        self.frontier_threshold = float(frontier_threshold)
        self.last_frontier_rows = -1
        self.last_frontier_jumps = -1
        self.last_frontier_cells = -1.0
        # False between a failed/bypassed device path and the next
        # successful cold build: gates the warm rung off stale residents
        self._device_valid = False
        # True between an observed device loss (is_device_loss at a
        # rung boundary) and the recover rung re-landing the residents;
        # gates the recover rung so it is a no-op on ordinary faults
        self._device_lost = False
        self.host_fallbacks = 0
        self.device_rebuilds = 0
        self.mesh_shrinks = 0
        # settle-on-success host mirror of the resident packed product
        # (rows < n scatter-updated on every delta consume): tier-2
        # digest reference and the warm-heal bit-identity witness
        self._packed_host: Optional[np.ndarray] = None
        self._corrupt_events = 0
        self.supervisor = DegradationSupervisor("route_engine")
        self._build(ls)
        get_auditor().register(self)

    def _max_nodes(self) -> int:
        """Residency bound: the resident DR is [n_pad, n_pad] int32 —
        whole on a single chip, row-sharded over a mesh (per-device
        footprint n_pad^2/ndev), so the bound scales with sqrt(ndev):
        12k single-chip, ~100k on a 64-way mesh."""
        if self.mesh is None:
            return ENGINE_MAX_NODES
        import math

        return int(ENGINE_MAX_NODES * math.sqrt(self.mesh.devices.size))

    # -- state -------------------------------------------------------------

    def _compile_backend(self, ls):
        """Backend hook: compile the layout + sweeper for a cold
        build."""
        graph = compile_ell(ls, align=self._align, direction="out")
        return graph, rs.RouteSweeper(
            graph, self.sample_names, plan=self.plan
        )

    def _full_resident(self, graph):
        """Backend hook: the cold full-product dispatch (DR + digests
        resident, packed product back)."""
        if self.mesh is None:
            # openr-lint: disable=sharding-spec -- single-chip cold
            # build (mesh is None): one device, no axis to spec
            return aot_call(
                "ell_full_resident", _full_resident_sweep,
                (
                    self.sweeper.v_t, self.sweeper.w_t,
                    self.sweeper.overloaded,
                    self.sweeper._samp_ids_dev,
                    self.sweeper._samp_v_dev,
                    self.sweeper._samp_w_dev, self.sweeper._pos_w_dev,
                ),
                dict(bands=graph.bands, n=graph.n_pad),
            )
        return aot_call(
            "ell_full_resident_sharded", _sharded_full_resident,
            (
                self.sweeper.v_t, self.sweeper.w_t,
                self.sweeper.overloaded,
                self.sweeper._samp_ids_dev, self.sweeper._samp_v_dev,
                self.sweeper._samp_w_dev, self.sweeper._pos_w_dev,
            ),
            dict(bands=graph.bands, n=graph.n_pad, mesh=self.mesh),
        )

    @requires_drain("flush")
    def _build(self, ls) -> None:
        # a cold rebuild replaces the whole result: drain any in-flight
        # delta first so a caller-held PendingDelta handle resolves
        self.flush()
        # invalid until this build completes: a failure below leaves
        # the engine torn (mirrors vs residents), and the gate forces
        # every later event through another cold build or the host rung
        self._device_valid = False
        # a staged speculation read the pre-build residents: dead now
        self._speculation = None
        graph, sweeper = self._compile_backend(ls)
        if graph.n_pad > self._max_nodes():
            raise ValueError(
                f"route engine residency bound: {graph.n_pad} > "
                f"{self._max_nodes()} (use the block/mesh sweep, or "
                "a larger mesh)"
            )
        self.graph = graph
        self.sweeper = sweeper
        # RAW collapsed min weights of the directed edges, indexed both
        # ways for O(degree) event diffing. STRICTLY raw: overload
        # flips never mutate these mirrors — effective-weight
        # transitions exist only inside one event's detection list
        # (conflating them made a later metric change on a drained
        # node's edge undetectable, a silent-stale-routes bug).
        self._w_out: Dict[int, Dict[int, int]] = {}
        self._w_in: Dict[int, Dict[int, int]] = {}
        for nm in graph.node_names:
            u = graph.node_index[nm]
            for v, w in _out_edges(ls, nm, graph.node_index).items():
                self._w_out.setdefault(u, {})[v] = w
                self._w_in.setdefault(v, {})[u] = w
        self._ov_host = {
            nm: ls.is_node_overloaded(nm) for nm in graph.node_names
        }
        fault_point(FAULT_COLD_BUILD)
        dr, digests, packed = self._full_resident(graph)
        self._dr = dr
        self._digests_dev = digests
        # the packed product stays RESIDENT: every later dispatch diffs
        # its fresh rows against this to compact the readback
        self._packed_dev = packed
        # explicit gather (device_get): under a mesh np.asarray would
        # be an implicit cross-device transfer the guard rejects
        packed_host = jax.device_get(packed)
        self.result = rs.assemble_result(self.sweeper, packed_host)
        # private copy: assemble_result may keep views of its input
        self._packed_host = np.array(packed_host)
        self.version = ls.topology_version
        self.aversion = ls.attributes_version
        self._device_valid = True
        self.cold_builds = getattr(self, "cold_builds", 0) + 1
        self.incremental_events = getattr(
            self, "incremental_events", 0
        )
        self.full_refreshes = getattr(self, "full_refreshes", 0)
        self.coalesced_events = getattr(self, "coalesced_events", 0)
        self.structural_events = getattr(self, "structural_events", 0)
        self.frontier_resolves = getattr(self, "frontier_resolves", 0)
        self.frontier_fallbacks = getattr(
            self, "frontier_fallbacks", 0
        )
        get_registry().counter_bump("route_engine.cold_builds")
        get_flight_recorder().note(
            "engine", path="cold_build", n=int(graph.n_pad)
        )

    def _refresh_sample_bands(self, patched, affected_nodes) -> bool:
        """A churn event that touched a SAMPLE node's own adjacencies
        changes the slot tables the next-hop masks are computed over
        (route_sweep._sample_stats closes over samp_v/samp_w) — refresh
        them from the PATCHED graph BEFORE the dispatch, so this very
        event's packed sample rows use current tables. Returns False
        when the slot-table shape changed (sample degree crossed a pad
        boundary — the packed width moves): the caller cold-rebuilds.
        Early mutation of the sweeper tables is safe on every fallback
        path because a cold rebuild rederives them from scratch."""
        if not (affected_nodes & set(self.sample_names)):
            return True
        sweeper = self.sweeper
        samp_v, samp_w = rs._sample_bands(patched, sweeper.sample_ids)
        if samp_v.shape != sweeper.samp_v.shape:
            return False
        up = (
            self.plan.replicate if self.plan is not None
            else jnp.asarray
        )
        sweeper.samp_v = self.result.samp_v = samp_v
        sweeper.samp_w = self.result.samp_w = samp_w
        sweeper._samp_v_dev = up(samp_v)
        sweeper._samp_w_dev = up(samp_w)
        return True

    # -- events ------------------------------------------------------------

    def _layout_changed(self, ctx) -> bool:
        """Backend hook: did this event change the static band layout
        (shapes under the resident tensors)? Speculation and bursts
        refuse such events — the committed path owns the recompile.
        ELL bands are plain (start, rows, k) records, comparable by
        value; the grouped backend overrides (its patch helper returns
        None on any layout break, so a ctx implies stability)."""
        return ctx["patched"].bands != self.graph.bands

    def _prepare_patch(self, ls, affected_sorted):
        """Backend hook: derive the patched graph + device patch
        tensors for one churn event. Returns a ctx dict (consumed by
        _run_bucket/_commit_device) or None when the event breaks the
        layout (caller cold-rebuilds)."""
        patched = ell_patch(self.graph, ls, affected_sorted, widen=True)
        if patched is None:
            return None
        # band patch tensors: the shared discipline (bucketed row
        # scatter; a WIDENED band — tensor shape changed — re-uploads
        # wholesale with a no-op scatter; node ids stay fixed so the
        # resident DR stays valid, at the cost of one jit recompile)
        from openr_tpu.ops.spf_sparse import band_patch_inputs

        # (no resident no-op triples: a mesh engine commits every
        # input replicated below, and a held single-device array would
        # make that a device-to-device copy)
        in_v, in_w, patch_ids, patch_v, patch_w, _ = band_patch_inputs(
            self.sweeper.v_t, self.sweeper.w_t, patched
        )
        if self.plan is not None:
            # commit the fresh patch uploads (and any widened band
            # re-upload) REPLICATED before the replicated_jit patch
            # dispatch reads them: an uncommitted operand would make
            # the dispatch replicate it itself — a device-to-device
            # copy per event (and a transfer_guard violation)
            up = self.plan.replicate
            in_v = tuple(up(t) for t in in_v)
            in_w = tuple(up(t) for t in in_w)
            patch_ids = tuple(up(t) for t in patch_ids)
            patch_v = tuple(up(t) for t in patch_v)
            patch_w = tuple(up(t) for t in patch_w)
        return {
            "patched": patched,
            "in_v": in_v, "in_w": in_w,
            "patch_ids": patch_ids,
            "patch_v": patch_v, "patch_w": patch_w,
            "patched_bands": None,  # sharded path: lazily dispatched
        }

    @solve_window
    @committed_dispatch
    def _run_bucket(self, ctx, k, e_dev, ov_new):
        """Backend hook: one detect+solve dispatch at bucket size k.
        Returns (segments, commit_state) where segments are per-shard
        IN-FLIGHT device arrays [k+1, 1+W] — nothing is copied to host
        here; the caller reads the tiny meta row for the retry ladder
        and the changed rows only at consume time. Every launch goes
        through the AOT executable cache (aot_call): after warmup the
        event window runs a pre-compiled XLA program with zero Python
        retrace/signature checks on the hot path."""
        e_u_d, e_v_d, e_wo_d, e_wn_d = e_dev
        fault_point(FAULT_DISPATCH)
        fault_point(FAULT_DEVICE_LOST)
        graph = ctx["patched"]
        if self.mesh is None:
            (new_v, new_w_t, dr, digests, packed_res,
             # openr-lint: disable=sharding-spec -- single-chip churn
             # dispatch (mesh is None): no mesh axis to spec; the mesh
             # branch below rides _sharded_churn_step's shard_map specs
             packed_dev) = aot_call(
                "ell_churn_step", _churn_step,
                (
                    ctx["in_v"], ctx["in_w"],
                    ctx["patch_ids"], ctx["patch_v"], ctx["patch_w"],
                    self._dr, self._digests_dev, self._packed_dev,
                    e_u_d, e_v_d, e_wo_d, e_wn_d,
                    ov_new,
                    self.sweeper._samp_ids_dev,
                    self.sweeper._samp_v_dev,
                    self.sweeper._samp_w_dev, self.sweeper._pos_w_dev,
                ),
                dict(bands=graph.bands, n=graph.n_pad, k=k),
            )
            # the fused step already patched the bands on device: cache
            # them so an overflow's _apply_patch_resident adopts these
            # instead of re-dispatching _patch_bands
            ctx["patched_bands"] = (new_v, new_w_t)
            segments = [packed_dev]
        else:
            self._ensure_residents()
            # band patch in its own small dispatch (see
            # _patch_bands_fn) — loop-invariant, dispatched once
            if ctx["patched_bands"] is None:
                ctx["patched_bands"] = self._dispatch_patch(ctx)
            new_v, new_w_t = ctx["patched_bands"]
            dr, digests, packed_res, packed_dev = aot_call(
                "ell_churn_step_sharded", _sharded_churn_step,
                (
                    new_v, new_w_t,
                    self._dr, self._digests_dev, self._packed_dev,
                    e_u_d, e_v_d, e_wo_d, e_wn_d,
                    ov_new,
                    self.sweeper._samp_ids_dev,
                    self.sweeper._samp_v_dev,
                    self.sweeper._samp_w_dev, self.sweeper._pos_w_dev,
                ),
                dict(
                    bands=graph.bands, n=graph.n_pad, k=k,
                    mesh=self.mesh,
                ),
            )
            segments = self._split_segments(packed_dev, k)
        return segments, (new_v, new_w_t, dr, digests, packed_res)

    def _ensure_residents(self) -> None:
        """Churn-path placement tripwire (mesh engines): the resident
        DR / digests / packed product must already sit at their
        planned shardings — the sharded dispatches re-commit them via
        out_specs, so any mismatch here means something moved them and
        the next dispatch would pay an XLA reshard. Counted as
        ops.reshard_events (and corrected) by ShardingPlan.ensure."""
        plan = self.plan
        self._dr = plan.ensure(self._dr, plan.rows, "_dr")
        self._digests_dev = plan.ensure(
            self._digests_dev, plan.vec, "_digests_dev"
        )
        self._packed_dev = plan.ensure(
            self._packed_dev, plan.rows, "_packed_dev"
        )

    def _dispatch_patch(self, ctx):
        """Backend hook: the standalone band-patch dispatch (mesh path;
        the single-chip engine fuses the patch into the churn step).
        Under a mesh the patch rides replicated_jit so its outputs are
        COMMITTED replicated — matching the sharded churn step's
        replicated in_specs, no broadcast copy at the consumer."""
        fn = (
            replicated_jit(_patch_bands_fn, self.mesh)
            if self.mesh is not None else _patch_bands
        )
        return fn(
            ctx["in_v"], ctx["in_w"],
            ctx["patch_ids"], ctx["patch_v"], ctx["patch_w"],
        )

    def _split_segments(self, packed_dev, k: int):
        """Per-shard [k+1, 1+W] segments of a sharded churn readback,
        read from the array's ADDRESSABLE SHARDS (ordered by row
        offset) — each shard's meta row and changed rows transfer from
        the device that solved them; rows a shard didn't solve never
        cross to host."""
        shards = sorted(
            packed_dev.addressable_shards,
            key=lambda sh: sh.index[0].start or 0,
        )
        return [sh.data for sh in shards]

    @solve_window
    def _commit_device(self, ctx, commit_state, ov_new) -> None:
        """Backend hook: adopt the dispatch's device state."""
        new_v, new_w_t, dr, digests, packed_res = commit_state
        self.sweeper.v_t = new_v
        self.sweeper.w_t = new_w_t
        self.sweeper.overloaded = ov_new
        self._dr = dr
        self._digests_dev = digests
        self._packed_dev = packed_res
        self.graph = self.sweeper.graph = ctx["patched"]

    @solve_window
    def _apply_patch_resident(self, ctx, ov_new) -> None:
        """Backend hook: adopt the event's band patch into the resident
        sweeper tensors WITHOUT a row re-solve — the full-width refresh
        applies this then runs the cold-build-shaped dispatch over the
        patched tensors (a widened band changed the static band shapes,
        so that dispatch recompiles once — the documented widening
        cost — but the layout itself is never re-derived on host)."""
        if ctx["patched_bands"] is None:
            ctx["patched_bands"] = self._dispatch_patch(ctx)
        new_v, new_w_t = ctx["patched_bands"]
        self.sweeper.v_t = new_v
        self.sweeper.w_t = new_w_t
        self.sweeper.overloaded = ov_new
        self.graph = self.sweeper.graph = ctx["patched"]

    def _commit_host_mirrors(self, ls, new_out, ov_flips) -> None:
        """Fold one committed event's raw-weight diff and overload
        flips into the O(E) host mirrors (shared by the bucketed and
        full-width commit paths)."""
        for u, seen in new_out.items():
            old = self._w_out.get(u, {})
            for v in set(old) - set(seen):
                self._w_in.get(v, {}).pop(u, None)
            self._w_out[u] = dict(seen)
            for v, w in seen.items():
                self._w_in.setdefault(v, {})[u] = w
        for nm in ov_flips:
            self._ov_host[nm] = ls.is_node_overloaded(nm)

    def _full_refresh(self, ls, ctx, ov_new, new_out, ov_flips,
                      defer=False):
        """Overflow path: the affected-row count exceeds every solve
        bucket (a fat-tree link up/down affects EVERY destination row
        through ECMP next-hop churn), so re-solving a subset saves
        nothing — but the LAYOUT is still patchable. Keep the patched
        resident tensors and run the full-width dispatch; the host
        layout recompile (the dominant cold-build cost: seconds at 10k)
        is skipped entirely.

        The readback is delta-compacted ON DEVICE against the resident
        previous packed product (_compact_changed): the host reads one
        scalar + the changed rows, applies them in place
        (assemble_result delta mode) and reports the moved names from
        that same diff — no full-product transfer, no host digest
        copy+diff, no RouteSweepResult re-assembly."""
        self._apply_patch_resident(ctx, ov_new)
        dr, digests, packed = self._full_resident(self.graph)
        # counted apart from incremental_events: the four event
        # classes (bucketed incremental / frontier re-solve /
        # full-width refresh / cold rebuild) stay disjoint in
        # artifacts
        self.full_refreshes += 1
        get_registry().counter_bump("route_engine.full_refreshes")
        get_flight_recorder().note("engine", path="full_refresh")
        return self._commit_full_width(
            ls, dr, digests, packed, new_out, ov_flips, defer=defer
        )

    @committed_dispatch
    def _commit_full_width(self, ls, dr, digests, packed, new_out,
                           ov_flips, defer=False):
        """Shared commit tail of the full-width refresh and the
        frontier re-solve: both produce a complete (dr, digests,
        packed) product in one wide dispatch, compact the diff on
        device, and apply only the changed rows on host. With
        ``defer=True`` the changed count stays an in-flight device
        scalar riding the async lane (PendingDelta full-width mode):
        the overflow rungs then also submit-and-walk-away, keeping the
        committed two-touch event window."""
        ch_count, comp = aot_call(
            "compact_changed", _compact_changed,
            (packed, self._packed_dev),
            dict(n=self.graph.n),
        )
        self._dr = dr
        self._digests_dev = digests
        self._packed_dev = packed
        self._commit_host_mirrors(ls, new_out, ov_flips)
        self.version = ls.topology_version
        self.aversion = ls.attributes_version
        # remember that events are running wide: start the next probe
        # at the top bucket (one dispatch) instead of re-climbing the
        # ladder; small events decay the hint back down as usual
        self._k_hint = _ROW_BUCKETS[-1]
        if defer:
            pending = PendingDelta(
                self, [comp], [-1], [None], int(comp.shape[0]),
                fw_count=ch_count,
            )
            self._pending = pending
            return pending
        da.kick_async(ch_count)
        m = int(da.reap_read(ch_count, kicked=True))
        names: List[str] = []
        # openr-lint: disable=host-branch-in-chain -- post-reap delta apply: the window already closed; the count only sizes the host mirror copy (audited)
        if m:
            names = self._apply_delta_rows(
                da.reap_read(_rows_slice(comp, 0, m))
            )
        bytes_read = m * comp.shape[1] * 4 + 4  # rows + the scalar
        self.last_delta_rows = m
        self.last_readback_bytes = bytes_read
        self.last_overlap_ms = 0.0
        reg = get_registry()
        reg.observe("ops.delta_rows", float(m))
        reg.observe("ops.readback_bytes", float(bytes_read))
        return sorted(names)

    @solve_window
    def _dispatch_frontier_probe(self, ctx, e_dev, limit):
        """Backend hook: dispatch the affected-cone probe
        (rs._cone_expand) against the PRE-patch resident tensors.
        Returns ``(cone, meta)`` — both in-flight device arrays, meta
        being the float32 row ``[rows, cells, jumps, converged]`` —
        or None when the backend has no frontier kernel (the caller
        then rides the full-width refresh).

        Ordering contract: this MUST run before _apply_patch_resident
        commits the event's band patch — the cone is the
        tight-closure under the OLD weights, so the resident
        v_t/w_t/_dr it reads have to be the pre-event ones (they are:
        bucketed dispatches are functional and nothing commits until
        _commit_device)."""
        e_u_d, e_v_d, e_wo_d, e_wn_d = e_dev
        lim = jnp.asarray([limit], dtype=jnp.float32)
        if self.plan is not None:
            lim = self.plan.replicate(lim)
        if self.mesh is None:
            # openr-lint: disable=sharding-spec -- single-chip frontier
            # probe (mesh is None): no mesh axis to spec
            return aot_call(
                "ell_frontier_probe", _frontier_probe,
                (
                    self.sweeper.v_t, self.sweeper.w_t, self._dr,
                    e_u_d, e_v_d, e_wo_d, e_wn_d, lim,
                ),
                dict(
                    bands=self.graph.bands, n=self.graph.n_pad,
                    max_jumps=_FRONTIER_MAX_JUMPS,
                ),
            )
        return aot_call(
            "ell_frontier_probe_sharded", _sharded_frontier_probe,
            (
                self.sweeper.v_t, self.sweeper.w_t, self._dr,
                e_u_d, e_v_d, e_wo_d, e_wn_d, lim,
            ),
            dict(
                bands=self.graph.bands, n=self.graph.n_pad,
                max_jumps=_FRONTIER_MAX_JUMPS, mesh=self.mesh,
            ),
        )

    @solve_window
    def _frontier_resident(self, cone):
        """Backend hook: the masked full-width dispatch — every row
        launches, but only cone cells re-relax from INF; all other
        cells keep their resident distances, which stay valid upper
        bounds (every cell whose old tight path crossed an increased
        edge is in the cone), so the fixed point converges in
        O(cone diameter) sweeps instead of O(graph diameter). Expects
        the band patch ALREADY adopted (_apply_patch_resident ran)."""
        if self.mesh is None:
            # openr-lint: disable=sharding-spec -- single-chip frontier
            # re-solve (mesh is None): no mesh axis to spec
            return aot_call(
                "ell_frontier_step", _frontier_step,
                (
                    self.sweeper.v_t, self.sweeper.w_t, cone, self._dr,
                    self.sweeper.overloaded,
                    self.sweeper._samp_ids_dev,
                    self.sweeper._samp_v_dev,
                    self.sweeper._samp_w_dev, self.sweeper._pos_w_dev,
                ),
                dict(bands=self.graph.bands, n=self.graph.n_pad),
            )
        return aot_call(
            "ell_frontier_step_sharded", _sharded_frontier_step,
            (
                self.sweeper.v_t, self.sweeper.w_t, cone, self._dr,
                self.sweeper.overloaded,
                self.sweeper._samp_ids_dev, self.sweeper._samp_v_dev,
                self.sweeper._samp_w_dev, self.sweeper._pos_w_dev,
            ),
            dict(
                bands=self.graph.bands, n=self.graph.n_pad,
                mesh=self.mesh,
            ),
        )

    @solve_window
    def _dispatch_overflow_chain(self, ctx, e_dev, ov_new, limit):
        """Backend hook: the FUSED overflow decision chain — probe,
        on-device frontier-vs-full-width seed select, warm re-solve,
        extraction and delta compaction in one dispatch
        (_overflow_chain). Returns the chain product tuple
        ``(dr, digests, packed, ch_count, comp, meta)`` with meta an
        in-flight device row, or None when the event WIDENED the band
        layout (static shapes changed under the resident tensors —
        the split probe/branch path owns that recompile)."""
        if ctx["patched"].bands != self.graph.bands:
            return None
        if ctx["patched_bands"] is None:
            ctx["patched_bands"] = self._dispatch_patch(ctx)
        new_v, new_w = ctx["patched_bands"]
        e_u_d, e_v_d, e_wo_d, e_wn_d = e_dev
        lim = jnp.asarray([limit], dtype=jnp.float32)
        if self.plan is not None:
            lim = self.plan.replicate(lim)
        if self.mesh is None:
            # openr-lint: disable=sharding-spec -- single-chip fused
            # overflow chain (mesh is None): no mesh axis to spec
            return aot_call(
                "ell_overflow_chain", _overflow_chain,
                (
                    self.sweeper.v_t, self.sweeper.w_t, new_v, new_w,
                    self._dr, self._packed_dev,
                    e_u_d, e_v_d, e_wo_d, e_wn_d, lim, ov_new,
                    self.sweeper._samp_ids_dev,
                    self.sweeper._samp_v_dev,
                    self.sweeper._samp_w_dev, self.sweeper._pos_w_dev,
                ),
                dict(
                    bands=self.graph.bands, n=self.graph.n_pad,
                    n_real=self.graph.n, max_jumps=_FRONTIER_MAX_JUMPS,
                ),
            )
        return aot_call(
            "ell_overflow_chain_sharded", _sharded_overflow_chain,
            (
                self.sweeper.v_t, self.sweeper.w_t, new_v, new_w,
                self._dr, self._packed_dev,
                e_u_d, e_v_d, e_wo_d, e_wn_d, lim, ov_new,
                self.sweeper._samp_ids_dev, self.sweeper._samp_v_dev,
                self.sweeper._samp_w_dev, self.sweeper._pos_w_dev,
            ),
            dict(
                bands=self.graph.bands, n=self.graph.n_pad,
                n_real=self.graph.n, max_jumps=_FRONTIER_MAX_JUMPS,
                mesh=self.mesh,
            ),
        )

    def _note_overflow_meta(self, meta, limit) -> str:
        """Post-hoc policy classification of a fused overflow chain's
        reaped probe meta: the SAME float32 compare the device seed
        select made, so the frontier/full-width counters match the
        branch the chain actually took. Mirrors the split path's
        counter/flight bookkeeping exactly (both counters bump on a
        fallback: it IS a full refresh)."""
        reg = get_registry()
        rows, jumps = int(meta[0]), int(meta[2])
        cells = float(meta[1])
        converged = bool(meta[3])
        self.last_frontier_rows = rows
        self.last_frontier_jumps = jumps
        self.last_frontier_cells = cells
        reg.observe("ops.frontier_rows", float(rows))
        reg.observe("ops.frontier_cells", cells)
        reg.observe("ops.frontier_jumps", float(jumps))
        if converged and np.float32(cells) <= np.float32(limit):
            self.frontier_resolves += 1
            reg.counter_bump("route_engine.frontier_resolves")
            get_flight_recorder().note(
                "engine", path="frontier_resolve"
            )
            return "frontier"
        self.frontier_fallbacks += 1
        reg.counter_bump("ops.frontier_fallbacks")
        get_flight_recorder().note(
            "engine", path="frontier_fallback", rows=rows, jumps=jumps
        )
        self.full_refreshes += 1
        reg.counter_bump("route_engine.full_refreshes")
        get_flight_recorder().note("engine", path="full_refresh")
        return "full_width"

    def _commit_overflow_chain(self, ls, chain, ctx, ov_new, new_out,
                               ov_flips, limit, defer=False):
        """Commit tail of the fused overflow chain: adopt the patch +
        chain product, then reap (or defer) the compacted delta AND
        the policy meta in one read phase — the counters classify
        post-hoc from the same meta the device branched on."""
        dr, digests, packed, ch_count, comp, meta_dev = chain
        # the chain read the pre-patch residents; adopt the patched
        # tensors now (patched_bands already dispatched, no extra
        # program launch)
        self._apply_patch_resident(ctx, ov_new)
        self._dr = dr
        self._digests_dev = digests
        self._packed_dev = packed
        self._commit_host_mirrors(ls, new_out, ov_flips)
        self.version = ls.topology_version
        self.aversion = ls.attributes_version
        self._k_hint = _ROW_BUCKETS[-1]
        if defer:
            pending = PendingDelta(
                self, [comp], [-1], [None], int(comp.shape[0]),
                fw_count=ch_count, meta_dev=meta_dev,
                meta_limit=limit,
            )
            self._pending = pending
            return pending
        da.kick_async(ch_count)
        da.kick_async(meta_dev)
        self._note_overflow_meta(
            da.reap_read(meta_dev, kicked=True), limit
        )
        m = int(da.reap_read(ch_count, kicked=True))
        names: List[str] = []
        if m:
            names = self._apply_delta_rows(
                da.reap_read(_rows_slice(comp, 0, m))
            )
        bytes_read = m * comp.shape[1] * 4 + 4
        self.last_delta_rows = m
        self.last_readback_bytes = bytes_read
        self.last_overlap_ms = 0.0
        reg = get_registry()
        reg.observe("ops.delta_rows", float(m))
        reg.observe("ops.readback_bytes", float(bytes_read))
        return sorted(names)

    @committed_dispatch
    def _overflow_refresh(self, ls, ctx, ov_new, new_out, ov_flips,
                          e_dev, defer=False):
        """Overflow policy: the affected-row count exceeded every
        solve bucket. The warm path is the FUSED chain
        (_dispatch_overflow_chain): probe + frontier-vs-full-width
        decision + re-solve + compaction in one dispatch, the branch
        taken ON DEVICE — no 16-byte meta readback between the probe
        and the re-solve, so a pipelined burst's dispatch chain never
        breaks here. When the event widened the band layout the split
        probe/branch path runs instead (the widening recompile
        dominates; one policy readback is noise there). Either way the
        readback stays delta-compacted (O(changed)).

        A chain/probe failure degrades WITHIN the warm rung: the
        full-width refresh is this path's own fallback, so the
        supervisor ladder (warm -> cold -> host) never sees a frontier
        error."""
        reg = get_registry()
        tracer = get_tracer()
        span = tracer.span_active("ops.frontier_resolve")
        rows = jumps = -1
        path = "full_width"
        try:
            # budget in CELLS (re-solve work), not rows-with-any-cell:
            # a single link down seeds one cell in nearly every
            # destination row, so a row count saturates at n while the
            # actual cone stays a sliver of the [n, n] product
            limit = self.frontier_threshold * float(self.graph.n) ** 2
            chain = None
            widened = False
            try:
                fault_point(FAULT_FRONTIER)
                chain = self._dispatch_overflow_chain(
                    ctx, e_dev, ov_new, limit
                )
                widened = chain is None
            except Exception:
                # degrade, don't propagate: full-width gives the same
                # bit-identical answer, just slower (counted so a
                # frontier-fallback storm is visible in telemetry)
                reg.counter_bump("route_engine.frontier_errors")
            if chain is not None:
                path = "fused_chain"
                got = self._commit_overflow_chain(
                    ls, chain, ctx, ov_new, new_out, ov_flips, limit,
                    defer=defer,
                )
                rows = self.last_frontier_rows
                jumps = self.last_frontier_jumps
                return got
            if widened:
                # split path (band widening recompiles anyway): probe,
                # then one async-lane policy readback + host branch
                probe = None
                try:
                    probe = self._dispatch_frontier_probe(
                        ctx, e_dev, limit
                    )
                except Exception:
                    reg.counter_bump("route_engine.frontier_errors")
                if probe is not None:
                    cone, meta = probe
                    # 16-byte policy readback: kicked onto the async
                    # lane so the decision read folds into the
                    # window's single read phase instead of a
                    # dedicated blocking sync
                    da.kick_async(meta)
                    meta = da.reap_read(meta, kicked=True)
                    rows, jumps = int(meta[0]), int(meta[2])
                    cells = float(meta[1])
                    converged = bool(meta[3])
                    self.last_frontier_rows = rows
                    self.last_frontier_jumps = jumps
                    self.last_frontier_cells = cells
                    reg.observe("ops.frontier_rows", float(rows))
                    reg.observe("ops.frontier_cells", cells)
                    reg.observe("ops.frontier_jumps", float(jumps))
                    # openr-lint: disable=host-branch-in-chain -- widened-layout split path: the band reshape recompiles the chain anyway, so the one policy branch stays host-side (audited)
                    if converged and cells <= limit:
                        path = "frontier"
                        return self._frontier_refresh(
                            ls, ctx, ov_new, new_out, ov_flips, cone,
                            defer=defer,
                        )
            self.frontier_fallbacks += 1
            reg.counter_bump("ops.frontier_fallbacks")
            get_flight_recorder().note(
                "engine", path="frontier_fallback", rows=rows, jumps=jumps
            )
            return self._full_refresh(
                ls, ctx, ov_new, new_out, ov_flips, defer=defer
            )
        finally:
            tracer.end_span_active(
                span, path=path, frontier_rows=rows,
                frontier_jumps=jumps,
            )

    def _frontier_refresh(self, ls, ctx, ov_new, new_out, ov_flips,
                          cone, defer=False):
        """Frontier path: adopt the band patch resident, then one
        masked dispatch seeds cone cells at INF while every other cell
        keeps its resident distance. Bit-identical to the cold solve
        by the unique-fixed-point argument (int32 min-plus over the
        patched weights has one fixed point, and any seed S with
        d* <= S converges to it); commits through the same
        delta-compacted tail as _full_refresh."""
        self._apply_patch_resident(ctx, ov_new)
        dr, digests, packed = self._frontier_resident(cone)
        self.frontier_resolves += 1
        get_registry().counter_bump("route_engine.frontier_resolves")
        get_flight_recorder().note("engine", path="frontier_resolve")
        return self._commit_full_width(
            ls, dr, digests, packed, new_out, ov_flips, defer=defer
        )

    def flush(self):
        """Consume the in-flight delta, if any (host-side apply of the
        pending event's changed rows into self.result). Returns the
        consumed PendingDelta or None."""
        return self._consume_pending(overlap=False)

    def _apply_delta_rows(self, rows: np.ndarray) -> List[str]:
        """Apply one compacted [m, 1+W] readback ([dest id, product]
        per row) into the resident host result, returning the touched
        destination names. O(m) — the host never walks all rows."""
        rows = rows[rows[:, 0] < self.graph.n]
        if not len(rows):
            return []
        # settle the packed mirror on success, same rows: after every
        # consume the mirror matches the resident product bit-for-bit
        # on real rows (the tier-2 digest invariant)
        if self._packed_host is not None:
            self._packed_host[rows[:, 0]] = rows[:, 1:]
        rs.assemble_result(self.sweeper, rows, into=self.result)
        names = self.graph.node_names
        return [names[int(t)] for t in rows[:, 0]]

    @committed_dispatch
    def _consume_pending(self, overlap: bool):
        """Drain the pending delta: read each shard's changed rows
        (O(changed) transfer) and apply them in place. When ``overlap``
        is True this runs INSIDE the next event's dispatch window —
        the host-side apply and the device solve proceed concurrently
        (the double-buffer payoff, recorded as
        ops.route_engine.overlap_ms). This is the window's REAP side:
        every read rides a copy kicked async at PendingDelta creation,
        so the host normally finds the bytes already landed."""
        p = self._pending
        if p is None:
            return None
        self._pending = None
        # a consume failure drops this delta un-applied; every deeper
        # ladder rung reassembles the whole result, so the staleness
        # cannot outlive the walk
        fault_point(FAULT_CONSUME)
        fault_point(FAULT_DEVICE_LOST)
        if overlap:
            # window N's staged reap drains inside window N+1's span:
            # the double-buffer overlap, witnessed for the per-drain
            # accounting
            da.note_overlapped_reap()
        if p.meta_dev is not None:
            # fused-overflow-chain pending: settle the policy
            # classification (frontier vs full-width counters) from
            # the meta row that rode the async lane since commit
            self._note_overflow_meta(
                da.reap_read(p.meta_dev, kicked=True), p.meta_limit
            )
            p.meta_dev = None
        tracer = get_tracer()
        span = tracer.span_active("ops.route_engine.delta_consume")
        reg = get_registry()
        sharded = self.mesh is not None
        t0 = time.perf_counter()
        names: List[str] = []
        total_rows = 0
        total_bytes = 0
        for seg, sl, m in zip(p.segs, p.dslices, p.ch_counts):
            t_sh = time.perf_counter()
            # openr-lint: disable=host-branch-in-chain -- pending-delta consume IS the drain point: every branch here runs after the overlapped reap lands (audited)
            if m is None:
                # FULL-WIDTH pending: the changed count rode the async
                # lane since the overflow commit; reap it, then pull
                # exactly the changed rows (compacted from ROW 0 — a
                # _compact_changed segment carries no meta row)
                m = int(da.reap_read(p.fw_count, kicked=True))
                shard_bytes = 4
                # openr-lint: disable=host-branch-in-chain -- post-reap apply: the count only sizes the row pull (audited)
                if m:
                    names.extend(self._apply_delta_rows(
                        da.reap_read(_rows_slice(seg, 0, m))
                    ))
                    total_rows += m
                    shard_bytes += m * seg.shape[1] * 4
                total_bytes += shard_bytes
                continue
            # meta row already crossed (retry ladder); count it
            shard_bytes = seg.shape[1] * 4
            # openr-lint: disable=host-branch-in-chain -- post-reap apply: the count only sizes the row pull (audited)
            if m:
                # the per-shard copy was kicked async at PendingDelta
                # creation: the reap normally finds the host value
                # already landed (explicit, guard-exempt)
                rows = (
                    da.reap_read(sl, kicked=True)
                    if isinstance(sl, jax.Array) else np.asarray(sl)
                )
                names.extend(self._apply_delta_rows(rows))
                total_rows += m
                shard_bytes += m * seg.shape[1] * 4
            total_bytes += shard_bytes
            if sharded:
                reg.counter_bump(
                    "ops.shard_readback_bytes", shard_bytes
                )
                if overlap:
                    reg.observe(
                        "ops.shard_consume_overlap_ms",
                        (time.perf_counter() - t_sh) * 1000.0,
                    )
        ms = (time.perf_counter() - t0) * 1000.0
        p.names = sorted(set(names))
        p.consumed = True
        p.delta_rows = total_rows
        p.readback_bytes = total_bytes
        p.overlap_ms = ms if overlap else 0.0
        self.last_delta_rows = total_rows
        self.last_readback_bytes = total_bytes
        self.last_overlap_ms = p.overlap_ms
        reg.observe("ops.delta_rows", float(total_rows))
        reg.observe("ops.readback_bytes", float(total_bytes))
        if overlap:
            reg.observe("ops.route_engine.overlap_ms", ms)
        tracer.end_span_active(
            span, overlap=overlap, delta_rows=total_rows,
            readback_bytes=total_bytes,
        )
        return p

    def churn_coalesced(self, ls, affected_sets, defer_consume=False):
        """Fold N patches that landed inside one debounce window into
        ONE fused dispatch + ONE compacted readback. Exactly
        equivalent to N sequential churn() calls by construction: the
        event diff compares the CURRENT LinkState against the resident
        raw-weight mirrors, so the union affected set describes the
        net effect and intermediate states are never observed."""
        union: Set[str] = set()
        for s in affected_sets:
            union |= set(s)
        if len(affected_sets) > 1:
            self.coalesced_events += 1
            get_registry().counter_bump(
                "route_engine.coalesced_events"
            )
        return self.churn(ls, union, defer_consume=defer_consume)

    def churn_window(self, ls, affected_sets, defer_consume=False):
        """Committed-dispatch entry point for one debounce window: N
        debounced events become ONE device program under ONE
        accounting window (``ops.host_touches.churn_window``). The
        batched result is bit-identical to N sequential ``churn()``
        calls — same union-diff argument as ``churn_coalesced`` — but
        the host only touches the device twice: once to submit the
        fused dispatch chain, once to reap the compacted delta.

        When a staged speculation (speculate_churn) matches this
        window's final composition — same union, same LinkState
        versions, residents untouched since staging — the window
        ADOPTS the already-dispatched solve (ops.spec_hits) and only
        pays the commit + reap; any mismatch cancels the speculation
        (ops.spec_cancels, never silent) and the committed path below
        re-dispatches from the unchanged residents, so the result is
        bit-identical to the sequential oracle either way."""
        union: Set[str] = set()
        for s in affected_sets:
            union |= set(s)
        spec = self._speculation
        self._speculation = None
        if spec is not None:
            if (
                spec.union == frozenset(union)
                and spec.version == ls.topology_version
                and spec.aversion == ls.attributes_version
                and spec.dr_ref is self._dr
                and self._device_valid
            ):
                return self._adopt_speculation(
                    ls, spec, affected_sets, defer_consume
                )
            get_registry().counter_bump("ops.spec_cancels")
            get_flight_recorder().note("engine", path="spec_cancel")
        with da.event_window("churn_window"):
            return self.churn_coalesced(
                ls, affected_sets, defer_consume=defer_consume
            )

    def speculate_churn(self, ls, affected_sets) -> bool:
        """Stage a SPECULATIVE dispatch of the debounce backlog's
        most-likely final composition (latest-wins: the coalesced
        union as of now) before the window closes — the device solves
        while the host is otherwise idling out the debounce timer. The
        dispatch is purely functional (residents never donated), so a
        wrong guess costs nothing but the wasted device cycles:
        churn_window cancels it and re-dispatches committed.

        Counted, never silent: ops.spec_dispatches on staging,
        ops.spec_skips when a composition refuses speculation (sample
        -band mutation, layout widening, bucket overflow — the paths
        whose side effects are not cancellable or whose committed
        replay differs), ops.spec_cancels on an abandoned or
        mismatched attempt. Returns True when a speculation is
        staged."""
        reg = get_registry()
        union: Set[str] = set()
        for s in affected_sets:
            union |= set(s)
        self._speculation = None
        if not union or not self._device_valid:
            reg.counter_bump("ops.spec_skips")
            return False
        if union & set(self.sample_names):
            # _refresh_sample_bands mutates the sweeper slot tables
            # EARLY (before dispatch) — not cancellable, so a window
            # touching a sample node's adjacencies never speculates
            reg.counter_bump("ops.spec_skips")
            return False
        try:
            ctx = self._prepare_patch(ls, sorted(union))
            if ctx is None or self._layout_changed(ctx):
                # layout break: the committed path cold-rebuilds (or
                # recompiles the widened shapes) — nothing to adopt
                reg.counter_bump("ops.spec_skips")
                return False
            _raw, new_out, ov_flips, changed = self._event_diff(
                ls, union, self.graph
            )
            if not changed:
                # attribute-only backlog: nothing route-affecting
                reg.counter_bump("ops.spec_skips")
                return False
            structural = any(
                wo >= INF or wn >= INF
                for (wo, wn) in changed.values()
            )
            ov_new, e_dev = self._upload_event(
                ctx["patched"], changed
            )
            k = next(b for b in _ROW_BUCKETS if b >= self._k_hint)
            if self._pending is not None:
                # the staged dispatch submits while the previous
                # window's reap is still in flight: depth-2 pipelining
                da.note_pipelined_dispatch(2)
            segments, commit_state = self._run_bucket(
                ctx, k, e_dev, ov_new
            )
            meta_rows = [
                _seg_meta(seg) if isinstance(seg, jax.Array)
                else seg[0, :2]
                for seg in segments
            ]
            n_meta = sum(
                1 for seg in segments if isinstance(seg, jax.Array)
            )
            if n_meta:
                da.count_dispatch(n_meta)
            for mrow in meta_rows:
                da.kick_async(mrow)
            metas = [
                da.reap_read(mrow, kicked=True)
                if isinstance(mrow, jax.Array) else mrow
                for mrow in meta_rows
            ]
            counts = [int(m[0]) for m in metas]
            ch_counts = [int(m[1]) for m in metas]
            if max(counts) > k:
                # overflow composition: the committed path walks the
                # bucket ladder / overflow policy — adopting a partial
                # bucket is never profitable
                reg.counter_bump("ops.spec_skips")
                return False
        except Exception:
            # speculation runs OUTSIDE the supervisor ladder: any
            # failure (chaos seam included) abandons the attempt and
            # the committed path re-dispatches from the unchanged
            # residents — a fault mid-speculation degrades within the
            # ladder at commit time, never up it
            reg.counter_bump("ops.spec_cancels")
            get_flight_recorder().note("engine", path="spec_abandon")
            return False
        spec = _Speculation()
        spec.union = frozenset(union)
        spec.version = ls.topology_version
        spec.aversion = ls.attributes_version
        spec.dr_ref = self._dr
        spec.ctx = ctx
        spec.segments = segments
        spec.counts = counts
        spec.ch_counts = ch_counts
        spec.commit_state = commit_state
        spec.ov_new = ov_new
        spec.k = k
        spec.new_out = new_out
        spec.ov_flips = ov_flips
        spec.structural = structural
        self._speculation = spec
        reg.counter_bump("ops.spec_dispatches")
        return True

    def _adopt_speculation(self, ls, spec, affected_sets,
                           defer_consume):
        """Commit a matched speculation as the window's result: the
        solve already ran, so the window is commit + reap only. The
        counter bookkeeping mirrors _churn_device exactly — an adopted
        window is indistinguishable from a committed one in the
        artifacts except for ops.spec_hits."""
        reg = get_registry()
        reg.counter_bump("ops.spec_hits")
        get_flight_recorder().note("engine", path="spec_hit")
        with da.event_window("churn_window"):
            if len(affected_sets) > 1:
                self.coalesced_events += 1
                reg.counter_bump("route_engine.coalesced_events")
            if spec.structural:
                self.structural_events += 1
                reg.counter_bump("route_engine.structural_events")
            # the previous window's delta (if any) drains here, inside
            # the adopted window — same overlap as _churn_device
            self._consume_pending(overlap=True)
            self._commit_device(spec.ctx, spec.commit_state,
                                spec.ov_new)
            self._commit_host_mirrors(ls, spec.new_out, spec.ov_flips)
            self.version = ls.topology_version
            self.aversion = ls.attributes_version
            self.incremental_events += 1
            reg.counter_bump("route_engine.incremental_events")
            self._k_hint = max(
                _ROW_BUCKETS[0], min(1024, 2 * max(spec.counts))
            )
            pending = PendingDelta(
                self, spec.segments, spec.counts, spec.ch_counts,
                spec.k,
            )
            self._pending = pending
            if defer_consume:
                return pending
            self._consume_pending(overlap=False)
            return pending.names

    def churn_burst(self, ls, apply_events, defer_consume=False):
        """Pipelined multi-event burst: every window's committed
        dispatch submits back to back — window N+1's solve is on the
        stream before window N's reap lands — then ALL reaps settle in
        one read run, so the whole burst costs ~2 host touches
        (ops.touches_per_drain) instead of 2 per window.

        ``apply_events`` is a list of callables; each mutates the
        LinkState and returns its affected-node set (the latest-wins
        delivery shape the debounce terminal hands the engine).
        Bit-identical to applying the events sequentially: each
        window's dispatch reads the previous window's COMMITTED device
        state (functional dispatches, residents never donated), and
        any hazard — bucket overflow, layout widening, sample-band
        mutation, a chaos-seam fault — cancels the burst back to a
        pre-burst snapshot and replays the whole thing as ONE
        coalesced committed window (ops.burst_cancels; the union-diff
        argument makes the replay equal the sequential chain).
        Returns the sorted union of moved destination names, or the
        LAST window's PendingDelta under ``defer_consume=True``."""
        if not apply_events:
            return []
        if not self._device_valid:
            # degraded: no residents to pipeline against — fold the
            # burst into one supervised window
            sets = [set(ev()) for ev in apply_events]
            return self.churn_window(
                ls, sets, defer_consume=defer_consume
            )
        with da.pipeline_drain("churn_burst"):
            return self._churn_burst_drain(
                ls, apply_events, defer_consume
            )

    def _burst_snapshot(self):
        """Pre-burst restore point: device refs (functional dispatches
        never donate them) + deep copies of the host mirrors the
        optimistic per-window commits mutate."""
        return dict(
            dr=self._dr, dig=self._digests_dev,
            packed=self._packed_dev,
            v_t=self.sweeper.v_t, w_t=self.sweeper.w_t,
            ov=self.sweeper.overloaded, graph=self.graph,
            w_out={u: dict(d) for u, d in self._w_out.items()},
            w_in={u: dict(d) for u, d in self._w_in.items()},
            ov_host=dict(self._ov_host),
            version=self.version, aversion=self.aversion,
            k_hint=self._k_hint,
        )

    def _burst_rollback(self, snap) -> None:
        self._dr = snap["dr"]
        self._digests_dev = snap["dig"]
        self._packed_dev = snap["packed"]
        self.sweeper.v_t = snap["v_t"]
        self.sweeper.w_t = snap["w_t"]
        self.sweeper.overloaded = snap["ov"]
        self.graph = self.sweeper.graph = snap["graph"]
        self._w_out = snap["w_out"]
        self._w_in = snap["w_in"]
        self._ov_host = snap["ov_host"]
        self.version = snap["version"]
        self.aversion = snap["aversion"]
        self._k_hint = snap["k_hint"]

    def _churn_burst_drain(self, ls, apply_events, defer_consume):
        """The drain body: submit phase pipelines every window's
        dispatch at ONE fixed bucket (climbing the ladder mid-burst
        would interleave a meta reap between submits and break the
        S...S,R...R phase shape), optimistically committing device
        state + host mirrors per window; the settle phase reaps every
        meta and every delta in one read run. Any overflow or
        pre-dispatch hazard rolls back to the snapshot and replays the
        burst as one coalesced supervised window."""
        reg = get_registry()
        self._speculation = None
        snap = self._burst_snapshot()
        union: Set[str] = set()
        # fixed bucket for the whole burst: first ladder rung >= hint
        k = next(b for b in _ROW_BUCKETS if b >= self._k_hint)
        staged: List[dict] = []
        cancel = False
        idx = 0
        try:
            while idx < len(apply_events):
                ev = apply_events[idx]
                idx += 1
                aff = set(ev())
                union |= aff
                if not aff:
                    continue
                if aff & set(self.sample_names):
                    cancel = True
                    break
                ctx = self._prepare_patch(ls, sorted(aff))
                if ctx is None or self._layout_changed(ctx):
                    cancel = True
                    break
                _raw, new_out, ov_flips, changed = self._event_diff(
                    ls, aff, self.graph
                )
                if not changed:
                    self.version = ls.topology_version
                    self.aversion = ls.attributes_version
                    continue
                structural = any(
                    wo >= INF or wn >= INF
                    for (wo, wn) in changed.values()
                )
                ov_new, e_dev = self._upload_event(
                    ctx["patched"], changed
                )
                if staged or self._pending is not None:
                    da.note_pipelined_dispatch(len(staged) + 1)
                segments, commit_state = self._run_bucket(
                    ctx, k, e_dev, ov_new
                )
                meta_rows = [
                    _seg_meta(seg) if isinstance(seg, jax.Array)
                    else seg[0, :2]
                    for seg in segments
                ]
                n_meta = sum(
                    1 for seg in segments
                    if isinstance(seg, jax.Array)
                )
                if n_meta:
                    da.count_dispatch(n_meta)
                for mrow in meta_rows:
                    da.kick_async(mrow)
                if not staged:
                    # first window drains any pre-burst delta while
                    # the burst solves (the double-buffer overlap)
                    self._consume_pending(overlap=True)
                # optimistic adoption: window N+1's dispatch must read
                # window N's committed state to equal the sequential
                # chain; the snapshot guards the whole prefix
                self._commit_device(ctx, commit_state, ov_new)
                self._commit_host_mirrors(ls, new_out, ov_flips)
                self.version = ls.topology_version
                self.aversion = ls.attributes_version
                staged.append(dict(
                    segments=segments, meta_rows=meta_rows,
                    structural=structural,
                ))
                da.note_window()
        except Exception:
            # chaos seam / dispatch failure mid-burst: degrade WITHIN
            # the ladder — roll back and let the supervised replay
            # walk warm -> cold -> host as usual, never up it
            cancel = True
        if not cancel and staged:
            # settle: one read run over every window's meta
            all_counts: List[List[int]] = []
            all_ch: List[List[int]] = []
            for st in staged:
                metas = [
                    da.reap_read(mrow, kicked=True)
                    if isinstance(mrow, jax.Array) else mrow
                    for mrow in st["meta_rows"]
                ]
                all_counts.append([int(m[0]) for m in metas])
                all_ch.append([int(m[1]) for m in metas])
            if max(max(c) for c in all_counts) > k:
                cancel = True
        if cancel:
            # one cancel path for every hazard: finish delivering the
            # remaining LinkState mutations, restore the pre-burst
            # state, and replay the net effect as ONE supervised
            # coalesced window (union-diff => bit-identical)
            while idx < len(apply_events):
                union |= set(apply_events[idx]())
                idx += 1
            self._burst_rollback(snap)
            reg.counter_bump("ops.burst_cancels")
            get_flight_recorder().note(
                "engine", path="burst_cancel",
                windows=len(apply_events),
            )
            if len(apply_events) > 1:
                self.coalesced_events += 1
                reg.counter_bump("route_engine.coalesced_events")
            return self.churn(
                ls, union, defer_consume=defer_consume
            )
        if not staged:
            # attribute-only burst
            if not defer_consume:
                self.flush()
            return []
        self._k_hint = max(
            _ROW_BUCKETS[0],
            min(1024, 2 * max(max(c) for c in all_counts)),
        )
        names: List[str] = []
        last = len(staged) - 1
        result = None
        for i, st in enumerate(staged):
            self.incremental_events += 1
            reg.counter_bump("route_engine.incremental_events")
            if st["structural"]:
                self.structural_events += 1
                reg.counter_bump("route_engine.structural_events")
            pending = PendingDelta(
                self, st["segments"], all_counts[i], all_ch[i], k
            )
            self._pending = pending
            if defer_consume and i == last:
                result = pending
                break
            self._consume_pending(overlap=False)
            names.extend(pending.names)
        if result is not None:
            return result
        return sorted(set(names))

    def churn(self, ls, affected_nodes: Set[str],
              defer_consume: bool = False):
        """Apply one churn event, SUPERVISED: the degradation ladder
        walks warm incremental re-solve → device-loss recovery → drain
        + cold device rebuild → host NumPy fallback, each rung
        producing a bit-identical route product, until one succeeds
        (LadderExhausted if none does). Returns the warm path's
        affected destination NAMES / PendingDelta
        (``defer_consume=True``), or None from the deeper rungs — the
        pre-existing cold-rebuild contract. The recover rung is inert
        (fails straight through) unless a rung failure was recognized
        as a device loss."""
        # corruption seam (non-raising): disarmed cost is one attribute
        # read inside consume_fault — the sanctioned churn-path budget
        if consume_fault(FAULT_CORRUPT):
            self._corrupt_events += 1
            self.corrupt_resident(self._corrupt_events)
        with da.event_window("churn"):
            return self._churn_supervised(ls, affected_nodes,
                                          defer_consume)

    def _churn_supervised(self, ls, affected_nodes: Set[str],
                          defer_consume: bool = False):
        return self.supervisor.run((
            ("warm", lambda: self._rung_guard(
                self._churn_device, ls, affected_nodes, defer_consume
            )),
            ("recover", lambda: self._rung_guard(
                self._device_recover, ls, affected_nodes, defer_consume
            )),
            ("cold", lambda: self._rung_guard(self._cold_recover, ls)),
            ("host", lambda: self._host_fallback(ls)),
        ))

    def _rung_guard(self, fn, *args):
        """Run one ladder rung, marking the engine device-lost when the
        failure is the accelerator dying (typed DeviceLostError, the
        ``device.lost`` seam, or a device-loss flavored
        XlaRuntimeError) — the marker arms the recover rung. The
        exception still propagates so the supervisor walks the
        ladder."""
        try:
            return fn(*args)
        except Exception as exc:  # noqa: BLE001 - re-raised below
            if is_device_loss(exc):
                self._device_valid = False
                self._device_lost = True
                get_registry().counter_bump("recovery.device_lost")
            raise

    @fault_boundary
    def _cold_recover(self, ls) -> None:
        """Ladder rung 2: drain + cold device rebuild. Layout, host
        mirrors, and residents are all rederived from the LinkState —
        the cold-twin contract of the parity suite makes the result
        bit-identical to the warm path's."""
        self._build(ls)
        return None

    def _make_sweeper(self, graph):
        """Backend hook: a fresh sweeper (device band/sample uploads)
        over an ALREADY-COMPILED host graph — the device-loss recovery
        path, which must not pay the host layout recompile."""
        return rs.RouteSweeper(graph, self.sample_names, plan=self.plan)

    @committed_dispatch
    def _probe_device(self, dev) -> bool:
        """Liveness probe for one mesh device (monkeypatchable: tests
        and the chaos harness simulate partial mesh loss here)."""
        try:
            # openr-lint: disable=committed-dispatch -- liveness probe:
            # the blocking sync IS the signal (recover rung, never on
            # the warm submit/reap path)
            jax.device_put(
                np.zeros((), np.int32), dev
            ).block_until_ready()
            return True
        except Exception:  # noqa: BLE001 - any failure means dead
            return False

    def _surviving_devices(self):
        return [d for d in self.mesh.devices.flat if self._probe_device(d)]

    @fault_boundary
    @requires_drain("_discard_pending")
    def _device_recover(self, ls, affected_nodes: Set[str],
                        defer_consume: bool = False):
        """Ladder rung 1: rebuild the residents on a live device from
        the host mirrors after a device loss. Single-chip (and a mesh
        whose devices all answer the liveness probe): re-land the
        resident sweeper + full product from ``self.graph`` — host
        layout intact, no ``compile_ell``, the dispatch shapes are
        already jitted. A mesh that lost devices SHRINKS to the
        survivors (typed ``recovery.mesh_shrinks`` counter — never
        silent) and cold-builds on the smaller mesh. Either way the
        rung finishes by re-running the warm churn body for the event
        that observed the loss, so the caller sees the ordinary warm
        contract."""
        if not self._device_lost:
            raise _DeviceStateInvalid(
                "no device loss observed (recover rung idle)"
            )
        self._discard_pending()
        reg = get_registry()
        tracer = get_tracer()
        span = tracer.span_active("recovery.device_rebuild")
        self._device_lost = False
        shrunk = False
        if self.mesh is not None:
            survivors = self._surviving_devices()
            if not survivors:
                tracer.end_span_active(span, ok=False)
                raise _DeviceStateInvalid(
                    "device recovery: no surviving devices in mesh"
                )
            if len(survivors) < self.mesh.devices.size:
                shrunk = True
                self.mesh_shrinks += 1
                reg.counter_bump("recovery.mesh_shrinks")
                self.mesh = Mesh(
                    np.asarray(survivors), self.mesh.axis_names
                )
                self.plan = ShardingPlan(self.mesh)
                self._align = self._base_align * self.mesh.devices.size
                reg.counter_set(
                    "recovery.mesh_size", self.mesh.devices.size
                )
        if shrunk:
            # per-shard row blocks changed: the layout must re-align,
            # so this is a true cold build on the surviving mesh
            self._build(ls)
        else:
            self.sweeper = self._make_sweeper(self.graph)
            dr, digests, packed = self._full_resident(self.graph)
            self._dr = dr
            self._digests_dev = digests
            self._packed_dev = packed
            packed_host = jax.device_get(packed)
            self.result = rs.assemble_result(self.sweeper, packed_host)
            self._packed_host = np.array(packed_host)
            self._device_valid = True
        self.device_rebuilds += 1
        reg.counter_bump("recovery.device_rebuilds")
        tracer.end_span_active(span, shrunk=shrunk)
        # the residents now mirror the last COMMITTED event; the event
        # that observed the loss has not landed — run it warm
        return self._churn_device(ls, affected_nodes, defer_consume)

    def _discard_pending(self) -> None:
        """Drop the in-flight delta WITHOUT the host-side apply: the
        host fallback replaces the whole result, so the pending rows
        are subsumed. A caller-held PendingDelta resolves (empty)."""
        p = self._pending
        self._pending = None
        # a staged speculation read residents this fallback bypasses
        self._speculation = None
        if p is not None:
            p.consumed = True
            get_registry().counter_bump("route_engine.deltas_discarded")

    # -- integrity plane (ResidentEngineContract) ---------------------

    audit_kind = "ell"

    def audit_ready(self) -> bool:
        return (
            self._device_valid
            and self._pending is None
            and self._packed_host is not None
        )

    def audit_residual(self) -> int:
        # openr-lint: disable=sharding-spec -- read-only audit probe off the churn path; bare jit stays placement-agnostic across single-chip and mesh engines (see integrity.kernels)
        return int(jax.device_get(integrity_kernels.ell_residual(
            self._dr, self.sweeper.v_t, self.sweeper.w_t,
            self.sweeper.overloaded, self.graph.bands,
        )))

    def audit_digest_pair(self) -> Tuple[int, int]:
        # real rows only: padding destination rows are never
        # delta-read-back, so they stay outside the mirror invariant
        n = self.graph.n
        # openr-lint: disable=sharding-spec -- read-only audit probe off the churn path; bare jit stays placement-agnostic across single-chip and mesh engines (see integrity.kernels)
        probe = integrity_kernels.fnv_device(self._packed_dev[:n])
        dev = int(jax.device_get(probe))
        host = integrity_kernels.fnv_host(self._packed_host[:n])
        return dev, host

    def audit_row_count(self) -> int:
        return self.graph.n

    def audit_sample_rows(self, rows: Sequence[int]) -> int:
        # pad the sample to a fixed pow2 bucket (>= 8) with repeats of
        # the first row — one compiled oracle shape, duplicates just
        # re-check the same row
        ids = list(int(r) for r in rows)
        b = 8
        while b < len(ids):
            b *= 2
        ids = ids + [ids[0]] * (b - len(ids))
        ids_t = jnp.asarray(np.asarray(ids, dtype=np.int32))
        if self.plan is not None:
            ids_t = self.plan.replicate(ids_t)
        return int(jax.device_get(self._sample_oracle(ids_t)))

    def _sample_oracle(self, ids_t):
        """Backend hook: tier-3 cold re-solve of the given rows."""
        # openr-lint: disable=sharding-spec -- read-only audit probe off the churn path; bare jit stays placement-agnostic across single-chip and mesh engines (see integrity.kernels)
        return integrity_kernels.ell_sample_oracle(
            self._dr, ids_t, self.sweeper.v_t, self.sweeper.w_t,
            self.sweeper.overloaded, self.graph.bands,
            self.graph.n_pad,
        )

    def quarantine(self, reason: str) -> None:
        """Poison the warm rung: the next churn's warm walk raises
        ``_DeviceStateInvalid`` and the ladder cold-rebuilds, even if
        ``integrity_heal`` never runs."""
        self._device_valid = False
        get_registry().counter_bump("route_engine.quarantines")

    @fault_boundary
    @requires_drain("_discard_pending")
    def integrity_heal(self) -> bool:
        """Warm heal: re-derive every resident from the resident band
        tensors — the ``_device_recover`` non-shrink body without the
        loss gate: no host layout recompile, no LinkState needed. The
        packed MIRROR is deliberately left untouched: the auditor's
        re-audit digest compares the healed device product against the
        PRE-corruption settle-on-success mirror, so a heal that fails
        to reproduce the exact bits is caught (and the engine stays
        quarantined for the ladder's true cold rebuild). Band-tensor
        corruption is therefore outside this heal's reach by design —
        the re-audit fails and the cold rung re-derives the bands from
        the LinkState."""
        self._discard_pending()
        dr, digests, packed = self._full_resident(self.graph)
        self._dr = dr
        self._digests_dev = digests
        self._packed_dev = packed
        self.result = rs.assemble_result(
            self.sweeper, jax.device_get(packed)
        )
        self._device_valid = True
        get_registry().counter_bump("route_engine.integrity_heals")
        return True

    def corrupt_resident(self, seed: int) -> None:
        """Deterministic ``device.corrupt_resident`` seam: flip one
        seeded bit in the resident packed product (tier-2 detects
        unconditionally — the mirror still holds the true bits) and OR
        one seeded bit into a resident DR cell (a RAISE, which tier 1
        usually catches: an uncorrupted neighbor re-derives the shorter
        true value; see kernels.py for the blind-spot analysis)."""
        rng = random.Random(seed)
        n = self.graph.n
        r = rng.randrange(n)
        c = rng.randrange(int(self._packed_dev.shape[1]))
        bit = jnp.int32(1 << rng.randrange(31))
        self._packed_dev = self._packed_dev.at[r, c].set(
            self._packed_dev[r, c] ^ bit
        )
        r2 = rng.randrange(n)
        c2 = rng.randrange(n)
        bit2 = jnp.int32(1 << rng.randrange(20))
        self._dr = self._dr.at[r2, c2].set(self._dr[r2, c2] | bit2)
        if self.plan is not None:
            # .at[].set may drop the explicit placement: re-pin so the
            # next churn dispatch sees the planned sharding
            self._packed_dev = self.plan.place(
                self._packed_dev, self.plan.rows
            )
            self._dr = self.plan.place(self._dr, self.plan.rows)
        get_registry().counter_bump("integrity.corruptions")

    def snapshot_resident_state(self) -> Optional[Dict[str, Any]]:
        """Warm-start material (versions + host copies of every
        resident) — sufficient for ``rehydrate_resident_state`` to
        re-land the residents bit-identically with zero solves."""
        if not self.audit_ready():
            return None
        return {
            "kind": self.audit_kind,
            "version": self.version,
            "aversion": self.aversion,
            "node_names": tuple(self.graph.node_names),
            "dr": np.array(jax.device_get(self._dr)),
            "digests": np.array(jax.device_get(self._digests_dev)),
            "packed": np.array(self._packed_host),
        }

    @requires_drain("flush")
    def rehydrate_resident_state(self, snap: Any) -> bool:
        """Re-land the residents from a snapshot taken by the SAME
        engine class at the SAME (topology, attributes, name-order)
        state; anything else returns False and the caller stays on its
        cold path."""
        if (
            not isinstance(snap, dict)
            or snap.get("kind") != self.audit_kind
            or snap.get("version") != self.version
            or snap.get("aversion") != self.aversion
            or tuple(snap.get("node_names", ()))
            != tuple(self.graph.node_names)
        ):
            return False
        self.flush()
        up = (
            self.plan.shard_rows if self.plan is not None
            else jnp.asarray
        )
        self._dr = up(snap["dr"])
        self._digests_dev = up(snap["digests"])
        self._packed_dev = up(snap["packed"])
        self.result = rs.assemble_result(
            self.sweeper, np.array(snap["packed"])
        )
        self._packed_host = np.array(snap["packed"])
        self._device_valid = True
        get_registry().counter_bump("route_engine.rehydrates")
        return True

    @fault_boundary
    @requires_drain("_discard_pending")
    def _host_fallback(self, ls) -> None:
        """Ladder rung 2: the device path is down — recompute the whole
        packed product on the host (ops.host_sweep, bit-identical to a
        cold device sweep by the replica contract) and mark the device
        residents invalid so no later warm rung reads them. Self-heals
        once the supervisor's breaker lets a cold rebuild through."""
        self._discard_pending()
        shim, packed = host_sweep.host_route_product(
            ls, self.sample_names, align=self._align
        )
        self.result = rs.assemble_result(shim, packed)
        self._device_valid = False
        # the device residents are stale relative to this host product:
        # drop the mirror so audit_ready gates the audit plane off too
        self._packed_host = None
        self.version = ls.topology_version
        self.aversion = ls.attributes_version
        self.host_fallbacks += 1
        get_registry().counter_bump("route_engine.host_fallbacks")
        return None

    def _event_diff(self, ls, affected_nodes: Set[str], graph):
        """Pure host-side event diff against the resident raw-weight
        mirrors: O(degree) per affected node, no device crossing.
        Returns ``(raw_changed, new_out, ov_flips, changed)`` — shared
        by the committed churn path and the speculative staging path
        (which must observe the SAME diff the committed dispatch
        would)."""
        # RAW weight diff of the affected nodes' out-edges (O(degree)
        # via the origin index + spf_sparse._out_edges, the same
        # collapse logic the compile uses)
        raw_changed: Dict[Tuple[int, int], Tuple[int, int]] = {}
        new_out: Dict[int, Dict[int, int]] = {}
        for nm in affected_nodes:
            u = graph.node_index[nm]
            seen = _out_edges(ls, nm, graph.node_index)
            new_out[u] = seen
            old = self._w_out.get(u, {})
            for v, wo in old.items():
                wn = seen.get(v, INF)
                if wn != wo:
                    raw_changed[(u, v)] = (wo, wn)
            for v, wn in seen.items():
                if v not in old:
                    raw_changed[(u, v)] = (INF, wn)
        # overload flips among the affected nodes (the churn contract:
        # a node whose drain state changed is in affected_nodes)
        ov_flips = {
            nm
            for nm in affected_nodes
            if nm in self._ov_host
            and ls.is_node_overloaded(nm) != self._ov_host[nm]
        }
        # DETECTION transitions: the raw diffs plus effective-weight
        # flips for edges whose usability changed with a node's drain
        # state. These are an event-local list — the raw mirrors above
        # are never polluted by them.
        changed: Dict[Tuple[int, int], Tuple[int, int]] = dict(
            raw_changed
        )
        for nm in ov_flips:
            x = graph.node_index[nm]
            draining = ls.is_node_overloaded(nm)
            # the reverse-relax mask blocks on the forward edge's DST
            # (transit there): flipping x changes the usability of
            # every edge INTO x (O(degree) via the dst index); edges
            # OUT of x are unaffected (origination is always allowed)
            for u, wo in self._w_in.get(x, {}).items():
                wn = new_out.get(u, self._w_out.get(u, {})).get(
                    x, wo
                )
                if draining:
                    changed[(u, x)] = (wo, INF)  # may break paths
                else:
                    changed[(u, x)] = (INF, wn)  # may create paths
        return raw_changed, new_out, ov_flips, changed

    def _upload_event(self, patched, changed):
        """Upload one event's edge-transition list (padded to a pow2
        bucket: one compiled shape per bucket, not per distinct churn
        size) and the patched overload mask. Padding edges are
        self-loops with INF on both sides -> never usable. Returns
        ``(ov_new, e_dev)`` committed replicated under a mesh (the
        sharded steps read them with P(None) in_specs; an unplaced
        upload would make XLA insert the broadcast on every
        dispatch)."""
        e_u = np.asarray([u for (u, _v) in changed], dtype=np.int32)
        e_v = np.asarray([v for (_u, v) in changed], dtype=np.int32)
        e_wo = np.asarray(
            [wo for (wo, _wn) in changed.values()], dtype=np.int32
        )
        e_wn = np.asarray(
            [wn for (_wo, wn) in changed.values()], dtype=np.int32
        )
        eb = 8
        while eb < len(e_u):
            eb *= 2
        pad = eb - len(e_u)
        if pad:
            e_u = np.concatenate([e_u, np.zeros(pad, np.int32)])
            e_v = np.concatenate([e_v, np.zeros(pad, np.int32)])
            e_wo = np.concatenate(
                [e_wo, np.full(pad, INF, np.int32)]
            )
            e_wn = np.concatenate(
                [e_wn, np.full(pad, INF, np.int32)]
            )
        up = self.plan.replicate if self.plan is not None \
            else jnp.asarray
        ov_new = up(patched.overloaded)
        e_dev = (up(e_u), up(e_v), up(e_wo), up(e_wn))
        return ov_new, e_dev

    @fault_boundary
    @committed_dispatch
    def _churn_device(self, ls, affected_nodes: Set[str],
                      defer_consume: bool = False):
        """Ladder rung 0 (warm): one incremental device event. Returns
        the list of affected destination NAMES (their digests/sample
        rows in self.result are refreshed in place); falls back to a
        cold rebuild (and returns None) when incrementality does not
        apply. With ``defer_consume=True`` the device state commits but
        the host apply is left in flight: the return value is a
        PendingDelta (consumed by the next churn inside its dispatch
        window, or by flush()/wait()) — self.result is stale until
        then."""
        if not self._device_valid:
            raise _DeviceStateInvalid(
                "device residents stale (host fallback active)"
            )
        graph = self.graph
        ctx = self._prepare_patch(ls, sorted(affected_nodes))
        if ctx is None or not self._refresh_sample_bands(
            ctx["patched"], affected_nodes
        ):
            self._build(ls)
            return None
        patched = ctx["patched"]

        raw_changed, new_out, ov_flips, changed = self._event_diff(
            ls, affected_nodes, graph
        )
        if not changed:
            # attribute-only event: nothing route-affecting
            self.version = ls.topology_version
            self.aversion = ls.attributes_version
            if not defer_consume:
                self.flush()
            return []
        # event classification: STRUCTURAL events (link up/down,
        # drain flips) have an INF endpoint in some transition;
        # metric churn never does. Counted apart so the frontier
        # policy's coverage is auditable (a structural event that
        # rides full-width below threshold is a regression — see
        # tests/test_frontier_parity.py).
        if any(
            wo >= INF or wn >= INF for (wo, wn) in changed.values()
        ):
            self.structural_events += 1
            get_registry().counter_bump(
                "route_engine.structural_events"
            )

        ov_new, e_dev = self._upload_event(patched, changed)
        buckets = [b for b in _ROW_BUCKETS if b >= self._k_hint]
        # pipelining witness: a pending delta means the PREVIOUS
        # window's reap is still in flight while this window's
        # dispatch submits — depth-2 double buffering
        was_pending = self._pending is not None
        # segments: per-shard IN-FLIGHT [k+1, 1+W] device arrays (ONE
        # for the single-chip engine), each leading with its own meta
        # row [affected, changed] — the bucket k bounds the PER-SHARD
        # affected count; only the meta crosses during the ladder
        segments: List = []
        counts: List[int] = []
        ch_counts: List[int] = []
        commit_state = None
        k = None
        overlapped = False
        for k in buckets:
            segments, commit_state = self._run_bucket(
                ctx, k, e_dev, ov_new
            )
            # kick every shard's 8-byte meta copy while still in the
            # SUBMIT phase: the transfers ride all devices' readback
            # lanes concurrently instead of draining one shard at a
            # time, and the window's host touches stay at two
            # (submit everything, then reap everything)
            meta_rows = [
                _seg_meta(seg) if isinstance(seg, jax.Array)
                else seg[0, :2]
                for seg in segments
            ]
            n_meta = sum(
                1 for seg in segments if isinstance(seg, jax.Array)
            )
            if n_meta:
                da.count_dispatch(n_meta)
            for mrow in meta_rows:
                da.kick_async(mrow)
            if not overlapped:
                if was_pending:
                    da.note_pipelined_dispatch(2)
                # the overlap window: the PREVIOUS event's delta is
                # consumed on host while this dispatch solves on device
                self._consume_pending(overlap=True)
                overlapped = True
            metas = [
                da.reap_read(mrow, kicked=True)
                if isinstance(mrow, jax.Array) else mrow
                for mrow in meta_rows
            ]
            counts = [int(m[0]) for m in metas]
            ch_counts = [int(m[1]) for m in metas]
            # openr-lint: disable=host-branch-in-chain -- bucket-ladder retry: climbing a rung recompiles anyway, so the overflow check stays host-side (audited)
            if max(counts) <= k:
                break
        # openr-lint: disable=host-branch-in-chain -- bucket-ladder retry: climbing a rung recompiles anyway, so the overflow check stays host-side (audited)
        if max(counts) > k:
            # beyond every bucket: keep the patched layout and let the
            # overflow policy pick frontier re-solve vs full-width
            # refresh (no host recompile on either path)
            return self._overflow_refresh(
                ls, ctx, ov_new, new_out, ov_flips, e_dev,
                defer=defer_consume,
            )
        # hint tracks the typical event size (decays toward small)
        self._k_hint = max(
            _ROW_BUCKETS[0], min(1024, 2 * max(counts))
        )

        # commit the device state NOW; the host-side result apply rides
        # the pending delta (consumed below, or deferred into the next
        # event's dispatch window)
        self._commit_device(ctx, commit_state, ov_new)
        self._commit_host_mirrors(ls, new_out, ov_flips)
        self.version = ls.topology_version
        self.aversion = ls.attributes_version
        self.incremental_events += 1
        get_registry().counter_bump("route_engine.incremental_events")
        pending = PendingDelta(self, segments, counts, ch_counts, k)
        self._pending = pending
        if defer_consume:
            return pending
        self._consume_pending(overlap=False)
        return pending.names


# -- grouped-backend engine ------------------------------------------------

from openr_tpu.ops import spf_grouped as sg  # noqa: E402


@functools.partial(jax.jit, static_argnames=("meta", "n"))
def _grouped_full_resident(
    v_t, w_t, overloaded, samp_ids, samp_v, samp_w, pos_w, meta, n,
):
    """Grouped-backend cold build: every destination row solved through
    the gather-free block-bipartite relaxation (ops.spf_grouped), DR +
    digests staying resident. The packed layout and digest algebra are
    identical to the ELL engine's — the two backends are
    bit-comparable by canonical digest."""
    t_ids = jnp.arange(n, dtype=jnp.int32)
    dr = sg._grouped_fixed_point(
        meta, v_t, w_t, overloaded, t_ids, n, reverse=True
    )
    nh_count = sg._grouped_nh_counts(
        dr, meta, v_t, w_t, overloaded, t_ids
    )
    d_s, packed_mask = rs._sample_stats(
        dr, samp_ids, samp_v, samp_w, overloaded, t_ids
    )
    digests, packed = _pack_product(
        dr, nh_count, d_s, packed_mask, pos_w
    )
    return dr, digests, packed


@functools.partial(jax.jit, static_argnames=("meta", "n", "mesh"))
def _sharded_grouped_full_resident(
    v_t, w_t, overloaded, samp_ids, samp_v, samp_w, pos_w, meta, n,
    mesh,
):
    nseg = len(v_t)

    def shard_fn(t_blk, *rest):
        v_r = rest[:nseg]
        w_r = rest[nseg : 2 * nseg]
        ov_r, sid_r, sv_r, sw_r, pw_r = rest[2 * nseg :]
        vote = lambda bit: jax.lax.psum(bit, SOURCES_AXIS)  # noqa: E731
        dr = sg._grouped_fixed_point(
            meta, v_r, w_r, ov_r, t_blk, n, reverse=True, vote=vote,
        )
        nh_count = sg._grouped_nh_counts(
            dr, meta, v_r, w_r, ov_r, t_blk
        )
        d_s, packed_mask = rs._sample_stats(
            dr, sid_r, sv_r, sw_r, ov_r, t_blk
        )
        digests, packed = _pack_product(
            dr, nh_count, d_s, packed_mask, pw_r
        )
        return dr, digests, packed

    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=tuple(
            [P(SOURCES_AXIS)]
            + [P(None, None)] * nseg
            + [P(None, None, None)] * nseg
            + [P(None), P(None), P(None, None), P(None, None), P(None)]
        ),
        out_specs=(
            P(SOURCES_AXIS, None),
            P(SOURCES_AXIS),
            P(SOURCES_AXIS, None),
        ),
    )(
        jnp.arange(n, dtype=jnp.int32),
        *v_t, *w_t, overloaded, samp_ids, samp_v, samp_w, pos_w,
    )


def _patch_segments_fn(w_t, upd_g, upd_s, upd_r, upd_w):
    """Scatter per-segment weight updates into the (replicated)
    resident segment tensors — the grouped analogue of _patch_bands.
    Padding entries repeat a real update (duplicates write the same
    value)."""
    return tuple(
        w.at[g, s, r].set(v)
        for w, g, s, r, v in zip(w_t, upd_g, upd_s, upd_r, upd_w)
    )


# single-chip dispatch; mesh engines ride replicated_jit (committed
# replicated outputs — see _patch_bands)
_patch_segments = jax.jit(_patch_segments_fn)


@functools.partial(jax.jit, static_argnames=("meta", "n", "k"))
def _grouped_churn_step(
    v_t, w_t, upd_g, upd_s, upd_r, upd_w,
    dr, digests, packed_res,
    e_u, e_v, e_w_old, e_w_new,
    overloaded_new,
    samp_ids, samp_v, samp_w, pos_w,
    meta, n, k,
):
    """Fused single-chip grouped churn dispatch: detection against the
    resident DR, segment-slot weight scatter, affected-row re-solve
    through the grouped relaxation — one device round trip, with the
    same delta-compacted readback as the ELL step."""
    count, local_ids, ids = _detect_rows(
        dr, e_u, e_v, e_w_old, e_w_new, k, 0
    )
    new_w = _patch_segments(w_t, upd_g, upd_s, upd_r, upd_w)
    dr, digests, packed_res, out = _resolve_and_pack(
        lambda t: sg._grouped_fixed_point(
            meta, v_t, new_w, overloaded_new, t, n, reverse=True,
        ),
        lambda rows, t: sg._grouped_nh_counts(
            rows, meta, v_t, new_w, overloaded_new, t
        ),
        overloaded_new, ids, local_ids, count,
        dr, digests, packed_res, samp_ids, samp_v, samp_w, pos_w, n, k,
    )
    return new_w, dr, digests, packed_res, out


@functools.partial(jax.jit, static_argnames=("meta", "n", "k", "mesh"))
def _sharded_grouped_churn_step(
    v_t, w_t, dr, digests, packed_res,
    e_u, e_v, e_w_old, e_w_new,
    overloaded_new,
    samp_ids, samp_v, samp_w, pos_w,
    meta, n, k, mesh,
):
    """Sharded grouped churn: per-shard detection + re-solve over the
    row-sharded resident DR (segment tensors arrive ALREADY PATCHED by
    _patch_segments, mirroring the ELL sharded path), delta-compacted
    per-shard readback."""
    nseg = len(v_t)
    rows_per = n // mesh.devices.size

    def shard_fn(dr_s, dg_s, pk_s, *rest):
        v_r = rest[:nseg]
        w_r = rest[nseg : 2 * nseg]
        (e_u_r, e_v_r, e_wo_r, e_wn_r, ov_r,
         sid_r, sv_r, sw_r, pw_r) = rest[2 * nseg :]
        row_start = (
            jax.lax.axis_index(SOURCES_AXIS) * rows_per
        ).astype(jnp.int32)
        count, local_ids, ids = _detect_rows(
            dr_s, e_u_r, e_v_r, e_wo_r, e_wn_r, k, row_start
        )
        vote = lambda bit: jax.lax.psum(bit, SOURCES_AXIS)  # noqa: E731
        return _resolve_and_pack(
            lambda t: sg._grouped_fixed_point(
                meta, v_r, w_r, ov_r, t, n, reverse=True, vote=vote,
            ),
            lambda rows, t: sg._grouped_nh_counts(
                rows, meta, v_r, w_r, ov_r, t
            ),
            ov_r, ids, local_ids, count, dr_s, dg_s, pk_s,
            sid_r, sv_r, sw_r, pw_r, n, k,
        )

    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=tuple(
            [P(SOURCES_AXIS, None), P(SOURCES_AXIS),
             P(SOURCES_AXIS, None)]
            + [P(None, None)] * nseg
            + [P(None, None, None)] * nseg
            + [P(None)] * 4
            + [P(None), P(None), P(None, None), P(None, None), P(None)]
        ),
        out_specs=(
            P(SOURCES_AXIS, None),
            P(SOURCES_AXIS),
            P(SOURCES_AXIS, None),
            P(SOURCES_AXIS, None),
        ),
    )(
        dr, digests, packed_res, *v_t, *w_t,
        e_u, e_v, e_w_old, e_w_new, overloaded_new,
        samp_ids, samp_v, samp_w, pos_w,
    )


@functools.partial(
    jax.jit, static_argnames=("meta", "n", "max_jumps")
)
def _grouped_frontier_probe(
    v_t, w_t, dr, e_u, e_v, e_w_old, e_w_new, cell_limit, meta, n,
    max_jumps,
):
    """Grouped frontier probe: the affected-cone expansion over the
    full resident DR and the PRE-patch segment slabs
    (sg._grouped_cone_expand) — the grouped twin of _frontier_probe,
    returning the same resident cone + 4-float meta
    [rows, cells, jumps, converged] policy row."""
    cone, rows, cells, jumps, ok = sg._grouped_cone_expand(
        dr, meta, v_t, w_t, e_u, e_v, e_w_old, e_w_new, max_jumps,
        cell_limit=cell_limit[0],
    )
    meta_row = jnp.stack(
        [rows.astype(jnp.float32), cells,
         jumps.astype(jnp.float32), ok.astype(jnp.float32)]
    )
    return cone, meta_row


@functools.partial(jax.jit, static_argnames=("meta", "n"))
def _grouped_frontier_step(
    v_t, w_t, cone, dr, overloaded, samp_ids, samp_v, samp_w, pos_w,
    meta, n,
):
    """Grouped frontier re-solve: full-width WARM fixed point through
    the gather-free grouped relaxation over the PATCHED segments, cone
    cells seeded at INF, every other cell keeping its resident
    distance — the grouped twin of _frontier_step, with the identical
    extraction/packing so the product stays bit-identical to the cold
    grouped build. Residents are NOT donated (retry-ladder hazard
    rule)."""
    t_ids = jnp.arange(n, dtype=jnp.int32)
    warm0 = jnp.where(cone, INF, dr)
    dr2 = sg._grouped_fixed_point(
        meta, v_t, w_t, overloaded, t_ids, n, reverse=True,
        init=warm0,
    )
    nh_count = sg._grouped_nh_counts(
        dr2, meta, v_t, w_t, overloaded, t_ids
    )
    d_s, packed_mask = rs._sample_stats(
        dr2, samp_ids, samp_v, samp_w, overloaded, t_ids
    )
    digests, packed = _pack_product(
        dr2, nh_count, d_s, packed_mask, pos_w
    )
    return dr2, digests, packed


@functools.partial(
    jax.jit,
    static_argnames=("meta", "n", "n_real", "max_jumps"),
)
def _grouped_overflow_chain(
    v_t, w_old_t, w_new_t, dr, packed_res,
    e_u, e_v, e_w_old, e_w_new, cell_limit, overloaded_new,
    samp_ids, samp_v, samp_w, pos_w, meta, n, n_real, max_jumps,
):
    """Grouped fused overflow chain: cone probe over the PRE-patch
    segment slabs, on-device frontier-vs-full seed select (the same
    collapse as _overflow_chain: full-width == frontier with an
    all-True cone), warm grouped re-solve over the PATCHED segments,
    extraction + delta compaction — one executable, meta riding the
    async lane for telemetry only. Segment shapes never change under
    grouped_patch, so this chain covers every grouped overflow."""
    cone, rows, cells, jumps, ok = sg._grouped_cone_expand(
        dr, meta, v_t, w_old_t, e_u, e_v, e_w_old, e_w_new, max_jumps,
        cell_limit=cell_limit[0],
    )
    meta_row = jnp.stack(
        [rows.astype(jnp.float32), cells,
         jumps.astype(jnp.float32), ok.astype(jnp.float32)]
    )
    use_frontier = jnp.logical_and(ok, cells <= cell_limit[0])
    eff_cone = jnp.logical_or(cone, jnp.logical_not(use_frontier))
    t_ids = jnp.arange(n, dtype=jnp.int32)
    warm0 = jnp.where(eff_cone, INF, dr)
    dr2 = sg._grouped_fixed_point(
        meta, v_t, w_new_t, overloaded_new, t_ids, n, reverse=True,
        init=warm0,
    )
    nh_count = sg._grouped_nh_counts(
        dr2, meta, v_t, w_new_t, overloaded_new, t_ids
    )
    d_s, packed_mask = rs._sample_stats(
        dr2, samp_ids, samp_v, samp_w, overloaded_new, t_ids
    )
    digests, packed = _pack_product(
        dr2, nh_count, d_s, packed_mask, pos_w
    )
    ch_count, comp = _compact_changed_body(packed, packed_res, n_real)
    return dr2, digests, packed, ch_count, comp, meta_row


@functools.partial(
    jax.jit, static_argnames=("meta", "n", "max_jumps", "mesh")
)
def _sharded_grouped_frontier_probe(
    v_t, w_t, dr, e_u, e_v, e_w_old, e_w_new, cell_limit, meta, n,
    max_jumps, mesh,
):
    """Sharded grouped frontier probe: each shard expands the cone
    over its own resident DR rows with the counters and growth bit
    psum-voted (device-invariant meta, replicated), the cone staying
    row-sharded for _sharded_grouped_frontier_step — same contract as
    _sharded_frontier_probe."""
    nseg = len(v_t)

    def shard_fn(dr_s, *rest):
        v_r = rest[:nseg]
        w_r = rest[nseg : 2 * nseg]
        e_u_r, e_v_r, e_wo_r, e_wn_r, lim_r = rest[2 * nseg :]
        vote = lambda bit: jax.lax.psum(bit, SOURCES_AXIS)  # noqa: E731
        cone, rows, cells, jumps, ok = sg._grouped_cone_expand(
            dr_s, meta, v_r, w_r, e_u_r, e_v_r, e_wo_r, e_wn_r,
            max_jumps, vote=vote, cell_limit=lim_r[0],
        )
        meta_row = jnp.stack(
            [rows.astype(jnp.float32), cells,
             jumps.astype(jnp.float32), ok.astype(jnp.float32)]
        )
        return cone, meta_row

    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=tuple(
            [P(SOURCES_AXIS, None)]
            + [P(None, None)] * nseg
            + [P(None, None, None)] * nseg
            + [P(None)] * 5
        ),
        out_specs=(P(SOURCES_AXIS, None), P(None)),
    )(dr, *v_t, *w_t, e_u, e_v, e_w_old, e_w_new, cell_limit)


@functools.partial(jax.jit, static_argnames=("meta", "n", "mesh"))
def _sharded_grouped_frontier_step(
    v_t, w_t, cone, dr, overloaded, samp_ids, samp_v, samp_w, pos_w,
    meta, n, mesh,
):
    """Sharded grouped frontier re-solve over the PATCHED (replicated)
    segment tensors, each shard warm-seeding its own DR rows outside
    its cone shard; the convergence vote is the only collective."""
    nseg = len(v_t)

    def shard_fn(t_blk, cone_s, dr_s, *rest):
        v_r = rest[:nseg]
        w_r = rest[nseg : 2 * nseg]
        ov_r, sid_r, sv_r, sw_r, pw_r = rest[2 * nseg :]
        vote = lambda bit: jax.lax.psum(bit, SOURCES_AXIS)  # noqa: E731
        warm0 = jnp.where(cone_s, INF, dr_s)
        dr2 = sg._grouped_fixed_point(
            meta, v_r, w_r, ov_r, t_blk, n, reverse=True, vote=vote,
            init=warm0,
        )
        nh_count = sg._grouped_nh_counts(
            dr2, meta, v_r, w_r, ov_r, t_blk
        )
        d_s, packed_mask = rs._sample_stats(
            dr2, sid_r, sv_r, sw_r, ov_r, t_blk
        )
        digests, packed = _pack_product(
            dr2, nh_count, d_s, packed_mask, pw_r
        )
        return dr2, digests, packed

    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=tuple(
            [P(SOURCES_AXIS), P(SOURCES_AXIS, None),
             P(SOURCES_AXIS, None)]
            + [P(None, None)] * nseg
            + [P(None, None, None)] * nseg
            + [P(None), P(None), P(None, None), P(None, None), P(None)]
        ),
        out_specs=(
            P(SOURCES_AXIS, None),
            P(SOURCES_AXIS),
            P(SOURCES_AXIS, None),
        ),
    )(
        jnp.arange(n, dtype=jnp.int32), cone, dr, *v_t, *w_t,
        overloaded, samp_ids, samp_v, samp_w, pos_w,
    )


@functools.partial(
    jax.jit,
    static_argnames=("meta", "n", "n_real", "max_jumps", "mesh"),
)
def _sharded_grouped_overflow_chain(
    v_t, w_old_t, w_new_t, dr, packed_res,
    e_u, e_v, e_w_old, e_w_new, cell_limit, overloaded_new,
    samp_ids, samp_v, samp_w, pos_w, meta, n, n_real, max_jumps,
    mesh,
):
    """Sharded grouped fused overflow chain — the grouped twin of
    _sharded_overflow_chain: psum-voted per-shard probe (policy inputs
    device-invariant, every shard takes the same seed select), warm
    grouped re-solve over the patched replicated segments, per-shard
    extraction, delta compaction after the shard_map in the same
    executable."""
    nseg = len(v_t)

    def shard_fn(t_blk, dr_s, *rest):
        v_r = rest[:nseg]
        w_o = rest[nseg : 2 * nseg]
        w_n = rest[2 * nseg : 3 * nseg]
        (e_u_r, e_v_r, e_wo_r, e_wn_r, lim_r, ov_r,
         sid_r, sv_r, sw_r, pw_r) = rest[3 * nseg :]
        vote = lambda bit: jax.lax.psum(bit, SOURCES_AXIS)  # noqa: E731
        cone, rows, cells, jumps, ok = sg._grouped_cone_expand(
            dr_s, meta, v_r, w_o, e_u_r, e_v_r, e_wo_r, e_wn_r,
            max_jumps, vote=vote, cell_limit=lim_r[0],
        )
        meta_row = jnp.stack(
            [rows.astype(jnp.float32), cells,
             jumps.astype(jnp.float32), ok.astype(jnp.float32)]
        )
        use_frontier = jnp.logical_and(ok, cells <= lim_r[0])
        eff_cone = jnp.logical_or(
            cone, jnp.logical_not(use_frontier)
        )
        warm0 = jnp.where(eff_cone, INF, dr_s)
        dr2 = sg._grouped_fixed_point(
            meta, v_r, w_n, ov_r, t_blk, n, reverse=True, vote=vote,
            init=warm0,
        )
        nh_count = sg._grouped_nh_counts(
            dr2, meta, v_r, w_n, ov_r, t_blk
        )
        d_s, packed_mask = rs._sample_stats(
            dr2, sid_r, sv_r, sw_r, ov_r, t_blk
        )
        digests, packed = _pack_product(
            dr2, nh_count, d_s, packed_mask, pw_r
        )
        return dr2, digests, packed, meta_row

    dr2, digests, packed, meta_row = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=tuple(
            [P(SOURCES_AXIS), P(SOURCES_AXIS, None)]
            + [P(None, None)] * nseg
            + [P(None, None, None)] * (2 * nseg)
            + [P(None)] * 6
            + [P(None), P(None, None), P(None, None), P(None)]
        ),
        out_specs=(
            P(SOURCES_AXIS, None),
            P(SOURCES_AXIS),
            P(SOURCES_AXIS, None),
            P(None),
        ),
    )(
        jnp.arange(n, dtype=jnp.int32), dr,
        *v_t, *w_old_t, *w_new_t,
        e_u, e_v, e_w_old, e_w_new, cell_limit, overloaded_new,
        samp_ids, samp_v, samp_w, pos_w,
    )
    ch_count, comp = _compact_changed_body(packed, packed_res, n_real)
    return dr2, digests, packed, ch_count, comp, meta_row


class GroupedRouteSweepEngine(RouteSweepEngine):
    """The incremental engine over the GROUPED (block-bipartite)
    relaxation backend — the gather-free flagship compute path
    (ops.spf_grouped, measured 3.5x over the ELL sweep on CPU),
    now with the same resident-DR incrementality and mesh sharding
    as the ELL engine.

    Churn contract: metric changes, overload flips and edge REMOVALS
    patch segment weight slots in place (spf_grouped.grouped_patch —
    node ids untouched, resident DR valid, a removed slot stays
    restorable). A NEW adjacency breaks the signature grouping and
    cold-rebuilds: the dense segments exist precisely because rows
    share source signatures, so structure growth is a layout event
    (the ELL engine covers growth-heavy churn; digests are
    bit-comparable across the two engines)."""

    audit_kind = "grouped"

    def audit_residual(self) -> int:
        # openr-lint: disable=sharding-spec -- read-only audit probe off the churn path; bare jit stays placement-agnostic across single-chip and mesh engines (see integrity.kernels)
        return int(jax.device_get(integrity_kernels.grouped_residual(
            self._dr, self.sweeper.v_t, self.sweeper.w_t,
            self.sweeper.overloaded, self.sweeper.meta,
        )))

    def _sample_oracle(self, ids_t):
        # openr-lint: disable=sharding-spec -- read-only audit probe off the churn path; bare jit stays placement-agnostic across single-chip and mesh engines (see integrity.kernels)
        return integrity_kernels.grouped_sample_oracle(
            self._dr, ids_t, self.sweeper.v_t, self.sweeper.w_t,
            self.sweeper.overloaded, self.sweeper.meta,
            self.graph.n_pad,
        )

    def _compile_backend(self, ls):
        graph = sg.compile_out_grouped(ls, align=self._align)
        self._slots = sg.slot_table(graph)
        return graph, sg.GroupedRouteSweeper(
            graph, self.sample_names, plan=self.plan
        )

    def _make_sweeper(self, graph):
        # device-loss recovery: re-land the segment tensors from the
        # current host graph; the slot table keys on layout, which a
        # patch never changes, so self._slots stays valid
        return sg.GroupedRouteSweeper(
            graph, self.sample_names, plan=self.plan
        )

    def _full_resident(self, graph):
        if self.mesh is None:
            # openr-lint: disable=sharding-spec -- single-chip cold
            # build (mesh is None): one device, no axis to spec
            return aot_call(
                "grouped_full_resident", _grouped_full_resident,
                (
                    self.sweeper.v_t, self.sweeper.w_t,
                    self.sweeper.overloaded,
                    self.sweeper._samp_ids_dev,
                    self.sweeper._samp_v_dev,
                    self.sweeper._samp_w_dev, self.sweeper._pos_w_dev,
                ),
                dict(meta=self.sweeper.meta, n=graph.n_pad),
            )
        return aot_call(
            "grouped_full_resident_sharded",
            _sharded_grouped_full_resident,
            (
                self.sweeper.v_t, self.sweeper.w_t,
                self.sweeper.overloaded,
                self.sweeper._samp_ids_dev, self.sweeper._samp_v_dev,
                self.sweeper._samp_w_dev, self.sweeper._pos_w_dev,
            ),
            dict(
                meta=self.sweeper.meta, n=graph.n_pad,
                mesh=self.mesh,
            ),
        )

    def _refresh_sample_bands(self, patched, affected_nodes) -> bool:
        if not (affected_nodes & set(self.sample_names)):
            return True
        sweeper = self.sweeper
        rows = [
            patched.out_slots(int(sid)) for sid in sweeper.sample_ids
        ]
        samp_v, samp_w = rs.pack_sample_rows(rows, sweeper.sample_ids)
        if samp_v.shape != sweeper.samp_v.shape:
            return False
        up = (
            self.plan.replicate if self.plan is not None
            else jnp.asarray
        )
        sweeper.samp_v = self.result.samp_v = samp_v
        sweeper.samp_w = self.result.samp_w = samp_w
        sweeper._samp_v_dev = up(samp_v)
        sweeper._samp_w_dev = up(samp_w)
        return True

    def _layout_changed(self, ctx) -> bool:
        # segment shapes never change under grouped_patch (it returns
        # None on any layout break), so a ctx implies a stable layout;
        # GridBand holds ndarrays, so the ELL value-compare would
        # raise on it anyway
        return False

    def _prepare_patch(self, ls, affected_sorted):
        got = sg.grouped_patch(
            self.graph, ls, affected_sorted, self._slots
        )
        if got is None:
            return None
        patched, updates = got
        # bucketed per-segment update index/value tensors: pad each
        # touched segment's list to a pow2 with repeats of entry 0
        # (identical value — idempotent); untouched segments get a
        # 1-entry no-op rewriting slot (0,0,0) to its CURRENT value
        # (known from the patched host arrays)
        seg_ws = [s.w for b in patched.bands for s in b.segments]
        up = (
            self.plan.replicate if self.plan is not None
            else jnp.asarray
        )
        upd_g, upd_s, upd_r, upd_w = [], [], [], []
        for si, w_host in enumerate(seg_ws):
            ups = updates.get(si)
            if not ups:
                ups = [(0, 0, 0, int(w_host[0, 0, 0]))]
            eb = 1
            while eb < len(ups):
                eb *= 2
            ups = ups + [ups[0]] * (eb - len(ups))
            arr = np.asarray(ups, dtype=np.int32)
            upd_g.append(up(arr[:, 0]))
            upd_s.append(up(arr[:, 1]))
            upd_r.append(up(arr[:, 2]))
            upd_w.append(up(arr[:, 3]))
        return {
            "patched": patched,
            "upd": (tuple(upd_g), tuple(upd_s), tuple(upd_r),
                    tuple(upd_w)),
            "patched_segs": None,
        }

    @solve_window
    @committed_dispatch
    def _run_bucket(self, ctx, k, e_dev, ov_new):
        e_u_d, e_v_d, e_wo_d, e_wn_d = e_dev
        fault_point(FAULT_DISPATCH)
        fault_point(FAULT_DEVICE_LOST)
        graph = ctx["patched"]
        upd_g, upd_s, upd_r, upd_w = ctx["upd"]
        if self.mesh is None:
            (new_w, dr, digests, packed_res,
             # openr-lint: disable=sharding-spec -- single-chip churn
             # dispatch (mesh is None): no mesh axis to spec
             packed_dev) = aot_call(
                "grouped_churn_step", _grouped_churn_step,
                (
                    self.sweeper.v_t, self.sweeper.w_t,
                    upd_g, upd_s, upd_r, upd_w,
                    self._dr, self._digests_dev, self._packed_dev,
                    e_u_d, e_v_d, e_wo_d, e_wn_d,
                    ov_new,
                    self.sweeper._samp_ids_dev,
                    self.sweeper._samp_v_dev,
                    self.sweeper._samp_w_dev, self.sweeper._pos_w_dev,
                ),
                dict(
                    meta=self.sweeper.meta, n=graph.n_pad, k=k,
                ),
            )
            # cache the fused step's on-device segment patch for an
            # overflow's _apply_patch_resident (mirrors the ELL path)
            ctx["patched_segs"] = new_w
            segments = [packed_dev]
        else:
            self._ensure_residents()
            if ctx["patched_segs"] is None:
                ctx["patched_segs"] = self._dispatch_patch(ctx)
            new_w = ctx["patched_segs"]
            (dr, digests, packed_res,
             packed_dev) = aot_call(
                "grouped_churn_step_sharded", _sharded_grouped_churn_step,
                (
                    self.sweeper.v_t, new_w,
                    self._dr, self._digests_dev, self._packed_dev,
                    e_u_d, e_v_d, e_wo_d, e_wn_d,
                    ov_new,
                    self.sweeper._samp_ids_dev,
                    self.sweeper._samp_v_dev,
                    self.sweeper._samp_w_dev, self.sweeper._pos_w_dev,
                ),
                dict(
                    meta=self.sweeper.meta, n=graph.n_pad, k=k,
                    mesh=self.mesh,
                ),
            )
            segments = self._split_segments(packed_dev, k)
        return segments, (new_w, dr, digests, packed_res)

    @solve_window
    def _commit_device(self, ctx, commit_state, ov_new) -> None:
        new_w, dr, digests, packed_res = commit_state
        self.sweeper.w_t = new_w
        self.sweeper.overloaded = ov_new
        self._dr = dr
        self._digests_dev = digests
        self._packed_dev = packed_res
        self.graph = self.sweeper.graph = ctx["patched"]

    @solve_window
    def _apply_patch_resident(self, ctx, ov_new) -> None:
        """Grouped full-width refresh patch: scatter the event's
        segment-slot weight updates into the resident segment tensors
        (segment SHAPES never change under grouped_patch, so the
        full-width dispatch re-runs without recompiling)."""
        if ctx["patched_segs"] is None:
            ctx["patched_segs"] = self._dispatch_patch(ctx)
        self.sweeper.w_t = ctx["patched_segs"]
        self.sweeper.overloaded = ov_new
        self.graph = self.sweeper.graph = ctx["patched"]

    def _dispatch_patch(self, ctx):
        upd_g, upd_s, upd_r, upd_w = ctx["upd"]
        fn = (
            replicated_jit(_patch_segments_fn, self.mesh)
            if self.mesh is not None else _patch_segments
        )
        return fn(self.sweeper.w_t, upd_g, upd_s, upd_r, upd_w)

    @solve_window
    def _dispatch_frontier_probe(self, ctx, e_dev, limit):
        """Grouped frontier probe: the dense cone expansion over the
        [G, S, R] segment slabs (sg._grouped_cone_expand) against the
        PRE-patch resident tensors — same ordering contract as the ELL
        hook (nothing commits before _apply_patch_resident, so the
        resident w_t/_dr this reads are the pre-event ones)."""
        e_u_d, e_v_d, e_wo_d, e_wn_d = e_dev
        lim = jnp.asarray([limit], dtype=jnp.float32)
        if self.plan is not None:
            lim = self.plan.replicate(lim)
        if self.mesh is None:
            # openr-lint: disable=sharding-spec -- single-chip frontier
            # probe (mesh is None): no mesh axis to spec
            return aot_call(
                "grouped_frontier_probe", _grouped_frontier_probe,
                (
                    self.sweeper.v_t, self.sweeper.w_t, self._dr,
                    e_u_d, e_v_d, e_wo_d, e_wn_d, lim,
                ),
                dict(
                    meta=self.sweeper.meta, n=self.graph.n_pad,
                    max_jumps=_FRONTIER_MAX_JUMPS,
                ),
            )
        return aot_call(
            "grouped_frontier_probe_sharded",
            _sharded_grouped_frontier_probe,
            (
                self.sweeper.v_t, self.sweeper.w_t, self._dr,
                e_u_d, e_v_d, e_wo_d, e_wn_d, lim,
            ),
            dict(
                meta=self.sweeper.meta, n=self.graph.n_pad,
                max_jumps=_FRONTIER_MAX_JUMPS, mesh=self.mesh,
            ),
        )

    @solve_window
    def _frontier_resident(self, cone):
        """Grouped masked full-width dispatch: warm fixed point with
        only cone cells reset, over the ALREADY-PATCHED resident
        segment tensors (_apply_patch_resident ran)."""
        if self.mesh is None:
            # openr-lint: disable=sharding-spec -- single-chip frontier
            # re-solve (mesh is None): no mesh axis to spec
            return aot_call(
                "grouped_frontier_step", _grouped_frontier_step,
                (
                    self.sweeper.v_t, self.sweeper.w_t, cone, self._dr,
                    self.sweeper.overloaded,
                    self.sweeper._samp_ids_dev,
                    self.sweeper._samp_v_dev,
                    self.sweeper._samp_w_dev, self.sweeper._pos_w_dev,
                ),
                dict(
                    meta=self.sweeper.meta, n=self.graph.n_pad,
                ),
            )
        return aot_call(
            "grouped_frontier_step_sharded",
            _sharded_grouped_frontier_step,
            (
                self.sweeper.v_t, self.sweeper.w_t, cone, self._dr,
                self.sweeper.overloaded,
                self.sweeper._samp_ids_dev, self.sweeper._samp_v_dev,
                self.sweeper._samp_w_dev, self.sweeper._pos_w_dev,
            ),
            dict(
                meta=self.sweeper.meta, n=self.graph.n_pad,
                mesh=self.mesh,
            ),
        )

    @solve_window
    def _dispatch_overflow_chain(self, ctx, e_dev, ov_new, limit):
        """Grouped fused overflow chain: segment SHAPES never change
        under grouped_patch, so every grouped overflow fuses — probe on
        the pre-patch slabs, on-device seed select, warm re-solve on
        the patched slabs, extraction + compaction in one dispatch."""
        if ctx["patched_segs"] is None:
            ctx["patched_segs"] = self._dispatch_patch(ctx)
        new_w = ctx["patched_segs"]
        e_u_d, e_v_d, e_wo_d, e_wn_d = e_dev
        lim = jnp.asarray([limit], dtype=jnp.float32)
        if self.plan is not None:
            lim = self.plan.replicate(lim)
        if self.mesh is None:
            # openr-lint: disable=sharding-spec -- single-chip fused
            # overflow chain (mesh is None): no mesh axis to spec
            return aot_call(
                "grouped_overflow_chain", _grouped_overflow_chain,
                (
                    self.sweeper.v_t, self.sweeper.w_t, new_w,
                    self._dr, self._packed_dev,
                    e_u_d, e_v_d, e_wo_d, e_wn_d, lim, ov_new,
                    self.sweeper._samp_ids_dev,
                    self.sweeper._samp_v_dev,
                    self.sweeper._samp_w_dev, self.sweeper._pos_w_dev,
                ),
                dict(
                    meta=self.sweeper.meta, n=self.graph.n_pad,
                    n_real=self.graph.n, max_jumps=_FRONTIER_MAX_JUMPS,
                ),
            )
        return aot_call(
            "grouped_overflow_chain_sharded",
            _sharded_grouped_overflow_chain,
            (
                self.sweeper.v_t, self.sweeper.w_t, new_w,
                self._dr, self._packed_dev,
                e_u_d, e_v_d, e_wo_d, e_wn_d, lim, ov_new,
                self.sweeper._samp_ids_dev, self.sweeper._samp_v_dev,
                self.sweeper._samp_w_dev, self.sweeper._pos_w_dev,
            ),
            dict(
                meta=self.sweeper.meta, n=self.graph.n_pad,
                n_real=self.graph.n, max_jumps=_FRONTIER_MAX_JUMPS,
                mesh=self.mesh,
            ),
        )
