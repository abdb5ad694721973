"""Incremental KSP2_ED_ECMP engine: persist paths across churn, re-solve
only affected destinations.

The per-build cost of the original device prefetch
(spf_solver._prefetch_ksp2_paths) is O(D) host work per churn event —
first-path traces, mask building, masked-row tracing and route assembly
for EVERY KSP2 destination — even though one adjacency change leaves
almost every destination's paths untouched. At fabric scale that host
work dominates the rebuild (reference convergence goal is <100 ms,
openr/docs/Introduction/Overview.md:28; the per-destination semantics
being preserved are LinkState.cpp:763 getKthPaths and Decision.cpp:908
selectBestPathsKsp2).

This engine caches, per destination: the traced first/second paths, the
first-path link (exclusion) set, and the masked-SPF distance row. On a
topology change it determines the exact set of destinations whose paths
may differ — everything else is primed straight from the cache — using
a sound distance-algebra test:

  For a changed directed edge C = (u, v) with weight w, C lies on some
  shortest path src -> dst iff

      d(src, u) + w + d(v, dst) == d(src, dst)

  If no changed edge lies on dst's shortest-path DAG under EITHER the
  old or the new distances, the DAG restricted to dst's explored region
  is unchanged, so the (canonically ordered) first-path trace output is
  unchanged. The same test bounds the MASKED graph of the second-path
  solve: masking only removes edges, so base distances lower-bound
  masked distances, giving a conservative (never unsound) filter.

  Soundness sketch for multiple simultaneous changes {C_i}: if a
  distance d(x, y) differs between the old and new graphs, some C_i
  lies on an old or new shortest x->y path (otherwise both old and new
  optima would be achievable in the other graph). Applying this to the
  endpoints of any DAG(dst) link whose membership flips places some
  C_i on DAG_old(dst) or DAG_new(dst) — exactly what the test checks.

The distances come from a device-resident all-pairs matrix over the
sliced-ELL bands (ops/spf_sparse.py): at KSP2 scale (n_pad <= 4096, the
engine's activation bound) a full all-sources solve is ONE source block
(~1-2 ms on-device), so every churn event recomputes it, swaps it with
the previous event's matrix (kept resident — no transfer), and reads
back one fused packet: the SPF view batch (served to SpfView, saving
its separate dispatch) plus old/new distance rows for the changed-edge
endpoints. Steady-state churn that touches no cached path costs ONE
device round trip and O(changed) host work.
"""

from __future__ import annotations

from typing import Dict, FrozenSet, List, Optional, Set, Tuple

import numpy as np

from openr_tpu.analysis.annotations import (
    mirrored_by,
    resident_buffers,
    thread_confined,
)
from openr_tpu.graph.linkstate import Link, LinkState
from openr_tpu.ops import dispatch_accounting as _da
from openr_tpu.ops.spf import INF

# Engine activation bound: the event loop keeps TWO device-resident
# [n_pad, n_pad] int32 matrices (current + previous all-pairs) — at the
# 12k bound that is ~1.2 GB, comfortably inside a single chip's HBM,
# and the per-event fused dispatch is one all-sources ELL solve. Past
# this, the all-pairs residency must shard over a device mesh (the ELL
# machinery already shards source rows — sharded_ell_all_sources); the
# bound is where single-chip residency stops, not where the algorithm
# does.
ENGINE_MAX_NODES = 12288

# Optional device mesh for the engine's all-pairs residency: when set
# (set_engine_mesh), the all-pairs fixed point and the masked batches
# run SHARDED over the mesh — per-device footprint n^2/ndev — and the
# activation bound scales with sqrt(ndev) (~100k on a 64-way mesh).
# The speculative resident-masks fast path runs mesh-wide too: the
# destination batch pads to a mesh multiple and the mask stack / dm
# residents stripe over the batch axis (ShardingPlan.batch3/rows).
# When the fast path CANNOT engage on-mesh (mask budget, empty batch)
# the drop is typed — decision.ksp2.spec_mesh_fallbacks plus a trace
# stamp — never silent.
_ENGINE_MESH = None


def set_engine_mesh(mesh) -> None:
    """Install (or clear, with None) the mesh the KSP2 engines shard
    their resident all-pairs state over. Takes effect on the next
    engine cold build."""
    global _ENGINE_MESH
    _ENGINE_MESH = mesh


def get_engine_mesh():
    return _ENGINE_MESH


def engine_max_nodes() -> int:
    """The activation bound under the current mesh setting: the two
    resident [n, n] matrices shard over rows, so the single-chip
    residency bound scales with sqrt(ndev)."""
    if _ENGINE_MESH is None:
        return ENGINE_MAX_NODES
    import math

    return int(ENGINE_MAX_NODES * math.sqrt(_ENGINE_MESH.devices.size))


# churn larger than this falls back to a full (cold) rebuild
ENGINE_MAX_CHANGED_PAIRS = 64
ENGINE_MAX_ENDPOINTS = 32
# if more than this fraction of destinations is affected, a cold
# rebuild is cheaper than the incremental machinery
ENGINE_FULL_REBUILD_FRACTION = 3  # affected * N > dsts  -> cold
# fast path: how many changed masked rows the fused dispatch reads back
# inline; more than this forces one extra full-matrix readback
ENGINE_ROW_BUDGET = 64


def _fast_path_enabled() -> bool:
    """The resident-mask speculative solve trades extra device compute
    (a masked re-solve of EVERY destination per event) for one fewer
    host<->device round trip. On the CPU backend round trips are free
    and the speculation is pure overhead (8x slower at fabric-1008 on
    the host clock), so it only engages on real accelerators; whether
    the trade pays on a chip the host is attached to is not measured
    (ROADMAP D5). OPENR_KSP2_FAST=1/0 overrides (tests force it on
    under the CPU mesh)."""
    import os

    override = os.environ.get("OPENR_KSP2_FAST")
    if override is not None:
        return override == "1"
    import jax

    return jax.devices()[0].platform != "cpu"


def _counters():
    from openr_tpu.decision import spf_solver as _ss

    return _ss.SPF_COUNTERS


def trace_paths_from_row(
    src: str,
    dest: str,
    index: Dict[str, int],
    dlist,
    excluded: Set[Link],
    cands_of,
    transit_blocked: Set[str],
    preds_cache: Optional[Dict[str, list]] = None,
):
    """Enumerate link-disjoint shortest paths src -> dest from a
    distance row — byte-identical to LinkState._trace_one_path over the
    same SPF (both walk predecessor links in canonical sorted order;
    reference: LinkState.cpp:399 traceOnePath).

    ``preds_cache``: predecessor lists depend only on (dlist, excluded,
    transit_blocked) — NOT on the destination — so a caller tracing
    many destinations from the SAME row under the same filters (the
    per-event first-path loops) passes one shared dict and each node's
    predecessor list is computed once per event instead of once per
    destination."""
    inf = int(INF)
    did = index.get(dest)
    if did is None:
        return []
    # numpy rows index/compare element-wise MUCH slower than a plain
    # list in the tight predecessor scans below (np.int32 arithmetic
    # per candidate); one bulk tolist() pays for itself immediately
    if isinstance(dlist, np.ndarray):
        dlist = dlist.tolist()
    if dlist[did] >= inf:
        return []

    visited: Set[Link] = set()
    preds: Dict[str, list] = (
        preds_cache if preds_cache is not None else {}
    )

    # first-path traces run with BOTH filter sets empty (nothing
    # excluded yet): skip the two per-candidate membership tests there
    # — this is the hottest loop of the per-event host work
    plain = not excluded and not transit_blocked

    def preds_of(v: str):
        got = preds.get(v)
        if got is None:
            dv = dlist[index[v]]
            if plain:
                got = preds[v] = [
                    (link, u)
                    for link, u, uid, w in cands_of(v)
                    if uid is not None and dlist[uid] + w == dv
                ]
            else:
                got = preds[v] = [
                    (link, u)
                    for link, u, uid, w in cands_of(v)
                    if uid is not None
                    and link not in excluded
                    and (u == src or u not in transit_blocked)
                    and dlist[uid] < inf
                    and dlist[uid] + w == dv
                ]
        return got

    def trace_one(v: str):
        if v == src:
            return []
        for link, u in preds_of(v):
            if link in visited:
                continue
            visited.add(link)
            sub = trace_one(u)
            if sub is not None:
                sub.append(link)
                return sub
        return None

    paths = []
    path = trace_one(dest)
    while path:
        paths.append(path)
        path = trace_one(dest)
    return paths


def make_cands_of(ls: LinkState, node_index: Dict[str, int]):
    """Per-build candidate list factory shared by the trace calls: up
    links of each node in canonical order with (origin, origin id,
    metric) pre-resolved."""
    in_cands: Dict[str, list] = {}

    def cands_of(v: str):
        got = in_cands.get(v)
        if got is None:
            got = in_cands[v] = [
                (
                    link,
                    link.other_node(v),
                    node_index.get(link.other_node(v)),
                    link.metric_from(link.other_node(v)),
                )
                for link in ls.ordered_links_from_node(v)
                if link.is_up()
            ]
        return got

    return cands_of


class _TraceArrays:
    """Int-encoded view of one build's candidate structure for the
    native batch tracer (native/spfcore.cpp ksp2_trace_batch): a
    candidate CSR in the same canonical order make_cands_of yields,
    a link table for id<->object mapping, and the transit-blocked
    bitmap. Built once per churn event and shared by every trace
    site; the Python tracer remains the fallback and the semantic
    reference."""

    __slots__ = (
        "off", "link", "uid", "w", "links", "lid_of", "blocked",
        "n_pad",
    )

    def __init__(self, graph, cands_of, transit_blocked):
        index = graph.node_index
        names = graph.node_names
        n_pad = graph.n_pad
        off = np.zeros(n_pad + 1, np.int32)
        link_l: List[int] = []
        uid_l: List[int] = []
        w_l: List[int] = []
        links: List[Link] = []
        # keyed by the Link VALUE (its hash is cached), not id(): the
        # Python tracer excludes via `link not in excluded` — a link
        # that flapped down and back up is a fresh-but-EQUAL object,
        # and an identity key would silently drop its exclusion
        lid_of: Dict[Link, int] = {}
        for i, v in enumerate(names):
            for lnk, _u, uuid, w in cands_of(v):
                lid = lid_of.get(lnk)
                if lid is None:
                    lid = lid_of[lnk] = len(links)
                    links.append(lnk)
                link_l.append(lid)
                uid_l.append(-1 if uuid is None else int(uuid))
                w_l.append(int(w))
            off[i + 1] = len(link_l)
        off[len(names) + 1 :] = len(link_l)
        self.off = off
        self.link = np.asarray(link_l, np.int32)
        self.uid = np.asarray(uid_l, np.int32)
        self.w = np.asarray(w_l, np.int32)
        self.links = links
        self.lid_of = lid_of
        blocked = np.zeros(n_pad, np.uint8)
        for nm in transit_blocked:
            bi = index.get(nm)
            if bi is not None:
                blocked[bi] = 1
        self.blocked = blocked
        self.n_pad = n_pad

    def _excl_arrays(self, excls):
        """Per-dst exclusion ranges; a link absent from the current
        candidate table is down, so its exclusion is vacuous."""
        ids: List[int] = []
        off = np.zeros(len(excls) + 1, np.int32)
        for i, excl in enumerate(excls):
            for lnk in excl:
                lid = self.lid_of.get(lnk)
                if lid is not None:
                    ids.append(lid)
            off[i + 1] = len(ids)
        return off, np.asarray(ids, np.int32)

    def trace(self, src_id, dst_ids, rows, shared_row, excls):
        """Batch-enumerate via the native core; None when it is
        unavailable. Paths come back as Link-object lists, identical
        in content and order to trace_paths_from_row."""
        from openr_tpu.graph import native_spf

        excl_off, excl_ids = self._excl_arrays(excls)
        got = native_spf.trace_batch(
            self.n_pad, len(self.links), self.off, self.link,
            self.uid, self.w, src_id, self.blocked,
            np.ascontiguousarray(dst_ids, np.int32),
            np.ascontiguousarray(rows, np.int32),
            shared_row, excl_off, excl_ids,
        )
        if got is None:
            return None
        links = self.links
        return [
            [[links[l] for l in p] for p in paths] for paths in got
        ]


def _path_nodes(src: str, path: List[Link]) -> List[str]:
    """Nodes visited after src along a traced path."""
    out = []
    cur = src
    for link in path:
        cur = link.other_node(cur)
        out.append(cur)
    return out


def _pad_ids(ids: List[int], bucket_min: int = 8) -> np.ndarray:
    """Pad an id list to a power-of-two bucket by repeating the first id
    (inert for row gathers) so jit shapes stay bounded."""
    bucket = bucket_min
    while bucket < len(ids):
        bucket *= 2
    return np.asarray(
        ids + [ids[0]] * (bucket - len(ids)), dtype=np.int32
    )


@mirrored_by(
    d_prev_dev="rebuilt by _cold_build from the resident EllState "
               "distance cache (engine invalidates to valid=False and "
               "re-seeds on the next sync)",
    dm_dev="rebuilt by _cold_build from the traced host-side dm rows",
    masks_t="re-derived by _cold_build from the band tensor shapes",
)
@resident_buffers("d_prev_dev", "dm_dev", "masks_t")
# externally serialized, never internally locked: every engine is
# created and driven by exactly one plane — Decision's under evb, a
# ctrl handler's under SolverCtrlHandler._lock, the twin's on its one
# thread. The shared-state rule merges all instances by class, so
# cross-role access to one instance is impossible by construction —
# hence "owner" confinement (same contract as WorldManager).
@thread_confined(
    "owner",
    "_mesh",
    "_mesh_knob",
    "_slot_maps",
    "_tarrays",
    "attr_sig",
    "aversion",
    "band_shapes",
    "d_base",
    "d_prev_dev",
    "dm",
    "dm_dev",
    "dst_pos",
    "dsts",
    "ecc_hops",
    "eff_w",
    "excl",
    "first_paths",
    "host_dsts",
    "last_affected",
    "masks_t",
    "node_label",
    "node_users",
    "ov",
    "pairs_by_node",
    "second_paths",
    "sid",
    "state",
    "valid",
    "version",
)
class Ksp2Engine:
    """Per-(LinkState, root) incremental KSP2 state. Invalid until the
    first successful cold build."""

    def __init__(self, src_name: str) -> None:
        self.src_name = src_name
        self.valid = False
        self.last_affected: Optional[Set[str]] = None
        # _mesh_knob: the module knob as of the last (re)build — the
        # change-detection identity. _mesh: the mesh the resident
        # arrays are ACTUALLY sharded over (None when the knob is off
        # OR the graph's n_pad does not divide by the mesh size, in
        # which case the single-chip dispatch runs instead).
        self._mesh_knob = _ENGINE_MESH
        self._mesh = None

    # -- public entry ------------------------------------------------------

    def sync(self, ls: LinkState, dsts: List[str]) -> Optional[Set[str]]:
        """Bring the cache to ls.topology_version, prime the LinkState
        kth-path cache for every destination, and return the set of
        destination names whose paths may have changed (for route
        reuse). Returns None when the engine had to cold-rebuild (no
        reuse this build) or cannot run (caller falls back).

        The whole device round trip runs inside one accounting window:
        every device readback must ride the committed chain
        (``aot_call`` + async kick, reaped via ``reap_read``), and the
        ``ops.host_touches.ksp2_window`` observation is the gate."""
        with _da.event_window("ksp2_window"):
            return self._sync_window(ls, dsts)

    def _sync_window(self, ls: LinkState, dsts: List[str]) -> Optional[Set[str]]:
        self.last_affected = None
        from openr_tpu.decision import spf_solver as _ss

        state = _ss._ELL_RESIDENT.state_for(ls)
        if (
            not self.valid
            or state is not getattr(self, "state", None)
            or dsts != self.dsts
            or self.sid != state.graph.node_index.get(self.src_name)
            # a widened band (ell_patch grew a slot class in place)
            # changed the band tensor shapes the resident masks were
            # built for: the masked fast path would shape-mismatch,
            # so re-seed everything from the new shapes
            or tuple(state.graph.bands) != getattr(
                self, "band_shapes", None
            )
            # the engine-mesh knob changed: resident arrays carry the
            # old sharding — re-seed under the new one
            or self._mesh_knob is not _ENGINE_MESH
        ):
            self._cold_build(ls, state, dsts)
            return None
        if (
            ls.topology_version == self.version
            and ls.attributes_version == self.aversion
        ):
            # nothing changed since the last build; the kth-path cache
            # was not invalidated, so priming is already in place
            self.last_affected = set()
            return set()
        affected_nodes = ls.affected_since(self.version)
        attr_nodes = ls.attr_affected_since(self.aversion)
        if affected_nodes is None or attr_nodes is None:
            self._cold_build(ls, state, dsts)
            return None
        affected_nodes = set(affected_nodes) | set(attr_nodes)
        changed = self._diff_pairs(ls, affected_nodes)
        if changed is None or len(changed) > ENGINE_MAX_CHANGED_PAIRS:
            self._cold_build(ls, state, dsts)
            return None
        ov_flips, label_flips = self._diff_nodes(ls, affected_nodes)
        if self.src_name in ov_flips:
            # the root's own drain state gates route selection broadly
            self._cold_build(ls, state, dsts)
            return None
        # an overload flip changes the EFFECTIVE weight (INF <-> w) of
        # every edge out of the node even though raw metrics are
        # untouched: inject those pairs so the membership tests run with
        # eff() consulting the old vs new overload maps (node_users
        # alone cannot recover destinations that should START routing
        # through a just-undrained node)
        for x in ov_flips:
            for link in ls.links_from_node(x):
                if not link.is_up():
                    continue
                pair = (x, link.other_node(x))
                if pair not in changed:
                    w = self.eff_w.get(
                        pair, min(int(link.metric_from(x)), INF - 1)
                    )
                    sig = self.attr_sig.get(pair, ())
                    changed[pair] = (w, w, sig, sig)
        if len(changed) > ENGINE_MAX_CHANGED_PAIRS:
            self._cold_build(ls, state, dsts)
            return None

        graph = state.graph
        ep = sorted(
            {graph.node_index[u] for (u, v), _ in changed.items()}
            | {graph.node_index[v] for (u, v), _ in changed.items()}
        )
        if len(ep) > ENGINE_MAX_ENDPOINTS:
            self._cold_build(ls, state, dsts)
            return None
        if not ep:
            ep = [self.sid]

        # one fused dispatch: all-pairs + view + old/new endpoint rows
        # (+ on the fast path: speculative masked re-solve of every
        # destination against the RESIDENT masks, row-diffed on device)
        from openr_tpu.ops import spf_sparse

        view_srcs = spf_sparse.ell_source_batch(graph, ls, self.src_name)
        srcs_dev, w_sv = spf_sparse._batch_args(graph, view_srcs)
        ep_ids = _pad_ids(ep)
        use_fast = getattr(self, "masks_t", None) is not None
        dm_new_dev = None
        # increase-edge delta for the warm-started fixed point: pairs
        # whose collapsed min weight went UP since d_prev_dev's epoch.
        # An overload flip changes effective weights without touching
        # the raw metrics the tight test runs on — force a cold seed.
        inc = None
        if not ov_flips:
            inc = [
                (graph.node_index[u], graph.node_index[v], int(w_old))
                for (u, v), (w_old, w_new, _so, _sn) in changed.items()
                if w_new > w_old
            ]
            # both the single-chip and the sharded dispatches thread
            # the delta into the warm-seeded fixed point now
            _counters()["decision.ksp2_warm_dispatches"] += 1
        if self._mesh is not None and use_fast:
            # mesh twin of the fused speculative dispatch; nothing is
            # donated (residents keep their NamedSharding placement),
            # the rebind below is a plain replace
            d_all_dev, dm_new_dev, packed = (
                spf_sparse.sharded_ell_all_view_rows_masked(
                    state, srcs_dev, w_sv, ep_ids, self.d_prev_dev,
                    self.masks_t, self.dm_dev, self.sid,
                    ENGINE_ROW_BUDGET, len(self.dsts), self._mesh,
                    inc=inc,
                )
            )
        elif self._mesh is not None:
            if _fast_path_enabled():
                # fast path requested but no resident masks on-mesh
                # (budget refusal at cold build): typed, not silent
                self._note_mesh_fallback("no_resident_masks")
            d_all_dev, packed = spf_sparse.sharded_ell_all_view_rows(
                state, srcs_dev, w_sv, ep_ids, self.d_prev_dev,
                self._mesh, inc=inc,
            )
        elif use_fast:
            # openr-lint: disable=donation-hazard -- intentional: the
            # dispatch consumes the previous epoch's resident
            # d_prev_dev/dm_dev (dead after this call, no retry path)
            # and both are rebound to the fresh outputs right below
            d_all_dev, dm_new_dev, packed = spf_sparse.ell_all_view_rows_masked(
                state, srcs_dev, w_sv, ep_ids, self.d_prev_dev,
                self.masks_t, self.dm_dev, self.sid, ENGINE_ROW_BUDGET,
                inc=inc, defer=True,
            )
        else:
            # openr-lint: disable=donation-hazard -- intentional: same
            # consume-and-rebind discipline as the fast path above
            d_all_dev, packed = spf_sparse.ell_all_view_rows(
                state, srcs_dev, w_sv, ep_ids, self.d_prev_dev, inc=inc,
                defer=True,
            )
        # the single-chip dispatches DONATE d_prev_dev (and dm_dev on
        # the fast path): adopt the outputs NOW, before any fallback
        # below can hand the dead buffers to _cold_build (which reuses
        # d_prev_dev as its placeholder). The sharded dispatches donate
        # nothing, so for them this is a plain rebind.
        self.d_prev_dev = d_all_dev
        if dm_new_dev is not None:
            self.dm_dev = dm_new_dev
        if not isinstance(packed, np.ndarray):
            # single-chip deferred dispatch: the packed readback was
            # kicked copy_to_host_async inside the wrapper — reap it
            # AFTER the residents adopted the donated outputs so a
            # reap failure can never hand dead buffers to _cold_build
            packed = _da.reap_read(packed, kicked=True)
        b = len(view_srcs)
        p = len(ep_ids)
        view_packed = packed[: 2 * b]
        rows_new = {int(i): packed[2 * b + x] for x, i in enumerate(ep_ids)}
        rows_old = {
            int(i): packed[2 * b + p + x] for x, i in enumerate(ep_ids)
        }
        self._preload_view(ls, graph, view_srcs, view_packed)
        d_new_src = view_packed[0].astype(np.int64)

        aff1, aff2 = self._affected_dsts(
            ls, graph, changed, d_new_src, rows_new, rows_old
        )
        dst_set = set(self.dst_pos)
        # slot-map drift: a band patch that changes a node's in-edge
        # SET re-packs that row's slot assignments, silently re-aiming
        # every resident mask bit stored for those slots (soak repro
        # seed 40018: a dropped link shifted two slots and a
        # destination's masked solve excluded the wrong edges,
        # yielding a metric-15 second path where the truth was 8).
        # Metric-only patches keep the slot map stable. Destinations
        # whose stored paths touch a re-slotted node join aff1 — the
        # stale-mask bucket, re-solved with FRESH masks.
        # only the fast path holds RESIDENT masks; the slow path
        # rebuilds masks fresh from the current slot_of every event,
        # so there is nothing to go stale there
        if (
            graph.slot_of is not None
            and getattr(self, "masks_t", None) is not None
        ):
            for nm in affected_nodes:
                nid = graph.node_index.get(nm)
                if nid is None:
                    continue
                new_map = graph.slot_of.get(nid, {})
                old_map = self._slot_maps.get(nid)
                if old_map is not None and old_map != new_map:
                    if nm == self.src_name:
                        # every destination's mask holds its first-hop
                        # bits in the ROOT's row (build_edge_masks
                        # sets both endpoint rows), and node_users
                        # never indexes the root — a re-slotted root
                        # stales every mask
                        aff1 |= set(self.dst_pos)
                    else:
                        aff1 |= self.node_users.get(nm, set())
                self._slot_maps[nid] = new_map
        aff1 &= dst_set
        aff2 &= dst_set
        # label/overload materialization extras: paths are unchanged
        # (distance tests cover path changes) but the ROUTES built from
        # them embed labels / drain state — invalidate route reuse only
        route_extra: Set[str] = set()
        for x in ov_flips | label_flips:
            if x in self.dst_pos:
                route_extra.add(x)
            route_extra |= self.node_users.get(x, set())
        route_extra &= dst_set
        affected = aff1 | aff2 | route_extra | (self.host_dsts & dst_set)

        if len(affected) * ENGINE_FULL_REBUILD_FRACTION > len(dsts):
            self._cold_build(ls, state, dsts)
            return None

        if use_fast:
            # parse the on-device row diff: meta row carries the top-K
            # changed row ids and the total count
            meta = packed[2 * b + 2 * p]
            ids = meta[:ENGINE_ROW_BUDGET]
            count = int(meta[ENGINE_ROW_BUDGET])
            changed_rows = packed[2 * b + 2 * p + 1 :]
            # the speculative matrix was adopted right after the
            # dispatch, so dispatch-2 corrections scatter into the
            # CURRENT resident state
            row_map = {}
            if count <= ENGINE_ROW_BUDGET:
                for x, i in enumerate(ids):
                    if int(i) >= 0:
                        row_map[self.dsts[int(i)]] = changed_rows[x]
            else:
                # budget overflow: one extra readback of the full
                # matrix (rare — means a large fraction of rows moved);
                # under the mesh the batch carries pad rows — drop them
                dm_full = np.asarray(
                    _da.reap_read(dm_new_dev)
                )[: len(self.dsts)]
                moved = np.flatnonzero((dm_full != self.dm).any(axis=1))
                row_map = {self.dsts[int(i)]: dm_full[int(i)] for i in moved}
            # host-fallback dsts: adopt moved speculative rows into the
            # host mirror (keeps the overflow diff and future row
            # budgets quiet) but never re-trace from them
            for dst in self.host_dsts & set(row_map):
                self.dm[self.dst_pos[dst]] = row_map[dst]
            a_retrace = (
                (aff2 | set(row_map)) - aff1 - self.host_dsts
            ) & dst_set
            ok = True
            if aff1:
                # first paths changed: masks are stale for these — the
                # speculative rows are garbage by construction; re-solve
                # with fresh masks (dispatch 2) and scatter corrections
                ok = self._recompute(ls, state, sorted(aff1), d_new_src)
            if not ok:
                self._cold_build(ls, state, dsts)
                return None
            if a_retrace:
                unrealized = self._retrace_only(
                    ls, graph, sorted(a_retrace), row_map
                )
                if unrealized:
                    # masks drifted for these: full per-dst repair
                    if not self._recompute(
                        ls, state, sorted(unrealized), d_new_src
                    ):
                        self._cold_build(ls, state, dsts)
                        return None
            # a moved speculative row means the destination's second
            # paths may have changed even when no membership test
            # fired — its routes must not be served from the reuse
            # cache (the soak's stale-route half of the same finding)
            affected |= set(row_map) & dst_set
        else:
            recompute = sorted(aff1 | aff2)
            if recompute:
                ok = self._recompute(ls, state, recompute, d_new_src)
                if not ok:
                    self._cold_build(ls, state, dsts)
                    return None
        self._prime_all(ls)

        # commit snapshots
        for pair, (_w_old, w_new, _sig_old, sig_new) in changed.items():
            if w_new >= INF and sig_new is None:
                self.eff_w.pop(pair, None)
                self.attr_sig.pop(pair, None)
                for end in pair:
                    self.pairs_by_node.get(end, set()).discard(pair)
            else:
                self.eff_w[pair] = w_new
                self.attr_sig[pair] = sig_new
                for end in pair:
                    self.pairs_by_node.setdefault(end, set()).add(pair)
        for x in ov_flips:
            self.ov[x] = ls.is_node_overloaded(x)
        for x in label_flips:
            db = ls.get_adjacency_databases().get(x)
            self.node_label[x] = db.node_label if db else 0
        if any(
            w_old >= INF or w_new >= INF
            for (w_old, w_new, _so, _sn) in changed.values()
        ):
            self.ecc_hops = ls.get_max_hops_to_node(self.src_name)
        self.d_base = d_new_src.astype(np.int32)
        self.version = ls.topology_version
        self.aversion = ls.attributes_version
        _counters()["decision.ksp2_incremental_syncs"] += 1
        _counters()["decision.ksp2_affected_dsts"] += len(affected)
        self.last_affected = affected
        return affected

    # -- cold build --------------------------------------------------------

    def _note_mesh_fallback(self, reason: str) -> None:
        """The speculative fast path could not run mesh-wide: bump the
        typed counter AND stamp the active trace span — the drop
        forfeits the warm-dispatch win exactly when sharding activates,
        so it must never be silent (issue 7 satellite)."""
        _counters()["decision.ksp2.spec_mesh_fallbacks"] += 1
        from openr_tpu.telemetry import get_tracer

        tracer = get_tracer()
        span = tracer.span_active(
            "decision.ksp2.spec_mesh_fallback", reason=reason
        )
        tracer.end_span_active(span, reason=reason)

    def _cold_build(self, ls: LinkState, state, dsts: List[str]) -> None:
        from openr_tpu.decision import spf_solver as _ss
        from openr_tpu.ops import spf_sparse
        import jax
        import jax.numpy as jnp

        self.valid = False
        graph = state.graph
        self.state = state
        self.dsts = list(dsts)
        self.band_shapes = tuple(graph.bands)
        # per-node slot-map snapshot for drift detection (see sync):
        # inner dicts are immutable-in-practice (ell_patch replaces a
        # node's map wholesale), so references compare by content later
        self._slot_maps = (
            dict(graph.slot_of) if graph.slot_of is not None else {}
        )
        self._mesh_knob = _ENGINE_MESH
        self._mesh = (
            _ENGINE_MESH
            if _ENGINE_MESH is not None
            and graph.n_pad % _ENGINE_MESH.devices.size == 0
            else None
        )
        self.sid = graph.node_index.get(self.src_name)
        if self.sid is None:
            return
        self.dst_pos = {d: i for i, d in enumerate(dsts)}
        n = graph.n_pad

        # fused dispatch seeds the resident all-pairs matrix AND serves
        # the view; d_prev is a placeholder on the cold path
        view_srcs = spf_sparse.ell_source_batch(graph, ls, self.src_name)
        srcs_dev, w_sv = spf_sparse._batch_args(graph, view_srcs)
        placeholder = getattr(self, "d_prev_dev", None)
        if placeholder is None or placeholder.shape != (n, n):
            if self._mesh is not None:
                # allocate the placeholder ALREADY row-sharded: an
                # unsharded [n, n] zeros would commit n^2 x 4 B to the
                # default device — exactly the single-chip footprint
                # the mesh mode exists to avoid
                from jax.sharding import NamedSharding, PartitionSpec

                placeholder = jax.jit(
                    lambda: jnp.zeros((n, n), dtype=jnp.int32),
                    out_shardings=NamedSharding(
                        self._mesh,
                        PartitionSpec(spf_sparse.SOURCES_AXIS, None),
                    ),
                )()
            else:
                placeholder = jnp.zeros((n, n), dtype=jnp.int32)
        if self._mesh is not None:
            d_all_dev, packed = spf_sparse.sharded_ell_all_view_rows(
                state, srcs_dev, w_sv,
                np.asarray([self.sid], np.int32),
                placeholder, self._mesh,
            )
        else:
            # the dispatch DONATES the placeholder (which may be the
            # previous d_prev_dev): drop our reference first so a
            # failed dispatch can't leave a dead buffer behind for the
            # next cold build to reuse
            self.d_prev_dev = None
            d_all_dev, packed = spf_sparse.ell_all_view_rows(
                state, srcs_dev, w_sv,
                np.asarray([self.sid], np.int32),
                placeholder, defer=True,
            )
            packed = _da.reap_read(packed, kicked=True)
        b = len(view_srcs)
        self._preload_view(ls, graph, view_srcs, packed[: 2 * b])
        self.d_base = packed[0].astype(np.int32)
        self.d_prev_dev = d_all_dev

        # first paths traced from the device base row (identical to the
        # host get_kth_paths(.., 1) trace — same canonical order)
        cands_of = make_cands_of(ls, graph.node_index)
        transit_blocked = {
            name
            for name in graph.node_names
            if ls.is_node_overloaded(name) and name != self.src_name
        }
        self.first_paths: Dict[str, List[List[Link]]] = {}
        self.second_paths: Dict[str, List[List[Link]]] = {}
        self.excl: Dict[str, Set[Link]] = {}
        self.node_users: Dict[str, Set[str]] = {}
        traced = self._trace_many(
            ls, graph, cands_of, transit_blocked, dsts, self.d_base,
            True, [set()] * len(dsts),
        )
        for dst, paths in zip(dsts, traced):
            self.first_paths[dst] = paths
            self.excl[dst] = {l for p in paths for l in p}

        # masked rows for every destination, chunked like the original
        # prefetch; second paths traced from them
        self.dm = np.full((len(dsts), n), INF, dtype=np.int32)
        self.host_dsts: Set[str] = set()
        self.masks_t = None  # set below; must be None while the
        self.dm_dev = None  # chunked solves run (no resident scatter)
        self._solve_masked_batches(
            ls, state, dsts, cands_of, transit_blocked
        )
        self._prime_all(ls)

        # fast path (1 device round trip per metric-churn event): keep
        # every destination's edge masks and masked rows RESIDENT so
        # the next event's fused dispatch can speculatively re-solve
        # and row-diff them on device. Gated on the same mask-memory
        # budget as the chunked dispatch.
        slots = sum(band.rows * band.k for band in graph.bands)
        ndev = self._mesh.devices.size if self._mesh is not None else 1
        # under the mesh the destination batch pads to a device
        # multiple so the mask stack / dm residents stripe evenly over
        # the batch axis (ShardingPlan.batch3 / rows); the budget is
        # charged for the PADDED batch — what the device actually holds
        b_pad = -(-len(dsts) // ndev) * ndev
        if (
            _fast_path_enabled()
            and dsts
            and b_pad * 2 * max(1, slots) <= _ss.KSP2_DEVICE_MASK_BUDGET
        ):
            excl_sets = [self.excl[d] for d in dsts]
            # pad rows carry empty exclusion sets: their (unmasked)
            # speculative solves are diff-masked out by d_real in the
            # sharded dispatch, so their churn never reads back
            excl_sets += [set()] * (b_pad - len(dsts))
            masks_all, _ok = spf_sparse.build_edge_masks(graph, excl_sets)
            if self._mesh is not None:
                from openr_tpu.parallel.mesh import ShardingPlan

                plan = ShardingPlan(self._mesh)
                self.masks_t = tuple(
                    plan.place(m, plan.batch3) for m in masks_all
                )
                dm_pad = np.full((b_pad, n), INF, dtype=np.int32)
                dm_pad[: len(dsts)] = self.dm
                self.dm_dev = plan.place(dm_pad, plan.rows)
            else:
                self.masks_t = tuple(jnp.asarray(m) for m in masks_all)
                self.dm_dev = jnp.asarray(self.dm)
        elif _fast_path_enabled() and self._mesh is not None and dsts:
            # speculative path requested but the padded mask stack
            # exceeds the device budget: typed drop, never silent
            self._note_mesh_fallback("mask_budget")

        # graph-attribute snapshots for churn diffing
        self.eff_w, self.attr_sig = {}, {}
        for name in graph.node_names:
            if name not in graph.node_index:
                continue
            sigs = self._node_sigs(ls, name)
            weights = self._min_weights(sigs)
            for other, sig in sigs.items():
                self.eff_w[(name, other)] = weights[other]
                self.attr_sig[(name, other)] = sig
        self.pairs_by_node = {}
        for pair in self.eff_w:
            self.pairs_by_node.setdefault(pair[0], set()).add(pair)
            self.pairs_by_node.setdefault(pair[1], set()).add(pair)
        self.ov = {
            name: ls.is_node_overloaded(name)
            for name in graph.node_names
        }
        self.node_label = {
            name: db.node_label
            for name, db in ls.get_adjacency_databases().items()
        }
        self.ecc_hops = ls.get_max_hops_to_node(self.src_name)
        self.version = ls.topology_version
        self.aversion = ls.attributes_version
        self.valid = True
        _counters()["decision.ksp2_cold_builds"] += 1

    # -- diffing -----------------------------------------------------------

    @staticmethod
    def _node_sigs(ls: LinkState, a: str) -> Dict[str, Tuple]:
        """Materialization-relevant attributes of every (a, other) link
        direction in ONE pass over a's ordered links: next-hop
        addresses, interfaces, adj labels, and canonical link identity
        (identity changes can reorder the deterministic trace's
        candidate list). One pass matters: per-pair scans made diffing
        a single churn event O(degree^2) on high-degree spines."""
        sigs: Dict[str, List[Tuple]] = {}
        for link in ls.ordered_links_from_node(a):
            if not link.is_up():
                continue
            sigs.setdefault(link.other_node(a), []).append(
                (
                    link.iface_from(a),
                    link.nh_v4_from(a).addr,
                    link.nh_v6_from(a).addr,
                    link.adj_label_from(a),
                    link.metric_from(a),
                )
            )
        return {other: tuple(s) for other, s in sigs.items()}

    @staticmethod
    def _min_weights(sigs: Dict[str, Tuple]) -> Dict[str, int]:
        """Collapsed min-metric per neighbor, derived from the sig
        tuples (metric is each sig's last element) — the ONE source of
        the min(metric, INF-1) reduction."""
        return {
            other: min(min(int(s[-1]), INF - 1) for s in sig_list)
            for other, sig_list in sigs.items()
        }

    def _diff_pairs(
        self, ls: LinkState, affected_nodes: Set[str]
    ) -> Optional[Dict[Tuple[str, str], Tuple]]:
        """Directed pairs incident to the affected nodes whose collapsed
        min-metric or materialization attributes changed:
        (u, v) -> (w_old, w_new, sig_old, sig_new). Parallel links are
        first-class: the pair model keeps MIN weights (exact for
        first-path membership; a conservative lower bound for the
        masked-graph membership test) while the per-link sigs catch
        sibling-only changes, and the per-link ELL slots
        (spf_sparse.compile_ell direction="in") make every member
        individually maskable (reference: LinkState.h:82)."""
        changed: Dict[Tuple[str, str], Tuple] = {}
        graph_index = self.state.graph.node_index
        seen_pairs: Set[Tuple[str, str]] = set()
        # one links pass per origin node, not per pair
        sig_cache: Dict[str, Dict[str, Tuple]] = {}
        w_cache: Dict[str, Dict[str, int]] = {}

        def node_view(a: str):
            if a not in sig_cache:
                sig_cache[a] = self._node_sigs(ls, a)
                w_cache[a] = self._min_weights(sig_cache[a])
            return sig_cache[a], w_cache[a]

        for x in affected_nodes:
            if x not in graph_index:
                return None  # node set changed
            neighbors: Set[str] = set()
            for link in ls.links_from_node(x):
                if not link.is_up():
                    continue
                neighbors.add(link.other_node(x))
            # pairs that vanished entirely (link down/removed: neither
            # direction survives in the current link set) — probed via
            # the incident-pair index, NOT a scan of every pair (at 4k
            # nodes that scan made each churn event O(affected x E))
            for (u, v) in list(self.pairs_by_node.get(x, ())):
                if (u, v) in seen_pairs:
                    continue
                other = v if u == x else u
                if other not in neighbors:
                    changed[(u, v)] = (
                        self.eff_w.get((u, v), INF), INF, None, None,
                    )
                    seen_pairs.add((u, v))
            for other in neighbors:
                for pair in ((x, other), (other, x)):
                    if pair in seen_pairs:
                        continue
                    seen_pairs.add(pair)
                    a, bnode = pair
                    sigs_a, ws_a = node_view(a)
                    w_new = ws_a.get(bnode, INF)
                    sig_new = sigs_a.get(bnode, ())
                    w_old = self.eff_w.get(pair, INF)
                    sig_old = self.attr_sig.get(pair, ())
                    if w_old != w_new or sig_old != sig_new:
                        changed[pair] = (w_old, w_new, sig_old, sig_new)
        return changed

    def _diff_nodes(
        self, ls: LinkState, affected_nodes: Set[str]
    ) -> Tuple[Set[str], Set[str]]:
        ov_flips = {
            x
            for x in affected_nodes
            if self.ov.get(x, False) != ls.is_node_overloaded(x)
        }
        dbs = ls.get_adjacency_databases()
        label_flips = {
            x
            for x in affected_nodes
            if self.node_label.get(x, 0)
            != (dbs[x].node_label if x in dbs else 0)
        }
        return ov_flips, label_flips

    # -- affected-set computation -----------------------------------------

    def _affected_dsts(
        self,
        ls: LinkState,
        graph,
        changed: Dict[Tuple[str, str], Tuple],
        d_new_src: np.ndarray,
        rows_new: Dict[int, np.ndarray],
        rows_old: Dict[int, np.ndarray],
    ) -> Tuple[Set[str], Set[str]]:
        """Returns (first-path affected, masked/second-path affected) —
        split because the former invalidates the destination's MASKS
        (forcing a fresh masked solve) while the latter only needs the
        second paths re-derived."""
        index = graph.node_index
        dst_ids = np.asarray(
            [index[d] for d in self.dsts], dtype=np.int64
        )
        d_old_src = self.d_base.astype(np.int64)
        d_new = d_new_src  # already int64
        inf = np.int64(INF)

        aff = d_new[dst_ids] != d_old_src[dst_ids]
        aff2_vec = np.zeros(len(self.dsts), dtype=bool)

        dm = self.dm.astype(np.int64, copy=False)
        dm_total = dm[np.arange(len(self.dsts)), dst_ids]

        def eff(w, origin, ov_map):
            if w >= INF:
                return inf
            if ov_map.get(origin, False) and origin != self.src_name:
                return inf
            return np.int64(w)

        ov_new = {
            x: ls.is_node_overloaded(x) for x in graph.node_names
        }
        for (u, v), (w_old, w_new, _so, _sn) in changed.items():
            uid, vid = index[u], index[v]
            r_old_v = rows_old[vid].astype(np.int64, copy=False)
            r_new_v = rows_new[vid].astype(np.int64, copy=False)
            wo = eff(w_old, u, self.ov)
            wn = eff(w_new, u, ov_new)
            # first-path DAG membership, old and new graphs (exact)
            if wo < inf:
                lhs = d_old_src[uid] + wo + r_old_v[dst_ids]
                valid = (
                    (d_old_src[uid] < inf)
                    & (r_old_v[dst_ids] < inf)
                )
                aff |= valid & (lhs == d_old_src[dst_ids])
            if wn < inf:
                lhs = d_new[uid] + wn + r_new_v[dst_ids]
                valid = (d_new[uid] < inf) & (r_new_v[dst_ids] < inf)
                aff |= valid & (lhs == d_new[dst_ids])
            # masked-graph membership bound (conservative: base
            # distances lower-bound masked distances). A destination
            # with dm_total == INF is disconnected in its masked graph;
            # metric-only churn cannot create connectivity, so those
            # rows are only dirtied by a link APPEARING (w: INF ->
            # finite) — without this guard the <= test against INF
            # fires for every disconnected row and the engine
            # degenerates to cold rebuilds.
            reachable_m = dm_total < inf
            if wo < inf:
                lhs = dm[:, uid] + wo + r_old_v[dst_ids]
                valid = (
                    (dm[:, uid] < inf)
                    & (r_old_v[dst_ids] < inf)
                    & reachable_m
                )
                aff2_vec |= valid & (lhs <= dm_total)
            if wn < inf:
                lhs = d_new[uid] + wn + r_new_v[dst_ids]
                valid = (
                    (d_new[uid] < inf)
                    & (r_new_v[dst_ids] < inf)
                    & reachable_m
                )
                aff2_vec |= valid & (lhs <= dm_total)
            if wo >= inf and wn < inf:
                # edge usable where it was not (link appeared, or its
                # origin was undrained — hence EFFECTIVE weights, not
                # raw: overload flips are injected with equal raw w):
                # disconnected masked rows may reconnect
                aff2_vec |= ~reachable_m
        aff1 = {self.dsts[i] for i in np.flatnonzero(aff)}
        aff2 = {self.dsts[i] for i in np.flatnonzero(aff2_vec)}
        return aff1, aff2

    # -- recompute ---------------------------------------------------------

    def _retrace_only(
        self, ls: LinkState, graph, dsts: List[str],
        row_map: Dict[str, np.ndarray],
    ) -> Set[str]:
        """Fast-path update for destinations whose MASKS are unchanged:
        adopt the speculative masked row (when it moved) and re-trace
        second paths with the current weights. First paths and
        exclusion sets stay as cached.

        Returns the destinations whose row could NOT be realized by a
        trace (a finite masked total with no path walking to it): that
        means the resident masks drifted from the destination's true
        exclusion set, so the speculative row is bogus — the caller
        must _recompute them from scratch (fresh first paths + masks).
        The mixed-churn soak caught exactly this as a silently dropped
        second path (seed 9013: stale masks yielded total 6 where the
        true masked distance was 8, the trace found nothing, and the
        destination was never invalidated)."""
        cands_of = make_cands_of(ls, graph.node_index)
        transit_blocked = {
            name
            for name in graph.node_names
            if ls.is_node_overloaded(name) and name != self.src_name
        }
        for dst in dsts:
            row = row_map.get(dst)
            if row is not None:
                self.dm[self.dst_pos[dst]] = row
            for path in self.second_paths.get(dst, []):
                for x in _path_nodes(self.src_name, path):
                    users = self.node_users.get(x)
                    if users is not None:
                        users.discard(dst)
        traced = self._trace_many(
            ls, graph, cands_of, transit_blocked, dsts,
            np.ascontiguousarray(
                self.dm[[self.dst_pos[d] for d in dsts]]
            ),
            False, [self.excl[d] for d in dsts],
        )
        unrealized: Set[str] = set()
        for dst, paths in zip(dsts, traced):
            if not paths:
                # empty trace: either the row is finite but unwalkable
                # (masks drifted toward extra paths) or INF where the
                # true masked graph has a path (masks drifted toward
                # extra exclusions) — indistinguishable without fresh
                # masks, and a genuinely second-path-less destination
                # just re-confirms cheaply. Recompute all of them.
                unrealized.add(dst)
                continue
            self.second_paths[dst] = paths
            for path in paths:
                for x in _path_nodes(self.src_name, path):
                    self.node_users.setdefault(x, set()).add(dst)
        return unrealized

    def _recompute(
        self, ls: LinkState, state, affected: List[str],
        d_new_src: np.ndarray,
    ) -> bool:
        from openr_tpu.decision import spf_solver as _ss
        from openr_tpu.ops import spf_sparse

        graph = state.graph
        cands_of = make_cands_of(ls, graph.node_index)
        transit_blocked = {
            name
            for name in graph.node_names
            if ls.is_node_overloaded(name) and name != self.src_name
        }
        for dst in affected:
            # drop stale reverse-index entries
            for path in self.first_paths.get(dst, []) + self.second_paths.get(
                dst, []
            ):
                for x in _path_nodes(self.src_name, path):
                    users = self.node_users.get(x)
                    if users is not None:
                        users.discard(dst)
        traced = self._trace_many(
            ls, graph, cands_of, transit_blocked, affected,
            d_new_src.astype(np.int32), True,
            [set()] * len(affected),
        )
        for dst, paths in zip(affected, traced):
            self.first_paths[dst] = paths
            self.excl[dst] = {l for p in paths for l in p}

        self.host_dsts -= set(affected)
        self._solve_masked_batches(
            ls, state, affected, cands_of, transit_blocked
        )
        return True

    def _solve_masked_batches(
        self, ls, state, dsts, cands_of, transit_blocked
    ) -> None:
        """Masked-SPF rows + second-path traces + dm/node_users updates
        for a destination subset (shared by cold build and incremental
        recompute; the two loops MUST stay identical — fallback
        accounting drifting between them was a review finding)."""
        from openr_tpu.decision import spf_solver as _ss
        from openr_tpu.ops import spf_sparse

        graph = state.graph
        chunk = _ss._ksp2_chunk(graph)

        def _submit(batch):
            """Stage 1 of the chunk pipeline: mask build + (async)
            masked solve + resident masks/dm scatter, all chained on
            the device stream. Returns the in-flight context
            ``(batch, ok, drows_dev, drows)`` — exactly one of the
            last two is set, depending on the mesh path."""
            # pad to a power-of-two bucket (capped at the chunk) so the
            # masked kernel compiles a handful of shapes, not one per
            # distinct affected-set size
            bucket = 8
            while bucket < len(batch):
                bucket *= 2
            bucket = min(bucket, chunk)
            if self._mesh is not None:
                # sharded batches divide destinations over the mesh
                ndev = self._mesh.devices.size
                bucket = max(bucket, ndev)
                bucket = ((bucket + ndev - 1) // ndev) * ndev
            excl_sets = [self.excl[d] for d in batch]
            pad = bucket - len(batch)
            masks, ok = spf_sparse.build_edge_masks(
                graph, excl_sets + [set()] * pad
            )
            drows_dev = None
            if self._mesh is not None:
                drows = spf_sparse.sharded_ell_masked_distances_resident(
                    state, self.sid, masks, self._mesh
                )
            else:
                # committed chain: the masked rows are kicked
                # copy_to_host_async; the resident scatter below chains
                # off the DEVICE rows, and the host copy is reaped once
                drows_dev = spf_sparse.ell_masked_distances_resident(
                    state, self.sid, masks, defer=True
                )
                drows = None
            _counters()["decision.ksp2_device_batches"] += 1
            if getattr(self, "masks_t", None) is not None:
                # fast path: keep the RESIDENT masks and masked-row
                # matrix in sync so the next event's speculative solve
                # uses current exclusions
                import jax.numpy as jnp

                ids = jnp.asarray(
                    np.asarray(
                        [self.dst_pos[d] for d in batch], np.int32
                    )
                )
                self.masks_t = tuple(
                    m_res.at[ids].set(jnp.asarray(m_new[: len(batch)]))
                    for m_res, m_new in zip(self.masks_t, masks)
                )
                rows_src = (
                    drows_dev[: len(batch)]
                    if drows_dev is not None
                    else jnp.asarray(drows[: len(batch)])
                )
                self.dm_dev = self.dm_dev.at[ids].set(rows_src)
            return batch, ok, drows_dev, drows

        def _settle(batch, ok, drows_dev, drows):
            """Stage 2: reap the masked rows, settle dm + fallback
            accounting, trace second paths — host work the NEXT
            chunk's already-submitted solve overlaps."""
            if drows is None:
                drows = _da.reap_read(drows_dev, kicked=True)
            traceable: List[int] = []
            for i, dst in enumerate(batch):
                if not ok[i]:
                    _counters()["decision.ksp2_host_fallbacks"] += 1
                    self.host_dsts.add(dst)
                    self.second_paths.pop(dst, None)
                    # keep the (unrepresentable-mask) solve row anyway:
                    # it is deterministic, so the fast path's on-device
                    # row diff stays quiet for this destination instead
                    # of burning a gather slot every event; host_dsts
                    # membership keeps it out of every cache read
                    self.dm[self.dst_pos[dst]] = drows[i]
                    continue
                self.dm[self.dst_pos[dst]] = drows[i]
                traceable.append(i)
            traced = self._trace_many(
                ls, graph, cands_of, transit_blocked,
                [batch[i] for i in traceable],
                np.ascontiguousarray(np.asarray(drows)[traceable]),
                False, [self.excl[batch[i]] for i in traceable],
            )
            for i, paths in zip(traceable, traced):
                self.second_paths[batch[i]] = paths

        # ONE-DEEP chunk pipeline: chunk i+1's masked solve is
        # submitted before chunk i's rows are reaped, so the device
        # round trip amortizes across in-flight chunks. Safe because
        # ``self.excl`` is fixed for the whole call (every chunk's
        # masks derive from the same exclusion table) and the settle
        # stage touches only host mirrors. The mesh path degrades to
        # eager per-chunk order — the sharded solve already returns
        # host rows, so there is nothing in flight to overlap.
        inflight = None
        for start in range(0, len(dsts), chunk):
            staged = _submit(dsts[start : start + chunk])
            if inflight is not None:
                if staged[2] is not None:
                    _da.note_pipelined_dispatch(2)
                    _da.note_overlapped_reap()
                _settle(*inflight)
            inflight = staged
        if inflight is not None:
            _settle(*inflight)
        for dst in dsts:
            if dst in self.host_dsts:
                continue
            for path in self.first_paths[dst] + self.second_paths.get(
                dst, []
            ):
                for x in _path_nodes(self.src_name, path):
                    self.node_users.setdefault(x, set()).add(dst)

    def _trace_arrays(self, ls, graph, cands_of, transit_blocked):
        """Per-event cache of the native tracer's int-encoded candidate
        structure. One build serves every trace site of the event (cold
        build first paths, recompute, retrace, masked second paths);
        None when the native core is unavailable (callers fall back to
        the Python tracer)."""
        from openr_tpu.graph import native_spf

        if not native_spf.is_available():
            return None
        key = (ls.topology_version, ls.attributes_version)
        cached = getattr(self, "_tarrays", None)
        if (
            cached is not None
            and cached[0] == key
            and cached[1] is graph
        ):
            return cached[2]
        arrays = _TraceArrays(graph, cands_of, transit_blocked)
        self._tarrays = (key, graph, arrays)
        return arrays

    def _trace_many(
        self, ls, graph, cands_of, transit_blocked, dsts, rows,
        shared_row, excls,
    ) -> List[List[List[Link]]]:
        """THE trace front-end for every per-event path enumeration:
        native batch when the core is available, else the Python tracer
        per destination — one site to keep the two byte-identical.
        ``rows``: one [n_pad] row (shared_row) or [len(dsts), n_pad];
        ``excls``: per-dst exclusion sets (empty for first paths)."""
        arrays = self._trace_arrays(ls, graph, cands_of, transit_blocked)
        if arrays is not None:
            got = arrays.trace(
                self.sid,
                np.asarray(
                    [graph.node_index[d] for d in dsts], np.int32
                ),
                rows, shared_row, excls,
            )
            if got is not None:
                return got
        shared_preds: Optional[Dict[str, list]] = (
            {} if shared_row else None
        )
        row_list = rows.tolist() if shared_row else None
        return [
            trace_paths_from_row(
                self.src_name, dst, graph.node_index,
                row_list if shared_row else rows[i].tolist(),
                excls[i], cands_of, transit_blocked,
                preds_cache=(
                    shared_preds if not excls[i] else None
                ),
            )
            for i, dst in enumerate(dsts)
        ]

    # -- priming / view preload -------------------------------------------

    def _prime_all(self, ls: LinkState) -> None:
        for dst in self.dsts:
            if dst in self.host_dsts:
                continue  # LinkState computes these lazily (host SPF)
            ls.prime_kth_paths(
                self.src_name, dst, 1, self.first_paths[dst]
            )
            ls.prime_kth_paths(
                self.src_name, dst, 2, self.second_paths.get(dst, [])
            )

    def _preload_view(self, ls, graph, view_srcs, view_packed) -> None:
        from openr_tpu.decision import spf_solver as _ss

        _ss._ELL_RESIDENT.preload_view(
            ls, graph, list(view_srcs), np.asarray(view_packed)
        )
