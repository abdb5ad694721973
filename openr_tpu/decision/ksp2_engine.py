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
engine's activation bound) a full all-sources solve is ONE source
block. A churn event is served by TWO programs, split by who reads the
result when:

- the ROWS solve (spf_sparse._ell_view_ep_rows), on the critical path:
  the fixed point from the root's view batch and the changed-edge
  endpoints only (40-48 source rows), warm-seeded from their rows of
  the previous event's matrix. It returns one fused packet: the SPF
  view batch (served to SpfView, saving its separate dispatch) plus
  old/new distance rows for the endpoints. The sync WAITS for this
  one (dispatch to readback it is the span ops.ksp2_all_pairs, which
  the KSP2 cells read as ksp2_all_pairs_ms), and while it is in flight
  does the host work that needs no row (the trace arrays' patch, the
  walk-reach proof);
- the MATRIX solve (spf_sparse._ell_all_view_rows), behind the window:
  the same warm fixed point from every node, relaxing the previous
  matrix in place (donated). No sync reads it on the host; it is the
  NEXT event's warm seed and the source of its old rows. It is
  dispatched after the last masked batch of the window, so that in
  device order it stands behind everything the window's host code
  waits on, and nobody blocks on it: ``d_prev_dev`` is a future until
  the next event's rows solve queues behind it.

Steady-state churn that touches no cached path WAITS for one device
round trip of 40-48 rows and does O(changed) host work. A mesh engine
(set_engine_mesh) keeps both in one fused, sharded program: its rows
come back on the host with the call.
"""

from __future__ import annotations

import time
from typing import Dict, FrozenSet, List, NamedTuple, Optional, Set, Tuple

import numpy as np

from openr_tpu.analysis.annotations import (
    mirrored_by,
    resident_buffers,
    thread_confined,
)
from openr_tpu.graph.linkstate import Link, LinkState
from openr_tpu.ops import dispatch_accounting as _da
from openr_tpu.ops.spf import INF
from openr_tpu.telemetry import get_tracer

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


# churn larger than this falls back to a full (cold) rebuild; the
# rows solve pads its endpoint rows, and both solves their increase
# list, to these, so every sync of an engine runs one compiled shape of
# each
ENGINE_MAX_CHANGED_PAIRS = 64
ENGINE_MAX_ENDPOINTS = 32


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


# one instance per engine, built and patched inside that engine's
# sync: the engine's own "owner" confinement covers it
@thread_confined(
    "owner", "_blocked_names", "_rows", "blocked", "link", "off", "uid",
    "w",
)
class _TraceArrays:
    """Int-encoded view of the candidate structure for the native batch
    tracer (native/spfcore.cpp ksp2_trace_batch): a candidate CSR in
    the same canonical order make_cands_of yields, a link table for
    id<->object mapping, and the transit-blocked bitmap. Built whole
    once per engine epoch and PATCHED per churn event: only the rows of
    the nodes the LinkState journals name are re-derived (building it
    whole sorts every node's links, O(E log E) of Python per event,
    which at 1016 nodes was the largest single item of an incremental
    sync) and written into the flat CSR where they lie (flattening it
    anew concatenates every node's row to move two). Shared by every
    trace site of an event; the Python tracer remains the fallback and
    the semantic reference."""

    __slots__ = (
        "off", "link", "uid", "w", "links", "lid_of", "blocked",
        "n_pad", "index", "_rows", "_excl_ids", "_blocked_names",
    )

    def __init__(self, graph, cands_of, transit_blocked):
        self.index = graph.node_index
        self.n_pad = graph.n_pad
        self.links: List[Link] = []
        # keyed by the Link VALUE (its hash is cached), not id(): the
        # Python tracer excludes via `link not in excluded` — a link
        # that flapped down and back up is a fresh-but-EQUAL object,
        # and an identity key would silently drop its exclusion
        self.lid_of: Dict[Link, int] = {}
        # id(exclusion set) -> (the set, its links' ids)
        self._excl_ids: Dict[int, Tuple[Set[Link], np.ndarray]] = {}
        # per node: (link ids, origin ids, weights), in canonical order
        self._rows: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = [
            self._row(cands_of(v)) for v in graph.node_names
        ]
        self._flatten()
        self._blocked_names = None
        self.set_blocked(transit_blocked)

    def _row(self, cands):
        link_l: List[int] = []
        uid_l: List[int] = []
        w_l: List[int] = []
        links, lid_of = self.links, self.lid_of
        for lnk, _u, uuid, w in cands:
            lid = lid_of.get(lnk)
            if lid is None:
                lid = lid_of[lnk] = len(links)
                links.append(lnk)
            else:
                # an equal link re-created by a flap: paths must carry
                # the LIVE object, whose metrics are the current ones
                links[lid] = lnk
            link_l.append(lid)
            uid_l.append(-1 if uuid is None else int(uuid))
            w_l.append(int(w))
        return (
            np.asarray(link_l, np.int32), np.asarray(uid_l, np.int32),
            np.asarray(w_l, np.int32),
        )

    def _flatten(self) -> None:
        off = np.zeros(self.n_pad + 1, np.int32)
        counts = [len(r[0]) for r in self._rows]
        off[1 : len(counts) + 1] = np.cumsum(counts)
        off[len(counts) + 1 :] = off[len(counts)]
        self.off = off
        for x, name in enumerate(("link", "uid", "w")):
            setattr(self, name, np.concatenate(
                [r[x] for r in self._rows]
            ))

    def rows_of(self, node_id: int):
        """(link ids, origin ids, weights) of a node's candidate
        in-links, in canonical order."""
        return self._rows[node_id]

    def patch(
        self, cands_of, dirty, transit_blocked
    ) -> Tuple[List[Tuple], int, int]:
        """Re-derive the rows of the ``dirty`` nodes (names) — a changed
        adjacency moves the candidate rows of its two ends only — and
        put each into the flat CSR where the old one lies: written over
        it where it is as long (a metric change), spliced in, with the
        offsets behind it moved, where it is not (a flap). Returns
        first (node id, what moved) for the nodes whose candidates are
        not, place for place, the origins they were: ``(place, +1)``
        where one link came in at ``place``, ``(place, -1)`` where the
        one at ``place`` went, None where more than that changed; then
        how many rows were written in place and how many spliced."""
        reordered = []
        written = spliced = 0
        off = self.off
        for name in dirty:
            i = self.index.get(name)
            if i is None:
                continue
            row = self._row(cands_of(name))
            if not np.array_equal(row[1], self._rows[i][1]):
                reordered.append((i, _one_moved(self._rows[i][1], row[1])))
            self._rows[i] = row
            start, end = int(off[i]), int(off[i + 1])
            grew = len(row[0]) - (end - start)
            for flat, part in zip(("link", "uid", "w"), row):
                was = getattr(self, flat)
                if grew == 0:
                    was[start:end] = part
                else:
                    setattr(self, flat, np.concatenate(
                        (was[:start], part, was[end:])
                    ))
            if grew == 0:
                written += 1
            else:
                off[i + 1 :] += grew
                spliced += 1
        self.set_blocked(transit_blocked)
        return reordered, written, spliced

    def set_blocked(self, transit_blocked) -> None:
        """The transit-blocked bitmap, rewritten where the set of
        drained nodes is not the one it was written from."""
        if transit_blocked == self._blocked_names:
            return
        blocked = np.zeros(self.n_pad, np.uint8)
        for nm in transit_blocked:
            bi = self.index.get(nm)
            if bi is not None:
                blocked[bi] = 1
        self.blocked = blocked
        self._blocked_names = frozenset(transit_blocked)

    def _excl_arrays(self, excls):
        """Per-dst exclusion ranges; a link absent from the current
        candidate table is down, so its exclusion is vacuous."""
        held, lid_of = self._excl_ids, self.lid_of
        ids: List[np.ndarray] = []
        off = np.zeros(len(excls) + 1, np.int32)
        for i, excl in enumerate(excls):
            # a set is traced many times over (every re-solve of its
            # row) and link ids only ever get added: looked up once a
            # set object, which the entry keeps alive
            got = held.get(id(excl))
            if got is None or got[0] is not excl:
                got = held[id(excl)] = (excl, np.asarray(
                    [lid for lid in map(lid_of.get, excl) if lid is not None],
                    np.int32,
                ))
            ids.append(got[1])
            off[i + 1] = off[i] + len(got[1])
        if len(held) > 4 * len(self._rows):
            held.clear()
        return off, (
            np.concatenate(ids) if ids else np.zeros(0, np.int32)
        )

    def trace(self, src_id, dst_ids, rows, shared_row, excls,
              reach=None):
        """Batch-enumerate via the native core; None when it is
        unavailable. Paths come back as Link-object lists, identical
        in content and order to trace_paths_from_row. ``reach``: an
        int32 [len(dst_ids), n_pad] of -1 that comes back, for every
        node a destination's traces consulted, with how far down the
        node's candidate list they looked (native_spf.trace_batch)."""
        from openr_tpu.graph import native_spf

        excl_off, excl_ids = self._excl_arrays(excls)
        got = native_spf.trace_batch(
            self.n_pad, len(self.links), self.off, self.link,
            self.uid, self.w, src_id, self.blocked,
            np.ascontiguousarray(dst_ids, np.int32),
            np.ascontiguousarray(rows, np.int32),
            shared_row, excl_off, excl_ids, reach,
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


# reach2, per destination and node (native/spfcore.cpp): -1 where the
# searches never came; else, of the searches that found a path, 1 + the
# place of the last candidate they examined (low 14 bits) and _RAN_OUT
# where one ran the list out; and _IN_LAST where the last search, the
# one that found none, reached the node
_DEPTH = (1 << 14) - 1
_RAN_OUT = 1 << 14
_IN_LAST = 1 << 15
# "read to its end, and to any length": what is said where nobody knows
# (not _IN_LAST: as an edge's tail, unknown must not pass for stuck)
_ALL_CONSULTED = _RAN_OUT | _DEPTH


def _one_moved(was: np.ndarray, now: np.ndarray):
    """``now`` is ``was`` with one element put in -> (place, +1), or
    with one taken out -> (place, -1); anything else -> None."""
    if len(now) == len(was) + 1:
        longer, shorter, step = now, was, 1
    elif len(was) == len(now) + 1:
        longer, shorter, step = was, now, -1
    else:
        return None
    differ = np.flatnonzero(longer[:-1] != shorter)
    place = int(differ[0]) if len(differ) else len(shorter)
    if not np.array_equal(longer[place + 1 :], shorter[place:]):
        return None
    return place, step


def _longest(traced: List[List[List[Link]]]) -> int:
    """Links on the longest path of a batch of traces (``hops`` on
    decision.ksp2_trace)."""
    return max(
        (len(path) for paths in traced for path in paths), default=0
    )


def _masked_buckets(chunk: int) -> Tuple[int, ...]:
    """Batch sizes the masked solve is compiled for: a sixteenth and a
    half of the chunk, and the chunk (64 / 512 / 1024 rows on a graph
    whose masks fit the budget whole). A batch of 8 rows and one of 64
    cost the device about the same, so finer buckets bought compiles,
    not time: the size of an affected set is the data's, and a bucket
    first reached inside a churn window stalls the rebuild while it
    compiles."""
    return tuple(sorted({max(8, chunk // 16), max(8, chunk // 2), chunk}))


class _ViewBatch(NamedTuple):
    """The root's view batch as an engine holds it across syncs."""

    index: Dict[str, int]  # the graph's node index it was derived under
    near: FrozenSet[str]  # the root and its up-neighbours, by name
    srcs: List[int]  # their ids, padded: ell_source_batch
    srcs_dev: object  # ... on the device
    w_sv_dev: object  # the root's direct metrics to them, on the device


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
)
@resident_buffers("d_prev_dev")
# externally serialized, never internally locked: every engine is
# created and driven by exactly one plane — Decision's under evb, a
# ctrl handler's under SolverCtrlHandler._lock, the twin's on its one
# thread. The shared-state rule merges all instances by class, so
# cross-role access to one instance is impossible by construction —
# hence "owner" confinement (same contract as WorldManager).
@thread_confined(
    "owner",
    "_blocked",
    "_carried",
    "_journal_read",
    "_link_slots",
    "_masked_warm",
    "_matrix_due",
    "_matrix_passes",
    "_slots_seen",
    "_mesh",
    "_mesh_knob",
    "_primed",
    "_tarrays",
    "_view",
    "attr_sig",
    "aversion",
    "band_shapes",
    "d_base",
    "d_prev_dev",
    "dm",
    "dst_ids",
    "dst_pos",
    "dst_rows",
    "dsts",
    "eff_w",
    "excl",
    "excl_slots",
    "excl_users",
    "first_paths",
    "host_dsts",
    "node_label",
    "node_users",
    "ov",
    "pairs_by_node",
    "second_paths",
    "sid",
    "staged",
    "state",
    "syncs_worked",
    "valid",
    "reach2",
    "version",
)
class Ksp2Engine:
    """Per-(LinkState, root) incremental KSP2 state. Invalid until the
    first successful cold build.

    A ``sync`` may run before the ``build_route_db`` that uses it (the
    solver stages one under the debounce's policy wait), so what a sync
    found moved is CARRIED until a build takes it (``take_affected``).
    The invariant: the set handed to a ``build_route_db`` covers every
    destination whose paths or routes moved since the previous
    ``build_route_db`` took one."""

    def __init__(self, src_name: str) -> None:
        self.src_name = src_name
        self.valid = False
        # the carry: the union of the affected sets of every sync since
        # the last take; None (a cold build: every destination) absorbs
        self._carried: Optional[Set[str]] = None
        # a sync ran ahead of its build (SpfSolver.speculate_views sets
        # it, the build that takes the carry clears it)
        self.staged = False
        # syncs that found work to do (a cold build or an incremental
        # step: the ones that open a decision.ksp2_sync span)
        self.syncs_worked = 0
        # _mesh_knob: the module knob as of the last (re)build — the
        # change-detection identity. _mesh: the mesh the resident
        # arrays are ACTUALLY sharded over (None when the knob is off
        # OR the graph's n_pad does not divide by the mesh size, in
        # which case the single-chip dispatch runs instead).
        self._mesh_knob = _ENGINE_MESH
        self._mesh = None
        # (band shapes, n_pad, root) the masked buckets are compiled for
        self._masked_warm = None
        # ((topology, attributes) versions, _TraceArrays) of the last
        # trace; None until one ran on the native core
        self._tarrays = None
        # ((versions from, versions to), nodes): the last reading of
        # the LinkState journals, which the sync and the trace arrays
        # it patches both ask for
        self._journal_read = None
        # the matrix solve the sync in hand still owes its window:
        # (resident state, the window's increase triple on the device),
        # from the rows dispatch until _dispatch_matrix sends it
        self._matrix_due = None
        # pass counts of matrix solves sent, on the device with their
        # readback kicked: booked once landed (_book_matrix_passes)
        self._matrix_passes: List = []
        self._drop_held()

    def _drop_held(self) -> None:
        """Forget the tables derived from the WHOLE graph that a warm
        sync patches from the window's change instead of deriving them
        again: a cold build makes them anew, and an engine that is not
        valid holds none."""
        # {(root, destination, rank): paths}: what _prime_all hands
        # LinkState's kth-path cache, both ranks of every destination
        # the host does not answer for
        self._primed: Dict[Tuple[str, str, int], List[List[Link]]] = {}
        # the drained nodes no path may run through (never the root),
        # as self.ov has them
        self._blocked: Optional[Set[str]] = None
        self._view: Optional[_ViewBatch] = None

    # -- public entry ------------------------------------------------------

    def sync(self, ls: LinkState, dsts: List[str]) -> Optional[Set[str]]:
        """Bring the cache to ls.topology_version, prime the LinkState
        kth-path cache for every destination, and return the set of
        destination names whose paths may have changed since the
        engine's own version. Returns None when the engine had to
        cold-rebuild or cannot run (caller falls back). What a build
        may reuse is not this return but the carry (``take_affected``):
        a sync is not pure in the version, and the one that did the
        work may have run before the build that asks.

        A sync at the version the engine is already at does no work: it
        returns the empty set, opens no ``decision.ksp2_sync`` span and
        moves no counter (the kth-path cache was not invalidated, so
        priming is in place).

        The whole device round trip runs inside one accounting window:
        every device readback must ride the committed chain
        (``aot_call`` + async kick, reaped via ``reap_read``), and the
        ``ops.host_touches.ksp2_window`` observation is the gate."""
        from openr_tpu.decision import spf_solver as _ss

        with _da.event_window("ksp2_window"):
            try:
                state = _ss._ELL_RESIDENT.state_for(ls)
                fits = self._fits(state, dsts)
                if (
                    fits
                    and ls.topology_version == self.version
                    and ls.attributes_version == self.aversion
                ):
                    return set()
                self.syncs_worked += 1
                with get_tracer().span(
                    "decision.ksp2_sync", changed_pairs=0, refreshed_rows=0
                ) as span:
                    affected = None
                    if fits:
                        affected = self._sync_window(ls, state, dsts, span)
                    else:
                        self._cold_build(ls, state, dsts)
                    if span is not None:
                        # a cold build re-derives every destination
                        span.attrs["cold"] = affected is None
                        span.attrs["affected"] = (
                            len(dsts) if affected is None else len(affected)
                        )
                    return affected
            except BaseException:
                # torn between two versions: the next sync (the
                # rebuild's own, where a stage raised) builds cold
                self.invalidate()
                raise

    def take_affected(self) -> Optional[Set[str]]:
        """Hand a ``build_route_db`` the carry and start a new one: the
        destinations whose paths or routes moved in any sync since the
        previous take, None for all of them (a cold build in between,
        or an engine that is not valid)."""
        carried, self._carried = self._carried, set()
        self.staged = False
        return carried

    def invalidate(self) -> None:
        """Make the next sync a cold build (and the next take "all")."""
        self.valid = False
        self._carried = None
        self.staged = False
        # a dispatch that raised may have consumed the donated buffer;
        # a matrix solve still owed would warm-seed an epoch that the
        # cold build replaces whole
        self.d_prev_dev = None
        self._matrix_due = None
        self._drop_held()

    def _fits(self, state, dsts: List[str]) -> bool:
        """The engine was built for this resident state, root and
        destination list: an incremental sync (or none) will do."""
        return (
            self.valid
            and state is self.state
            and dsts == self.dsts
            and self.sid == state.graph.node_index.get(self.src_name)
            # a widened band (ell_patch grew a slot class in place)
            # changed the band tensor shapes the masked buckets were
            # compiled for: re-seed everything from the new shapes
            and tuple(state.graph.bands) == self.band_shapes
            # the engine-mesh knob changed: resident arrays carry the
            # old sharding — re-seed under the new one
            and self._mesh_knob is _ENGINE_MESH
        )

    def _sync_window(
        self, ls: LinkState, state, dsts: List[str], span=None
    ) -> Optional[Set[str]]:
        tracer = get_tracer()
        # what the window changed: the journals' nodes, their links'
        # pairs against the engine's snapshot, the drain and label
        # flips; ``flips`` stays None where the answer is a cold build
        with tracer.span("decision.ksp2_diff", nodes=0, pairs=0) as diff:
            affected_nodes = self._journal_nodes(
                ls, (self.version, self.aversion)
            )
            changed = flips = None
            if affected_nodes is not None:
                changed = self._diff_pairs(ls, affected_nodes)
                if (
                    changed is not None
                    and len(changed) <= ENGINE_MAX_CHANGED_PAIRS
                ):
                    flips = self._diff_nodes(ls, affected_nodes)
            if diff is not None:
                diff.attrs["nodes"] = len(affected_nodes or ())
                diff.attrs["pairs"] = len(changed or ())
        if span is not None and changed is not None:
            span.attrs["changed_pairs"] = len(changed)
        if flips is None:
            self._cold_build(ls, state, dsts)
            return None
        ov_flips, label_flips = flips
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
        # the overload map and the transit-blocked set as they are NOW:
        # the ones held, with the window's flips applied (the root's
        # own flip went to a cold build above)
        ov_new, blocked = self.ov, self._blocked
        if ov_flips:
            ov_new, blocked = dict(ov_new), set(blocked)
            for x in ov_flips:
                ov_new[x] = ls.is_node_overloaded(x)
                (blocked.add if ov_new[x] else blocked.discard)(x)

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

        # the rows this window reads: view + old/new endpoint rows
        from openr_tpu.ops import spf_sparse

        view_srcs, srcs_dev, w_sv, view_reused = self._view_batch(
            ls, graph, affected_nodes
        )
        counters = _counters()
        patched_were = counters["decision.ksp2_trace_rows_patched"]
        spliced_were = counters["decision.ksp2_trace_rows_spliced"]
        # padded to the limits checked above, as the cold build pads
        # its own: every sync of an engine runs ONE compiled shape of
        # each program, whatever the window carried
        ep_ids = _pad_ids(ep, ENGINE_MAX_ENDPOINTS)
        # increase-edge delta for the warm-started fixed points: pairs
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
            # the single-chip programs and the sharded dispatch all
            # thread the delta into the warm-seeded fixed point
            counters["decision.ksp2_warm_dispatches"] += 1
        verdict = None
        # dispatch to readback of the program the sync waits for its
        # distances in: the rows solve on one chip (the view batch and
        # the endpoints), the fused all-pairs program on a mesh
        with tracer.span(
            "ops.ksp2_all_pairs",
            rows=(
                graph.n_pad if self._mesh is not None
                else len(view_srcs) + len(ep_ids)
            ),
            batches=1,
        ) as ap_span:
            if self._mesh is not None:
                # nothing is donated on the mesh (residents keep their
                # NamedSharding placement): the rebind is a plain replace
                d_all_dev, packed, passes = (
                    spf_sparse.sharded_ell_all_view_rows(
                        state, srcs_dev, w_sv, ep_ids, self.d_prev_dev,
                        self._mesh, inc=inc,
                        inc_bucket=ENGINE_MAX_CHANGED_PAIRS,
                    )
                )
                self.d_prev_dev = d_all_dev
            else:
                self._book_matrix_passes()
                if not self.d_prev_dev.is_ready():
                    # the previous window's matrix solve still runs:
                    # the rows solve queues behind it on the device,
                    # and this sync waits for both
                    counters["decision.ksp2_matrix_unready"] += 1
                # d_prev_dev is READ here and stays the engine's live
                # matrix: the solve that consumes it is owed from now
                # (_matrix_due) and sent by _dispatch_matrix behind the
                # window's last masked batch. A raise anywhere before
                # that finds the previous epoch's matrix, a raise in
                # that dispatch finds None (it drops the reference
                # first), and either way sync() invalidates: no path
                # hands _cold_build a donated buffer
                packed, passes, inc_dev = spf_sparse.ell_view_ep_rows(
                    state, srcs_dev, w_sv, ep_ids, self.d_prev_dev,
                    inc=inc, inc_bucket=ENGINE_MAX_CHANGED_PAIRS,
                )
                self._matrix_due = (state, inc_dev)
            if not ov_flips:
                # with the rows in flight, what needs none of them: the
                # trace arrays' patch for the window's nodes and the
                # walk-reach proof over the masked rows the engine
                # holds (the mesh's call came back with its rows)
                # (the all-pairs span less this one = the dispatch
                # and the blocked part of the reap)
                with tracer.span(
                    "decision.ksp2_walk_proof", candidates=0, proven=0
                ) as proof:
                    verdict = self._second_paths_may_move(
                        ls, graph, changed, ov_new, blocked
                    )
                    if proof is not None and verdict is not None:
                        # destinations it could not clear, and cleared
                        may = int(verdict[0].sum())
                        proof.attrs["candidates"] = may
                        proof.attrs["proven"] = len(self.dsts) - may
            if self._mesh is None:
                packed, passes = self._reap_all_pairs(packed, passes)
            if ap_span is not None:
                ap_span.attrs["passes"] = passes
        b = len(view_srcs)
        p = len(ep_ids)
        view_packed = packed[: 2 * b]
        rows_new = {int(i): packed[2 * b + x] for x, i in enumerate(ep_ids)}
        rows_old = {
            int(i): packed[2 * b + p + x] for x, i in enumerate(ep_ids)
        }
        self._preload_view(ls, graph, view_srcs, view_packed)
        d_new_src = view_packed[0].astype(np.int64)

        # the membership tests and the set algebra on what they name
        with tracer.span(
            "decision.ksp2_affected", first=0, second=0
        ) as aff_span:
            aff1, aff2, row_stands, rows_proven = self._affected_dsts(
                graph, changed, d_new_src, rows_new, rows_old, ov_new,
                verdict,
            )
            dst_set = set(self.dst_pos)
            aff1 &= dst_set
            aff2 &= dst_set
            # label/overload materialization extras: paths are
            # unchanged (distance tests cover path changes) but the
            # ROUTES built from them embed labels / drain state —
            # invalidate route reuse only
            route_extra: Set[str] = set()
            for x in ov_flips | label_flips:
                if x in self.dst_pos:
                    route_extra.add(x)
                route_extra |= self.node_users.get(x, set())
            route_extra &= dst_set
            affected = (
                aff1 | aff2 | route_extra | (self.host_dsts & dst_set)
            )
            if aff_span is not None:
                aff_span.attrs["first"] = len(aff1)
                aff_span.attrs["second"] = len(aff2)

        # however many the tests name, the incremental machinery is the
        # cheaper way: it re-derives what moved, where a cold build
        # re-derives every path and every route besides (an event on
        # the root's own pod names nine destinations in ten and moves
        # 0 to all of them)
        if aff1 or aff2:
            # its self time is the bookkeeping (_set_first_paths,
            # node_users, the exclusion sets' slots): the masked solves
            # and the traces are its children
            with tracer.span(
                "decision.ksp2_recompute",
                first=len(aff1), second=len(aff2), moved=0,
            ) as rc_span:
                moved = self._recompute(
                    ls, state, aff1, aff2, d_new_src, changed,
                    row_stands, rows_proven, blocked,
                    # a refresh sends the window's last masked batch
                    matrix_behind=rows_proven, sync_span=span,
                )
                if rc_span is not None:
                    rc_span.attrs["moved"] = len(moved)
            # of the destinations the tests named, those whose paths
            # came back as they were keep their routes
            affected = moved | route_extra | (self.host_dsts & dst_set)
        if not rows_proven:
            named = aff1 | aff2
            refreshed = self._refresh_rows(
                state, [d for d in self.dsts if d not in named]
            )
            if span is not None:
                span.attrs["refreshed_rows"] = refreshed
        # a window that named no destination sent no masked batch
        self._dispatch_matrix(span)
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
        self.ov, self._blocked = ov_new, blocked
        for x in label_flips:
            db = ls.get_adjacency_databases().get(x)
            self.node_label[x] = db.node_label if db else 0
        self.d_base = d_new_src.astype(np.int32)
        self.version = ls.topology_version
        self.aversion = ls.attributes_version
        counters["decision.ksp2_incremental_syncs"] += 1
        counters["decision.ksp2_affected_dsts"] += len(affected)
        if span is not None:
            # what following the window's change came to: candidate
            # rows of the trace arrays written in place and spliced,
            # and whether the held view batch went to the device as it
            # was
            span.attrs["trace_rows_patched"] = (
                counters["decision.ksp2_trace_rows_patched"] - patched_were
            )
            span.attrs["trace_rows_spliced"] = (
                counters["decision.ksp2_trace_rows_spliced"] - spliced_were
            )
            span.attrs["view_reused"] = int(view_reused)
        if self._carried is not None:
            self._carried |= affected
        return affected

    def _journal_nodes(
        self, ls: LinkState, since: Tuple[int, int]
    ) -> Optional[Set[str]]:
        """Nodes the LinkState's two journals name between ``since``
        (topology, attributes versions) and now; None where they cannot
        say. The sync and the trace arrays it patches start from the
        same versions, so the second to ask gets the first's reading
        (callers do not change the set)."""
        stretch = (since, (ls.topology_version, ls.attributes_version))
        if self._journal_read is None or self._journal_read[0] != stretch:
            moved = ls.affected_since(since[0])
            attrs = ls.attr_affected_since(since[1])
            self._journal_read = (
                stretch,
                None if moved is None or attrs is None
                else set(moved) | set(attrs),
            )
        return self._journal_read[1]

    def _view_batch(self, ls: LinkState, graph, affected_nodes=None):
        """The root's view batch (its id and its up-neighbours', padded)
        and its direct metrics to them: ``(ids, ids on the device,
        metrics on the device, reused)``. They change only with one of
        the root's own links, whose two ends the journals then name;
        a sync whose ``affected_nodes`` hold neither the root nor a
        neighbour sends the held device arrays as they are."""
        held = self._view
        reused = (
            held is not None
            and affected_nodes is not None
            and held.index is graph.node_index
            and held.near.isdisjoint(affected_nodes)
        )
        if not reused:
            from openr_tpu.ops import spf_sparse

            srcs = spf_sparse.ell_source_batch(graph, ls, self.src_name)
            held = self._view = _ViewBatch(
                graph.node_index,
                frozenset(graph.node_names[i] for i in srcs),
                srcs, *spf_sparse._batch_args(graph, srcs),
            )
        return held.srcs, held.srcs_dev, held.w_sv_dev, reused

    # -- cold build --------------------------------------------------------

    def _cold_build(self, ls: LinkState, state, dsts: List[str]) -> None:
        from openr_tpu.decision import spf_solver as _ss
        from openr_tpu.ops import spf_sparse
        import jax
        import jax.numpy as jnp

        self.valid = False
        self._carried = None
        self._drop_held()
        graph = state.graph
        self.state = state
        self.dsts = list(dsts)
        self.band_shapes = tuple(graph.bands)
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
        # the destinations' node ids and their rows of dm: fixed while
        # _fits holds (a patched graph keeps its node index; a
        # recompiled one is another resident state)
        self.dst_ids = np.asarray(
            [graph.node_index[d] for d in dsts], dtype=np.int64
        )
        self.dst_rows = np.arange(len(dsts))
        n = graph.n_pad

        # seed the resident all-pairs matrix and serve the view; d_prev
        # is a placeholder on the cold path
        view_srcs, srcs_dev, w_sv, _ = self._view_batch(ls, graph)
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
        ep_ids = _pad_ids([self.sid], ENGINE_MAX_ENDPOINTS)
        with get_tracer().span(
            "ops.ksp2_all_pairs", rows=n, batches=1,
        ) as ap_span:
            if self._mesh is not None:
                d_all_dev, packed, passes = (
                    spf_sparse.sharded_ell_all_view_rows(
                        state, srcs_dev, w_sv, ep_ids,
                        placeholder, self._mesh,
                        inc_bucket=ENGINE_MAX_CHANGED_PAIRS,
                    )
                )
                self.d_prev_dev = d_all_dev
            else:
                # the matrix solve first, cold-seeded, then the rows
                # solve off the matrix it leaves (nothing increased
                # since: its seed is the fixed point), at the shapes
                # every later sync runs them in: both executables are
                # compiled here, none in a churn window. The matrix
                # dispatch DONATES the placeholder (which may be the
                # previous d_prev_dev): drop our reference first so a
                # failed dispatch can't leave a dead buffer behind for
                # the next cold build to reuse
                self.d_prev_dev = None
                self.d_prev_dev, matrix_passes = (
                    spf_sparse.ell_all_view_rows(
                        state, placeholder, spf_sparse._inc_args(
                            None, ENGINE_MAX_CHANGED_PAIRS
                        ),
                    )
                )
                self._matrix_passes.append(matrix_passes)
                packed, passes, _ = spf_sparse.ell_view_ep_rows(
                    state, srcs_dev, w_sv, ep_ids, self.d_prev_dev,
                    inc=[], inc_bucket=ENGINE_MAX_CHANGED_PAIRS,
                )
                packed, passes = self._reap_all_pairs(packed, passes)
                self._book_matrix_passes()
            if ap_span is not None:
                ap_span.attrs["passes"] = passes
        b = len(view_srcs)
        self._preload_view(ls, graph, view_srcs, packed[: 2 * b])
        self.d_base = packed[0].astype(np.int32)

        # first paths traced from the device base row (identical to the
        # host get_kth_paths(.., 1) trace — same canonical order)
        cands_of = make_cands_of(ls, graph.node_index)
        self.ov = {
            name: ls.is_node_overloaded(name)
            for name in graph.node_names
        }
        # drained nodes: reachable, but no path runs through one (the
        # root, drained or not, still originates)
        transit_blocked = self._blocked = {
            name for name, drained in self.ov.items()
            if drained and name != self.src_name
        }
        self.first_paths: Dict[str, List[List[Link]]] = {}
        self.second_paths: Dict[str, List[List[Link]]] = {}
        self.excl: Dict[str, Set[Link]] = {}
        self.excl_users: Dict[Link, Set[int]] = {}
        # destination -> (the slots its exclusion set holds, whether
        # they could carry it), and the slot index they were read off
        self.excl_slots: Dict[str, Tuple[np.ndarray, bool]] = {}
        self._slots_seen = None
        self._link_slots: Dict[Link, Tuple[int, ...]] = {}
        self.node_users: Dict[str, Set[str]] = {}
        # per destination and node, how far down the node's candidate
        # list the second-path traces looked (-1: never there);
        # everything, until a trace has said otherwise
        self.reach2 = np.full(
            (len(dsts), n), _ALL_CONSULTED, dtype=np.int32
        )
        traced = self._trace_many(
            ls, graph, cands_of, transit_blocked, dsts, self.d_base,
            True, [set()] * len(dsts),
        )
        for dst, paths in zip(dsts, traced):
            self._set_first_paths(dst, paths)

        # masked rows for every destination, chunked like the original
        # prefetch; second paths traced from them
        self.dm = np.full((len(dsts), n), INF, dtype=np.int32)
        self.host_dsts: Set[str] = set()
        self._solve_masked_batches(
            ls, state, dsts, cands_of, transit_blocked
        )
        for dst in dsts:
            self._note_paths(dst)
        self._prime_all(ls)
        warm_key = (self.band_shapes, n, self.sid)
        if self._mesh is None and self._masked_warm != warm_key:
            # the buckets this build's own batches did not reach
            for bucket in _masked_buckets(_ss._ksp2_chunk(graph)):
                spf_sparse.warm_masked_distances_resident(
                    state, self.sid, bucket
                )
            self._masked_warm = warm_key

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
        self.node_label = {
            name: db.node_label
            for name, db in ls.get_adjacency_databases().items()
        }
        self.version = ls.topology_version
        self.aversion = ls.attributes_version
        self.valid = True
        _counters()["decision.ksp2_cold_builds"] += 1

    # -- diffing -----------------------------------------------------------

    @staticmethod
    def _node_sigs(
        ls: LinkState, a: str, far_side: bool = False
    ) -> Dict[str, Tuple]:
        """Materialization-relevant attributes of every (a, other) link
        direction in ONE pass over a's ordered links: next-hop
        addresses, interfaces, adj labels, and canonical link identity
        (identity changes can reorder the deterministic trace's
        candidate list). One pass matters: per-pair scans made diffing
        a single churn event O(degree^2) on high-degree spines. With
        ``far_side`` the same links read from their other end: the
        (other, a) directions, keyed by ``other`` as well."""
        sigs: Dict[str, List[Tuple]] = {}
        for link in ls.ordered_links_from_node(a):
            if not link.is_up():
                continue
            other = link.other_node(a)
            end = other if far_side else a
            sigs.setdefault(other, []).append(
                (
                    link.iface_from(end),
                    link.nh_v4_from(end).addr,
                    link.nh_v6_from(end).addr,
                    link.adj_label_from(end),
                    link.metric_from(end),
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
            # both directions of every pair at x off ONE pass over x's
            # own links (a link carries both ends' attributes, and the
            # canonical order of two parallel links is the same seen
            # from either end): walking each neighbour's links for its
            # side cost a spine's whole degree per neighbour of an FSW
            out_sigs = self._node_sigs(ls, x)
            in_sigs = self._node_sigs(ls, x, far_side=True)
            out_ws = self._min_weights(out_sigs)
            in_ws = self._min_weights(in_sigs)
            for other in neighbors:
                for pair, sigs, ws in (
                    ((x, other), out_sigs, out_ws),
                    ((other, x), in_sigs, in_ws),
                ):
                    if pair in seen_pairs:
                        continue
                    seen_pairs.add(pair)
                    w_new = ws.get(other, INF)
                    sig_new = sigs.get(other, ())
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

    def _eff(self, w, origin: str, ov_map: Dict[str, bool]):
        """The EFFECTIVE weight of an edge of raw weight ``w`` out of
        ``origin`` under an overload map: no path runs on through a
        drained node (the root, drained or not, still originates)."""
        if w >= INF:
            return np.int64(INF)
        if ov_map.get(origin, False) and origin != self.src_name:
            return np.int64(INF)
        return np.int64(w)

    def _affected_dsts(
        self,
        graph,
        changed: Dict[Tuple[str, str], Tuple],
        d_new_src: np.ndarray,
        rows_new: Dict[int, np.ndarray],
        rows_old: Dict[int, np.ndarray],
        ov_new: Dict[str, bool],
        verdict: Optional[Tuple[np.ndarray, np.ndarray, List[Tuple]]],
    ) -> Tuple[Set[str], Set[str], Set[str], bool]:
        """Returns (first-path affected, masked/second-path affected,
        row_stands, rows_proven). The first two are split because the
        former invalidates the destination's MASKS (forcing a fresh
        masked solve) while the latter only needs the second paths
        re-derived. ``ov_new``: the overload map after the window
        (``self.ov`` holds the map before it). ``verdict``: what
        _second_paths_may_move said of the window (the caller asked it
        while the rows were in flight: it reads none of them), which
        narrows the second set; None where it does not answer.
        ``row_stands``: of the second set, the destinations whose
        masked row provably stands as long as their first paths, and
        so their masks, do; _recompute re-traces them off the row it
        has. ``rows_proven``: whether every row the sync does
        not re-solve is proven to stand; where not (a drain flip,
        parallel links, no native tracer), the sync re-solves them all
        to keep the rows exact."""
        index = graph.node_index
        dst_ids = self.dst_ids
        d_old_src = self.d_base.astype(np.int64)
        d_new = d_new_src  # already int64
        inf = np.int64(INF)

        aff = d_new[dst_ids] != d_old_src[dst_ids]
        aff2_vec = np.zeros(len(self.dsts), dtype=bool)
        row_stands: Set[str] = set()
        rows_proven = False

        # int32 as held: widened a column at a time where sums are
        # taken (the whole matrix is 8 MB a sync to widen)
        dm = self.dm
        dm_total = dm[self.dst_rows, dst_ids].astype(np.int64)

        eff = self._eff

        # links (either direction) usable now that were not before
        appeared = len({
            frozenset((u, v))
            for (u, v), (w_old, w_new, _so, _sn) in changed.items()
            if eff(w_old, u, self.ov) >= inf and eff(w_new, u, ov_new) < inf
        })
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
            dm_u = dm[:, uid].astype(np.int64)
            if wo < inf:
                lhs = dm_u + wo + r_old_v[dst_ids]
                valid = (
                    (dm_u < inf)
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
                # disconnected masked rows may reconnect. Through ONE
                # new link only those whose masked graph already
                # reaches the edge's tail (a destination whose first
                # paths take every link of the root reaches nothing,
                # and a link elsewhere cannot change that); with
                # several, one may carry the reach to the next
                if appeared > 1:
                    aff2_vec |= ~reachable_m
                else:
                    aff2_vec |= ~reachable_m & (dm_u < inf)
        if verdict is not None:
            # a destination whose first paths move gets fresh masks
            # and a fresh solve whatever this says; one whose row
            # may move is re-solved even where the bound above sees
            # no shortest second path through the edge, because the
            # verdict reads the rows as exact (a row left stale at
            # a node that mattered to nothing is a wrong verdict
            # the day it matters)
            may_move, row_moves, mended = verdict
            for col, at, values in mended:
                self.dm[at, col] = values
            aff2_vec = (aff2_vec & may_move) | row_moves
            row_stands = {
                self.dsts[i]
                for i in np.flatnonzero(aff2_vec & ~row_moves)
            }
            rows_proven = True
        aff1 = {self.dsts[i] for i in np.flatnonzero(aff)}
        aff2 = {self.dsts[i] for i in np.flatnonzero(aff2_vec)}
        return aff1, aff2, row_stands, rows_proven

    def _second_paths_may_move(
        self, ls, graph, changed, ov_new, blocked
    ) -> Optional[Tuple[np.ndarray, np.ndarray, List[Tuple]]]:
        """Of a window with no drain flip (``ov_new``, ``blocked``: the
        overload map and the transit-blocked set after it), read off
        the masked rows ``self.dm`` and the walks ``self.reach2`` as
        the engine holds them and off NO distance row of the window's
        own solve, so the sync asks while that solve is in flight.
        Two [D] bools: the destinations whose second-path trace can
        come out differently after the window's changes, first paths
        and masks unchanged; and, of those, the ones whose masked row
        may have moved (the rest need a trace, not a solve). Third,
        where the window is ONE link's, the rows that did move, in one
        column only and where no walk read it, as ``(column, row
        positions, new values)``: the caller writes them into ``dm``
        and they are in neither vector (_one_column_moves). None where
        the question is not this simple (parallel links, no native
        tracer): the caller keeps its DAG-membership bound and
        re-solves every row it does not name.

        The trace of destination i is a function of the prefixes it
        read of the predecessor lists of the nodes it consults
        (``reach2[i]``, as the native tracer reported them: a walk
        takes the first unspent predecessor and looks no further), and
        a node's list is a function of its candidate in-links, of i's
        exclusions and of i's masked distances. So i's second paths
        stand when (a) at the node the changed link points INTO no
        walk looked as far down as that link (and, for an edge that
        only got dearer, no path runs over it), and (b) its masked row
        is provably what it was: a raised or removed edge u->v moves
        the row only where it was tight and v has no other tight,
        unexcluded predecessor (with one, v keeps its distance and so
        does everything behind it); a lowered or new one only where
        dm[u] + w undercuts dm[v]. The DAG-membership bound asks
        whether the edge lies on SOME shortest second path, which on a
        fat-tree is true of every spine link for some 300
        destinations; this asks whether the walk ever looked.

        Several edges at once (a node that re-costs all its links, a
        window of several events) are answered edge by edge, from the
        rows and the walks as they were BEFORE the window; the two
        vectors are the unions. That is sound because neither argument
        reads anything another changed edge could have moved. (b):
        the old row d stays feasible under the new weights where no
        lowered or new edge undercuts it (d[u] + w_new >= d[v], each
        read off the old row), and stays attained where every raised
        or removed edge that was tight leaves its head another tight,
        unexcluded predecessor over an edge the window did NOT change:
        by induction on d (metrics are >= 1) the nearest such head
        keeps its distance through that predecessor, which is nearer
        still, and so on outwards; a feasible row that every node
        attains over a tight edge is the row. Hence a predecessor
        over another changed edge is not counted as support. (a): with
        the row where it was, a node's predecessor list differs from
        the old one only at the places of changed edges; a walk that
        read none of those places reads what it read before, from its
        first step to its last (the first place at which it could
        diverge would be one of them), and the last search's reach is
        closed under the new edges where none leads from inside it to
        outside. Only the one-column mending is kept to a single
        link: its two proofs read each other's column."""
        pairs = list(changed.items())
        if not pairs:
            return None
        dm, eff = self.dm, self._eff
        one_link = len(pairs) == 1 or (
            len(pairs) == 2 and pairs[0][0] == pairs[1][0][::-1]
        )
        index = graph.node_index
        # the candidate rows as the last sync's traces saw them, before
        # this event's patch lands on them
        cached = self._tarrays
        if cached is None or cached[0] != (self.version, self.aversion):
            return None
        was = {index[v]: cached[1].rows_of(index[v]) for (_u, v), _ in pairs}
        arrays = self._trace_arrays(
            ls, graph, make_cands_of(ls, graph.node_index), blocked
        )
        if arrays is None:
            return None
        inf = np.int64(INF)
        may = np.zeros(len(self.dsts), dtype=bool)
        row_moves = np.zeros(len(self.dsts), dtype=bool)
        mended: List[Tuple] = []
        # head -> the tails of the window's changed edges into it: no
        # support for a row that another of them was tight in
        changed_into: Dict[int, Set[int]] = {}
        for (u, v), _ in pairs:
            changed_into.setdefault(index[v], set()).add(index[u])
        for (u, v), (w_old, w_new, _so, _sn) in pairs:
            uid, vid = index[u], index[v]
            lids, uids, ws = arrays.rows_of(vid)
            at_new = np.flatnonzero(uids == uid)
            at_old = np.flatnonzero(was[vid][1] == uid)
            if len(at_new) > 1 or len(at_old) > 1 or len(uids) >= _DEPTH:
                return None  # parallel links: which one changed?
            if not len(at_new) and not len(at_old):
                continue
            # where the link sits among v's candidates: the same place
            # before and after, or, gone or new, the place it left or
            # took, with every candidate before it where it was
            at = int(at_new[0] if len(at_new) else at_old[0])
            wo, wn = eff(w_old, u, self.ov), eff(w_new, u, ov_new)
            dm_u = dm[:, uid].astype(np.int64)
            dm_v = dm[:, vid].astype(np.int64)
            # (a): v's list changes where the edge is, or was, on it —
            # or keeps it with other attributes (the route's to carry)
            # — and a walk saw that only if it looked that far down
            if wn > wo:
                # an edge that only got dearer, or went, takes nothing
                # from a walk but itself: a branch that died dies
                # sooner, a search that failed still fails, and a
                # predecessor tried before the one taken is skipped
                # now as it failed then. Only a walk THROUGH it moves
                consulted = self._second_paths_over(u, v)
            else:
                # one that appeared or got cheaper (or changed what a
                # route carries of it) can turn up in any list a walk
                # read as far as its place, or read to the end
                looked = self.reach2[:, vid]
                consulted = (looked >= 0) & (
                    ((looked & _DEPTH) > at) | ((looked & _RAN_OUT) != 0)
                )
                # ... or give the LAST search, the one that found no
                # further path, a way on: it reached v, and the edge
                # helps it only from a tail it had not reached as well
                # (what it reached is the set nothing leads on from
                # once the paths are taken; an edge inside the set
                # leaves it the set). Only the last search may be
                # argued so: a list that ran out earlier may have run
                # out after a path took its last way on, and the new
                # edge can change which path comes first
                stuck = (looked >= 0) & ((looked & _IN_LAST) != 0)
                if uid != self.sid:
                    tail = self.reach2[:, uid]
                    stuck &= ~((tail >= 0) & ((tail & _IN_LAST) != 0))
                consulted |= stuck
            if wn != wo:
                consulted &= (dm_u < inf) & (
                    (dm_u + wo == dm_v) | (dm_u + wn == dm_v)
                )
            may |= consulted
            moves = np.zeros(len(self.dsts), dtype=bool)
            if wn < wo:
                moves = (dm_u < inf) & (dm_u + wn < dm_v)
            elif wn > wo:
                other = np.zeros(len(self.dsts), dtype=bool)
                for lid, cu, cw in zip(
                    lids.tolist(), uids.tolist(), ws.tolist()
                ):
                    if cu < 0 or cu in changed_into[vid] or (
                        cu != self.sid and arrays.blocked[cu]
                    ):
                        continue
                    dm_c = dm[:, cu].astype(np.int64)
                    tight = (dm_c < inf) & (dm_c + cw == dm_v)
                    users = self.excl_users.get(arrays.links[lid])
                    if users:
                        tight[list(users)] = False
                    other |= tight
                moves = (dm_u < inf) & (dm_u + wo == dm_v) & ~other
            if one_link and moves.any():
                at, values = self._one_column_moves(
                    ls, arrays, dm, u, v, uid, vid, wo, wn,
                    np.flatnonzero(moves & ~consulted),
                )
                if len(at):
                    mended.append((vid, at, values))
                    moves[at] = False
            row_moves |= moves
        if len(mended) == 2:
            # a link's two directions each moved a column of one row:
            # the two proofs read each other's column as it was
            both = np.intersect1d(mended[0][1], mended[1][1])
            if len(both):
                row_moves[both] = True
                mended = [
                    (col, at[keep], values[keep])
                    for col, at, values in mended
                    for keep in [~np.isin(at, both)]
                ]
        return may | row_moves, row_moves, mended

    def _one_column_moves(
        self, ls, arrays, dm, u, v, uid, vid, wo, wn, rows
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Of ``rows`` (positions of destinations whose masked row the
        change of edge u->v moves, their walks not through it), those
        whose row moves in column v ALONE, where no walk of theirs
        read that column: ``(positions, new dm[:, v])``. For these the
        row is mended here and nothing else is done: no masks, no
        solve, no trace.

        The column alone: a raised or removed edge leaves every other
        node its distance where each node v leads on to over a tight
        edge has another tight, unexcluded predecessor (it keeps its
        distance, and so does everything behind it); a lowered or new
        one where v's new distance undercuts no node v leads on to.
        v's new distance is the least over its usable predecessors, as
        the masked relax computes it. Nobody read it: the tracer reads
        a row at the nodes a walk consulted and at every candidate
        predecessor of those (``ensure_preds`` in native/spfcore.cpp
        tests each for tightness), and at the destination; so not
        where reach2 shows the walks at neither v nor any neighbour of
        v, now or before the change. On a fat-tree whose root has one
        uplink its first paths leave free, every masked graph hangs
        off that uplink and a change at a leaf of its plane moves one
        column of six hundred rows that matter to one route."""
        none = np.zeros(0, dtype=np.int64)
        if not len(rows):
            return none, none
        inf = np.int64(INF)
        index = arrays.index
        # v's neighbours, now and as the last traces saw them
        out_w: Dict[int, int] = {}
        for link in ls.links_from_node(v):
            if link.is_up():
                sid = index.get(link.other_node(v))
                if sid is not None:
                    w = min(int(link.metric_from(v)), INF - 1)
                    out_w[sid] = min(out_w.get(sid, w), w)
        near = set(out_w)
        for a, b in self.pairs_by_node.get(v, ()):
            near.add(index[b if a == v else a])
        near.discard(vid)
        dst_ids = self.dst_ids[rows]
        unread = (dst_ids != vid) & (self.reach2[rows, vid] < 0)
        for sid in near:
            unread &= self.reach2[rows, sid] < 0
        rows = rows[unread]
        if not len(rows):
            return none, none
        sub = dm[rows].astype(np.int64)
        place = {int(pos): k for k, pos in enumerate(rows)}
        blocked = arrays.blocked

        def reached_over(nid, but=-1, weight_of=None) -> np.ndarray:
            """[rows, candidates] int64: each row's distance to node
            ``nid`` over each of its usable candidate in-links (INF
            where the tail is out of reach, drained, ``but``, or the
            row excludes the link)."""
            lids, uids, ws = arrays.rows_of(nid)
            keep = (uids >= 0) & (uids != but)
            keep[keep] &= (uids[keep] == self.sid) | (
                blocked[uids[keep]] == 0
            )
            lids, uids = lids[keep], uids[keep]
            ws = np.minimum(ws[keep].astype(np.int64), inf - 1)
            if weight_of is not None:
                ws[uids == weight_of[0]] = weight_of[1]
            tails = sub[:, uids]
            over = np.where(
                (tails < inf) & (ws[None, :] < inf), tails + ws[None, :], inf
            )
            for j, lid in enumerate(lids.tolist()):
                users = self.excl_users.get(arrays.links[lid])
                if users:
                    hit = [place[p] for p in users if p in place]
                    if hit:
                        over[hit, j] = inf
            return over

        # v's new distance: the least over the predecessors it has now
        # (the changed edge among them at its new weight, if it stays)
        over_v = reached_over(vid, weight_of=(uid, wn))
        new_v = (
            over_v.min(axis=1) if over_v.shape[1]
            else np.full(len(rows), inf, dtype=np.int64)
        )
        alone = np.ones(len(rows), dtype=bool)
        old_v = sub[:, vid]
        for sid, w in out_w.items():
            if wn < wo:
                alone &= new_v + w >= sub[:, sid]
                continue
            tight = (old_v < inf) & (old_v + w == sub[:, sid])
            if tight.any():
                others = reached_over(sid, but=vid)
                alone &= ~tight | (
                    others == sub[:, sid][:, None]
                ).any(axis=1)
        return rows[alone], new_v[alone].astype(np.int32)

    # -- recompute ---------------------------------------------------------

    def _second_paths_over(self, u: str, v: str) -> np.ndarray:
        """[D] bool: destinations with a second path that crosses a
        link from ``u`` to ``v``."""
        over = np.zeros(len(self.dsts), dtype=bool)
        users = self.node_users.get(v, set())
        if u != self.src_name:
            users = users & self.node_users.get(u, set())
        for dst in users:
            for path in self.second_paths.get(dst, ()):
                cur = self.src_name
                for link in path:
                    nxt = link.other_node(cur)
                    if cur == u and nxt == v:
                        over[self.dst_pos[dst]] = True
                    cur = nxt
        return over

    def _set_first_paths(self, dst: str, paths: List[List[Link]]) -> None:
        """First paths, the exclusion set the masked solve takes from
        them, and the index of which destinations exclude a link."""
        pos = self.dst_pos[dst]
        for link in self.excl.get(dst, ()):
            self.excl_users[link].discard(pos)
        self.excl_slots.pop(dst, None)
        self.first_paths[dst] = paths
        excl = self.excl[dst] = {l for p in paths for l in p}
        for link in excl:
            self.excl_users.setdefault(link, set()).add(pos)

    def _recompute(
        self, ls: LinkState, state, aff1: Set[str], aff2: Set[str],
        d_new_src: np.ndarray, changed,
        row_stands: Set[str], rows_proven: bool,
        transit_blocked: Set[str], matrix_behind: bool = True,
        sync_span=None,
    ) -> Set[str]:
        """Re-derive the paths of the destinations the membership tests
        named (``aff1``: first paths, ``aff2``: second) and return
        those whose paths MOVED: the tests name every destination
        whose shortest-path DAG holds a changed edge, but the canonical
        trace walks one branch of that DAG, so most come back link for
        link as they were (a metric raised on one of 36 equal spines
        moves the destinations traced through that spine, not the 300
        whose DAG merely contains it; a link of the root's own FSW
        lies on the DAG of every destination outside the pod). A
        destination whose two path sets are unchanged and cross no
        changed pair keeps its cached routes.

        In three steps, each doing less than the one before it was
        asked to. First paths are re-traced for all of ``aff1``. One
        whose first paths come back as they were keeps its exclusion
        set, hence its masks, and is then no different from a
        destination ``aff1`` never named: where the window's rows are
        proven (``rows_proven``) it is re-solved only if ``aff2``
        names it, and re-traced off the row the engine holds, no mask
        built and nothing sent to the device, if ``row_stands`` has it
        (_second_paths_may_move). Where they are not proven, every
        named destination is re-solved as it always was.

        ``matrix_behind``: no masked batch follows this call's in the
        window, so the matrix solve the sync owes goes to the device
        behind the last one here (_dispatch_matrix), ahead of the host
        work that follows it. ``sync_span``: the window's
        ``decision.ksp2_sync``, which that dispatch is booked on where
        no masked span is open to take it."""
        graph = state.graph
        cands_of = make_cands_of(ls, graph.node_index)
        named = sorted(aff1 | aff2)
        before = {
            dst: (self.first_paths.get(dst), self.second_paths.get(dst))
            for dst in named
        }
        first = sorted(aff1)
        traced = self._trace_many(
            ls, graph, cands_of, transit_blocked, first,
            d_new_src.astype(np.int32), True, [set()] * len(first),
        )
        fresh_masks: Set[str] = set()
        for dst, paths in zip(first, traced):
            if paths != self.first_paths.get(dst):
                self._set_first_paths(dst, paths)
                fresh_masks.add(dst)
        if rows_proven:
            solve = [
                dst for dst in named
                if dst in fresh_masks
                or dst in self.host_dsts
                or (dst in aff2 and dst not in row_stands)
            ]
            solved = set(solve)
            retrace = [
                dst for dst in named
                if dst in aff2 and dst not in solved
            ]
        else:
            solve, retrace = named, []

        self.host_dsts -= set(solve)
        if solve:
            self._solve_masked_batches(
                ls, state, solve, cands_of, transit_blocked,
                index_users=False, matrix_behind=matrix_behind,
            )
        elif matrix_behind:
            self._dispatch_matrix(sync_span)
        if retrace:
            at = [self.dst_pos[dst] for dst in retrace]
            reach = np.full((len(retrace), graph.n_pad), -1, dtype=np.int32)
            traced = self._trace_many(
                ls, graph, cands_of, transit_blocked, retrace,
                np.ascontiguousarray(self.dm[at]), False,
                [self.excl[dst] for dst in retrace], reach,
            )
            for dst, paths in zip(retrace, traced):
                self.second_paths[dst] = paths
            reach[:, self.sid] = _ALL_CONSULTED
            self.reach2[at] = reach
        # links whose weight or materialization attributes changed: a
        # path across one keeps its links and still changes its route
        touched: Set[Link] = set()
        for u, v in changed:
            touched.update(
                link for link in ls.links_from_node(u)
                if link.other_node(u) == v
            )
        moved: Set[str] = set()
        for dst in named:
            first, second = before[dst]
            now = (self.first_paths[dst], self.second_paths.get(dst))
            if (
                dst not in self.host_dsts
                and now == (first, second)
                and touched.isdisjoint(self.excl[dst])
                and all(touched.isdisjoint(path) for path in now[1] or ())
            ):
                # the fresh lists are equal to the cached ones; keep
                # the cached objects so every holder sees one list
                self.first_paths[dst] = first
                if second is not None:
                    self.second_paths[dst] = second
                continue
            moved.add(dst)
            # reverse index: drop the old paths' nodes, add the new
            for path in (first or []) + (second or []):
                for x in _path_nodes(self.src_name, path):
                    users = self.node_users.get(x)
                    if users is not None:
                        users.discard(dst)
            if dst in self.host_dsts:
                continue
            for path in now[0] + (now[1] or []):
                for x in _path_nodes(self.src_name, path):
                    self.node_users.setdefault(x, set()).add(dst)
        for dst in named:
            self._note_paths(dst)
        return moved

    def _dispatch_matrix(self, span=None) -> None:
        """Send the matrix solve the sync in hand owes its window (once:
        nothing where none is owed, as in a cold build or on a mesh;
        ``span``: the span open around the call, the masked solve's or
        the sync's, which gets the dispatch's host time as
        ``matrix_dispatch_ms``):
        the all-sources fixed point that relaxes ``d_prev_dev`` in
        place, warm-seeded with the window's increase triple as the
        rows solve put it on the device. Called behind the window's
        last masked dispatch and ahead of the host work that follows
        it, so that in device order it stands behind everything this
        window's host code waits on; its output is adopted as
        ``d_prev_dev`` at once, a future nobody blocks on."""
        from openr_tpu.ops import spf_sparse

        due, self._matrix_due = self._matrix_due, None
        if due is None:
            return
        state, inc_dev = due
        t0 = time.perf_counter()
        # the dispatch DONATES the matrix: drop our reference first, so
        # a dispatch that raises leaves None and not a dead buffer
        d_prev, self.d_prev_dev = self.d_prev_dev, None
        self.d_prev_dev, passes = spf_sparse.ell_all_view_rows(
            state, d_prev, inc_dev
        )
        self._matrix_passes.append(passes)
        _counters()["decision.ksp2_matrix_deferred"] += 1
        if span is not None:
            span.attrs["matrix_dispatch_ms"] = round(
                (time.perf_counter() - t0) * 1000.0, 4
            )

    def _book_matrix_passes(self) -> None:
        """Book the pass counts of the matrix solves that have landed
        (``ops.ksp2.matrix_passes``); one still running keeps its count
        for a later call. Never blocks."""
        from openr_tpu.ops import spf_sparse

        landed, running = [], []
        for passes in self._matrix_passes:
            (landed if passes.is_ready() else running).append(passes)
        if not landed:
            return
        self._matrix_passes = running
        for passes in _da.reap_read(landed, kicked=True):
            spf_sparse.note_ksp2_passes("matrix", passes)

    @staticmethod
    def _reap_all_pairs(packed, passes):
        """The packed rows and the pass count of the program the sync
        waits for on the host: two outputs of one program, kicked
        together, brought over by one reap; the count is booked here,
        where it is first known."""
        from openr_tpu.ops import spf_sparse

        packed, passes = _da.reap_read((packed, passes), kicked=True)
        return packed, spf_sparse.note_ksp2_passes("all_pairs", passes)

    @staticmethod
    def _reap_masked(out_dev, out_host):
        """One masked batch's ``(rows, passes)`` on the host: reaped
        off the device with the count booked (one chip), or as the
        mesh's solve already returned them."""
        from openr_tpu.ops import spf_sparse

        if out_host is not None:
            return out_host
        rows, passes = _da.reap_read(out_dev, kicked=True)
        return rows, spf_sparse.note_ksp2_passes("masked", passes)

    def _masked_dispatch(self, state, batch: List[str], span=None):
        """Masks of one chunk's destinations, padded to one of the
        chunk's three buckets (all compiled by the cold build, so no
        affected-set size compiles later), and their masked solve
        dispatched; the mask build's host time and the masks' bytes
        are summed onto ``span`` (the caller's ops.ksp2_masked_solve:
        ``masks_ms``, ``mask_bytes``). Returns ``(ok, out_dev, out_host)``: which masks
        the slots could carry, and the program's ``(rows, passes)``
        either on the device with their readback kicked (one chip) or
        on the host (the mesh's sharded solve returns them so);
        _reap_masked takes either."""
        from openr_tpu.decision import spf_solver as _ss
        from openr_tpu.ops import spf_sparse

        graph = state.graph
        bucket = next(
            b for b in _masked_buckets(_ss._ksp2_chunk(graph))
            if b >= len(batch)
        )
        if self._mesh is not None:
            # sharded batches divide destinations over the mesh
            ndev = self._mesh.devices.size
            bucket = -(-max(bucket, ndev) // ndev) * ndev
        t0 = time.perf_counter()
        masks, ok = self._batch_masks(graph, batch, bucket)
        if span is not None:
            # attributes, not child spans: the span's self time is
            # what ksp2_masked_solve_ms reads
            span.attrs["masks_ms"] = round(
                span.attrs["masks_ms"]
                + (time.perf_counter() - t0) * 1000.0, 4
            )
            span.attrs["mask_bytes"] += sum(m.nbytes for m in masks)
        _counters()["decision.ksp2_device_batches"] += 1
        if self._mesh is not None:
            return ok, None, spf_sparse.sharded_ell_masked_distances_resident(
                state, self.sid, masks, self._mesh
            )
        # committed chain: the masked rows and their pass count are
        # kicked copy_to_host_async and the host copies reaped once
        return ok, spf_sparse.ell_masked_distances_resident(
            state, self.sid, masks, defer=True
        ), None

    def _batch_masks(self, graph, batch: List[str], bucket: int):
        """``build_edge_masks`` of the batch's exclusion sets padded to
        ``bucket``, from the slots each destination's set was last
        found to hold (``excl_slots``). A row is re-solved far more
        often than its first paths move (a window in which a raised
        edge was some node's only support re-solves hundreds whose
        masks stand), so the walk over its hundred excluded links is
        made when the set changes (_set_first_paths drops the entry) or
        when ell_patch re-packed a row one of its links ends in, not a
        solve."""
        from openr_tpu.ops import spf_sparse

        held = self.excl_slots
        if graph.slot_of is not self._slots_seen:
            # rows re-packed since the last batch: a link that came or
            # went moves its row's other links to other slots
            was = self._slots_seen or {}
            shifted = {
                key
                for nid, now in graph.slot_of.items()
                for old in [was.get(nid, now)]
                if old is not now and old != now
                for key in old.keys() | now.keys()
                if old.get(key) != now.get(key)
            }
            if shifted:
                for link, users in self.excl_users.items():
                    if spf_sparse.link_key(link) in shifted:
                        for pos in users:
                            held.pop(self.dsts[pos], None)
            self._slots_seen = graph.slot_of
            self._link_slots = {}
        for dst in batch:
            if dst not in held:
                # first paths share their links near the root: each
                # link's two slots are looked up once an index
                held[dst] = spf_sparse.excluded_slots(
                    graph, self.excl[dst], memo=self._link_slots
                )
        ok = np.ones(bucket, dtype=bool)
        ok[: len(batch)] = [held[dst][1] for dst in batch]
        return spf_sparse.masks_from_slots(
            graph, [held[dst][0] for dst in batch], bucket
        ), ok

    def _refresh_rows(self, state, dsts: List[str]) -> int:
        """Masked rows of ``dsts`` solved again and kept, nothing
        traced: what keeps ``dm`` exact through a window whose changes
        _second_paths_may_move does not answer for. Returns how many
        rows that was (``refreshed_rows`` on decision.ksp2_sync)."""
        from openr_tpu.decision import spf_solver as _ss

        dsts = [d for d in dsts if d not in self.host_dsts]
        if not dsts:
            return 0
        chunk = _ss._ksp2_chunk(state.graph)
        with get_tracer().span(
            "ops.ksp2_masked_solve", rows=len(dsts),
            batches=-(-len(dsts) // chunk), refresh=True, passes=0,
            masks_ms=0.0, mask_bytes=0,
        ) as span:
            for start in range(0, len(dsts), chunk):
                batch = dsts[start : start + chunk]
                ok, *out = self._masked_dispatch(state, batch, span)
                if start + chunk >= len(dsts):
                    # the window's last masked batch: the matrix solve
                    # behind it, ahead of its reap
                    self._dispatch_matrix(span)
                drows, passes = self._reap_masked(*out)
                if span is not None:
                    span.attrs["passes"] = max(span.attrs["passes"], passes)
                keep = np.flatnonzero(ok[: len(batch)])
                self.dm[[self.dst_pos[batch[i]] for i in keep]] = (
                    drows[keep]
                )
        return len(dsts)

    def _solve_masked_batches(
        self, ls, state, dsts, cands_of, transit_blocked,
        index_users: bool = True, matrix_behind: bool = True,
    ) -> None:
        """Masked-SPF rows + second-path traces + dm/node_users updates
        for a destination subset (shared by cold build and incremental
        recompute; the two loops MUST stay identical — fallback
        accounting drifting between them was a review finding).
        ``matrix_behind``: as _recompute's (a cold build owes no matrix
        solve, and _dispatch_matrix then sends none)."""
        from openr_tpu.decision import spf_solver as _ss

        graph = state.graph
        chunk = _ss._ksp2_chunk(graph)

        def _settle(span, batch, ok, out_dev, out_host):
            """Stage 2: reap the masked rows, settle dm + fallback
            accounting, trace second paths — host work the NEXT
            chunk's already-submitted solve overlaps."""
            drows, passes = self._reap_masked(out_dev, out_host)
            if span is not None:
                span.attrs["passes"] = max(span.attrs["passes"], passes)
            traceable: List[int] = []
            for i, dst in enumerate(batch):
                self.dm[self.dst_pos[dst]] = drows[i]
                if not ok[i]:
                    _counters()["decision.ksp2_host_fallbacks"] += 1
                    self.host_dsts.add(dst)
                    self.second_paths.pop(dst, None)
                    self.reach2[self.dst_pos[dst]] = _ALL_CONSULTED
                    # host_dsts membership keeps its row out of every
                    # cache read
                    continue
                traceable.append(i)
            reach = np.full(
                (len(traceable), graph.n_pad), -1, dtype=np.int32
            )
            traced = self._trace_many(
                ls, graph, cands_of, transit_blocked,
                [batch[i] for i in traceable],
                np.ascontiguousarray(np.asarray(drows)[traceable]),
                False, [self.excl[batch[i]] for i in traceable],
                reach,
            )
            for i, paths in zip(traceable, traced):
                self.second_paths[batch[i]] = paths
            if traceable:
                # a path ends at the root over a link of the root's:
                # what changes there can change the count of paths
                reach[:, self.sid] = _ALL_CONSULTED
                self.reach2[
                    [self.dst_pos[batch[i]] for i in traceable]
                ] = reach

        # ONE-DEEP chunk pipeline: chunk i+1's masked solve is
        # submitted before chunk i's rows are reaped, so the device
        # round trip amortizes across in-flight chunks. Safe because
        # ``self.excl`` is fixed for the whole call (every chunk's
        # masks derive from the same exclusion table) and the settle
        # stage touches only host mirrors. The mesh path degrades to
        # eager per-chunk order — the sharded solve already returns
        # host rows, so there is nothing in flight to overlap.
        # the span's self time is mask build, dispatch and readback:
        # the second-path traces of _settle nest their own span in it
        # ``passes``: the most any of the span's batches ran
        with get_tracer().span(
            "ops.ksp2_masked_solve", rows=len(dsts),
            batches=-(-len(dsts) // chunk), passes=0,
            masks_ms=0.0, mask_bytes=0,
        ) as span:
            inflight = None
            for start in range(0, len(dsts), chunk):
                # stage 1: mask build + (async) masked solve
                batch = dsts[start : start + chunk]
                staged = (
                    batch, *self._masked_dispatch(state, batch, span)
                )
                if matrix_behind and start + chunk >= len(dsts):
                    # behind the last batch, ahead of every settle
                    self._dispatch_matrix(span)
                if inflight is not None:
                    if staged[2] is not None:
                        _da.note_pipelined_dispatch(2)
                        _da.note_overlapped_reap()
                    _settle(span, *inflight)
                inflight = staged
            if inflight is not None:
                _settle(span, *inflight)
        if not index_users:
            return  # _recompute indexes the destinations that moved
        for dst in dsts:
            if dst in self.host_dsts:
                continue
            for path in self.first_paths[dst] + self.second_paths.get(
                dst, []
            ):
                for x in _path_nodes(self.src_name, path):
                    self.node_users.setdefault(x, set()).add(dst)

    def _trace_arrays(self, ls, graph, cands_of, transit_blocked):
        """The native tracer's int-encoded candidate structure, current
        for ``ls``. One build serves every trace site of an event (cold
        build first paths, recompute, retrace, masked second paths) and
        the next event patches it from the LinkState journals; None
        when the native core is unavailable (callers fall back to the
        Python tracer)."""
        from openr_tpu.graph import native_spf

        if not native_spf.is_available():
            return None
        key = (ls.topology_version, ls.attributes_version)
        cached = self._tarrays
        # (an engine serves one LinkState for life, so the journals
        # read below are the ones the cached versions came from)
        if cached is not None and (
            cached[1].index is not graph.node_index
            or cached[1].n_pad != graph.n_pad
        ):
            cached = None
        if cached is not None and cached[0] == key:
            return cached[1]
        dirty = None
        if cached is not None:
            dirty = self._journal_nodes(ls, cached[0])
        reach2 = getattr(self, "reach2", None)
        if dirty is None:
            if self._tarrays is not None:
                # arrays were held and could not be patched: in a churn
                # window this stays where the cold build left it
                _counters()["decision.ksp2_trace_reflattens"] += 1
            arrays = _TraceArrays(graph, cands_of, transit_blocked)
            if reach2 is not None:
                reach2[reach2 >= 0] = _ALL_CONSULTED
        else:
            arrays = cached[1]
            reordered, written, spliced = arrays.patch(
                cands_of, dirty, transit_blocked
            )
            _counters()["decision.ksp2_trace_rows_patched"] += written
            _counters()["decision.ksp2_trace_rows_spliced"] += spliced
            for nid, moved in reordered:
                # reach2 holds places in the list as it was. Where one
                # link came or went, what was read past its place
                # shifts by one (a walk that read as far as the link
                # that went is re-traced anyway: the tests name it);
                # where more changed, the places say nothing any more
                # and every walk that read some of the list read it all
                if reach2 is None:
                    continue
                col = reach2[:, nid]
                if moved is None:
                    col[col >= 0] = _ALL_CONSULTED
                else:
                    place, step = moved
                    shift = (col >= 0) & ((col & _DEPTH) > place) & (
                        (col & _DEPTH) != _DEPTH
                    )
                    col[shift] += step
        self._tarrays = (key, arrays)
        return arrays

    def _trace_many(
        self, ls, graph, cands_of, transit_blocked, dsts, rows,
        shared_row, excls, reach=None,
    ) -> List[List[List[Link]]]:
        """THE trace front-end for every per-event path enumeration:
        native batch when the core is available, else the Python tracer
        per destination — one site to keep the two byte-identical.
        ``rows``: one [n_pad] row (shared_row) or [len(dsts), n_pad];
        ``excls``: per-dst exclusion sets (empty for first paths);
        ``reach``: as _TraceArrays.trace's."""
        with get_tracer().span(
            "decision.ksp2_trace", dsts=len(dsts),
            rank=1 if shared_row else 2,
        ) as span:
            arrays = self._trace_arrays(
                ls, graph, cands_of, transit_blocked
            )
            if arrays is not None:
                got = arrays.trace(
                    self.sid,
                    np.asarray(
                        [graph.node_index[d] for d in dsts], np.int32
                    ),
                    rows, shared_row, excls, reach,
                )
                if got is not None:
                    if span is not None:
                        span.attrs["hops"] = _longest(got)
                    return got
            if span is not None:
                span.attrs["python"] = True
            if reach is not None:
                # the Python tracer does not say which lists it
                # consulted: all of them to the end, for all we know
                reach[:] = _ALL_CONSULTED
            shared_preds: Optional[Dict[str, list]] = (
                {} if shared_row else None
            )
            row_list = rows.tolist() if shared_row else None
            got = [
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
            if span is not None:
                span.attrs["hops"] = _longest(got)
            return got

    # -- priming / view preload -------------------------------------------

    def _note_paths(self, dst: str) -> None:
        """Bring the two entries ``dst`` has in the priming map to the
        paths the engine holds of it now; none for a destination the
        host answers for (LinkState computes those lazily, host SPF)."""
        first, second = (self.src_name, dst, 1), (self.src_name, dst, 2)
        if dst in self.host_dsts:
            self._primed.pop(first, None)
            self._primed.pop(second, None)
        else:
            self._primed[first] = self.first_paths[dst]
            self._primed[second] = self.second_paths.get(dst, [])

    def _prime_all(self, ls: LinkState) -> None:
        """The sync's last step: both ranks of every destination into
        LinkState's kth-path cache, which a topology change emptied
        (the ctrl API and the host fallback read it). The map is kept
        from sync to sync and patched where a window moved a
        destination (_note_paths), so this is one bulk update and no
        Python per destination."""
        ls.prime_kth_paths_bulk(self._primed)

    def _preload_view(self, ls, graph, view_srcs, view_packed) -> None:
        from openr_tpu.decision import spf_solver as _ss

        _ss._ELL_RESIDENT.preload_view(
            ls, graph, list(view_srcs), np.asarray(view_packed)
        )
