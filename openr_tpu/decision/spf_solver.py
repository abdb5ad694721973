"""SpfSolver: per-prefix best-route selection and next-hop computation.

Behavioral parity with the reference ``openr/decision/Decision.cpp``
SpfSolverImpl (buildRouteDb:569, createRouteForPrefix:402,
selectBestRoutes:737, maybeFilterDrainedNodes:783, selectBestPathsSpf:847,
selectBestPathsKsp2:908, addBestPaths:1033, getNextHopsWithMetric:1124,
getNextHopsThrift:1211) — re-architected so the graph math runs on TPU:

- shortest-path distances and ECMP first-hop sets come from the batched
  kernels in ``openr_tpu.ops.spf`` over the area's compiled
  ``GraphSnapshot`` ("device" backend), or from the host Dijkstra oracle
  ("host" backend; both are parity-tested against each other);
- per-prefix selection/filtering logic stays host-side where the data is
  ragged (it is cheap: O(advertisers) per prefix).

KSP2_ED_ECMP path enumeration uses host-side backtracing over SPF
predecessor links (paths are short; the SPF runs behind them are memoized).
"""

from __future__ import annotations

import contextlib
import os
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Set, Tuple

import numpy as np

from openr_tpu.analysis.annotations import thread_confined
from openr_tpu.decision.prefix_state import NodeAndArea, PrefixEntries, PrefixState
from openr_tpu.decision.rib import DecisionRouteDb, RibMplsEntry, RibUnicastEntry
from openr_tpu.faults.injector import fault_point, register_fault_site
from openr_tpu.graph.linkstate import Link, LinkState
from openr_tpu.graph.snapshot import INF, GraphSnapshot, SnapshotCache
from openr_tpu.types import (
    BinaryAddress,
    IpPrefix,
    MplsAction,
    MplsActionCode,
    NextHop,
    PrefixEntry,
    PrefixType,
)
from openr_tpu.types.lsdb import PrefixForwardingAlgorithm, PrefixForwardingType
from openr_tpu.utils.constants import is_mpls_label_valid

Metric = int
AreaLinkStates = Dict[str, LinkState]


def make_next_hop(
    address: BinaryAddress,
    if_name: Optional[str],
    metric: Metric,
    mpls_action: Optional[MplsAction] = None,
    area: Optional[str] = None,
    neighbor_node_name: Optional[str] = None,
) -> NextHop:
    """reference: openr/common/Util.cpp createNextHop"""
    if if_name is not None:
        address = BinaryAddress(addr=address.addr, if_name=if_name)
    return NextHop(
        address=address,
        metric=int(metric),
        mpls_action=mpls_action,
        area=area,
        neighbor_node_name=neighbor_node_name,
    )


@dataclass
class BestRouteSelectionResult:
    """reference: openr/decision/Decision.h BestRouteSelectionResult"""

    success: bool = False
    all_node_areas: Set[NodeAndArea] = field(default_factory=set)
    best_node_area: NodeAndArea = ("", "")

    def has_node(self, node: str) -> bool:
        return any(n == node for n, _ in self.all_node_areas)


def select_best_prefix_metrics(entries: PrefixEntries) -> Set[NodeAndArea]:
    """Pick advertisers with the best (path_pref DESC, source_pref DESC,
    distance ASC) metrics. The initial best is (0, 0, 0): advertisements
    strictly worse than the zero-metric tuple select nothing — matching the
    reference exactly. reference: openr/common/Util.h:549."""
    best_tuple = (0, 0, 0)
    best_keys: Set[NodeAndArea] = set()
    for key, entry in entries.items():
        t = entry.metrics.comparison_key()
        if t < best_tuple:
            continue
        if t > best_tuple:
            best_tuple = t
            best_keys.clear()
        best_keys.add(key)
    return best_keys


def select_best_node_area(
    all_node_areas: Set[NodeAndArea], my_node_name: str
) -> NodeAndArea:
    """Deterministic representative: self if present, else smallest key.
    reference: openr/common/Util.cpp:1057."""
    ordered = sorted(all_node_areas)
    for node_area in ordered:
        if node_area[0] == my_node_name:
            return node_area
    return ordered[0]


def get_prefix_forwarding_type_and_algorithm(
    entries: PrefixEntries, best_node_areas: Set[NodeAndArea]
) -> Tuple[PrefixForwardingType, PrefixForwardingAlgorithm]:
    """Lowest-common-denominator forwarding config among best advertisers.
    reference: openr/common/Util.cpp:617."""
    if not entries:
        return (PrefixForwardingType.IP, PrefixForwardingAlgorithm.SP_ECMP)
    ftype = PrefixForwardingType.SR_MPLS
    falgo = PrefixForwardingAlgorithm.KSP2_ED_ECMP
    for node_area, entry in entries.items():
        if node_area not in best_node_areas:
            continue
        ftype = min(ftype, entry.forwarding_type)
        falgo = min(falgo, entry.forwarding_algorithm)
        if (
            ftype == PrefixForwardingType.IP
            and falgo == PrefixForwardingAlgorithm.SP_ECMP
        ):
            break
    return (ftype, falgo)


# Alternate solver backends registered by plugins (the north-star
# "drop-in SpfSolver implementation" hook; reference: the pluginStart
# registration point, openr/plugin/Plugin.h:24-34). A factory takes
# (link_state, root) and returns an object implementing the SpfView
# query protocol: is_reachable / metric_to / next_hops_toward /
# metric_between.
_SPF_BACKENDS: Dict[str, "Callable[[LinkState, str], object]"] = {}


def register_spf_backend(name: str, factory) -> None:
    """Register a custom SPF view backend usable as
    ``SpfSolver(..., backend=name)``. Built-in names ("device", "native",
    "host") cannot be overridden."""
    assert name not in ("device", "native", "host"), name
    _SPF_BACKENDS[name] = factory


def unregister_spf_backend(name: str) -> None:
    _SPF_BACKENDS.pop(name, None)


# above this node count the device backend switches from the dense
# snapshot (O(N^2) metric matrix) to the resident sliced-ELL kernel
SPARSE_NODE_THRESHOLD = 4096

# Solver observability (exported through Decision.get_counters).
# Process-global by design, mirroring the reference's fb303 counter
# singletons (fb303::fbData->addStatValue) — and the ELL resident cache
# these count against is itself process-global device state. The host
# fallback counter tracks SpfView.metric_between queries answered by a
# full host Dijkstra because the queried source was outside the device
# batch — at scale that is an O(N log N) cliff that must stay at zero on
# the hot path (round-1 review: silent fallback).
# Since the telemetry spine landed this is a registry-backed shim: the
# same `SPF_COUNTERS[k] += 1` / `dict(SPF_COUNTERS)` call sites, but
# the store of record is openr_tpu.telemetry's process-wide Registry,
# so OpenrCtrl.get_counters / breeze / the benchmark see these names
# without a per-module merge loop.
from openr_tpu.telemetry import get_registry as _get_registry
from openr_tpu.telemetry import get_tracer as _get_tracer

SPF_COUNTERS = _get_registry().counter_dict(
    [
        "decision.spf_host_fallback",
        "decision.ell_full_compiles",
        "decision.ell_patches",
        "decision.ksp2_device_batches",
        "decision.ksp2_host_fallbacks",
        "decision.ksp2_cold_builds",
        "decision.ksp2_incremental_syncs",
        "decision.ksp2_warm_dispatches",
        # the all-pairs matrix a one-chip engine keeps is solved BEHIND
        # the window (ksp2_engine._dispatch_matrix): incremental syncs
        # that dispatched it so, and syncs that began while the
        # previous window's was still not ready (their rows solve then
        # queued behind it on the device)
        "decision.ksp2_matrix_deferred",
        "decision.ksp2_matrix_unready",
        "decision.ksp2_affected_dsts",
        # the KSP2 engine's trace arrays under churn: candidate rows a
        # sync wrote into the flat CSR in place (a metric change) and
        # spliced in (a flap), and whole rebuilds of arrays an engine
        # already held (0 once its cold build is behind it)
        "decision.ksp2_trace_rows_patched",
        "decision.ksp2_trace_rows_spliced",
        "decision.ksp2_trace_reflattens",
        "decision.ksp2_route_reuses",
        # prefixes the route loop walked in the builds a KSP2 engine
        # answered for: against those builds' prefixes, how often the
        # engine's carry served as an index (all of them on a cold
        # build or a prefix event)
        "decision.ksp2_routes_visited",
        "decision.sp_route_reuses",
        "decision.ell_prewarms",
        # a view's solve program was dispatched to the device (dense
        # spf_view_batch or ELL reconverge; a preloaded view is not one)
        "decision.device_solves",
        "decision.device_state_resets",
        "decision.backend_switches",
        # SpfSolver._views LRU demotions — the miniature of
        # tenancy.evictions; a hot loop here means the view cache cap
        # (OPENR_VIEW_CACHE_CAP) is below the live area count
        "route_engine.view_evictions",
    ]
)

# SpfSolver._views LRU capacity (graphs, not views). Overridable per
# solver via the view_cache_cap constructor arg.
VIEW_CACHE_CAP_DEFAULT = int(
    os.environ.get("OPENR_VIEW_CACHE_CAP", "4") or 4
)

# the Decision degradation ladder's injection seam (a fresh device
# view solve; see openr_tpu.faults)
FAULT_SPF_SOLVE = register_fault_site("decision.spf_solve")

# KSP2 device prefetch: below this many KSP2 destinations the host path
# is cheaper than a device dispatch; batches are fixed-size so the
# masked kernel compiles once per (topology bands, chunk) shape.
KSP2_DEVICE_MIN_DSTS = 32
# mask-memory budget per dispatch (bool slots); the chunk adapts so
# small graphs take ONE dispatch and one readback while 10k+-node
# graphs stay within device memory
KSP2_DEVICE_MASK_BUDGET = 32_000_000


def _ksp2_chunk(graph) -> int:
    # grow from 1 so the budget holds even when a single chunk of 32
    # bool masks would already exceed it at extreme ELL slot counts
    slots = sum(band.rows * band.k for band in graph.bands)
    chunk = 1
    while (
        chunk < 1024
        and chunk * 2 * max(1, slots) <= KSP2_DEVICE_MASK_BUDGET
    ):
        chunk *= 2
    return chunk



import weakref as _weakref

# weakly keyed by the LIVE LinkState: an id()-keyed memo can serve a
# dead graph's signature when CPython recycles the address for a new
# LinkState whose version counters pass through the same values — the
# SP-reuse soak caught exactly that as a parity break across worlds
_LINKS_SIG_MEMO: "_weakref.WeakKeyDictionary" = (
    _weakref.WeakKeyDictionary()
)

_EMPTY_PREFIXES: frozenset = frozenset()
# what a label that no node's route holds reads as: (node, entry)
_NO_LABEL_ROUTE = (None, None)


def _local_links_sig(ls: LinkState, node: str) -> tuple:
    """Signature of every route input read off the root's own links
    during next-hop materialization (Decision.cpp:1211): iface, metric,
    peer, liveness, v6/v4 next-hop addresses. Shared by the node-label
    and SP-reuse caches so their invalidation can't drift apart.

    Memoized per live graph x (topology version, attribute version,
    node): every field below moves one of the two versions when it
    changes, so both caches' per-build probes share one link walk."""
    per_ls = _LINKS_SIG_MEMO.get(ls)
    if per_ls is None:
        per_ls = {}
        _LINKS_SIG_MEMO[ls] = per_ls
    key = (ls.topology_version, ls.attributes_version, node)
    sig = per_ls.get(key)
    if sig is None:
        while len(per_ls) > 32:  # a few roots x live versions
            per_ls.pop(next(iter(per_ls)))
        sig = tuple(
            (
                link.iface_from(node),
                link.metric_from(node),
                link.other_node(node),
                link.is_up(),
                link.nh_v6_from(node).addr,
                link.nh_v4_from(node).addr,
            )
            for link in ls.ordered_links_from_node(node)
        )
        per_ls[key] = sig
    return sig


def get_spf_counters() -> Dict[str, int]:
    out = dict(SPF_COUNTERS)
    # sharded-dispatch placement/readback counters: surfaced in the
    # same snapshot so the benchmark and the reshard-storm runbook
    # recipe read one merged view (0 when no mesh ever activated)
    _reg = _get_registry()
    for _k in (
        "ops.reshard_events", "ops.shard_readback_bytes",
        # committed-dispatch accounting: submit/reap discipline of the
        # churn windows plus the AOT executable cache's hit economics
        "ops.host_dispatches", "ops.blocking_syncs",
        "ops.async_reaps", "ops.aot_compiles", "ops.aot_hits",
        "ops.aot_fallbacks",
    ):
        out[_k] = _reg.counter_get(_k)
    # fold in the ops-level resident-band counters under the same
    # namespace (one merged view for Decision.get_counters and the
    # churn smoke test)
    try:
        from openr_tpu.ops.spf_sparse import ELL_COUNTERS
    except Exception:
        return out
    for k, v in ELL_COUNTERS.items():
        out["decision." + k] = v
    return out


class SpfView:
    """SPF results for one area as seen from one root node.

    Device backend: distances + ECMP first-hop matrix from the jitted
    kernels over the area snapshot (dense for moderate N, sparse
    edge-list past SPARSE_NODE_THRESHOLD). Host backend: the Dijkstra
    oracle.
    """

    def __init__(self, ls: LinkState, root: str, backend: str):
        self._ls = ls
        self._root = root
        if backend == "native":
            from openr_tpu.graph import native_spf

            if not native_spf.is_available():
                raise native_spf.NativeBuildError(
                    "backend='native' needs a C++ compiler to build "
                    "native/spfcore.cpp; this machine has none"
                )
        self._backend = backend
        if backend == "device":
            if (
                len(ls.get_adjacency_databases()) > SPARSE_NODE_THRESHOLD
                # a batched tenant-plane dispatch (or a KSP2 engine)
                # already solved this exact view: consume it instead
                # of building the dense snapshot
                or _ELL_RESIDENT.has_preloaded(ls, root)
            ):
                self._init_device_sparse()
            else:
                self._init_device()
        elif backend == "native":
            self._init_native()
        else:
            self._init_host()

    # -- device backend ---------------------------------------------------

    def _init_device(self) -> None:
        """Batched {source} + neighbors SPF: the only rows a route rebuild
        consumes (source distances for best-path selection, neighbor rows
        for ECMP first hops and LFA — reference: Decision.cpp:1124, :1192).
        Readback is O(B x N), not O(N^2)."""
        from openr_tpu.ops import spf as spf_ops

        tracer = _get_tracer()
        self._d_all = None
        self._fh = None
        # LinkState -> device-resident arrays, host side (the row
        # patch's jit launch included)
        with tracer.span("graph.view_sync", formulation="dense") as span:
            self._snap: GraphSnapshot = _SNAPSHOTS.get(self._ls)
            sid = self._snap.id_of(self._root)
            self._sid = sid
            if sid is None:
                return
            if span is not None:
                span.attrs["rows"] = self._snap.rows_to_upload()
            srcs, srcs_dev = spf_ops.source_batch(self._snap, sid)
            dev = self._snap.device_arrays()
        bucket = srcs_dev.shape[0]
        SPF_COUNTERS["decision.device_solves"] += 1
        # the dispatch returns before the device is done ...
        with tracer.span(
            "ops.spf_view_batch", batch=bucket, n_pad=self._snap.n_pad
        ):
            packed = spf_ops.spf_view_batch_packed(
                dev.metric, dev.overloaded, srcs_dev
            )
        # ... and the host waits for the rest of it here: one
        # device->host transfer
        with tracer.span("ops.solve_readback", bytes=packed.nbytes):
            packed_host = np.asarray(packed)
        self._d = packed_host[:bucket]
        self._fh_batch = packed_host[bucket:].astype(bool)
        self._batch_srcs = srcs  # row i of _d is distances from srcs[i]
        self._row_of = {nid: i for i, nid in enumerate(srcs)}

    def _init_device_sparse(self) -> None:
        """Large-area device backend over resident sliced-ELL bands: the
        same batched {source} + neighbors view as the dense path (packed
        distances + on-device ECMP first hops, one transfer), but no
        dense N x N matrix is ever built — and the bands stay resident on
        the device across rebuilds, so steady-state churn costs one fused
        O(rows x K) scatter + solve dispatch (ops.spf_sparse ELL; the
        incremental-rebuild analogue of reference Decision.cpp:1896-1917)."""
        self._d_all = None
        self._fh = None
        if self._root not in self._ls.get_adjacency_databases():
            self._snap = None
            self._sid = None
            return
        graph, srcs, packed = _ELL_RESIDENT.view_packed(
            self._ls, self._root
        )
        self._snap = _SparseIndexAdapter(graph)
        self._sid = graph.node_index[self._root]
        b = len(srcs)
        self._d = packed[:b]
        self._fh_batch = packed[b:].astype(bool)
        self._batch_srcs = srcs
        # padding repeats the source id; keep the first (real) row
        row_of: Dict[int, int] = {}
        for i, nid in enumerate(srcs):
            row_of.setdefault(nid, i)
        self._row_of = row_of

    # -- native backend ---------------------------------------------------

    def _init_native(self) -> None:
        """Multithreaded C++ Dijkstra core (native/spfcore.cpp)."""
        from openr_tpu.graph import native_spf

        self._snap = _SNAPSHOTS.get(self._ls)
        sid = self._snap.id_of(self._root)
        self._sid = sid
        if sid is None:
            self._d_all = None
            self._fh = None
            return
        self._d_all = native_spf.all_pairs_distances(self._snap)
        self._fh = native_spf.first_hop_matrix(
            self._snap, sid, self._d_all[sid], self._d_all
        ).astype(bool)

    # -- host backend -----------------------------------------------------

    def _init_host(self) -> None:
        self._spf = self._ls.get_spf_result(self._root)

    # -- queries ----------------------------------------------------------

    def is_reachable(self, dst: str) -> bool:
        if self._backend == "device":
            if self._sid is None:
                return dst == self._root
            did = self._snap.id_of(dst)
            return did is not None and self._d[0, did] < INF
        if self._backend == "native":
            if self._sid is None:
                return dst == self._root
            did = self._snap.id_of(dst)
            return did is not None and self._d_all[self._sid, did] < INF
        return dst in self._spf

    def metric_to(self, dst: str) -> Optional[Metric]:
        if self._backend == "device":
            if self._sid is None:
                return 0 if dst == self._root else None
            did = self._snap.id_of(dst)
            if did is None or self._d[0, did] >= INF:
                return None
            return int(self._d[0, did])
        if self._backend == "native":
            if self._sid is None:
                return 0 if dst == self._root else None
            did = self._snap.id_of(dst)
            if did is None or self._d_all[self._sid, did] >= INF:
                return None
            return int(self._d_all[self._sid, did])
        res = self._spf.get(dst)
        return res.metric if res is not None else None

    def next_hops_toward(self, dst: str) -> Set[str]:
        if self._backend == "device":
            if self._sid is None:
                return set()
            did = self._snap.id_of(dst)
            if did is None:
                return set()
            col = self._fh_batch[: len(self._batch_srcs), did]
            return {
                self._snap.node_names[self._batch_srcs[i]]
                for i in np.nonzero(col)[0]
            }
        if self._backend == "native":
            if self._sid is None:
                return set()
            did = self._snap.id_of(dst)
            if did is None:
                return set()
            col = self._fh[:, did]
            return {
                self._snap.node_names[v]
                for v in np.nonzero(col)[0]
                if v < self._snap.n
            }
        res = self._spf.get(dst)
        return set(res.next_hops) if res is not None else set()

    def metric_between(self, a: str, b: str) -> Optional[Metric]:
        """Distance from node a to b, where a is the root or one of its
        neighbors (all LFA needs — reference: Decision.cpp:1192)."""
        if a == b:
            return 0
        if self._backend == "device":
            if self._sid is None:
                return None
            aid, bid = self._snap.id_of(a), self._snap.id_of(b)
            if aid is None or bid is None:
                return None
            row = self._row_of.get(aid)
            if row is None:
                # not in the batch (a is neither root nor neighbor):
                # fall back to the host oracle, correctness over speed.
                # Counted: at scale this is an O(N log N) cliff that must
                # stay at zero on the hot path (LFA only queries
                # neighbors, which the batch always covers).
                SPF_COUNTERS["decision.spf_host_fallback"] += 1
                res = self._ls.get_spf_result(a)
                return res[b].metric if b in res else None
            if self._d[row, bid] >= INF:
                return None
            return int(self._d[row, bid])
        if self._backend == "native":
            if self._d_all is None:
                return None
            aid, bid = self._snap.id_of(a), self._snap.id_of(b)
            if aid is None or bid is None or self._d_all[aid, bid] >= INF:
                return None
            return int(self._d_all[aid, bid])
        res = self._ls.get_spf_result(a)
        return res[b].metric if b in res else None


_SNAPSHOTS = SnapshotCache()


class _SparseIndexAdapter:
    """Gives the sparse device backend the same id_of/node_names surface
    the dense GraphSnapshot provides to the query methods."""

    __slots__ = ("node_names", "node_index", "n", "n_pad", "overloaded")

    def __init__(self, graph):
        # alias, don't copy: the sparse graph's name tuple is shared
        # across patches, so identity survives churn (the labels cache
        # keys on it)
        self.node_names = graph.node_names
        self.node_index = graph.node_index
        self.n = graph.n
        self.n_pad = graph.n_pad
        self.overloaded = graph.overloaded

    def id_of(self, node):
        return self.node_index.get(node)


class _EllResidentCache:
    """Device-resident sliced-ELL state per LinkState identity.

    The bands live on the device across rebuilds (EllState). On a
    topology change the LinkState journal's affected set drives
    ``ell_patch(widen=True)`` and one fused scatter+solve dispatch
    (``EllState.reconverge``); a row outgrowing its slot class widens
    its band in place (node ids stable), so only a node-set change or
    a journal gap forces ``compile_ell`` from scratch. This is the
    sparse analogue of the dense path's SnapshotCache row-patching
    (reference incremental rebuild: openr/decision/Decision.cpp:1896-1917)."""

    def __init__(self) -> None:
        # ls -> (synced topology_version, EllState)
        self._cache = _weakref.WeakKeyDictionary()
        # views the KSP2 engines already computed inside their rows
        # solves this build — consumed (popped) by view_packed so
        # SpfView does not pay a second device round trip. Entries are
        # (weakref(ls), version, root, graph, srcs, packed): identity
        # goes through the weakref (id() reuse after gc must never
        # serve a dead graph's rows), consume-once, bounded FIFO (one
        # entry per area engine per build).
        self._preloaded: List[tuple] = []

    def preload_view(self, ls, graph, srcs, packed) -> None:
        self.preload_views(ls, [(graph, srcs, packed)])

    def preload_views(self, ls, views) -> None:
        """Batch preload — the fleet twin's fan-in: every vantage's
        solved view from one batched tenant dispatch lands here so the
        per-vantage ``build_route_db`` calls each consume theirs with
        zero device work. ``views``: [(graph, srcs, packed)]."""
        # dead-graph entries can never match; drop them so MB-scale
        # packed rows don't stay pinned behind a dead LinkState
        self._preloaded = [
            e for e in self._preloaded if e[0]() is not None
        ]
        for graph, srcs, packed in views:
            root = graph.node_names[srcs[0]]
            self._preloaded.append(
                (
                    _weakref.ref(ls), ls.topology_version, root,
                    graph, srcs, packed,
                )
            )
        # bound growth on unconsumed entries — but never below the
        # area count (every area engine preloads BEFORE any view is
        # consumed, so a fixed cap would evict the earliest areas'
        # views each build) nor below THIS batch's size (an N-vantage
        # fleet preload must never evict its own earlier entries)
        cap = max(8, len(self._cache), len(views))
        del self._preloaded[:-cap]

    def has_preloaded(self, ls, root: str) -> bool:
        """True when view_packed would be satisfied by a preloaded
        entry (no device round trip). SpfView's device branch uses
        this to route moderate-N areas through the sparse consumption
        path when the tenant plane already solved them batched."""
        return any(
            e[0]() is ls
            and e[1] == ls.topology_version
            and e[2] == root
            for e in self._preloaded
        )

    def _sync(self, ls: LinkState):
        """Resolve the resident state for ``ls``: returns
        ``(state, pending)`` where ``pending`` is a journaled patched
        EllGraph whose rows are NOT yet applied to the resident bands
        (None when the bands are current or were just fully compiled).
        The cache version is committed by the caller once the pending
        rows actually land (fused into a solve, or via apply_patch)."""
        from openr_tpu.ops import spf_sparse

        entry = self._cache.get(ls)
        if entry is not None:
            version, state = entry
            if version == ls.topology_version:
                return state, None
            # the host's re-derivation of the changed rows: under
            # decision.prewarm, or under graph.view_sync where no
            # prewarm ran
            with _get_tracer().span(
                "ops.ell_patch", rows=0, widened=0
            ) as span:
                affected = ls.affected_since(version)
                patched = (
                    spf_sparse.ell_patch(
                        state.graph, ls, sorted(affected), widen=True
                    )
                    if affected is not None
                    else None
                )
                if span is not None and patched is not None:
                    span.attrs["rows"] = sum(
                        len(r) for r in (patched.changed or {}).values()
                    )
                    span.attrs["widened"] = len(patched.widened or ())
            if patched is not None:
                SPF_COUNTERS["decision.ell_patches"] += 1
                return state, patched
        state = spf_sparse.EllState(spf_sparse.compile_ell(ls))
        SPF_COUNTERS["decision.ell_full_compiles"] += 1
        self._cache[ls] = (ls.topology_version, state)
        return state, None

    def state_for(self, ls: LinkState):
        """Synced resident state for solve-free consumers (the KSP2
        masked batches): pending rows are scattered WITHOUT a view
        solve."""
        state, pending = self._sync(ls)
        if pending is not None:
            state.apply_patch(pending)
            self._cache[ls] = (ls.topology_version, state)
        return state

    def view_packed(
        self, ls: LinkState, root: str
    ) -> Tuple[object, List[int], np.ndarray]:
        """Sync the resident bands to ``ls`` and solve the batched
        {root} + neighbors view — pending patch rows ride the FUSED
        scatter+solve dispatch (EllState.reconverge). Returns (EllGraph,
        batch srcs, packed [2B, n_pad] host array: B distance rows then
        B first-hop rows)."""
        from openr_tpu.ops import spf_sparse

        for i, entry in enumerate(self._preloaded):
            ls_ref, version, entry_root, graph, srcs, packed = entry
            if (
                ls_ref() is ls
                and version == ls.topology_version
                and entry_root == root
            ):
                del self._preloaded[i]
                return graph, srcs, packed
        tracer = _get_tracer()
        with tracer.span("graph.view_sync", formulation="ell") as span:
            state, pending = self._sync(ls)
            graph = pending if pending is not None else state.graph
            srcs = spf_sparse.ell_source_batch(graph, ls, root)
            if span is not None:
                span.attrs["rows"] = (
                    sum(len(r) for r in (pending.changed or {}).values())
                    if pending is not None
                    else 0
                )
        SPF_COUNTERS["decision.device_solves"] += 1
        packed_dev = state.reconverge(graph, srcs)
        # reconverge's own span (ops.ell_reconverge) ends when the
        # dispatch returns; the host waits for the device here, and the
        # solve's two scalars arrive in the same read: ``passes`` = the
        # passes over the bands of both its loops (the cone seed's
        # support passes, then the relax passes), ``reset_rows`` = the
        # batch rows a tight increased edge flagged (their cone was
        # computed, or, cold, they restarted whole), ``whole_passes`` =
        # the passes that ran over the whole graph and not a frontier
        with tracer.span(
            "ops.solve_readback", bytes=packed_dev.nbytes
        ) as span:
            packed, passes, reset_rows, whole_passes = state.fetch_view(
                packed_dev
            )
            if span is not None:
                span.attrs["passes"] = passes
                span.attrs["whole_passes"] = whole_passes
                span.attrs["reset_rows"] = reset_rows
        self._cache[ls] = (ls.topology_version, state)
        return state.graph, srcs, packed


_ELL_RESIDENT = _EllResidentCache()


def export_resident_state(ls: LinkState):
    """The version-matched, solved resident ``EllState`` for ``ls`` —
    or None when nothing warm exists. The crash-safe state plane
    (``openr_tpu.state.snapshot``) serializes its warm material from
    this; the EllState itself never leaves the process."""
    entry = _ELL_RESIDENT._cache.get(ls)
    if entry is None:
        return None
    version, state = entry
    if version != ls.topology_version or state._d_dev is None:
        return None
    return state


def fleet_preload_views(ls: LinkState, views) -> None:
    """Install one batched wave's per-vantage solved views (the
    digital twin's fan-in): each ``(graph, srcs, packed)`` triple is
    consumed once by the matching root's next SpfView, so N vantage
    route rebuilds follow one ``world_dispatch`` with zero further
    device work."""
    _ELL_RESIDENT.preload_views(ls, views)


def seed_resident_state(ls: LinkState, state) -> None:
    """Install a rehydrated ``EllState`` as the resident entry for
    ``ls`` at its current topology version (warm-boot path: the state
    plane rebuilt it from a persisted snapshot, digest-gated)."""
    _ELL_RESIDENT._cache[ls] = (ls.topology_version, state)


def reset_device_caches() -> None:
    """Drop every module-level device-derived cache (resident ELL
    bands, preloaded views, compiled graph snapshots). The degradation
    ladder's cold rung calls this when a device solve failed: the next
    build recompiles and re-lands everything from the LinkState alone,
    so a torn dispatch can never leave half-synced resident state
    behind."""
    _ELL_RESIDENT._cache = _weakref.WeakKeyDictionary()
    _ELL_RESIDENT._preloaded = []
    _SNAPSHOTS.invalidate()
    try:
        # lazy: the tenant plane is optional and must not make the
        # cold rung's recovery path depend on its import
        from openr_tpu.ops import world_batch as _world_batch

        _world_batch.reset_world_manager()
    except Exception:
        pass


class _RouteTable:
    """The routes of the root the solver last built for, held from one
    build to the next. A build that can name what moved since the one
    before it (``seq``) patches ``unicast`` and ``mpls`` in place;
    every other build fills a new table. Never handed out: callers get
    copies (``RouteBuild.materialise``)."""

    __slots__ = ("meta", "seq", "n_prefixes", "unicast", "mpls", "own_labels")

    def __init__(self) -> None:
        # what the table was filled from (build_routes' reuse meta)
        self.meta: Optional[tuple] = None
        # the build that last wrote it
        self.seq = 0
        # prefixes it answers for, routeless ones included
        self.n_prefixes = 0
        self.unicast: Dict[IpPrefix, RibUnicastEntry] = {}
        self.mpls: Dict[int, RibMplsEntry] = {}
        # the labels of the root's own adjacency and static routes,
        # which every build lists anew
        self.own_labels: frozenset = frozenset()


class RouteBuild:
    """What one full build did to the solver's table.

    ``base_seq`` is the build the table's untouched entries date from:
    every key outside ``touched_unicast`` / ``touched_mpls`` (key -> new
    entry, or None for a route that went) maps to the very object it
    mapped to after build ``base_seq`` of ``solver``. None where the
    build filled a new table (``why`` says what stood in the way); the
    touched maps are then None too and ``materialise`` is the result."""

    __slots__ = (
        "solver", "seq", "base_seq", "touched_unicast", "touched_mpls",
        "why", "_table",
    )

    def __init__(
        self, solver, table: _RouteTable, base_seq: Optional[int],
        touched_unicast: Optional[Dict], touched_mpls: Optional[Dict],
        why: str,
    ) -> None:
        self.solver = solver
        self.seq = table.seq
        self.base_seq = base_seq
        self.touched_unicast = touched_unicast
        self.touched_mpls = touched_mpls
        self.why = why
        self._table = table

    @property
    def size(self) -> int:
        """Routes in the table this build left."""
        return len(self._table.unicast) + len(self._table.mpls)

    @property
    def touched(self) -> int:
        """Keys this build wrote: the whole table where it filled one."""
        if self.base_seq is None:
            return self.size
        return len(self.touched_unicast) + len(self.touched_mpls)

    def materialise(self) -> DecisionRouteDb:
        """The whole result as a db of the caller's own. Good until the
        solver's next build for this root, which may patch the table."""
        table = self._table
        if table.seq != self.seq:
            raise RuntimeError(
                f"route build {self.seq} read after build {table.seq} "
                "patched its table"
            )
        return DecisionRouteDb(
            unicast_routes=dict(table.unicast),
            mpls_routes=dict(table.mpls),
        )


# externally serialized, never internally locked: every solver is
# created and driven by exactly one plane — Decision's under evb, a
# ctrl handler's (fleet FIB builds, replica absorb) under
# SolverCtrlHandler._lock, the twin's on its one thread. The
# shared-state rule merges all instances by class, so cross-role
# access to one instance is impossible by construction — hence
# "owner" confinement (same contract as WorldManager).
@thread_confined(
    "owner",
    "_advertisers_cache",
    "_build_seq",
    "_ksp2_dsts_cache",
    "_ksp2_engines",
    "_ksp2_select",
    "_ksp2_tracked",
    "_ksp2_tracked_of",
    "_ksp2_untracked",
    "_label_cache",
    "_label_state",
    "_labels_cache",
    "_route_table",
    "_sp_prev_seq",
    "_sp_reuse",
    "_spec_staged",
    "_static_routes_version",
    "_views",
    "backend",
    "best_routes_cache",
    "static_mpls_routes",
)
class SpfSolver:
    """reference: openr/decision/Decision.h:202 SpfSolver (pImpl)."""

    def __init__(
        self,
        my_node_name: str,
        enable_v4: bool = False,
        compute_lfa_paths: bool = False,
        enable_ordered_fib: bool = False,
        bgp_dry_run: bool = False,
        enable_best_route_selection: bool = True,
        backend: str = "device",
        view_cache_cap: Optional[int] = None,
    ):
        self.my_node_name = my_node_name
        self.enable_v4 = enable_v4
        self.compute_lfa_paths = compute_lfa_paths
        self.enable_ordered_fib = enable_ordered_fib
        self.bgp_dry_run = bgp_dry_run
        self.enable_best_route_selection = enable_best_route_selection
        self.backend = backend
        # _views LRU capacity (per-graph slots); None -> env/default
        self.view_cache_cap = max(
            1,
            view_cache_cap
            if view_cache_cap is not None
            else VIEW_CACHE_CAP_DEFAULT,
        )
        self.static_mpls_routes: Dict[int, List[NextHop]] = {}
        self.best_routes_cache: Dict[IpPrefix, BestRouteSelectionResult] = {}
        # root -> (d, fh_matrix, node_names, links_sig,
        # {node: (label, entry)}) for the incremental node-label fast
        # path; per-root so ctrl queries for other nodes don't thrash
        # the hot path's slot
        self._label_cache: Dict[str, tuple] = {}
        # per-graph SPF view cache: ls -> {(version, root): view}.
        # STRONG object keys (no id-reuse aliasing), LRU-bounded: a
        # weak dict can never collect here because each SpfView holds
        # its graph (view._ls), so the value would pin its own key
        self._views: Dict[LinkState, Dict] = {}
        # incremental KSP2 engines keyed weakly by LinkState: a dead
        # area graph must release its engine (resident [n, n] device
        # matrix + path caches) instead of pinning it until eviction
        self._ksp2_engines = _weakref.WeakKeyDictionary()
        # speculation ledger: ls -> (version, root) staged by
        # speculate_views (once per debounce window, under the policy
        # wait) and not yet consumed by a rebuild. Weakly
        # keyed like _ksp2_engines; the staged view itself lives in
        # _views (it IS the rebuild's cache entry on a hit)
        self._spec_staged = _weakref.WeakKeyDictionary()
        # route reuse across churn: the routes of the last build's
        # root, patched in place by a build that knows what moved (the
        # engine's affected set, the SP dirty test) and refilled by one
        # that does not. Its best-route results are best_routes_cache
        self._route_table: Optional[_RouteTable] = None
        # nodes the engine's affected set actually covers (its KSP2
        # destinations); reuse is only sound for prefixes whose
        # advertisers all lie inside this set
        self._ksp2_tracked: Set[str] = set()
        # what it was made from (_prefetch_ksp2_paths): it is replaced
        # only when that changes, so its identity can key a cache
        self._ksp2_tracked_of: Optional[tuple] = None
        # (advertisers cache, tracked set, the KSP2 prefixes with an
        # advertiser outside it): the part of a bulk build's visit set
        # that no carry names, made once per prefix-state version and
        # tracked set, not by a pass over the KSP2 prefixes per build
        self._ksp2_untracked: Optional[tuple] = None
        # advertiser sets per prefix, cached per prefix_state VERSION:
        # rebuilding them per prefix per event made the reuse loop
        # itself the cost it was meant to avoid (~30us x n_prefixes of
        # entries_for + set building per churn event)
        self._advertisers_cache: Optional[tuple] = None
        # root -> (build seq, {area -> previous build's
        # route-determining signature}) for the SP reuse dirty test
        # (_sp_dirty_nodes): batched distance + first-hop matrices,
        # overload bits, node labels, local-link signature per area
        # ("absent" + versions for areas the root is not in). Bounded
        # like _label_cache.
        self._sp_reuse: Dict[str, tuple] = {}
        # monotonically increasing build counter: ties each cached
        # state to the build that produced it, so the label-route
        # patch below can prove its base state is the SAME build the
        # SP dirty set was diffed against
        self._build_seq = 0
        self._sp_prev_seq: Optional[int] = None
        # [seconds in, calls of] _select_best_paths_ksp2 since the
        # per-prefix pass in hand began (``select_ms`` / ``selected``
        # on decision.ksp2_routes: two clock reads a call, no span)
        self._ksp2_select = [0.0, 0]
        # per-prefix-state-version KSP2 destination sets (see
        # _prefetch_ksp2_paths)
        self._ksp2_dsts_cache: Optional[tuple] = None
        # root -> (seq, label_to_node, winners, collision labels,
        # labels-by-node, area): the assembled node-label route map,
        # patchable in O(dirty) when the SP dirty test names the only
        # destinations whose routes could have moved
        self._label_state: Dict[str, tuple] = {}
        # node-label vector cache per live graph: labels only move on
        # an attribute change, so the O(N) rebuild is skipped across
        # metric churn. Weakly keyed (like _ksp2_engines) so a dead
        # area's slot can never alias a recycled id.
        self._labels_cache = _weakref.WeakKeyDictionary()
        # bumped on every static-MPLS mutation: _add_best_paths merges
        # static next hops into self-advertised anycast routes, so the
        # reuse meta must change when they do
        self._static_routes_version = 0

    # -- static MPLS routes ----------------------------------------------

    def update_static_mpls_routes(
        self,
        routes_to_update: Dict[int, List[NextHop]],
        routes_to_delete: List[int],
    ) -> None:
        for label, nhs in routes_to_update.items():
            self.static_mpls_routes[label] = list(nhs)
        for label in routes_to_delete:
            self.static_mpls_routes.pop(label, None)
        self._static_routes_version += 1

    # -- degradation-ladder hooks -----------------------------------------

    def reset_device_state(self) -> None:
        """Discard every solver cache derived from device solves (and
        the module-level resident/compiled caches behind them). The
        ladder's cold rung runs this before a full rebuild so the
        rebuild recomputes everything from the LinkStates alone —
        nothing cached across a failed or torn device dispatch can
        leak into the recovered route database."""
        self._views = {}
        self._ksp2_engines = _weakref.WeakKeyDictionary()
        self._spec_staged = _weakref.WeakKeyDictionary()
        self._labels_cache = _weakref.WeakKeyDictionary()
        self._route_table = None
        self._advertisers_cache = None
        self._ksp2_dsts_cache = None
        self._ksp2_tracked = set()
        self._ksp2_tracked_of = None
        self._ksp2_untracked = None
        self._sp_reuse = {}
        self._sp_prev_seq = None
        self._label_cache = {}
        self._label_state = {}
        reset_device_caches()
        SPF_COUNTERS["decision.device_state_resets"] += 1

    def set_backend(self, backend: str) -> None:
        """Switch the solve backend. The view/route caches are not
        backend-keyed, so a flip must drop them — otherwise a view
        solved by the old backend would satisfy the new backend's
        cache probe."""
        if backend == self.backend:
            return
        self.backend = backend
        self.reset_device_state()
        SPF_COUNTERS["decision.backend_switches"] += 1

    # -- SPF views --------------------------------------------------------

    def prewarm(
        self, area_link_states: AreaLinkStates, trace=None
    ) -> None:
        """Publication-time overlap hook (called by the decision module
        as publications land, right AFTER it has armed the debounce
        timer and so before the debounced rebuild can fire): push
        pending topology deltas into the device-resident ELL bands now,
        so the host patch and the band scatter run inside the policy
        wait instead of sitting on the rebuild's critical path. Touches
        only graphs that ALREADY have resident state (never compiles a
        new one) and swallows failures — this is an overlap
        optimization, not a correctness step: the rebuild re-syncs and
        no-ops when the bands are already current.

        Safe to call once per publication in a burst: the EllState
        journal MERGES stacked patches (snapshot-keyed edge deltas, see
        spf_sparse.EllState._note_patch), so N prewarmed publications
        inside one debounce window still leave the debounced rebuild on
        the warm-solve path — burst churn pays one fused dispatch, not
        a forced cold seed.

        ``trace`` is the debounce window's: the patch runs on the
        caller's thread with the timer already armed, so it is the part
        of ``decision.debounce`` that is work under the policy wait (it
        lengthens the window only by what it runs past the deadline),
        and gets a span of its own there (none when there is nothing
        to patch)."""
        if self.backend != "device":
            return
        for ls in area_link_states.values():
            try:
                entry = _ELL_RESIDENT._cache.get(ls)
                if entry is None or entry[0] == ls.topology_version:
                    continue
                tracer = _get_tracer()
                with tracer.span(
                    "decision.prewarm",
                    trace=trace,
                    rows=len(ls.affected_since(entry[0]) or ()),
                ):
                    # the window's trace active on this thread, so the
                    # patch's own spans (ops.ell_patch, ops.ell_scatter)
                    # nest in this one
                    tracer.activate(trace)
                    try:
                        _ELL_RESIDENT.state_for(ls)
                    finally:
                        tracer.deactivate()
                SPF_COUNTERS["decision.ell_prewarms"] += 1
            except Exception:
                continue

    def speculate_views(
        self,
        my_node_name: str,
        area_link_states: AreaLinkStates,
        prefix_state: PrefixState,
    ) -> int:
        """Window-opening speculation hook (the decision module calls
        this once per debounce window, from the first publication that
        finds no other queued behind it, which at one publication a
        window is the one that opens it, right AFTER it has armed the
        timer and ``prewarm`` has returned): the LSDB as it stands is
        then what the rebuild will compute for, so do the rebuild's
        device step for it NOW, under the policy wait, on the committed
        path's own code (blocking; the timer's callback runs on the
        same thread and so cannot fire before this returns). Which step
        is read off what serves the root's view in that area:

        - no KSP2 engine: solve the view through ``_view`` and let the
          rebuild's ``_view`` land on a cache hit;
        - a live, valid engine at this root: the view there comes out
          of the engine's own rows solve, so sync the ENGINE
          (``_prefetch_ksp2_area``, the carry left untaken: the
          window's ``build_route_db`` finds it at its version, its own
          ``sync`` does nothing, and it takes what this one found
          moved). No view is solved on the dense or ELL view path
          there, which that area otherwise never runs.

        Counted, never silent: ``ops.spec_dispatches`` on stage,
        ``ops.spec_hits`` when the rebuild finds the view solved / the
        engine at the version it builds for, ``ops.spec_cancels`` when
        a later publication moved the version past the stage (a view is
        then re-solved, bit-identical: it is pure in (version, root);
        an engine steps on from the staged version and the build takes
        the union, so only the overlap is lost) or the stage raised
        (abandoned, never an escalation: a torn engine is invalid and
        the rebuild builds it cold on its own ladder). A graph whose
        version has not moved (a prefix-only window) has its view
        cached already: nothing is dispatched and no counter moves.
        Stands down (``ops.spec_skips``) while any chaos fault is
        armed: every fault seam belongs to the committed path's
        degradation ladder, and a speculative solve consuming a charge
        would let a fault escape the rung that owns it. And for an
        engine that is not valid: its next sync is a cold build, which
        is the rebuild's. Host and native backends return at once."""
        from openr_tpu.faults.injector import get_injector

        reg = _get_registry()
        if self.backend != "device":
            return 0
        if get_injector().any_armed:
            reg.counter_bump("ops.spec_skips")
            return 0
        staged = 0
        for area in sorted(area_link_states):
            ls = area_link_states[area]
            if not ls.has_node(my_node_name):
                continue
            key = (ls.topology_version, my_node_name)
            per_ls = self._views.get(ls)
            if per_ls is not None and key in per_ls:
                # the version has not moved since the view was solved
                # (first, and cheap: a window of prefix or attribute
                # changes only ends here with no counter moved)
                continue
            engine = self._ksp2_engines.get(ls)
            if engine is not None and engine.src_name != my_node_name:
                engine = None  # another root's: the view path serves
            prev = self._spec_staged.pop(ls, None)
            if prev is not None or (engine is not None and engine.staged):
                # an earlier stage for this graph died unconsumed
                reg.counter_bump("ops.spec_cancels")
            try:
                if engine is None:
                    self._view(area, ls, my_node_name)
                    self._spec_staged[ls] = key
                else:
                    engine.staged = False
                    dsts = self._ksp2_area_dsts(
                        my_node_name, area_link_states, prefix_state
                    )[0][area]
                    if (
                        not engine.valid
                        or len(dsts) < KSP2_DEVICE_MIN_DSTS
                    ):
                        reg.counter_bump("ops.spec_skips")
                        continue
                    self._prefetch_ksp2_area(
                        area, ls, my_node_name, dsts, take=False
                    )
                    engine.staged = True
            except Exception:
                # abandoned speculation, never an escalation: the
                # committed rebuild owns the retry ladder
                reg.counter_bump("ops.spec_cancels")
                continue
            reg.counter_bump("ops.spec_dispatches")
            staged += 1
        return staged

    def _view(self, area: str, ls: LinkState, root: str) -> SpfView:
        del area  # identity of the LinkState object is the key
        per_ls = self._views.get(ls)
        if per_ls is None:
            per_ls = {}
        else:
            # re-insert on hit: eviction is LRU, not FIFO — with 5+
            # areas a FIFO bound evicts the hottest graph every build,
            # which silently disables the SP dirty test
            del self._views[ls]
        self._views[ls] = per_ls
        while len(self._views) > self.view_cache_cap:
            self._views.pop(next(iter(self._views)))
            SPF_COUNTERS["route_engine.view_evictions"] += 1
        key = (ls.topology_version, root)
        view = per_ls.get(key)
        spec = self._spec_staged.get(ls)
        if spec is not None:
            if view is not None and spec == key:
                # the debounced rebuild consumed the staged view —
                # the speculative solve paid off
                del self._spec_staged[ls]
                _get_registry().counter_bump("ops.spec_hits")
            elif spec[0] != key[0]:
                # the graph moved past the staged version: the
                # speculative solve died unconsumed
                del self._spec_staged[ls]
                _get_registry().counter_bump("ops.spec_cancels")
            # same version, different root (a ctrl query): the staged
            # view stays armed for the rebuild
        if view is None:
            # drop stale versions of this graph
            for k in [k for k in per_ls if k[0] != key[0]]:
                del per_ls[k]
            if self.backend == "device":
                # the degradation ladder's device seam: a cached view
                # never fails (its rows already crossed), a fresh
                # device solve can
                fault_point(FAULT_SPF_SOLVE)
            factory = _SPF_BACKENDS.get(self.backend)
            view = (
                factory(ls, root)
                if factory is not None
                else SpfView(ls, root, self.backend)
            )
            per_ls[key] = view
        return view

    # -- SP route reuse dirty test ----------------------------------------

    def _sp_dirty_nodes(
        self,
        my_node_name: str,
        area_link_states: AreaLinkStates,
    ) -> Tuple[bool, Optional[Set[str]]]:
        """Per-destination change detection for SP_ECMP route reuse.

        A non-KSP2 route from ``my_node_name`` toward advertiser ``a``
        is a pure function of: (1) the prefix entries (version-gated by
        the caller), (2) the batched view's distance and first-hop
        COLUMNS for ``a`` (reachability, best metric, ECMP first hops —
        reference: Decision.cpp:847/:1124), (3) the distance columns of
        the first-hop NEIGHBORS themselves (remaining metric =
        shortest - metric_to(nh), Decision.cpp:1211), (4) the
        advertiser's overload bit (maybeFilterDrainedNodes,
        Decision.cpp:783) and node label (SR PUSH materialization), and
        (5) the local link signature (iface, metric, addresses).

        Compares all of (2)-(5) against the previous build and returns
        ``(stored, dirty)``: ``stored`` is True when a fresh signature
        was recorded (detection will be available next build); ``dirty``
        is the set of node names whose routes MAY have changed, or None
        when no comparable previous signature exists (first build,
        topology re-index, neighbor-set change, non-device backend).

        Multi-area: cross-area best-path selection takes the min over
        every area's view (Decision.cpp:1124 loops areas), so a node is
        clean only if it is clean in EVERY area; per-area signatures are
        compared independently and the dirty sets unioned. An area the
        root is absent from contributes a constant "unreachable" to
        route derivation — it is version-pinned instead of column-
        compared, so the root appearing there (or any churn inside it)
        disables reuse for that build.
        """
        per_area = []
        for area in sorted(area_link_states):
            ls = area_link_states[area]
            if not ls.has_node(my_node_name):
                per_area.append((area, ls, None))
                continue
            view = self._view(area, ls, my_node_name)
            d = getattr(view, "_d", None)
            fh = getattr(view, "_fh_batch", None)
            snap = getattr(view, "_snap", None)
            srcs = getattr(view, "_batch_srcs", None)
            if d is None or fh is None or snap is None or srcs is None:
                return False, None
            per_area.append((area, ls, (view, d, fh, snap, srcs)))
        rec = self._sp_reuse.get(my_node_name)
        prev_all = rec[1] if rec is not None else None
        self._sp_prev_seq = rec[0] if rec is not None else None
        if prev_all is not None and set(prev_all) != {
            a for a, _ls, _v in per_area
        }:
            prev_all = None
        fresh_all: Dict[str, tuple] = {}
        dirty_all: Optional[Set[str]] = (
            set() if prev_all is not None else None
        )
        for area, ls, viewdata in per_area:
            if viewdata is None:
                # root-absent area: pin its whole state
                sig = (
                    "absent",
                    ls.topology_version,
                    ls.attributes_version,
                )
                fresh_all[area] = sig
                if dirty_all is not None and prev_all[area] != sig:
                    dirty_all = None
                continue
            dirty = self._sp_dirty_one_area(
                my_node_name,
                ls,
                viewdata,
                None if prev_all is None else prev_all[area],
                fresh_all,
                area,
            )
            if dirty_all is not None:
                dirty_all = (
                    None if dirty is None else dirty_all | dirty
                )
        # re-insert at the end: eviction below is LRU-by-build, so
        # ctrl queries for other roots can't evict the hot root's slot
        self._sp_reuse.pop(my_node_name, None)
        self._sp_reuse[my_node_name] = (self._build_seq, fresh_all)
        while len(self._sp_reuse) > 8:  # bound ctrl-query growth
            self._sp_reuse.pop(next(iter(self._sp_reuse)))
        return True, dirty_all

    def _sp_dirty_one_area(
        self,
        my_node_name: str,
        ls: LinkState,
        viewdata: tuple,
        prev: Optional[tuple],
        fresh_all: Dict[str, tuple],
        area: str,
    ) -> Optional[Set[str]]:
        """One area's signature build + comparison for _sp_dirty_nodes;
        records the fresh signature into ``fresh_all[area]`` and
        returns the area's dirty set (None = no comparable previous
        signature)."""
        _view, d, fh, snap, srcs = viewdata
        b = len(srcs)
        names = snap.node_names
        n = len(names)
        # the device matrices pad the column (destination) axis to the
        # compiled shape; only the first n columns name real nodes.
        # Without LFA only the ROOT's distance row is ever consumed
        # (metric_to/is_reachable read d[0]; neighbor rows feed LFA,
        # which gates reuse off entirely) — comparing just that row
        # keeps remote churn that reroutes around the root invisible,
        # as it should be.
        d = d[0:1, :n]
        fh = fh[:b, :n]
        links_sig = _local_links_sig(ls, my_node_name)
        # the cache value retains the names referent: identity (shared
        # across snapshot patches on both backends) or content must
        # match, so an id()-reuse after GC can never alias orderings
        lc = self._labels_cache.get(ls)
        if (
            lc is not None
            and lc[0] == ls.attributes_version
            and (lc[1] is names or list(lc[1]) == list(names))
        ):
            labels = lc[2]
        else:
            adj_dbs = ls.get_adjacency_databases()
            labels = np.fromiter(
                (
                    adj_dbs[nm].node_label if nm in adj_dbs else -1
                    for nm in names
                ),
                dtype=np.int64,
                count=n,
            )
            self._labels_cache[ls] = (
                ls.attributes_version,
                names,
                labels,
            )
        ov_arr = getattr(snap, "overloaded", None)
        if ov_arr is not None:
            # snapshots rebuild on every topology change (overload
            # flips included), so their host mask is always current;
            # copy — the sparse resident graph patches it in place
            ov = np.array(ov_arr[:n], dtype=bool)
        else:
            ov = np.fromiter(
                (ls.is_node_overloaded(nm) for nm in names),
                dtype=bool,
                count=n,
            )
        dirty: Optional[Set[str]] = None
        if (
            prev is not None
            and len(prev) == 7
            and prev[4] == links_sig
            and prev[0].shape == d.shape
            and prev[1].shape == fh.shape
            and list(prev[2]) == list(srcs)
            and (
                prev[3] is names or list(prev[3]) == list(names)
            )
        ):
            col_changed = (
                (prev[0] != d).any(axis=0)
                | (prev[1] != fh).any(axis=0)
                | (prev[5] != ov)
                | (prev[6] != labels)
            )
            changed_rows = [
                i
                for i, nid in enumerate(srcs)
                if col_changed[int(nid)]
            ]
            if changed_rows:
                # a shifted neighbor column changes the remaining
                # metric of every destination it first-hops for (old
                # OR new first-hop sets — a hop can appear/vanish)
                dep = (
                    fh[changed_rows].any(axis=0)
                    | prev[1][changed_rows].any(axis=0)
                )
                dirty_mask = col_changed | dep
            else:
                dirty_mask = col_changed
            dirty = {
                str(names[int(i)])
                for i in np.flatnonzero(dirty_mask)
            }
        fresh_all[area] = (
            d.copy(),
            fh.copy(),
            tuple(int(s) for s in srcs),
            names,
            links_sig,
            ov,
            labels,
        )
        return dirty

    # -- route computation ------------------------------------------------

    def build_route_db(
        self,
        my_node_name: str,
        area_link_states: AreaLinkStates,
        prefix_state: PrefixState,
    ) -> Optional[DecisionRouteDb]:
        """Full RIB computation. reference: Decision.cpp:569 buildRouteDb.
        The db is the caller's own: no later build writes to it."""
        build = self.build_routes(
            my_node_name, area_link_states, prefix_state
        )
        return build.materialise() if build is not None else None

    def build_routes(
        self,
        my_node_name: str,
        area_link_states: AreaLinkStates,
        prefix_state: PrefixState,
    ) -> Optional[RouteBuild]:
        """``build_route_db`` for a caller that holds the previous
        result and wants what changed: the same computation, handed
        back as the record of what it wrote (``RouteBuild``)."""
        if not any(ls.has_node(my_node_name) for ls in area_link_states.values()):
            return None

        self._build_seq += 1
        # out of the solver while it is written: a build that raises
        # half way leaves no table, and the next one fills a new one
        table, self._route_table = self._route_table, None
        affected = self._prefetch_ksp2_paths(
            my_node_name, area_link_states, prefix_state
        )

        # Per-prefix route reuse: any prefix whose advertisers provably
        # produce a byte-identical route is served from the table
        # instead of re-derived (reference analogue: the per-prefix
        # incremental rebuild, Decision.cpp:1896-1917).
        # object references, not id()s: a recycled id on a NEW
        # graph/prefix-state whose version counters matched could alias
        # (plain classes compare by identity; the single slot pins them
        # only until the next build)
        meta = (
            prefix_state,
            prefix_state.version,
            my_node_name,
            self._static_routes_version,
            tuple(
                (a, ls) for a, ls in sorted(area_link_states.items())
            ),
        )
        # two independent change detectors feed the reuse gate:
        # - the KSP2 engine's affected set (covers its tracked
        #   destinations' full path state, second paths included)
        # - the SP dirty test (covers EVERY node's shortest-path route
        #   inputs column-wise; sound only for non-KSP2 prefixes)
        # LFA consumes neighbor-row distances the engine's affected
        # test does not model, so reuse is gated off with it.
        sp_stored, sp_dirty = (
            self._sp_dirty_nodes(my_node_name, area_link_states)
            if not self.compute_lfa_paths
            else (False, None)
        )
        iter_prefixes = prefix_state.prefixes()
        # (what a build does not visit it keeps, so the table has to
        # hold every prefix: the meta says it was filled from this
        # prefix state at this version, and the count holds it to that)
        meta_ok = (
            table is not None
            and table.meta == meta
            and table.n_prefixes == len(iter_prefixes)
        )
        reuse = (
            affected
            if (
                affected is not None
                and not self.compute_lfa_paths
                and meta_ok
            )
            else None
        )
        reuse_sp = sp_dirty if meta_ok else None
        populate = (
            affected is not None or sp_stored
        ) and not self.compute_lfa_paths

        adv_map = None
        if reuse is not None or reuse_sp is not None:
            # built only when reuse can actually consult it: an
            # LFA-enabled or engine-less solver never reads the map,
            # and building it would re-impose the very per-event cost
            # the cache exists to avoid
            adv_key = (prefix_state, prefix_state.version)
            if (
                self._advertisers_cache is None
                or self._advertisers_cache[0] != adv_key
            ):
                ksp2 = PrefixForwardingAlgorithm.KSP2_ED_ECMP
                amap = {
                    p: (
                        {node for (node, _a) in entries},
                        any(
                            e.forwarding_algorithm == ksp2
                            for e in entries.values()
                        ),
                    )
                    for p, entries in prefix_state.prefixes().items()
                }
                # inverted index + KSP2 set: the bulk-reuse path below
                # touches only the prefixes a dirty node advertises
                adv_index: Dict[str, Set[IpPrefix]] = {}
                ksp2_set: Set[IpPrefix] = set()
                for p, (advs, has_k) in amap.items():
                    if has_k:
                        ksp2_set.add(p)
                    for n in advs:
                        adv_index.setdefault(n, set()).add(p)
                self._advertisers_cache = (
                    adv_key, amap, adv_index, ksp2_set
                )
            adv_map = self._advertisers_cache[1]

        # Bulk reuse: with a valid SP dirty set, only prefixes
        # advertised by a dirty node (or carrying a KSP2 entry, whose
        # gate needs the engine's affected set) can produce a different
        # route — every other route stays where it is in the table, and
        # the build neither visits nor copies it. The dirty set says
        # what moved since the build that stored the signature it was
        # compared with, so that has to be the build the table dates
        # from.
        bulk = (
            reuse_sp is not None
            and adv_map is not None
            and table.seq == self._sp_prev_seq
        )
        n_prefixes = len(iter_prefixes)
        ksp2_reused = 0
        why = ""
        if bulk:
            _key, _amap, adv_index, ksp2_set = self._advertisers_cache
            if reuse is not None:
                # the engine's carry is an index into the prefixes, as
                # the SP dirty set is: the gate below can only refuse a
                # KSP2 prefix with an advertiser the carry names or no
                # engine tracks, so those are the ones it is shown
                must: Set[IpPrefix] = set(self._ksp2_untracked_prefixes())
                for n in reuse:
                    must |= adv_index.get(n, _EMPTY_PREFIXES)
            else:
                must = set(ksp2_set)
            for n in reuse_sp:
                must |= adv_index.get(n, _EMPTY_PREFIXES)
            # what the loop is not shown is adopted: the KSP2 prefixes
            # among it on the engine's word, the rest on the SP dirty
            # test's
            ksp2_reused = len(ksp2_set) - len(ksp2_set & must)
            SPF_COUNTERS["decision.sp_route_reuses"] += (
                table.n_prefixes - len(must) - ksp2_reused
            )
            # the prefixes this build answers for: the KSP2 ones and
            # whatever else the loop is shown
            n_prefixes = len(must) + ksp2_reused
            iter_prefixes = must
            base_seq: Optional[int] = table.seq
            touched_unicast: Optional[Dict] = {}
        else:
            # a new table, filled from the old one where the gate
            # allows; create_route_for_prefix files best results under
            # the attribute, so that starts anew as well
            why = self._why_not_patched(table, meta, sp_dirty)
            base_seq, touched_unicast = None, None
            old_unicast = table.unicast if meta_ok else {}
            old_best = self.best_routes_cache
            self.best_routes_cache = {}
            table = _RouteTable()
        unicast = table.unicast

        # where the KSP2 engine ran, the loop below is the KSP2 share
        # of route derivation: label stacks re-derived for the
        # destinations the engine named, the rest served from the table
        # counted here and booked once: a counter bump per prefix is a
        # registry round trip per prefix
        with (
            _get_tracer().span(
                "decision.ksp2_routes",
                prefixes=n_prefixes,
                visited=len(iter_prefixes),
            )
            if affected is not None
            else contextlib.nullcontext()
        ) as ksp2_span:
            self._ksp2_select = [0.0, 0]
            for prefix in iter_prefixes:
                if adv_map is not None:
                    advertisers, has_ksp2 = adv_map[prefix]
                    # a held route is reusable when every input that
                    # could change it is provably unchanged:
                    # - non-KSP2 prefix + every advertiser clean under the
                    #   SP dirty test (column-wise vs the previous build)
                    # - OR every advertiser is tracked by the KSP2 engine
                    #   and outside its affected set. An advertiser covered
                    #   by neither detector forces a re-derive.
                    ok = (
                        not has_ksp2
                        and reuse_sp is not None
                        and advertisers.isdisjoint(reuse_sp)
                    )
                    if ok:
                        SPF_COUNTERS["decision.sp_route_reuses"] += 1
                    elif (
                        reuse is not None
                        and advertisers <= self._ksp2_tracked
                        and advertisers.isdisjoint(reuse)
                    ):
                        ok = True
                        ksp2_reused += 1
                    if ok:
                        if not bulk:
                            entry = old_unicast.get(prefix)
                            if entry is not None:
                                unicast[prefix] = entry
                            best = old_best.get(prefix)
                            if best is not None:
                                self.best_routes_cache[prefix] = best
                        continue
                entry = self.create_route_for_prefix(
                    my_node_name, area_link_states, prefix_state, prefix
                )
                if entry is not None:
                    unicast[prefix] = entry
                elif bulk:
                    unicast.pop(prefix, None)
                if bulk:
                    touched_unicast[prefix] = entry
            SPF_COUNTERS["decision.ksp2_route_reuses"] += ksp2_reused
            if affected is not None:
                SPF_COUNTERS["decision.ksp2_routes_visited"] += len(
                    iter_prefixes
                )
            if ksp2_span is not None:
                ksp2_span.attrs["reused"] = ksp2_reused
                select_s, selected = self._ksp2_select
                ksp2_span.attrs["select_ms"] = round(select_s * 1e3, 4)
                ksp2_span.attrs["selected"] = selected
        table.meta = meta
        table.seq = self._build_seq
        table.n_prefixes = len(prefix_state.prefixes())

        # MPLS routes for node (SR) labels (label routes depend only on
        # the graph, so the raw dirty set applies regardless of the
        # prefix-state meta gate), then the root's own: its adjacency
        # labels and the static routes, over a node label of the same
        # number
        label_to_node, touched_labels = self._build_node_label_routes(
            my_node_name, area_link_states, sp_dirty=sp_dirty
        )
        own = self._own_label_routes(my_node_name, area_link_states)
        touched_mpls: Optional[Dict] = None
        if bulk and touched_labels is not None:
            # a node's label the patch moved, an own label that went
            # (the node label it stood over shows again, if any), and
            # the own routes as this build made them
            touched_mpls = {
                label: label_to_node.get(label, _NO_LABEL_ROUTE)[1]
                for label in touched_labels.union(
                    table.own_labels.difference(own)
                )
            }
            touched_mpls.update(own)
            mpls = table.mpls
            for label, entry in touched_mpls.items():
                if entry is None:
                    mpls.pop(label, None)
                else:
                    mpls[label] = entry
        else:
            if bulk:
                why = "label_map_rebuilt"
                base_seq, touched_unicast = None, None
            # bulk-assemble: mpls_routes is a label-keyed dict, so
            # insertion order is irrelevant; per-entry add calls cost
            # ~250 ms/build at 100k
            table.mpls = {lab: ne[1] for lab, ne in label_to_node.items()}
            table.mpls.update(own)
        table.own_labels = frozenset(own)

        if populate:
            self._route_table = table
        return RouteBuild(
            self, table, base_seq, touched_unicast, touched_mpls, why
        )

    @staticmethod
    def _why_not_patched(
        table: Optional[_RouteTable], meta: tuple,
        sp_dirty: Optional[Set[str]],
    ) -> str:
        """Why a build fills a new table, for the record it returns."""
        if table is None:
            # none kept: the first build, one after a reset, a backend
            # flip or a build that raised; or nothing to keep one for
            # (LFA, a backend with no change detector)
            return "no_table"
        if table.meta != meta:
            # another root, prefix state or version of it, static
            # routes, set of areas
            return "inputs_changed"
        if sp_dirty is None:
            return "no_dirty_set"
        return "table_not_of_last_build"

    def _own_label_routes(
        self, my_node_name: str, area_link_states: AreaLinkStates
    ) -> Dict[int, RibMplsEntry]:
        """The MPLS routes of the root's own links and the static ones:
        a handful, made anew by every build."""
        own: Dict[int, RibMplsEntry] = {}
        # MPLS routes for adjacency labels
        for _, ls in sorted(area_link_states.items()):
            for link in ls.ordered_links_from_node(my_node_name):
                top_label = link.adj_label_from(my_node_name)
                if top_label == 0:
                    continue
                if not is_mpls_label_valid(top_label):
                    continue
                own[top_label] = RibMplsEntry(
                    top_label,
                    {
                        make_next_hop(
                            link.nh_v6_from(my_node_name),
                            link.iface_from(my_node_name),
                            link.metric_from(my_node_name),
                            MplsAction(action=MplsActionCode.PHP),
                            link.area,
                            link.other_node(my_node_name),
                        )
                    },
                )

        # static MPLS routes
        for label, nhs in self.static_mpls_routes.items():
            own[label] = RibMplsEntry(label, set(nhs))
        return own

    def _ksp2_untracked_prefixes(self) -> frozenset:
        """The KSP2 prefixes with an advertiser outside
        ``_ksp2_tracked`` (a node present in an area whose engine does
        not track it, or one that advertises no KSP2 prefix itself):
        no carry ever names them, so the reuse gate refuses them in
        every build and a bulk build has to show them to it."""
        adv, tracked = self._advertisers_cache, self._ksp2_tracked
        made = self._ksp2_untracked
        if made is None or made[0] is not adv or made[1] is not tracked:
            _key, amap, _index, ksp2_set = adv
            made = (
                adv,
                tracked,
                frozenset(
                    p for p in ksp2_set if not amap[p][0] <= tracked
                ),
            )
            self._ksp2_untracked = made
        return made[2]

    # -- node-label routes -------------------------------------------------

    def _derive_label_entry(
        self,
        my_node_name: str,
        node: str,
        area: str,
        area_link_states: AreaLinkStates,
        top_label: int,
    ) -> Optional["RibMplsEntry"]:
        """One node's SR label route (PHP to self; SWAP/PHP toward a
        remote node). None when the node is unreachable."""
        if node == my_node_name:
            nh = make_next_hop(
                BinaryAddress.from_str("::"),
                None,
                0,
                MplsAction(action=MplsActionCode.POP_AND_LOOKUP),
                area,
                None,
            )
            return RibMplsEntry(top_label, {nh})
        metric_nhs = self._get_next_hops_with_metric(
            my_node_name, {(node, area)}, False, area_link_states
        )
        if not metric_nhs[1]:
            return None
        return RibMplsEntry(
            top_label,
            self._get_next_hops(
                my_node_name,
                {(node, area)},
                False,
                False,
                metric_nhs[0],
                metric_nhs[1],
                top_label,
                area_link_states,
                {},
            ),
        )

    def _store_label_state(
        self, my_node_name: str, areas: Tuple[str, ...], result, winners,
        collisions, labels_by,
    ) -> None:
        self._label_state.pop(my_node_name, None)
        self._label_state[my_node_name] = (
            self._build_seq, result, winners, collisions, labels_by,
            areas,
        )
        while len(self._label_state) > 8:
            self._label_state.pop(next(iter(self._label_state)))

    def _patch_node_label_routes(
        self,
        my_node_name: str,
        area_link_states: AreaLinkStates,
        dirty: Set[str],
        st: tuple,
    ) -> Optional[Tuple[Dict[int, Tuple[str, "RibMplsEntry"]], Set[int]]]:
        """O(dirty) update of the node-label route map, in place:
        re-derive only the destinations the SP dirty test names,
        keeping every other (node, entry) pair of the previous build
        where it is. Returns the map and the labels it wrote or
        dropped, or None when a contested label's winner must be
        recomputed from scratch (the losing claimants' entries were
        never derived), falling back to the full loop."""
        areas = tuple(sorted(area_link_states))
        _seq, result, winners, collisions, labels_by, st_areas = st
        if st_areas != areas:
            return None
        adj_dbs = [
            (a, area_link_states[a].get_adjacency_databases())
            for a in areas
        ]
        # out of the solver while it is patched: giving up (or a raise)
        # half way leaves no state, and the full loop stores a new one
        del self._label_state[my_node_name]
        touched: Set[int] = set()
        for node in sorted(dirty):
            old_label = labels_by.pop(node, None)
            # a node of several areas (a border) is derived for the
            # last of them, as the full loop leaves it
            found = [(a, dbs[node]) for a, dbs in adj_dbs if node in dbs]
            if len({db.node_label for _, db in found}) > 1:
                return None  # one label an area: the full loop's case
            area, db = found[-1] if found else (areas[-1], None)
            top_label = db.node_label if db is not None else 0
            if top_label == 0 or not is_mpls_label_valid(top_label):
                top_label = None
            entry = (
                self._derive_label_entry(
                    my_node_name, node, area, area_link_states,
                    top_label,
                )
                if top_label is not None
                else None
            )
            was_winner = winners.get(node)
            keeps_label = (
                old_label is not None and old_label == top_label
            )
            if was_winner is not None and not (
                keeps_label and entry is not None
            ):
                # the winner of old_label disappears: a losing
                # claimant (whose entry was never derived) may take
                # over — only the full loop knows who
                if old_label in collisions:
                    return None
                result.pop(old_label, None)
                touched.add(old_label)
                winners.pop(node, None)
            if top_label is None:
                continue
            labels_by[node] = top_label
            if entry is None:
                continue
            existing = result.get(top_label)
            if existing is not None and existing[0] != node:
                collisions.add(top_label)
                if existing[0] < node:
                    continue  # smaller name keeps the label
                winners.pop(existing[0], None)
            result[top_label] = (node, entry)
            touched.add(top_label)
            winners[node] = (top_label, entry)
        self._store_label_state(
            my_node_name, areas, result, winners, collisions, labels_by
        )
        return result, touched

    def _build_node_label_routes(
        self,
        my_node_name: str,
        area_link_states: AreaLinkStates,
        sp_dirty: Optional[Set[str]] = None,
    ) -> Tuple[Dict[int, Tuple[str, "RibMplsEntry"]], Optional[Set[int]]]:
        """SR node-label routes for every labeled node
        (reference: Decision.cpp:600-650 buildRouteDb label loop), and
        the labels whose route differs from the previous build's map
        where that map was patched (None where it was built anew).

        Incremental fast paths (device backend, no LFA): (1) when the
        SP dirty test proves which destinations' routes could have
        moved (over every area: it unions the areas' dirty sets), the
        previous build's assembled map is PATCHED in O(dirty)
        (_patch_node_label_routes) — the O(N) loop never runs, with one
        area or several; (2) otherwise, with one area, the batched
        view's column diff marks label routes reusable per destination
        and the loop re-derives only the changed ones. The map that
        comes back is the solver's own, patched again by the next
        build: read it, do not keep it."""
        label_to_node: Dict[int, Tuple[str, RibMplsEntry]] = {}

        if sp_dirty is not None and not self.compute_lfa_paths:
            st = self._label_state.get(my_node_name)
            if (
                st is not None
                and self._sp_prev_seq is not None
                and st[0] == self._sp_prev_seq
            ):
                patched = self._patch_node_label_routes(
                    my_node_name, area_link_states, sp_dirty, st
                )
                if patched is not None:
                    return patched

        reusable: Dict[str, Tuple[int, RibMplsEntry]] = {}
        cache_probe = None
        if len(area_link_states) == 1:
            ((area, ls),) = area_link_states.items()
            view = self._view(area, ls, my_node_name)
            d = getattr(view, "_d", None)
            fh = getattr(view, "_fh_batch", None)
            if d is not None and fh is not None and view._snap is not None:
                names = list(view._snap.node_names)
                links_sig = _local_links_sig(ls, my_node_name)
                cache_probe = (d.copy(), fh.copy(), names, links_sig)
                prev = self._label_cache.get(my_node_name)
                if (
                    prev is not None
                    and prev[2] == names
                    and prev[3] == links_sig
                    and prev[0].shape == d.shape
                    and prev[1].shape == fh.shape
                ):
                    # column-wise: a dst is dirty if ANY source row's
                    # distance (root or neighbor — LFA reads neighbor
                    # rows) or first-hop bit changed
                    changed = np.flatnonzero(
                        (prev[0] != d).any(axis=0)
                        | (prev[1] != fh).any(axis=0)
                    )
                    changed_ids = set(int(i) for i in changed)
                    # next-hop derivation subtracts the neighbor's own
                    # distance (remaining = shortest - metric_to(nh)), so
                    # a shifted neighbor row invalidates EVERY label route
                    neighbor_ids = {
                        int(i) for i in view._batch_srcs
                    }
                    if changed_ids.isdisjoint(neighbor_ids):
                        reusable = {
                            node: lab_entry
                            for node, lab_entry in prev[4].items()
                            if (
                                view._snap.id_of(node) is not None
                                and view._snap.id_of(node)
                                not in changed_ids
                            )
                        }

        built: Dict[str, Tuple[int, RibMplsEntry]] = {}
        labels_by: Dict[str, int] = {}
        collisions: Set[int] = set()
        # a node that shows another label in another area leaves two
        # routes behind: nothing the O(dirty) patch keeps track of
        one_label_a_node = True
        for area, ls in sorted(area_link_states.items()):
            for node, adj_db in sorted(ls.get_adjacency_databases().items()):
                top_label = adj_db.node_label
                if labels_by.get(node, top_label) != top_label:
                    one_label_a_node = False
                if top_label == 0:
                    continue
                if not is_mpls_label_valid(top_label):
                    continue
                labels_by[node] = top_label
                # label collision: deterministically keep the smaller name
                # (reference: Decision.cpp:620-633)
                existing = label_to_node.get(top_label)
                if existing is not None and existing[0] != node:
                    collisions.add(top_label)
                    if existing[0] < node:
                        continue
                cached = (
                    reusable.get(node)
                    if node != my_node_name
                    else None
                )
                if cached is not None and cached[0] == top_label:
                    label_to_node[top_label] = (node, cached[1])
                    built[node] = cached
                    continue
                entry = self._derive_label_entry(
                    my_node_name, node, area, area_link_states,
                    top_label,
                )
                if entry is None:
                    continue
                label_to_node[top_label] = (node, entry)
                built[node] = (top_label, entry)

        self._label_cache.pop(my_node_name, None)
        if cache_probe is not None:
            # re-insert at the end: eviction below is LRU-by-build
            self._label_cache[my_node_name] = (*cache_probe, built)
            while len(self._label_cache) > 8:  # bound ctrl-query growth
                self._label_cache.pop(next(iter(self._label_cache)))
        if one_label_a_node:
            # the winners are patched in place from here on, and
            # _label_cache keeps ``built`` beside the matrices it was
            # derived from: a copy
            self._store_label_state(
                my_node_name, tuple(sorted(area_link_states)),
                label_to_node, dict(built), collisions, labels_by,
            )
        else:
            self._label_state.pop(my_node_name, None)
        return label_to_node, None

    def create_route_for_prefix(
        self,
        my_node_name: str,
        area_link_states: AreaLinkStates,
        prefix_state: PrefixState,
        prefix: IpPrefix,
    ) -> Optional[RibUnicastEntry]:
        """reference: Decision.cpp:402 createRouteForPrefix."""
        all_entries = prefix_state.entries_for(prefix)
        if not all_entries:
            return None
        self.best_routes_cache.pop(prefix, None)

        # keep only entries from nodes reachable in their own area
        entries: PrefixEntries = dict(all_entries)
        for area, ls in area_link_states.items():
            view = self._view(area, ls, my_node_name)
            for node_area in list(entries):
                node, prefix_area = node_area
                if area == prefix_area and not view.is_reachable(node):
                    del entries[node_area]
        if not entries:
            return None

        if prefix.is_v4 and not self.enable_v4:
            return None

        has_bgp = has_non_bgp = missing_mv = False
        has_self_prepend_label = True
        for node_area, entry in entries.items():
            is_bgp = entry.type == PrefixType.BGP
            has_bgp |= is_bgp
            has_non_bgp |= not is_bgp
            if node_area[0] == my_node_name:
                has_self_prepend_label &= entry.prepend_label is not None
            if is_bgp and entry.mv is None:
                missing_mv = True
        if has_bgp:
            if has_non_bgp and not self.enable_best_route_selection:
                return None
            if missing_mv:
                return None  # a BGP advertiser without its metric vector

        best = self._select_best_routes(
            my_node_name, entries, has_bgp, area_link_states
        )
        if not best.success:
            return None
        if not best.all_node_areas:
            return None
        self.best_routes_cache[prefix] = best

        # routes to self-advertised prefixes are already programmed locally
        # unless we advertise with a prepend label (anycast origination)
        if best.has_node(my_node_name) and not has_self_prepend_label:
            return None

        ftype, falgo = get_prefix_forwarding_type_and_algorithm(
            entries, best.all_node_areas
        )
        if falgo == PrefixForwardingAlgorithm.SP_ECMP:
            return self._select_best_paths_spf(
                my_node_name,
                prefix,
                best,
                entries,
                has_bgp,
                ftype,
                area_link_states,
            )
        if falgo == PrefixForwardingAlgorithm.KSP2_ED_ECMP:
            t0 = time.perf_counter()
            try:
                return self._select_best_paths_ksp2(
                    my_node_name,
                    prefix,
                    best,
                    entries,
                    has_bgp,
                    ftype,
                    area_link_states,
                )
            finally:
                self._ksp2_select[0] += time.perf_counter() - t0
                self._ksp2_select[1] += 1
        return None

    # -- best route selection --------------------------------------------

    def _select_best_routes(
        self,
        my_node_name: str,
        entries: PrefixEntries,
        is_bgp: bool,
        area_link_states: AreaLinkStates,
    ) -> BestRouteSelectionResult:
        """reference: Decision.cpp:737 selectBestRoutes."""
        ret = BestRouteSelectionResult()
        if self.enable_best_route_selection:
            ret.all_node_areas = select_best_prefix_metrics(entries)
            if ret.all_node_areas:
                ret.best_node_area = select_best_node_area(
                    ret.all_node_areas, my_node_name
                )
            ret.success = True
        elif is_bgp:
            return self._run_best_path_selection_bgp(
                my_node_name, entries, area_link_states
            )
        else:
            ret.all_node_areas = set(entries)
            ret.best_node_area = min(ret.all_node_areas)
            ret.success = True
        return self._maybe_filter_drained_nodes(ret, area_link_states)

    def _run_best_path_selection_bgp(
        self,
        my_node_name: str,
        entries: PrefixEntries,
        area_link_states: AreaLinkStates,
    ) -> BestRouteSelectionResult:
        """MetricVector-ordered BGP best-path selection.
        reference: Decision.cpp:807 runBestPathSelectionBgp."""
        from openr_tpu.decision.metric_vector import (
            CompareResult,
            compare_metric_vectors,
        )

        ret = BestRouteSelectionResult()
        best_vector = None
        for node_area in sorted(entries):
            entry = entries[node_area]
            result = (
                CompareResult.WINNER
                if best_vector is None
                else compare_metric_vectors(entry.mv, best_vector)
            )
            if result in (CompareResult.TIE, CompareResult.ERROR):
                return ret  # ambiguous ordering: no route (success=False)
            if result == CompareResult.WINNER:
                ret.all_node_areas.clear()
            if result in (CompareResult.WINNER, CompareResult.TIE_WINNER):
                best_vector = entry.mv
                ret.best_node_area = node_area
            if result in (
                CompareResult.WINNER,
                CompareResult.TIE_WINNER,
                CompareResult.TIE_LOOSER,
            ):
                ret.all_node_areas.add(node_area)
        ret.success = True
        return self._maybe_filter_drained_nodes(ret, area_link_states)

    def _maybe_filter_drained_nodes(
        self,
        result: BestRouteSelectionResult,
        area_link_states: AreaLinkStates,
    ) -> BestRouteSelectionResult:
        """Drop overloaded (drained) advertisers; if everyone is drained,
        fall back to the unfiltered set. The representative best_node_area
        is kept as originally selected (matches the reference exactly).
        reference: Decision.cpp:783 maybeFilterDrainedNodes."""
        filtered = BestRouteSelectionResult(
            success=result.success,
            all_node_areas={
                (node, area)
                for node, area in result.all_node_areas
                if area not in area_link_states
                or not area_link_states[area].is_node_overloaded(node)
            },
            best_node_area=result.best_node_area,
        )
        return result if not filtered.all_node_areas else filtered

    def _get_min_next_hop_threshold(
        self, best: BestRouteSelectionResult, entries: PrefixEntries
    ) -> Optional[int]:
        """Max of advertised minNexthop requirements among best advertisers.
        reference: Decision.cpp:767 getMinNextHopThreshold."""
        threshold: Optional[int] = None
        for node_area in best.all_node_areas:
            entry = entries.get(node_area)
            if entry is None or entry.min_nexthop is None:
                continue
            if threshold is None or entry.min_nexthop > threshold:
                threshold = entry.min_nexthop
        return threshold

    # -- SP_ECMP ----------------------------------------------------------

    def _select_best_paths_spf(
        self,
        my_node_name: str,
        prefix: IpPrefix,
        best: BestRouteSelectionResult,
        entries: PrefixEntries,
        is_bgp: bool,
        ftype: PrefixForwardingType,
        area_link_states: AreaLinkStates,
    ) -> Optional[RibUnicastEntry]:
        """reference: Decision.cpp:847 selectBestPathsSpf."""
        per_destination = ftype == PrefixForwardingType.SR_MPLS

        # anycast origination: if we also advertise this prefix with a
        # prepend label, don't compute paths toward ourselves
        filtered_best = set(best.all_node_areas)
        if best.has_node(my_node_name) and per_destination:
            for node_area, entry in entries.items():
                if node_area[0] == my_node_name and entry.prepend_label is not None:
                    filtered_best.discard(node_area)
                    break

        min_metric, next_hop_nodes = self._get_next_hops_with_metric(
            my_node_name, filtered_best, per_destination, area_link_states
        )
        if not next_hop_nodes:
            return None

        next_hops = self._get_next_hops(
            my_node_name,
            best.all_node_areas,
            prefix.is_v4,
            per_destination,
            min_metric,
            next_hop_nodes,
            None,
            area_link_states,
            entries,
        )
        return self._add_best_paths(
            my_node_name, prefix, best, entries, is_bgp, next_hops
        )

    # -- KSP2_ED_ECMP -----------------------------------------------------

    def _ksp2_area_dsts(
        self,
        my_node_name: str,
        area_link_states: AreaLinkStates,
        prefix_state: PrefixState,
    ) -> Tuple[Dict[str, List[str]], Dict[str, List[str]]]:
        """Per area, the nodes other than the root that advertise a
        KSP2_ED_ECMP prefix there, sorted (the engine's destination
        list); and, for each such node, the areas it is NOT a
        destination of (what ``_ksp2_tracked`` has to ask of the graphs
        every build: empty with one area). The scan is O(total prefix
        entries): cached per prefix-state version (at 100k SP-only
        fabrics it burned ~0.4 s/event discovering an empty set every
        build), and with it the sort, so that a build whose engine is
        already synced makes no pass over the destinations."""
        dsts_key = (
            prefix_state,
            prefix_state.version,
            my_node_name,
            tuple(sorted(area_link_states)),
        )
        if (
            self._ksp2_dsts_cache is not None
            and self._ksp2_dsts_cache[0] == dsts_key
        ):
            return self._ksp2_dsts_cache[1:]
        found: Dict[str, Set[str]] = {
            area: set() for area in area_link_states
        }
        for prefix in prefix_state.prefixes():
            for (node, p_area), entry in prefix_state.entries_for(
                prefix
            ).items():
                if (
                    entry.forwarding_algorithm
                    == PrefixForwardingAlgorithm.KSP2_ED_ECMP
                    and node != my_node_name
                    and p_area in found
                ):
                    found[p_area].add(node)
        area_dsts = {area: sorted(nodes) for area, nodes in found.items()}
        elsewhere: Dict[str, List[str]] = {}
        if len(found) > 1:
            for node in set().union(*found.values()):
                others = [a for a in found if node not in found[a]]
                if others:
                    elsewhere[node] = others
        self._ksp2_dsts_cache = (dsts_key, area_dsts, elsewhere)
        return area_dsts, elsewhere

    def _prefetch_ksp2_paths(
        self,
        my_node_name: str,
        area_link_states: AreaLinkStates,
        prefix_state: PrefixState,
    ) -> Optional[Set[str]]:
        """Batch the KSP2 second-path SPFs onto the device.

        Host semantics (LinkState.get_kth_paths, reference
        LinkState.cpp:763) run ONE Dijkstra per destination over the
        graph minus that destination's first-path links — O(N) SPFs per
        rebuild, the quadratic cliff at fabric scale.

        Moderate N (<= ksp2_engine.ENGINE_MAX_NODES): the incremental
        Ksp2Engine persists paths across churn and re-solves only the
        destinations a change can affect; returns that affected set so
        build_route_db can reuse the untouched routes (None = no reuse
        this build). Larger N: the original per-build chunked masked
        dispatch (every destination, every build).

        Parallel links (LAGs) are first-class: the per-link ELL slots
        (spf_sparse.compile_ell direction="in" + build_edge_masks via
        graph.slot_of) mask individual group members, so no host
        fallback and no engine cold-rebuild on LAG fabrics
        (reference: LinkState.h:82 Link identity).

        Multi-area: one engine per area graph primes that area's paths.
        Route reuse needs EVERY area signaled — KSP2 paths toward a
        best advertiser are computed in every area's graph it appears
        in (_select_best_paths_ksp2 loops all areas), so a single
        unsignaled area's churn could silently change reused routes."""
        if self.backend != "device":
            return None
        area_dsts, elsewhere = self._ksp2_area_dsts(
            my_node_name, area_link_states, prefix_state
        )
        if not any(area_dsts.values()):
            return None

        union_affected: Set[str] = set()
        all_signaled = True
        ran_any = False
        for area, ls in sorted(area_link_states.items()):
            dsts = area_dsts[area]
            if (
                len(dsts) < KSP2_DEVICE_MIN_DSTS
                or not ls.has_node(my_node_name)
            ):
                all_signaled = False  # area covered by the host path
                continue
            result = self._prefetch_ksp2_area(
                area, ls, my_node_name, dsts
            )
            if result is None:
                all_signaled = False
                continue
            ran_any = True
            union_affected |= result
        if not ran_any or not all_signaled:
            return None
        # a best advertiser's paths are computed in EVERY area graph it
        # appears in: a node advertising in area a but merely PRESENT
        # in area b is untracked by b's engine, so b-churn would never
        # land it in the affected set — its routes must not be reused.
        # Every area ran, so the tracked set is every destination but
        # those strays; it keeps its identity while they stay the same
        # (build_route_db's untracked prefixes are cached against it)
        stray = frozenset(
            n
            for n, areas in elsewhere.items()
            if any(area_link_states[a].has_node(n) for a in areas)
        )
        made = self._ksp2_tracked_of
        if made is None or made[0] is not area_dsts or made[1] != stray:
            self._ksp2_tracked = {my_node_name}.union(
                *area_dsts.values()
            ).difference(stray)
            self._ksp2_tracked_of = (area_dsts, stray)
        return union_affected

    def _prefetch_ksp2_area(
        self,
        area: str,
        ls: LinkState,
        my_node_name: str,
        dsts: List[str],
        take: bool = True,
    ) -> Optional[Set[str]]:
        """Device-batch one area's KSP2 paths; returns the affected set
        (cold build = all dsts) or None when the area's paths came from
        the legacy per-build dispatch / host fallback (no reuse).

        ``take=False`` is the stage (``speculate_views``): the engine
        is synced and nothing else; what it found moved stays in the
        engine's carry for the window's ``build_route_db``, whose own
        call here takes it."""
        from openr_tpu.decision import ksp2_engine

        if (
            len(ls.get_adjacency_databases())
            <= ksp2_engine.engine_max_nodes()  # mesh-scaled bound
        ):
            engine = self._ksp2_engines.get(ls)
            if engine is not None and engine.src_name != my_node_name:
                # one engine per graph: keep the hot root's; other
                # roots (ctrl queries) take the host path
                return None
            if engine is None:
                engine = ksp2_engine.Ksp2Engine(my_node_name)
                self._ksp2_engines[ls] = engine
            staged, worked = engine.staged, engine.syncs_worked
            synced = engine.sync(ls, dsts)
            if not take:
                return synced
            if staged:
                # the build found the engine where the stage left it,
                # at the version it builds for, or had to step on
                _get_registry().counter_bump(
                    "ops.spec_hits"
                    if engine.syncs_worked == worked
                    else "ops.spec_cancels"
                )
            affected = engine.take_affected()
            if affected is None and engine.valid:
                # cold build: no reuse this time, but the per-prefix
                # cache built now is valid for the NEXT event — signal
                # "engine ran" with the all-affected set
                return set(dsts)
            return affected

        from openr_tpu.ops import spf_sparse

        # the same resident device bands the sparse view solves on —
        # incremental ell_patch sync, no band re-upload per dispatch
        state = _ELL_RESIDENT.state_for(ls)
        graph = state.graph
        sid = graph.node_index.get(my_node_name)
        if sid is None:
            return
        # first paths: host trace off the one memoized base SPF
        exclusion_sets = []
        for dst in dsts:
            links: Set[Link] = set()
            for path in ls.get_kth_paths(my_node_name, dst, 1):
                links.update(path)
            exclusion_sets.append(links)

        cands_of = ksp2_engine.make_cands_of(ls, graph.node_index)
        transit_blocked = {
            name
            for name in graph.node_names
            if ls.is_node_overloaded(name) and name != my_node_name
        }

        chunk = _ksp2_chunk(graph)
        for start in range(0, len(dsts), chunk):
            batch_dsts = dsts[start : start + chunk]
            batch_excl = exclusion_sets[start : start + chunk]
            pad = chunk - len(batch_dsts)
            masks, ok = spf_sparse.build_edge_masks(
                graph, batch_excl + [set()] * pad
            )
            drows, _passes = spf_sparse.ell_masked_distances_resident(
                state, sid, masks
            )
            SPF_COUNTERS["decision.ksp2_device_batches"] += 1
            for i, dst in enumerate(batch_dsts):
                if not ok[i]:
                    SPF_COUNTERS["decision.ksp2_host_fallbacks"] += 1
                    continue  # host path computes it lazily
                paths = ksp2_engine.trace_paths_from_row(
                    my_node_name,
                    dst,
                    graph.node_index,
                    drows[i].tolist(),
                    batch_excl[i],
                    cands_of,
                    transit_blocked,
                )
                ls.prime_kth_paths(my_node_name, dst, 2, paths)

    def _select_best_paths_ksp2(
        self,
        my_node_name: str,
        prefix: IpPrefix,
        best: BestRouteSelectionResult,
        entries: PrefixEntries,
        is_bgp: bool,
        ftype: PrefixForwardingType,
        area_link_states: AreaLinkStates,
    ) -> Optional[RibUnicastEntry]:
        """2-shortest edge-disjoint ECMP over SR-MPLS tunnels.
        reference: Decision.cpp:908 selectBestPathsKsp2."""
        if ftype != PrefixForwardingType.SR_MPLS:
            return None

        next_hops: Set[NextHop] = set()
        paths: List[Tuple[str, list]] = []  # (area, path)

        for area, ls in sorted(area_link_states.items()):
            for node, best_area in sorted(best.all_node_areas):
                if node == my_node_name and best_area == area:
                    continue
                for path in ls.get_kth_paths(my_node_name, node, 1):
                    paths.append((area, path))

            first_count = len(paths)
            # one advertiser in one area: its second paths were solved
            # with its first paths' links removed, so none contains one
            anycast = (
                len(best.all_node_areas) > 1 or len(area_link_states) > 1
            )
            for node, best_area in sorted(best.all_node_areas):
                if area != best_area:
                    continue
                for sec_path in ls.get_kth_paths(my_node_name, node, 2):
                    # avoid double-spray: drop second paths that contain a
                    # first path (anycast in meshes)
                    if anycast and any(
                        LinkState.path_a_in_path_b(paths[i][1], sec_path)
                        for i in range(first_count)
                    ):
                        continue
                    paths.append((area, sec_path))

        if not paths:
            return None

        for path_area, path in paths:
            ls = area_link_states[path_area]
            adj_dbs = ls.get_adjacency_databases()
            cost = 0
            labels: List[int] = []
            next_node = my_node_name
            valid = True
            for link in path:
                hop_metric, next_node = link.metric_and_other(next_node)
                cost += hop_metric
                db = adj_dbs.get(next_node)
                if db is None:
                    valid = False
                    break
                labels.append(db.node_label)
            if not valid:
                continue
            # stack order: bottom-of-stack first => reverse the hop
            # order, then drop the first hop's own label (PHP)
            del labels[0]
            labels.reverse()
            dst_entry = entries.get((next_node, path_area))
            if dst_entry is not None and dst_entry.prepend_label is not None:
                labels.insert(0, dst_entry.prepend_label)

            mpls_action = None
            if labels:
                mpls_action = MplsAction(
                    action=MplsActionCode.PUSH, push_labels=tuple(labels)
                )
            first_link = path[0]
            next_hops.add(
                make_next_hop(
                    first_link.nh_v4_from(my_node_name)
                    if prefix.is_v4
                    else first_link.nh_v6_from(my_node_name),
                    first_link.iface_from(my_node_name),
                    cost,
                    mpls_action,
                    first_link.area,
                    first_link.other_node(my_node_name),
                )
            )

        return self._add_best_paths(
            my_node_name, prefix, best, entries, is_bgp, next_hops
        )

    # -- shared route assembly -------------------------------------------

    def _add_best_paths(
        self,
        my_node_name: str,
        prefix: IpPrefix,
        best: BestRouteSelectionResult,
        entries: PrefixEntries,
        is_bgp: bool,
        next_hops: Set[NextHop],
    ) -> Optional[RibUnicastEntry]:
        """reference: Decision.cpp:1033 addBestPaths."""
        min_next_hop = self._get_min_next_hop_threshold(best, entries)
        if min_next_hop is not None and min_next_hop > len(next_hops):
            return None

        if best.has_node(my_node_name):
            prepend_label = None
            for node_area, entry in entries.items():
                if node_area[0] == my_node_name and entry.prepend_label is not None:
                    prepend_label = entry.prepend_label
                    break
            assert prepend_label is not None, "self route without prepend label"
            static_nhs = self.static_mpls_routes.get(prepend_label)
            if static_nhs:
                for nh in static_nhs:
                    next_hops.add(make_next_hop(nh.address, None, 0, None))

        best_entry = entries[best.best_node_area]
        return RibUnicastEntry(
            prefix=prefix,
            nexthops=next_hops,
            best_prefix_entry=best_entry,
            best_area=best.best_node_area[1],
            do_not_install=is_bgp and self.bgp_dry_run,
        )

    # -- next-hop math ----------------------------------------------------

    def _get_min_cost_nodes(
        self, view: SpfView, dst_node_areas: Set[NodeAndArea]
    ) -> Tuple[Metric, Set[str]]:
        """reference: Decision.cpp:1099 getMinCostNodes."""
        shortest: Optional[Metric] = None
        min_cost_nodes: Set[str] = set()
        for dst_node, _ in dst_node_areas:
            metric = view.metric_to(dst_node)
            if metric is None:
                continue
            if shortest is None or shortest >= metric:
                if shortest is None or shortest > metric:
                    shortest = metric
                    min_cost_nodes.clear()
                min_cost_nodes.add(dst_node)
        return (shortest if shortest is not None else -1, min_cost_nodes)

    def _get_next_hops_with_metric(
        self,
        my_node_name: str,
        dst_node_areas: Set[NodeAndArea],
        per_destination: bool,
        area_link_states: AreaLinkStates,
    ) -> Tuple[Metric, Dict[Tuple[str, str], Metric]]:
        """Map (first-hop node, dst) -> remaining distance from that first
        hop to the destination. reference: Decision.cpp:1124."""
        next_hop_nodes: Dict[Tuple[str, str], Metric] = {}
        shortest: Optional[Metric] = None

        for area, ls in sorted(area_link_states.items()):
            view = self._view(area, ls, my_node_name)
            area_min, min_cost_nodes = self._get_min_cost_nodes(
                view, dst_node_areas
            )
            if not min_cost_nodes:
                continue
            if shortest is not None and shortest < area_min:
                continue
            if shortest is None or shortest > area_min:
                shortest = area_min
                next_hop_nodes.clear()

            for dst_node in min_cost_nodes:
                dst_ref = dst_node if per_destination else ""
                for nh in view.next_hops_toward(dst_node):
                    next_hop_nodes[(nh, dst_ref)] = shortest - view.metric_to(nh)

            if self.compute_lfa_paths:
                # RFC 5286 loop-free alternates
                for link in ls.ordered_links_from_node(my_node_name):
                    if not link.is_up():
                        continue
                    neighbor = link.other_node(my_node_name)
                    neighbor_to_here = view.metric_between(
                        neighbor, my_node_name
                    )
                    if neighbor_to_here is None:
                        continue
                    for dst_node, dst_area in dst_node_areas:
                        if area != dst_area:
                            continue
                        dist_from_neighbor = view.metric_between(
                            neighbor, dst_node
                        )
                        if dist_from_neighbor is None:
                            continue
                        if dist_from_neighbor < shortest + neighbor_to_here:
                            key = (
                                neighbor,
                                dst_node if per_destination else "",
                            )
                            prev = next_hop_nodes.get(key)
                            if prev is None or prev > dist_from_neighbor:
                                next_hop_nodes[key] = dist_from_neighbor

        return (shortest if shortest is not None else -1, next_hop_nodes)

    def _get_next_hops(
        self,
        my_node_name: str,
        dst_node_areas: Set[NodeAndArea],
        is_v4: bool,
        per_destination: bool,
        min_metric: Metric,
        next_hop_nodes: Dict[Tuple[str, str], Metric],
        swap_label: Optional[int],
        area_link_states: AreaLinkStates,
        entries: PrefixEntries,
    ) -> Set[NextHop]:
        """Materialize per-link next-hops from the first-hop node map.
        reference: Decision.cpp:1211 getNextHopsThrift."""
        assert next_hop_nodes
        next_hops: Set[NextHop] = set()
        for area, ls in sorted(area_link_states.items()):
            for link in ls.ordered_links_from_node(my_node_name):
                dst_iter = (
                    sorted(dst_node_areas) if per_destination else [("", "")]
                )
                for dst_node, dst_area in dst_iter:
                    if dst_area and dst_area != area:
                        continue
                    neighbor = link.other_node(my_node_name)
                    remaining = next_hop_nodes.get((neighbor, dst_node))
                    if remaining is None or not link.is_up():
                        continue
                    # don't reach dst via another destination node
                    if (
                        dst_node
                        and (neighbor, area) in dst_node_areas
                        and neighbor != dst_node
                    ):
                        continue
                    dist_over_link = link.metric_from(my_node_name) + remaining
                    # without LFA only shortest-path links qualify
                    if not self.compute_lfa_paths and dist_over_link != min_metric:
                        continue

                    mpls_action = None
                    if swap_label is not None:
                        nh_is_dst = (neighbor, area) in dst_node_areas
                        mpls_action = (
                            MplsAction(action=MplsActionCode.PHP)
                            if nh_is_dst
                            else MplsAction(
                                action=MplsActionCode.SWAP,
                                swap_label=swap_label,
                            )
                        )
                    if dst_node:
                        push_labels: List[int] = []
                        dst_entry = entries.get((dst_node, area))
                        if dst_entry is not None and dst_entry.prepend_label is not None:
                            push_labels.append(dst_entry.prepend_label)
                            if not is_mpls_label_valid(push_labels[-1]):
                                continue
                        if dst_node != neighbor:
                            db = ls.get_adjacency_databases().get(dst_node)
                            if db is None:
                                continue
                            push_labels.append(db.node_label)
                            if not is_mpls_label_valid(push_labels[-1]):
                                continue
                        if push_labels:
                            mpls_action = MplsAction(
                                action=MplsActionCode.PUSH,
                                push_labels=tuple(push_labels),
                            )

                    next_hops.add(
                        make_next_hop(
                            link.nh_v4_from(my_node_name)
                            if is_v4
                            else link.nh_v6_from(my_node_name),
                            link.iface_from(my_node_name),
                            dist_over_link,
                            mpls_action,
                            link.area,
                            link.other_node(my_node_name),
                        )
                    )
        return next_hops
