"""LinkState -> device-array snapshot compiler, with incremental patching.

The TPU compute path never walks the host object graph. Each topology
version of a ``LinkState`` is *compiled* into dense arrays:

- node-name interning: sorted names -> dense ids (stable for a given node
  set, so unchanged topologies reuse the resident snapshot)
- ``metric[N, N]`` int32 directed min-metric matrix (INF where no up link;
  min over parallel links per direction)
- ``overloaded[N]`` node transit-exclusion mask
- per-source-node directed-link metadata for next-hop materialization

This replaces the reference's per-(source, useLinkMetric) SPF memo cache
(reference: openr/decision/LinkState.cpp:794-803): the memo key is
``LinkState.topology_version`` and the cached artifact is the HBM-resident
metric matrix, against which any batch of sources is solved.

Incremental path: LinkState journals the affected nodes of every topology
change. When the node set is unchanged, a new snapshot is produced by
*patching* only the affected rows — and the device copy is updated with a
row-scatter instead of re-uploading the whole matrix, so the steady-state
churn cost is O(changed rows), not O(N^2). The hop-count matrix is derived
from the metric matrix on device.

Padding: N is padded to the next multiple of 128 (TPU lane width) so
recompilation only happens when the node count crosses a bucket boundary.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from openr_tpu.graph.linkstate import Link, LinkState

# Distance/metric infinity sentinel. Chosen so that INF + INF still fits
# in int32 (no wraparound in the relaxation adds): 2**30 - 1, and
# 2*(2**30 - 1) == 2**31 - 2 < 2**31 - 1.
INF = np.int32((1 << 30) - 1)

_PAD = 128
# row-patch bucket sizes (jit specializes per bucket; ids are padded by
# repeating the first row, which is an idempotent scatter)
_PATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)


def _padded(n: int) -> int:
    return max(_PAD, ((n + _PAD - 1) // _PAD) * _PAD)


def pad_patch_rows(rows: np.ndarray) -> Optional[np.ndarray]:
    """Pad changed-row ids up to the shared bucket sizes (jit programs
    specialize per bucket; padding repeats the first row, an idempotent
    scatter). Returns None when the change exceeds the largest bucket —
    callers should fall back to a full matrix upload instead of compiling
    ever-larger scatter programs."""
    if len(rows) > _PATCH_BUCKETS[-1]:
        return None
    bucket = next(b for b in _PATCH_BUCKETS if b >= max(1, len(rows)))
    ids = np.full(bucket, rows[0] if len(rows) else 0, dtype=np.int32)
    ids[: len(rows)] = rows
    return ids


@dataclass
class DirectedLink:
    """Host-side metadata for one direction of one up link."""

    link: Link
    src: str
    dst: str
    src_id: int
    dst_id: int
    metric: int


class _DeviceArrays:
    """Resident device arrays for one snapshot. Unpacks like the
    (metric, hop, overloaded) tuple it replaced, but the hop matrix is
    derived on first access instead of eagerly per patch."""

    __slots__ = ("metric", "overloaded", "_hop")

    def __init__(self, metric, overloaded):
        self.metric = metric
        self.overloaded = overloaded
        self._hop = None

    @property
    def hop(self):
        if self._hop is None:
            self._hop = _derive_hop(self.metric)
        return self._hop

    def __iter__(self):
        return iter((self.metric, self.hop, self.overloaded))


@dataclass
class GraphSnapshot:
    area: str
    version: int
    node_names: List[str]  # index == dense node id
    node_index: Dict[str, int]
    n: int  # real node count
    n_pad: int  # padded node count (matrix dimension)
    metric: np.ndarray  # [n_pad, n_pad] int32, INF where no edge
    overloaded: np.ndarray  # [n_pad] bool
    # per node id: directed links leaving that node
    links_from: List[List[DirectedLink]]
    _hop: Optional[np.ndarray] = None
    _dev: Optional[tuple] = None
    _parent: Optional["GraphSnapshot"] = None
    _changed_rows: Optional[np.ndarray] = None

    def id_of(self, node: str) -> Optional[int]:
        return self.node_index.get(node)

    @property
    def hop(self) -> np.ndarray:
        """Hop-count (unweighted) matrix, derived lazily."""
        if self._hop is None:
            self._hop = np.where(
                self.metric < INF, np.int32(1), INF
            ).astype(np.int32)
        return self._hop

    def patch_plan(self) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """(changed_row_ids, changed_row_values) when this snapshot is an
        unrealized patch of a parent whose device copy the caller owns.
        Callers driving their own resident device matrix (the fused
        ``ops.spf.reconverge_step``) apply this instead of re-uploading;
        returns None for a full compile. Detaches the parent chain.

        Covers METRIC rows only: the caller must refresh its overloaded
        mask from ``self.overloaded`` on every step (an O(N) upload) —
        overload flips arrive through the same patch journal but are not
        part of the row scatter."""
        if self._parent is None or self._changed_rows is None:
            return None
        rows = self._changed_rows
        self._parent = None
        return rows, self.metric[rows, :]

    def rows_to_upload(self) -> int:
        """Metric rows the next ``device_arrays()`` sends to the device:
        none once resident, the changed rows of an unrealized patch,
        else the whole matrix."""
        if self._dev is not None:
            return 0
        parent = self._parent
        if (
            parent is not None
            and parent._dev is not None
            and self._changed_rows is not None
        ):
            return len(self._changed_rows)
        return self.n_pad

    def device_arrays(self):
        """(metric, hop, overloaded) as device arrays. Patched snapshots
        update their parent's resident arrays with a row scatter. The hop
        (unweighted) matrix is derived lazily on first access — most
        consumers (route rebuilds) never touch it."""
        if self._dev is not None:
            return self._dev
        import jax.numpy as jnp

        parent = self._parent
        rows = self._changed_rows
        padded_rows = pad_patch_rows(rows) if rows is not None else None
        if (
            parent is not None
            and parent._dev is not None
            and padded_rows is not None
        ):
            p_metric = parent._dev.metric
            metric_dev = _patch_rows(
                p_metric,
                jnp.asarray(padded_rows),
                jnp.asarray(self.metric[padded_rows, :]),
            )
            overloaded_dev = jnp.asarray(self.overloaded)
        else:
            metric_dev = jnp.asarray(self.metric)
            overloaded_dev = jnp.asarray(self.overloaded)
        self._dev = _DeviceArrays(metric_dev, overloaded_dev)
        # release the parent chain: resident arrays now belong to us
        self._parent = None
        return self._dev


@functools.lru_cache(maxsize=1)
def _patch_fn():
    import jax

    @jax.jit
    def patch(m, ids, vals):
        return m.at[ids, :].set(vals)

    return patch


def _patch_rows(metric_dev, row_ids, row_vals):
    # the jitted scatter must be a process-wide singleton: a fresh jit
    # closure per call would recompile on every churn step, which is
    # catastrophic when compilation is remote
    return _patch_fn()(metric_dev, row_ids, row_vals)


@functools.lru_cache(maxsize=1)
def _hop_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def derive(m):
        return jnp.where(m < INF, jnp.int32(1), INF)

    return derive


def _derive_hop(metric_dev):
    return _hop_fn()(metric_dev)


def _build_node_row(
    ls: LinkState,
    name: str,
    index: Dict[str, int],
    metric: np.ndarray,
) -> List[DirectedLink]:
    """Fill row index[name] of the metric matrix and return the node's
    directed-link metadata."""
    i = index[name]
    metric[i, :] = INF
    out: List[DirectedLink] = []
    for link in ls.ordered_links_from_node(name):
        if not link.is_up():
            continue
        dst = link.other_node(name)
        j = index.get(dst)
        if j is None:
            continue
        m = min(int(link.metric_from(name)), int(INF) - 1)
        out.append(
            DirectedLink(
                link=link, src=name, dst=dst, src_id=i, dst_id=j, metric=m
            )
        )
        if m < metric[i, j]:
            metric[i, j] = m
    return out


def compile_snapshot(ls: LinkState) -> GraphSnapshot:
    """Full compile of the current LinkState topology."""
    names = sorted(ls.get_adjacency_databases().keys())
    index = {name: i for i, name in enumerate(names)}
    n = len(names)
    n_pad = _padded(n)

    metric = np.full((n_pad, n_pad), INF, dtype=np.int32)
    overloaded = np.zeros((n_pad,), dtype=bool)
    links_from: List[List[DirectedLink]] = [[] for _ in range(n)]

    for name in names:
        i = index[name]
        overloaded[i] = ls.is_node_overloaded(name)
        links_from[i] = _build_node_row(ls, name, index, metric)

    return GraphSnapshot(
        area=ls.area,
        version=ls.topology_version,
        node_names=names,
        node_index=index,
        n=n,
        n_pad=n_pad,
        metric=metric,
        overloaded=overloaded,
        links_from=links_from,
    )


def patch_snapshot(
    prev: GraphSnapshot, ls: LinkState, affected: List[str]
) -> GraphSnapshot:
    """Produce a new snapshot by re-deriving only the affected rows.
    Caller guarantees the node set is unchanged."""
    metric = prev.metric.copy()
    overloaded = prev.overloaded.copy()
    links_from = list(prev.links_from)
    rows = []
    for name in affected:
        i = prev.node_index.get(name)
        if i is None:
            continue
        rows.append(i)
        overloaded[i] = ls.is_node_overloaded(name)
        links_from[i] = _build_node_row(ls, name, prev.node_index, metric)
    return GraphSnapshot(
        area=ls.area,
        version=ls.topology_version,
        node_names=prev.node_names,
        node_index=prev.node_index,
        n=prev.n,
        n_pad=prev.n_pad,
        metric=metric,
        overloaded=overloaded,
        links_from=links_from,
        _parent=prev,
        _changed_rows=np.asarray(sorted(rows), dtype=np.int32),
    )


class SnapshotCache:
    """Versioned snapshot cache keyed by LinkState *identity* (weakly
    held); patches incrementally when the change journal covers the gap
    and the node set is unchanged."""

    def __init__(self) -> None:
        import weakref

        self._cache: "weakref.WeakKeyDictionary[LinkState, GraphSnapshot]" = (
            weakref.WeakKeyDictionary()
        )

    def get(self, ls: LinkState) -> GraphSnapshot:
        snap = self._cache.get(ls)
        if snap is not None and snap.version == ls.topology_version:
            return snap
        snap = self._compile_or_patch(ls, snap)
        self._cache[ls] = snap
        return snap

    def _compile_or_patch(
        self, ls: LinkState, prev: Optional[GraphSnapshot]
    ) -> GraphSnapshot:
        if prev is not None:
            affected = ls.affected_since(prev.version)
            if (
                affected is not None
                and len(affected) <= max(8, prev.n // 4)
                and len(ls.get_adjacency_databases()) == prev.n
                and all(name in prev.node_index for name in affected)
            ):
                # same node set guaranteed: count matches and every
                # touched node is known
                return patch_snapshot(prev, ls, sorted(affected))
        return compile_snapshot(ls)

    def invalidate(self) -> None:
        self._cache.clear()
