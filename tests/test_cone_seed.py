"""The seed of the warm sliced-ELL solve, held to its own contract.

``_ell_reconverge`` seeds its relax loop from the previous distances
and, in a batch row that a tight increased edge flags, restarts only the
columns no in-edge supports any more (``spf_sparse._cone_seed``). End
equality with the cold program — the bar ``test_incremental_parity.py``
sets — cannot tell a sound seed from a lucky one: a seed below the new
fixed point in a column nobody reads would pass it. So this file holds
BOTH, after every one of a few hundred mixed events on three shapes:

(a) the packed view equals the cold program's (``_ell_view_batch`` over
    the same patched bands), byte for byte;
(b) the seed itself, recomputed from the very arrays the dispatch was
    given, satisfies ``d* <= seed <= d0`` in every column, ``d*`` from a
    host Dijkstra that shares no code with the solve.

And the precondition the induction rests on, weights >= 1: where the
program can see a zero it must take the whole-row restart (no support
pass, the eccentricity in relax passes), and the cone rule applied there
anyway IS unsound, which the last test shows on the smallest case.
Counts, never times: this is the CPU.
"""

from __future__ import annotations

import functools
import heapq
import random
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from openr_tpu.models import topologies
from openr_tpu.ops import spf_sparse
from openr_tpu.ops.spf import INF
from tests.test_incremental_parity import load
from tests.test_sp_route_reuse import _drop_adj, _mutate_metric, _restore_adj

EVENTS = 300


# -- shapes -------------------------------------------------------------------


def _metric_grid(side: int, rng: random.Random):
    def node(r, c):
        return f"node-{r * side + c:03d}"

    edges = []
    for r in range(side):
        for c in range(side):
            if c + 1 < side:
                edges.append((node(r, c), node(r, c + 1), rng.randint(1, 10)))
            if r + 1 < side:
                edges.append((node(r, c), node(r + 1, c), rng.randint(1, 10)))
    return topologies.build_topology(f"grid-{side}", edges), node(0, 0)


def _fat_tree(rng: random.Random):
    topo = topologies.fat_tree(
        3, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=3)
    return topo, "rsw-0-0"


def _path(n: int, metrics):
    edges = [
        (f"n{i:02d}", f"n{i + 1:02d}", metrics[i]) for i in range(n - 1)
    ]
    return topologies.build_topology(f"path-{n}", edges), "n00"


def _metric_path(rng: random.Random):
    return _path(24, [rng.randint(1, 10) for _ in range(23)])


SHAPES = {
    "grid": lambda rng: _metric_grid(7, rng),
    "fat_tree": _fat_tree,
    "path": _metric_path,
}


# -- the references -----------------------------------------------------------


def _dijkstra_rows(graph, srcs) -> np.ndarray:
    """[B, n_pad] distances over the host bands of ``graph``: plain
    heap Dijkstra per source. A node extends paths if it is the source
    (an overloaded source still originates) or is not overloaded."""
    out_edges = [[] for _ in range(graph.n_pad)]
    for band, src_b, w_b in zip(graph.bands, graph.src, graph.w):
        for r in range(band.rows):
            head = band.start + r
            for tail, w in zip(src_b[r], w_b[r]):
                if int(w) < INF and int(tail) != head:
                    out_edges[int(tail)].append((head, int(w)))
    rows = np.full((len(srcs), graph.n_pad), INF, dtype=np.int64)
    for x, s in enumerate(srcs):
        dist = rows[x]
        dist[s] = 0
        heap = [(0, int(s))]
        while heap:
            du, u = heapq.heappop(heap)
            if du > dist[u]:
                continue
            if u != s and graph.overloaded[u]:
                continue
            for v, w in out_edges[u]:
                if du + w < dist[v]:
                    dist[v] = du + w
                    heapq.heappush(heap, (du + w, v))
    return np.minimum(rows, INF).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("bands", "n"))
def _seed_and_d0(srcs_t, ws_t, inc_t, inc_h, inc_w, overloaded, d_prev, srcs,
                 bands, n):
    seed, d0, support, reset = spf_sparse._reconverge_seed(
        srcs_t, ws_t, inc_t, inc_h, inc_w, overloaded, d_prev, srcs, bands, n)
    whole_row, _ = spf_sparse._warm_seed(d_prev, inc_t, inc_h, inc_w, d0)
    return seed, d0, whole_row, support, reset


class _World:
    """``EllState`` under churn, with every dispatch's inputs kept so
    the seed can be rebuilt from them afterwards."""

    def __init__(self, topo, root, monkeypatch):
        self.ls = load(topo)
        self.root = root
        self.state = spf_sparse.EllState(spf_sparse.compile_ell(self.ls))
        self.dispatched = None
        real = spf_sparse._ell_reconverge

        def keeping(*args, **kwargs):
            # copies: d_prev (argument 9) is donated to the dispatch
            inc_t, inc_h, inc_w, ov, d_prev, srcs = (
                np.array(a) for a in args[5:11])
            self.dispatched = (inc_t, inc_h, inc_w, ov, d_prev, srcs)
            return real(*args, **kwargs)

        monkeypatch.setattr(spf_sparse, "_ell_reconverge", keeping)
        self.kept = 0       # columns of flagged rows the seed kept
        self.support = 0    # support passes over all solves
        self.flagged = 0    # solves that flagged a row

    def patch(self, affected):
        patched = spf_sparse.ell_patch(
            self.state.graph, self.ls, sorted(affected), widen=True)
        assert patched is not None
        return patched

    def solve(self, affected):
        graph = self.patch(affected) if affected else self.state.graph
        srcs = spf_sparse.ell_source_batch(graph, self.ls, self.root)
        packed, passes, reset_rows = self.state.fetch_view(
            self.state.reconverge(graph, srcs))
        # (a) the cold program over the same bands
        cold, _ = spf_sparse._ell_view_batch(
            tuple(graph.src), tuple(graph.w), graph.overloaded,
            *spf_sparse._batch_args(graph, srcs), graph.bands, graph.n_pad)
        np.testing.assert_array_equal(packed, np.asarray(cold))
        # (b) the seed of that dispatch, from the arrays it was given
        # and the bands it left resident
        d_star = _dijkstra_rows(graph, srcs)
        np.testing.assert_array_equal(packed[: len(srcs)], d_star)
        seed, d0, whole_row, support, reset = (
            np.asarray(x) for x in _seed_and_d0(
                self.state.src, self.state.w, *self.dispatched,
                bands=graph.bands, n=graph.n_pad))
        assert (d_star <= seed).all(), np.argwhere(d_star > seed)[:5]
        assert (seed <= d0).all(), np.argwhere(seed > d0)[:5]
        # never looser than the whole-row seed it narrows
        assert (seed <= whole_row).all()
        assert int(reset.sum()) == reset_rows
        self.kept += int((seed < whole_row).sum())
        self.support += int(support)
        self.flagged += bool(reset_rows)
        return dict(passes=passes, reset_rows=reset_rows,
                    support=int(support), seed=seed, d_star=d_star, d0=d0,
                    graph=graph, srcs=srcs)


def _adj(ls, node):
    return ls.get_adjacency_databases()[node].adjacencies


def _set_metric(ls, node, i, metric):
    """Returns the nodes whose band rows the change touches."""
    _mutate_metric(ls, node, i, metric)
    return {node, _adj(ls, node)[i].other_node_name}


class _Churn:
    """Seeded mutations of a LinkState; each returns the nodes whose
    band rows it touched."""

    def __init__(self, world: _World, rng: random.Random):
        self.ls, self.rng = world.ls, rng
        self.names = sorted(world.ls.get_adjacency_databases())
        self.dropped = []   # (node, adjacency) awaiting a restore

    def _link(self):
        while True:
            node = self.rng.choice(self.names)
            if _adj(self.ls, node):
                return node, self.rng.randrange(len(_adj(self.ls, node)))

    def raise_(self):
        node, i = self._link()
        m = _adj(self.ls, node)[i].metric
        return _set_metric(self.ls, node, i, m + self.rng.randint(1, 9))

    def lower(self):
        node, i = self._link()
        m = _adj(self.ls, node)[i].metric
        return _set_metric(self.ls, node, i, max(1, m - self.rng.randint(1, 9)))

    def both(self):
        return self.raise_() | self.lower() | self.raise_()

    def recost(self):
        """Every link of one node, as ``node-metric`` does."""
        node, _ = self._link()
        step = self.rng.randint(1, 4)
        touched = set()
        for i, a in enumerate(_adj(self.ls, node)):
            touched |= _set_metric(self.ls, node, i, a.metric + step)
        return touched

    def withdraw(self):
        node, i = self._link()
        gone = _drop_adj(self.ls, node, i)
        self.dropped.append((node, gone))
        return {node, gone.other_node_name}

    def restore(self):
        if not self.dropped:
            return self.lower()
        node, adj = self.dropped.pop(self.rng.randrange(len(self.dropped)))
        _restore_adj(self.ls, node, adj)
        return {node, adj.other_node_name}

    def overload(self):
        node = self.rng.choice(self.names)
        db = self.ls.get_adjacency_databases()[node]
        self.ls.update_adjacency_database(
            replace(db, is_overloaded=not db.is_overloaded))
        return {node} | {a.other_node_name for a in db.adjacencies}

    KINDS = ("raise_", "lower", "both", "recost", "withdraw", "restore",
             "overload", "stacked")


@pytest.mark.parametrize("seed", [11, 4294967311])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_seed_lies_between_the_new_fixed_point_and_the_cold_init(
        shape, seed, monkeypatch):
    rng = random.Random(seed)
    topo, root = SHAPES[shape](rng)
    world = _World(topo, root, monkeypatch)
    churn = _Churn(world, rng)
    world.solve([])     # cold: the forced reset, no support pass
    assert world.support == 0
    counters0 = dict(spf_sparse.ELL_COUNTERS)
    seen = set()
    for _ in range(EVENTS):
        kind = rng.choice(_Churn.KINDS)
        seen.add(kind)
        if kind == "stacked":
            # a patch that lands with no solve (the prewarm flow), then
            # another on top: one warm solve sees both through the
            # journal's snapshots
            world.state.apply_patch(world.patch(churn.raise_()))
            world.solve(churn.both())
        else:
            world.solve(getattr(churn, kind)())
    assert seen == set(_Churn.KINDS)
    counters = spf_sparse.ELL_COUNTERS
    # the mechanism ran: warm solves, rows flagged, support passes, and
    # seeds that kept what a whole-row restart would have thrown away
    assert counters["ell_warm_solves"] - counters0["ell_warm_solves"] \
        >= EVENTS // 2
    assert world.flagged >= EVENTS // 10
    assert world.support >= world.flagged // 2
    assert world.kept > 0


# -- the precondition: weights >= 1 -------------------------------------------


def _path_world(metrics, monkeypatch):
    topo, root = _path(len(metrics) + 1, metrics)
    world = _World(topo, root, monkeypatch)
    cold = world.solve([])
    assert cold["passes"] == len(metrics)     # the path's eccentricity
    return world


def test_one_zero_metric_link_anywhere_takes_the_whole_row(monkeypatch):
    """A real slot of the patched bands carries 0: no row may take the
    cone, wherever the raised edge is."""
    metrics = [3, 1, 4, 1, 5, 9, 2, 6, 0, 3, 5, 8]
    world = _path_world(metrics, monkeypatch)
    ecc = len(metrics)
    got = world.solve(_set_metric(world.ls, "n02", 1, 7))   # n02 -> n03
    assert got["reset_rows"] >= 1
    assert got["support"] == 0
    assert got["passes"] >= ecc - 1
    # and with the zero gone the same raise takes the cone: the heads
    # behind n03 have no second parent on a path, so the cone is the
    # rest of the path, walked by both loops
    world.solve(_set_metric(world.ls, "n08", 1, 1)
                | _set_metric(world.ls, "n09", 0, 1))
    got = world.solve(_set_metric(world.ls, "n02", 1, 9))
    assert got["reset_rows"] >= 1 and got["support"] >= 1


def test_a_zero_metric_pair_that_loses_its_parent_is_not_left_holding_itself(
        monkeypatch):
    """n03 =0= n04, fed by n02 -> n03. Raise n02 -> n03: under the old
    distances n03 and n04 each offer the other its old value across
    the zero link, so the support rule alone would call both supported.
    The program sees the zero and restarts the row whole; the rule
    applied anyway gives a seed BELOW the new fixed point."""
    metrics = [2, 2, 2, 0, 2, 2]
    world = _path_world(metrics, monkeypatch)
    ecc = len(metrics)
    got = world.solve(_set_metric(world.ls, "n02", 1, 6))   # n02 -> n03
    assert got["reset_rows"] >= 1
    assert got["support"] == 0
    assert got["passes"] >= ecc - 1
    # the same inputs through _cone_seed with nothing marked ``whole``
    graph, srcs = got["graph"], got["srcs"]
    inc_t, inc_h, inc_w, ov, d_prev, _ = world.dispatched
    tight = spf_sparse._tight_increases(
        jnp.asarray(d_prev), inc_t, inc_h, jnp.asarray(inc_w))
    reset = jnp.any(tight, axis=1)
    unsound, _ = spf_sparse._cone_seed(
        reset, jnp.zeros_like(reset), jnp.asarray(got["d0"]),
        jnp.asarray(d_prev),
        lambda x: spf_sparse._ell_relax_raw(
            x, graph.bands, world.state.src, world.state.w, jnp.asarray(ov)),
    )
    assert (np.asarray(unsound) < got["d_star"]).any()
    assert (got["seed"] >= got["d_star"]).all()
