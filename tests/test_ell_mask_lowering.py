"""The warm ELL solve, compiled for a TPU v5e that is described and not
attached, looks no per-node boolean up per edge (PR 29).

``_ell_reconverge`` at ``fabric-5000``'s shapes used to hold four
gathers whose result was a ``pred`` of a band's [rows, k] shape, two of
them inside the while loop: the overload mask read at every edge's
tail, 81% of the device's time on the chip. The mask now sits on the
distance columns, so the compiled program has none. This is a count
from a compile, not a time; it is what stops the gather coming back
through a refactor.

Since PR 31 the same compile also holds the shape of the cone seed: two
``while`` loops under their own name scopes (``ell.cone_seed`` before
``ell.relax``), and, run on the CPU at a small size, the counts that say
when the first one runs at all.

The helpers are ``tests/chipbench/test_tpu_lowering.py``'s; its fixture
describes the topology only once a test of this file has started, and
skips where it cannot be described.
"""

from __future__ import annotations

import re

import numpy as np
import pytest

from tests.chipbench.test_tpu_lowering import (  # noqa: F401 - fixture
    _config,
    _link_state,
    _shape,
    one_chip,
)


def _edge_shaped_pred_gathers(text: str, bands) -> list:
    shapes = set()
    for band in bands:
        shapes.add(f"pred[{band.rows},{band.k}]")
        shapes.add(f"pred[{band.rows * band.k}]")
    found = []
    for line in text.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?%\S+ = (pred\[[0-9,]*\])\S* gather\(", line
        )
        if m and m.group(1) in shapes:
            found.append(line.strip()[:160])
    return found


@pytest.fixture(scope="module")
def fabric_5000_text(one_chip):
    """``_ell_reconverge`` compiled for the described chip at
    ``fabric-5000``'s shapes: (bands, optimised HLO text, output
    shapes)."""
    import jax
    import jax.numpy as jnp

    from openr_tpu.graph import snapshot
    from openr_tpu.ops import spf_sparse

    config = _config("fabric-5000")
    ls = _link_state(config)
    graph = spf_sparse.compile_ell(ls)
    assert [(b.rows, b.k) for b in graph.bands] == [(4032, 8), (960, 128)]
    batch = len(spf_sparse.ell_source_batch(graph, ls, config["vantage"]))
    rows = snapshot.pad_patch_rows(np.array([0], dtype=np.int32)).shape[0]
    inc = spf_sparse.pad_increase_edges([(0, 1, 1)])[0].shape[0]
    i32 = jnp.int32

    def per_band(shape_of):
        return tuple(_shape(one_chip, shape_of(b), i32) for b in graph.bands)

    compiled = spf_sparse._ell_reconverge.lower(
        per_band(lambda b: (b.rows, b.k)),
        per_band(lambda b: (b.rows, b.k)),
        per_band(lambda b: (rows,)),
        per_band(lambda b: (rows, b.k)),
        per_band(lambda b: (rows, b.k)),
        _shape(one_chip, (inc,), i32),
        _shape(one_chip, (inc,), i32),
        _shape(one_chip, (inc,), i32),
        _shape(one_chip, (graph.n_pad,), jnp.bool_),
        _shape(one_chip, (batch, graph.n_pad), i32),
        _shape(one_chip, (batch,), i32),
        bands=graph.bands, n=graph.n_pad,
    ).compile()
    out = [
        tuple(o.shape) for o in jax.tree_util.tree_leaves(compiled.out_info)
    ]
    return graph.bands, compiled.as_text(), out, (batch, graph.n_pad)


def test_ell_reconverge_gathers_no_mask_per_edge(fabric_5000_text):
    bands, text, _, _ = fabric_5000_text
    # the reading is of this text: the distance gathers must be in it
    assert re.search(r"= s32\[\d+,16\]\S* fusion\(.*while/body/gather", text)
    assert _edge_shaped_pred_gathers(text, bands) == []


def test_ell_reconverge_is_one_program_with_two_named_loops(fabric_5000_text):
    """Warm, cold and zero-metric inputs share this one executable: the
    support loop and the relax loop are both in it, each under its own
    name scope, and what comes out is what the benchmark's own lowering
    tests pin: the bands, the packed view, the distance rows and
    ``_solve_stats`` of shape (2,)."""
    bands, text, out, (batch, n_pad) = fabric_5000_text
    assert out == [(b.rows, b.k) for b in bands] * 2 + [
        (2 * batch, n_pad), (batch, n_pad), (2,)]
    # both loops gather distances under their own scope
    assert re.search(r"ell\.cone_seed/while/body/.*gather", text)
    assert re.search(r"ell\.relax/while/body/.*gather", text)
    # and no mask is looked up per edge in either
    assert not re.search(r"pred\[[0-9,]*\]\S* gather\(.*ell\.cone_seed", text)


def test_the_count_sees_what_it_is_for():
    """Lines as the parent's compile printed them (4 such gathers; 2
    shown) against one that must not count."""
    from openr_tpu.ops.spf_sparse import EllBand

    bands = (EllBand(0, 4032, 8), EllBand(4032, 960, 128))
    parent = (
        "  %gather.15 = pred[4032,8]{1,0:T(8,128)(4,1)} gather(%param_0.17, "
        "%transpose.53), offset_dims={}, collapsed_slice_dims={0}\n"
        "  ROOT %gather.28 = pred[960,128]{1,0:T(8,128)(4,1)} gather("
        "%param_0.23, %transpose.65), offset_dims={}\n"
        "  %gather.36 = pred[16]{0:T(512)(128)(4,1)} gather(%param_0.35, "
        "%transpose.89), offset_dims={}\n"
        "  %gather.2 = s32[960,128,16]{2,1,0} gather(%p, %q)\n"
    )
    assert len(_edge_shaped_pred_gathers(parent, bands)) == 2


# -- when the support loop runs, as counts on the CPU -------------------------


def _small_state():
    from openr_tpu.models import topologies
    from openr_tpu.ops import spf_sparse
    from tests.test_incremental_parity import load

    ls = load(topologies.grid(6))
    return ls, spf_sparse.EllState(spf_sparse.compile_ell(ls))


def _relax_loop_count(seed, graph, state):
    """What ``_ell_reconverge``'s relax loop counts from ``seed``: passes
    until one changes nothing, that one included."""
    import jax.numpy as jnp

    from openr_tpu.ops import spf_sparse

    d, passes = jnp.asarray(seed), 0
    while True:
        nxt = spf_sparse._ell_relax(
            d, graph.bands, state.src, state.w, state.overloaded)
        passes += 1
        if not bool((nxt < d).any()):
            return passes
        d = nxt


@pytest.mark.parametrize("case", ["forced-reset", "pure-decrease", "raise"])
def test_support_passes_run_only_where_a_row_takes_the_cone(case, monkeypatch):
    """``stats[0]`` is support passes + relax passes. The forced reset
    (a first solve: ``_FORCE_RESET_EDGE``) restarts every row whole with
    no support pass; a solve that flags no row runs none either; a raise
    that is tight runs at least one. In each case the rest of
    ``stats[0]`` is the relax loop's own count from that seed."""
    import jax
    import jax.numpy as jnp

    from openr_tpu.ops import spf_sparse
    from tests.test_cone_seed import _set_metric

    ls, state = _small_state()
    kept = {}
    real = spf_sparse._ell_reconverge

    def keeping(*args, **kwargs):
        kept["args"] = [np.array(a) for a in args[5:11]]
        out = real(*args, **kwargs)
        kept["leaves"] = jax.tree_util.tree_leaves(out)
        return out

    monkeypatch.setattr(spf_sparse, "_ell_reconverge", keeping)

    def solve(affected):
        graph = state.graph
        if affected:
            graph = spf_sparse.ell_patch(
                graph, ls, sorted(affected), widen=True)
        srcs = spf_sparse.ell_source_batch(graph, ls, "node-0")
        _, passes, reset_rows = state.fetch_view(state.reconverge(graph, srcs))
        seed, _, support, _ = spf_sparse._reconverge_seed(
            state.src, state.w, *(jnp.asarray(a) for a in kept["args"]),
            graph.bands, graph.n_pad)
        return graph, len(srcs), passes, reset_rows, int(support), seed

    def recost(metric):
        # node-14 = (2, 2) of the 6 x 6 grid, an interior node: two of
        # its four links point away from the corner
        touched = set()
        for i in range(4):
            touched |= _set_metric(ls, "node-14", i, metric)
        return solve(touched)

    got = solve([])
    if case != "forced-reset":
        recost(5)
        got = recost(1)
    if case == "raise":
        got = recost(3)
    graph, batch, passes, reset_rows, support, seed = got
    assert [tuple(x.shape) for x in kept["leaves"]] == (
        [(b.rows, b.k) for b in graph.bands] * 2
        + [(2 * batch, graph.n_pad), (batch, graph.n_pad), (2,)])
    if case == "forced-reset":
        assert reset_rows == batch and support == 0
    elif case == "pure-decrease":
        assert reset_rows == 0 and support == 0
    else:
        assert reset_rows >= 1 and support >= 1
    assert passes == support + _relax_loop_count(seed, graph, state)


# -- the KSP2 programs at both KSP2 cells' band shapes ------------------------

# configuration -> the (rows, slots) of the bands compile_ell gives it:
# the fabric's three degree classes; the grid's one (degree 2 to 4)
KSP2_BANDS = {
    "fabric-1000-ksp2": [(624, 8), (288, 16), (104, 128)],
    "grid-1000-ksp2": [(961, 8)],
}


@pytest.fixture(scope="module", params=sorted(KSP2_BANDS))
def ksp2_shapes(request, one_chip):
    """What the KSP2 engine hands its two programs on the 1016-node
    fabric and on the 31 x 31 grid: the bands, and shapes for the
    resident tensors."""
    import jax.numpy as jnp

    from chipbench.served_paths import pipeline_grid  # noqa: F401 - grid
    from openr_tpu.decision import ksp2_engine, spf_solver
    from openr_tpu.ops import spf_sparse

    config = _config(request.param)
    ls = _link_state(config)
    graph = spf_sparse.compile_ell(ls)
    assert [(b.rows, b.k) for b in graph.bands] == KSP2_BANDS[request.param]
    assert graph.n_pad == 1024
    # a cold build solves every destination in one masked batch
    assert spf_solver._ksp2_chunk(graph) == 1024
    i32, n = jnp.int32, graph.n_pad

    def per_band(shape_of, dtype=i32):
        return tuple(
            _shape(one_chip, shape_of(b), dtype) for b in graph.bands)

    view = len(spf_sparse.ell_source_batch(graph, ls, config["vantage"]))
    # the one shape an engine's rows solve and its matrix solve have:
    # both lists padded to the engine's bounds on them
    inc = spf_sparse.pad_increase_edges(
        [(0, 1, 1)], ksp2_engine.ENGINE_MAX_CHANGED_PAIRS)[0].shape[0]
    ep = ksp2_engine._pad_ids([0], ksp2_engine.ENGINE_MAX_ENDPOINTS)
    assert (inc, ep.shape[0]) == (64, 32)
    return {
        "graph": graph,
        "bands": (per_band(lambda b: (b.rows, b.k)),) * 2,
        "masks": lambda rows: per_band(
            lambda b: (rows, b.rows, b.k), jnp.bool_),
        "overloaded": _shape(one_chip, (n,), jnp.bool_),
        "src_id": _shape(one_chip, (), i32),
        # after the bands and the overload mask, _ell_view_ep_rows
        # takes "view" + "matrix" and _ell_all_view_rows "matrix"
        "view": [
            _shape(one_chip, (view,), i32), _shape(one_chip, (view,), i32),
            _shape(one_chip, ep.shape, i32),
        ],
        "matrix": [_shape(one_chip, (n, n), i32)]
        + [_shape(one_chip, (inc,), i32)] * 3,
    }


@pytest.mark.parametrize("rows", [64, 512, 1024])
def test_masked_batch_lowers_and_gathers_no_mask_per_edge(
        ksp2_shapes, rows):
    """``jit__ell_masked_source_batch`` at the three buckets the engine
    compiles on either graph: the two an incremental sync pads its
    destinations to, and the one that holds them all (the cold build's
    batch; on the grid also the refresh of a window of several links:
    1024 rows x 961 x 8 mask bytes). The
    per-destination edge mask is an operand of the relax, selected
    against the weights; what PR 29 took out of ``_ell_relax`` — a
    ``pred`` gathered per edge — is in neither."""
    import jax

    from openr_tpu.ops import spf_sparse

    from openr_tpu.decision import ksp2_engine, spf_solver

    k = ksp2_shapes
    graph = k["graph"]
    assert rows in ksp2_engine._masked_buckets(spf_solver._ksp2_chunk(graph))
    compiled = spf_sparse._ell_masked_source_batch.lower(
        *k["bands"], k["masks"](rows), k["overloaded"], k["src_id"],
        bands=graph.bands, n=graph.n_pad,
    ).compile()
    # the rows, and the loop's own counter carried out beside them
    assert [tuple(o.shape) for o in
            jax.tree_util.tree_leaves(compiled.out_info)] \
        == [(rows, graph.n_pad), ()]
    text = compiled.as_text()
    assert re.search(r"while/body/.*gather", text)
    assert _edge_shaped_pred_gathers(text, graph.bands) == []
    assert not re.search(r"= pred\[[0-9,]*\]\S* gather\(", text)


def test_all_pairs_program_lowers_at_both_ksp2_cells(ksp2_shapes):
    """The matrix solve of a KSP2 sync, at the one shape the engine
    runs it in on either graph: the all-pairs fixed point from all 1024
    rows, relaxing the donated previous matrix in place, and the fixed
    point's pass count as a second output. The view and the endpoint
    rows are the rows solve's now (below)."""
    import jax

    from chipbench import roofline_ksp2
    from openr_tpu.ops import spf_sparse

    k = ksp2_shapes
    graph, n = k["graph"], k["graph"].n_pad
    lowered = spf_sparse._ell_all_view_rows.lower(
        *k["bands"], k["overloaded"], *k["matrix"],
        bands=graph.bands, n=n,
    )
    compiled = lowered.compile()
    out = [
        tuple(o.shape) for o in jax.tree_util.tree_leaves(compiled.out_info)
    ]
    assert out == [(n, n), ()]
    text = compiled.as_text()
    # chipbench/roofline_ksp2.py finds the program in a device trace by
    # this name (ALL_PAIRS: ksp2_all_pairs_roofline and
    # ksp2_all_pairs_pass_roofline read its device time): the function
    # keeps it though it lost the view and the rows
    assert text.splitlines()[0].split()[1].rstrip(",") \
        == roofline_ksp2.ALL_PAIRS == "jit__ell_all_view_rows"
    assert _edge_shaped_pred_gathers(text, graph.bands) == []
    # the matrix is relaxed in place: the donated operand is the output
    assert "input_output_alias" in text
    # the resident matrix and the loop's second copy fit the chip many
    # times over
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


def test_rows_program_lowers_at_both_ksp2_cells(ksp2_shapes):
    """The rows solve of a KSP2 sync, the program the sync waits for,
    at the one shape the engine runs it in on either graph: the fixed
    point from the view batch and 32 endpoints (40 rows on the grid, 48
    on the fabric), their seed rows gathered from the resident matrix,
    which it does not donate; the packed view, first hops and old and
    new endpoint rows, and the pass count."""
    import jax

    from chipbench import roofline_ksp2
    from openr_tpu.ops import spf_sparse

    k = ksp2_shapes
    graph, n = k["graph"], k["graph"].n_pad
    compiled = spf_sparse._ell_view_ep_rows.lower(
        *k["bands"], k["overloaded"], *k["view"], *k["matrix"],
        bands=graph.bands, n=n,
    ).compile()
    out = [
        tuple(o.shape) for o in jax.tree_util.tree_leaves(compiled.out_info)
    ]
    view, ep = k["view"][0].shape[0], k["view"][2].shape[0]
    assert (view, ep) == ({"fabric-1000-ksp2": 16, "grid-1000-ksp2": 8}[
        "fabric-1000-ksp2" if len(graph.bands) == 3 else "grid-1000-ksp2"
    ], 32)
    assert out == [(2 * view + 2 * ep, n), ()]
    text = compiled.as_text()
    # a module of its own: the readers that find the matrix solve by
    # name must not count this one's device time into it
    name = text.splitlines()[0].split()[1].rstrip(",")
    assert name == "jit__ell_view_ep_rows" != roofline_ksp2.ALL_PAIRS
    # (the view's first hops look ``overloaded`` up per source row,
    # pred[16]: per row, not per edge)
    assert _edge_shaped_pred_gathers(text, graph.bands) == []
    assert "input_output_alias" not in text
    # it relaxes [view + ep, n] rows, not the matrix
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
