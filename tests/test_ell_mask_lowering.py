"""The warm ELL solve, compiled for a TPU v5e that is described and not
attached, looks no per-node boolean up per edge (PR 29).

``_ell_reconverge`` at ``fabric-5000``'s shapes used to hold four
gathers whose result was a ``pred`` of a band's [rows, k] shape, two of
them inside the while loop: the overload mask read at every edge's
tail, 81% of the device's time on the chip. The mask now sits on the
distance columns, so the compiled program has none. This is a count
from a compile, not a time; it is what stops the gather coming back
through a refactor.

The helpers are ``tests/chipbench/test_tpu_lowering.py``'s; its fixture
describes the topology only once a test of this file has started, and
skips where it cannot be described.
"""

from __future__ import annotations

import re

import numpy as np

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


def test_ell_reconverge_gathers_no_mask_per_edge(one_chip):
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

    text = spf_sparse._ell_reconverge.lower(
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
    ).compile().as_text()
    # the reading is of this text: the distance gathers must be in it
    assert re.search(r"= s32\[\d+,16\]\S* fusion\(.*while/body/gather", text)
    assert _edge_shaped_pred_gathers(text, graph.bands) == []


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
