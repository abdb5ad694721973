"""Both cells' solve programs compile for a TPU v5e that is described,
not attached (on-chip-measurement guide, section 2.3), at the shapes
the two configurations give them. Nothing runs, so nothing here is a
time; what a pass proves is that the chip's compiler accepts the
programs before any chip time is spent on them.

The topology is described inside a fixture: only the worker that is
handed this file loads the TPU's library, and only once a test of it
has started.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile is written to the persistent cache but cannot be
    # read back without a chip; keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def _config(name: str) -> dict:
    with open(os.path.join(REPO, "chipbench", "configs", name + ".json"),
              encoding="utf-8") as f:
        return json.load(f)


def _link_state(config: dict):
    from chipbench import topology
    from openr_tpu.graph.linkstate import LinkState

    topo = topology.build(config["topology"], config["forwarding"])
    ls = LinkState(area=topo.area)
    for name in sorted(topo.adj_dbs):
        ls.update_adjacency_database(topo.adj_dbs[name])
    return ls


def _shape(sharding, shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def test_dense_view_batch_lowers_at_fabric_1000(one_chip):
    """``jit__spf_view_batch``: 16 source rows (the vantage and its 8
    FSWs, padded) over the 1016 nodes padded to 1024."""
    import jax.numpy as jnp

    from chipbench import roofline
    from openr_tpu.graph import snapshot
    from openr_tpu.ops import spf

    config = _config("fabric-1000")
    n_pad = snapshot._padded(config["size"]["nodes"])
    batch = roofline.batch_rows(config["size"]["degree"]["rsw"])
    assert (batch, n_pad) == (16, 1024)
    compiled = spf._spf_view_batch.lower(
        _shape(one_chip, (n_pad, n_pad), jnp.int32),
        _shape(one_chip, (n_pad,), jnp.bool_),
        _shape(one_chip, (batch,), jnp.int32),
        use_link_metric=True, impl=spf.JNP,
    ).compile()
    out = compiled.memory_analysis().output_size_in_bytes
    assert out == 2 * batch * n_pad * 4  # distances + first-hop rows


def test_ell_reconverge_lowers_at_fabric_5000(one_chip):
    """``jit__ell_reconverge``: the warm churn dispatch over the bands
    ``compile_ell`` gives the 4992-node fabric, with a one-row patch per
    band and the smallest increase bucket — the shape of a window that
    carries one event."""
    import jax.numpy as jnp

    from openr_tpu.graph import snapshot
    from openr_tpu.ops import spf_sparse

    config = _config("fabric-5000")
    ls = _link_state(config)
    graph = spf_sparse.compile_ell(ls)
    assert [(b.rows, b.k) for b in graph.bands] == [(4032, 8), (960, 128)]
    assert graph.n_pad == config["size"]["nodes"] == 4992
    batch = len(spf_sparse.ell_source_batch(graph, ls, config["vantage"]))
    assert batch == 16
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
    assert compiled.memory_analysis().temp_size_in_bytes > 0
