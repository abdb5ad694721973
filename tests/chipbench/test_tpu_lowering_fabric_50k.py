"""``fabric-50k``'s programs compile for a TPU v5e that is described,
not attached (on-chip-measurement guide, section 2.3), at the shapes the
configuration gives them: the three bands ``compile_ell`` makes of the
893-pod fabric (k=8 for the RSWs, k=128 for the FSWs, k=1024 for the 288
SSWs, a band no other cell has), 16 source rows, and the patch of an
FSW-SSW flap, which touches one row of the k=128 band and one of the
k=1024 band in one window. Nothing runs, so nothing here is a time.

The fabric is built once for the module (half a minute on the CPU: 1.2 M
adjacencies), and the configuration's ``size`` block is held to it on
the way. ``test_tpu_lowering.py`` is the benchmark's and is not edited;
this file follows it.
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


@pytest.fixture(scope="module")
def config() -> dict:
    with open(os.path.join(REPO, "chipbench", "configs", "fabric-50k.json"),
              encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def fabric(config):
    """(LinkState, EllGraph) of the whole 893-pod fabric."""
    from chipbench import topology
    from openr_tpu.graph.linkstate import LinkState
    from openr_tpu.ops import spf_sparse

    topo = topology.build(config["topology"], config["forwarding"])
    size = config["size"]
    assert len(topo.adj_dbs) == size["nodes"] == 50296
    assert topo.links() == size["links"] == 600096
    assert len(topo.prefix_dbs) == size["prefixes"]
    for tier, degree in size["degree"].items():
        assert sum(n.startswith(tier) for n in topo.adj_dbs) == size[tier]
        assert {len(db.adjacencies) for n, db in topo.adj_dbs.items()
                if n.startswith(tier)} == {degree}, tier
    ls = LinkState(area=topo.area)
    for name in sorted(topo.adj_dbs):
        ls.update_adjacency_database(topo.adj_dbs[name])
    del topo
    return ls, spf_sparse.compile_ell(ls)


def test_the_fabric_compiles_to_three_bands(config, fabric):
    from chipbench import roofline
    from openr_tpu.ops import spf_sparse

    ls, graph = fabric
    size = config["size"]
    # one band a tier: rows are the tier's switches, k the power of two
    # at or above its degree
    assert [(b.start, b.rows, b.k) for b in graph.bands] == [
        (0, size["rsw"], 8),
        (size["rsw"], size["fsw"], 128),
        (size["rsw"] + size["fsw"], size["ssw"], 1024)]
    assert graph.n == size["nodes"] and graph.n_pad == 50304
    slots = sum(b.rows * b.k for b in graph.bands)
    assert (graph.edges, slots) == (2 * size["links"], 1_552_256)
    # 10.0 x fabric-5000's 155,136 slots; 77.3% of them hold an edge
    assert 0.773 < graph.edges / slots < 0.774
    batch = spf_sparse.ell_source_batch(graph, ls, config["vantage"])
    assert len(batch) == roofline.batch_rows(size["degree"]["rsw"]) == 16
    # what a solve hands back: distances and first hops, 6.4 MB
    assert 2 * len(batch) * graph.n_pad * 4 == 6_438_912


def _shape(sharding, shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def test_ell_reconverge_lowers_at_fabric_50k_with_an_fsw_ssw_patch(
        one_chip, config, fabric):
    """``jit__ell_reconverge``: the warm churn dispatch over the three
    bands with the scatter of an FSW-SSW flap fused in (one row of the
    k=128 band, one of the k=1024 band, the k=8 band's no-op triple:
    all the smallest bucket) and the smallest increase bucket."""
    import jax
    import jax.numpy as jnp

    from openr_tpu.graph import snapshot
    from openr_tpu.ops import spf_sparse

    ls, graph = fabric
    batch = len(spf_sparse.ell_source_batch(graph, ls, config["vantage"]))
    # the flap's patch, from the program's own functions: fsw-0-0 and
    # ssw-0-0 re-derived, one row in each of their bands
    patched = spf_sparse.ell_patch(graph, ls, ["fsw-0-0", "ssw-0-0"],
                                   widen=True)
    assert sorted(patched.changed) == [1, 2] and not patched.widened
    rows = [
        np.zeros(1, np.int32) if r is None else r
        for _w, r in spf_sparse._band_patch_rows(patched)]
    assert [r.shape for r in rows] == [(1,)] * 3
    assert snapshot.pad_patch_rows(patched.changed[2]).shape == (1,)
    inc = spf_sparse.pad_increase_edges([(0, 1, 1)])[0].shape[0]
    i32 = jnp.int32

    def per_band(shape_of):
        return tuple(_shape(one_chip, shape_of(b, r), i32)
                     for b, r in zip(graph.bands, rows))

    compiled = spf_sparse._ell_reconverge.lower(
        per_band(lambda b, r: (b.rows, b.k)),
        per_band(lambda b, r: (b.rows, b.k)),
        per_band(lambda b, r: r.shape),
        per_band(lambda b, r: (len(r), b.k)),
        per_band(lambda b, r: (len(r), b.k)),
        _shape(one_chip, (inc,), i32),
        _shape(one_chip, (inc,), i32),
        _shape(one_chip, (inc,), i32),
        _shape(one_chip, (graph.n_pad,), jnp.bool_),
        _shape(one_chip, (batch, graph.n_pad), i32),
        _shape(one_chip, (batch,), i32),
        bands=graph.bands, n=graph.n_pad,
    ).compile()
    out = jax.tree_util.tree_leaves(compiled.out_info)
    assert [tuple(o.shape) for o in out] == [
        (42864, 8), (7144, 128), (288, 1024),
        (42864, 8), (7144, 128), (288, 1024),
        (2 * batch, graph.n_pad), (batch, graph.n_pad), (2,)]
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes >= 2 * batch * graph.n_pad * 4
    # the relax's [slots, batch] intermediates: well inside 16 GB
    assert 0 < memory.temp_size_in_bytes < 4 << 30


@pytest.mark.parametrize("band", [1, 2], ids=["k128", "k1024"])
@pytest.mark.parametrize("bucket", [1, 2])
def test_patch_band_lowers_at_the_two_wide_bands(
        one_chip, fabric, band, bucket):
    """``jit_patch`` (``_patch_band``): the publication-time scatter of
    ``SpfSolver.prewarm``, one program a band and a bucket of rows. An
    event touches one row a band; two are two events' rows that one
    patch carried."""
    import jax.numpy as jnp

    from openr_tpu.ops import spf_sparse

    b = fabric[1].bands[band]
    i32 = jnp.int32
    compiled = spf_sparse._patch_band.lower(
        _shape(one_chip, (b.rows, b.k), i32),
        _shape(one_chip, (b.rows, b.k), i32),
        _shape(one_chip, (bucket,), i32),
        _shape(one_chip, (bucket, b.k), i32),
        _shape(one_chip, (bucket, b.k), i32),
    ).compile()
    assert compiled.memory_analysis().output_size_in_bytes \
        >= 2 * b.rows * b.k * 4
