"""Multi-tenant batched worlds (ops.world_batch): per-tenant bit
parity vs the sequential single-graph engines, compile-count flatness
as tenants join a warm shape bucket, and the residency arbiter's
evict -> warm-rehydrate round trip."""

import numpy as np
import pytest

from openr_tpu.decision.spf_solver import (
    SPF_COUNTERS,
    SpfSolver,
    reset_device_caches,
)
from openr_tpu.load.admission import DebounceController
from openr_tpu.models import topologies
from openr_tpu.ops.spf_sparse import (
    compile_ell,
    ell_source_batch,
    ell_view_batch_packed,
)
from openr_tpu.ops.world_batch import (
    TENANCY_COUNTERS,
    WorldManager,
    get_world_manager,
    reset_world_manager,
)
from openr_tpu.telemetry import get_registry, jax_hooks
from tests.test_sp_route_reuse import (
    _drop_adj,
    _mutate_metric,
    _restore_adj,
    _set_overload,
)
from tests.test_spf_sparse import load


def _mixed_tenants(extra_seed=0):
    """8 mixed-size worlds spanning two shape buckets."""
    topos = [
        topologies.grid(3),
        topologies.grid(4),
        topologies.grid(5),
        topologies.random_mesh(20, 3, seed=7 + extra_seed),
        topologies.random_mesh(30, 4, seed=11 + extra_seed),
        topologies.random_mesh(48, 4, seed=13 + extra_seed),
        topologies.random_mesh(64, 3, seed=17 + extra_seed),
        topologies.random_mesh(150, 3, seed=19 + extra_seed),
    ]
    lss = [load(t) for t in topos]
    roots = [sorted(ls.get_adjacency_databases())[0] for ls in lss]
    return [
        (f"t{i}", ls, root)
        for i, (ls, root) in enumerate(zip(lss, roots))
    ]


def _sequential_oracle(ls, root):
    graph = compile_ell(ls)
    srcs = ell_source_batch(graph, ls, root)
    return srcs, np.asarray(ell_view_batch_packed(graph, srcs))


def _assert_parity(mgr, items, tag=""):
    views = mgr.solve_views(items)
    for (tid, ls, root), (_graph, srcs, packed) in zip(items, views):
        ref_srcs, ref = _sequential_oracle(ls, root)
        assert srcs == ref_srcs, (tag, tid)
        assert packed.shape == ref.shape, (tag, tid)
        np.testing.assert_array_equal(packed, ref, err_msg=f"{tag}:{tid}")
    return views


class TestBatchedParity:
    def test_cold_batched_matches_sequential(self):
        items = _mixed_tenants()
        mgr = WorldManager(slots_per_bucket=8)
        _assert_parity(mgr, items, "cold")
        # one dispatch per populated bucket, not one per tenant
        assert mgr.bucket_count() >= 2
        assert mgr.resident_count() == len(items)

    def test_metric_churn_batched_matches_sequential(self):
        items = _mixed_tenants(extra_seed=100)
        mgr = WorldManager(slots_per_bucket=8)
        _assert_parity(mgr, items, "cold")
        warm0 = TENANCY_COUNTERS["warm_solves"]
        # churn a subset of tenants; the untouched ones must come back
        # bit-identical from their mirrors
        for _tid, ls, root in items[::2]:
            _mutate_metric(ls, root, 0, 55)
        _assert_parity(mgr, items, "metric-churn")
        assert TENANCY_COUNTERS["warm_solves"] - warm0 >= len(items[::2])

    def test_structural_churn_batched_matches_sequential(self):
        items = _mixed_tenants(extra_seed=200)
        mgr = WorldManager(slots_per_bucket=8)
        _assert_parity(mgr, items, "cold")
        _tid, ls, _root = items[3]
        nodes = sorted(ls.get_adjacency_databases())
        dropped = _drop_adj(ls, nodes[1], 0)
        _assert_parity(mgr, items, "link-down")
        _restore_adj(ls, nodes[1], dropped)
        _assert_parity(mgr, items, "link-up")
        _tid2, ls2, _root2 = items[4]
        nodes2 = sorted(ls2.get_adjacency_databases())
        _set_overload(ls2, nodes2[2], True)
        _assert_parity(mgr, items, "overload-on")
        _set_overload(ls2, nodes2[2], False)
        _assert_parity(mgr, items, "overload-off")

    def test_batch_composition_independence(self):
        # a tenant's rows must not depend on who shares the batch:
        # solo solve == batched-with-7-others solve, bit for bit
        items = _mixed_tenants(extra_seed=300)
        solo = WorldManager(slots_per_bucket=8)
        solo_views = solo.solve_views([items[0]])
        batched = WorldManager(slots_per_bucket=8)
        batched_views = batched.solve_views(items)
        np.testing.assert_array_equal(
            solo_views[0][2], batched_views[0][2]
        )


class TestCompileFlatness:
    def test_bucket_join_is_retrace_free(self):
        if not jax_hooks.install():
            pytest.skip("jax.monitoring unavailable")
        reg = get_registry()
        items = _mixed_tenants(extra_seed=400)
        mgr = WorldManager(slots_per_bucket=8)
        mgr.solve_views(items)  # warm every bucket shape
        # warm the resident patch-scatter executable too
        _mutate_metric(items[1][1], items[1][2], 0, 77)
        mgr.solve_views(items)
        compiles0 = reg.counter_get("jax.compile_count")
        buckets0 = TENANCY_COUNTERS["bucket_compiles"]
        # NEW tenants with the same shapes (same topologies, fresh
        # worlds, different metrics) joining the warm buckets
        join = [
            (f"j{i}", ls, root)
            for i, (_tid, ls, root) in enumerate(
                _mixed_tenants(extra_seed=400)
            )
        ]
        for _tid, ls, root in join:
            _mutate_metric(ls, root, 0, 33)
        mgr.solve_views(join)
        # churn + warm re-solve of an original tenant, still flat
        _mutate_metric(items[1][1], items[1][2], 0, 88)
        mgr.solve_views(items)
        assert reg.counter_get("jax.compile_count") == compiles0
        assert TENANCY_COUNTERS["bucket_compiles"] == buckets0


class TestResidencyArbiter:
    def test_evict_rehydrate_parity_and_warmness(self):
        # 3 same-bucket tenants in a 2-slot bucket: solving all three
        # forces an eviction; churning the evicted-but-solved tenant
        # must rehydrate it WARM (journal replay), not cold
        topos = [
            topologies.grid(3),
            topologies.grid(4),
            topologies.random_mesh(20, 3, seed=7),
        ]
        lss = [load(t) for t in topos]
        items = [
            (f"e{i}", ls, sorted(ls.get_adjacency_databases())[0])
            for i, ls in enumerate(lss)
        ]
        mgr = WorldManager(slots_per_bucket=2)
        ev0 = TENANCY_COUNTERS["evictions"]
        _assert_parity(mgr, items, "wave")
        assert TENANCY_COUNTERS["evictions"] > ev0
        assert mgr.resident_count() == 2
        evicted = [
            t
            for t in (mgr._tenants[tid] for tid, _ls, _r in items)
            if t.slot is None and t.solved
        ]
        assert evicted, "an already-solved tenant should be evicted"
        tid = evicted[0].tenant_id
        idx = [t for t, _ls, _r in items].index(tid)
        ls = items[idx][1]
        _mutate_metric(
            ls, sorted(ls.get_adjacency_databases())[0], 0, 123
        )
        r0 = TENANCY_COUNTERS["rehydrations"]
        w0 = TENANCY_COUNTERS["warm_solves"]
        c0 = TENANCY_COUNTERS["cold_solves"]
        _assert_parity(mgr, items, "rehydrate")
        assert TENANCY_COUNTERS["rehydrations"] - r0 >= 1
        assert TENANCY_COUNTERS["warm_solves"] - w0 >= 1
        assert TENANCY_COUNTERS["cold_solves"] == c0

    def test_occupancy_gauges(self):
        items = _mixed_tenants(extra_seed=500)[:3]
        mgr = WorldManager(slots_per_bucket=8)
        mgr.solve_views(items)
        assert TENANCY_COUNTERS["active"] == len(mgr._tenants)
        assert TENANCY_COUNTERS["resident"] == mgr.resident_count()
        mgr.drop(items[0][0])
        assert TENANCY_COUNTERS["active"] == len(mgr._tenants)

    def test_ls_identity_change_readmits_cold(self):
        topo = topologies.grid(3)
        ls1 = load(topo)
        root = sorted(ls1.get_adjacency_databases())[0]
        mgr = WorldManager(slots_per_bucket=4)
        _assert_parity(mgr, [("x", ls1, root)], "first")
        # same tenant id, brand-new LinkState object: must not serve
        # the old world's rows
        ls2 = load(topo)
        _mutate_metric(ls2, root, 0, 99)
        a0 = TENANCY_COUNTERS["admissions"]
        _assert_parity(mgr, [("x", ls2, root)], "readmit")
        assert TENANCY_COUNTERS["admissions"] - a0 == 1


class TestDecisionWiring:
    def test_reset_device_caches_resets_world(self):
        mgr = get_world_manager()
        topo = topologies.grid(3)
        ls = load(topo)
        root = sorted(ls.get_adjacency_databases())[0]
        mgr.solve_views([("r", ls, root)])
        assert mgr.resident_count() == 1
        reset_device_caches()
        assert get_world_manager() is not mgr
        assert get_world_manager().resident_count() == 0
        reset_world_manager()


class TestViewCacheLru:
    def test_configurable_cap_and_eviction_counter(self):
        lss = [load(topologies.grid(3)) for _ in range(3)]
        areas = {f"a{i}": ls for i, ls in enumerate(lss)}
        solver = SpfSolver("node-0", view_cache_cap=2)
        assert solver.view_cache_cap == 2
        e0 = SPF_COUNTERS["route_engine.view_evictions"]
        for a, ls in areas.items():
            solver._view(a, ls, "node-0")
        assert len(solver._views) == 2
        assert SPF_COUNTERS["route_engine.view_evictions"] - e0 == 1

    def test_env_default(self, monkeypatch):
        import openr_tpu.decision.spf_solver as mod

        monkeypatch.setattr(mod, "VIEW_CACHE_CAP_DEFAULT", 7)
        assert SpfSolver("n").view_cache_cap == 7
        assert SpfSolver("n", view_cache_cap=3).view_cache_cap == 3


class TestDebounceSelfTune:
    def _controller(self, **kw):
        kw.setdefault("base_max_s", 0.25)
        kw.setdefault("cap_s", 2.0)
        kw.setdefault("widen_depth", 8)
        kw.setdefault("narrow_depth", 2)
        kw.setdefault("metric_prefix", f"tune{id(self)}")
        return DebounceController(**kw)

    def test_sheds_narrow_the_band(self):
        c = self._controller(tune_period=4)
        reg = get_registry()
        prefix = c._prefix
        adj0 = reg.counter_get(f"{prefix}.debounce_band_adjustments")
        for _ in range(4):
            c.observe(3)
        assert c.widen_depth == 8  # first period only samples
        reg.counter_bump(f"{prefix}.admission.sheds")
        for _ in range(4):
            c.observe(3)
        assert c.widen_depth == 7
        assert (
            reg.counter_get(f"{prefix}.debounce_band_adjustments")
            - adj0
            == 1
        )

    def test_band_floor_is_pinned_above_narrow(self):
        c = self._controller(tune_period=1, narrow_depth=2, widen_depth=4)
        reg = get_registry()
        c.observe(0)  # first sample
        for _ in range(10):
            reg.counter_bump(f"{c._prefix}.admission.sheds")
            c.observe(0)
        assert c.widen_depth == 3  # narrow_depth + 1, never lower

    def test_quiet_periods_relax_back(self):
        c = self._controller(tune_period=1)
        reg = get_registry()
        c.observe(0)  # first period only records the sample
        reg.counter_bump(f"{c._prefix}.admission.sheds")
        c.observe(0)  # shed seen: engage earlier
        assert c.widen_depth == 7
        c.observe(0)  # quiet period: relax toward configured band
        assert c.widen_depth == 8
        c.observe(0)  # never above the configured value
        assert c.widen_depth == 8

    def test_self_tune_off_keeps_fixed_band(self):
        c = self._controller(self_tune=False, tune_period=1)
        reg = get_registry()
        for _ in range(5):
            reg.counter_bump(f"{c._prefix}.admission.sheds")
            c.observe(3)
        assert c.widen_depth == 8

    def test_fsm_unchanged_by_tuning_defaults(self):
        # the original hysteresis behavior under short horizons
        c = self._controller(cap_s=1.0)
        assert c.observe(10) == DebounceController.WIDEN
        assert c.observe(10) == DebounceController.WIDEN
        assert c.observe(50) == DebounceController.STEADY
        assert c.observe(0) == DebounceController.NARROW
        assert c.observe(0) == DebounceController.NARROW
        assert c.observe(0) == DebounceController.STEADY
