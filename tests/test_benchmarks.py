"""Smoke tests keeping the benchmark harnesses importable and runnable
at tiny sizes (the reference keeps its benchmark fixtures compiling in
CI the same way)."""

import json

from benchmarks import bench_config_store, bench_decision, bench_fib
from benchmarks import bench_kvstore
from openr_tpu.models import topologies


class TestBenchmarkHarnesses:
    def test_decision_case(self, capsys):
        topo = topologies.grid(3)
        bench_decision.run_case(
            "smoke", topo, "node-0", "node-1", "host", iters=1
        )
        out = json.loads(capsys.readouterr().out.strip())
        assert out["bench"] == "decision.smoke"
        assert out["unicast_routes"] == 8
        assert out["cold_build_ms"] > 0

    def test_kvstore_merge_and_dump(self, capsys):
        bench_kvstore.bench_merge(10, iters=2)
        bench_kvstore.bench_dump(10, iters=2)
        lines = capsys.readouterr().out.strip().splitlines()
        assert len(lines) == 2
        assert all(json.loads(l)["bench"].startswith("kvstore.") for l in lines)

    def test_fib_program(self, capsys):
        bench_fib.bench_program(10)
        out = json.loads(capsys.readouterr().out.strip())
        assert out["program_ms"] > 0
        assert out["incremental_1_route_ms"] > 0

    def test_config_store(self, capsys):
        bench_config_store.bench(10)
        out = json.loads(capsys.readouterr().out.strip())
        assert out["write_ms"] > 0 and out["load_ms"] > 0

    def test_scale(self, capsys):
        from benchmarks import bench_scale

        bench_scale.main(["--nodes", "100", "--block", "64"])
        out = json.loads(capsys.readouterr().out.strip())
        assert out["oracle_spot_check"] == "passed"
        assert out["edges"] > 0

    def test_decision_ksp2_case(self, capsys):
        from openr_tpu.types.lsdb import (
            PrefixForwardingAlgorithm,
            PrefixForwardingType,
        )

        topo = topologies.grid(3)
        bench_decision.run_case(
            "smoke_ksp2", topo, "node-0", "node-1", "host",
            forwarding=(
                PrefixForwardingType.SR_MPLS,
                PrefixForwardingAlgorithm.KSP2_ED_ECMP,
            ),
            iters=1,
        )
        out = json.loads(capsys.readouterr().out.strip())
        assert out["unicast_routes"] == 8
        assert out["churn_rebuild_ms"] > 0

    def test_scale_churn(self, capsys):
        from benchmarks import bench_scale

        bench_scale.main(
            ["--churn", "--nodes", "100", "--churn-events", "2"]
        )
        out = json.loads(capsys.readouterr().out.strip())
        assert out["bench"].startswith("scale.ell_churn")
        assert out["oracle_spot_check"] == "passed"
        assert "device_only_ms" in out


class TestKsp2ChurnLeg:
    def test_ksp2_churn_bench_smoke(self):
        """The official bench's third leg (bench.py OPENR_BENCH_KSP2)
        must run end to end: engine churn rebuilds with zero host
        fallbacks on a parallel-link-free fabric."""
        from benchmarks.bench_scale import ksp2_churn_bench

        out = ksp2_churn_bench(120, 3)
        assert out["events"] == 3
        assert out["ksp2_host_fallbacks"] == 0
        assert out["incremental_syncs"] == 3
        assert out["median_ms"] > 0

    def test_sp_only_churn_bench_smoke(self):
        """The north-star-framing leg (full-SPF reconvergence of one
        node's RouteDb, every prefix SP_ECMP): no KSP2 engine state at
        all, host rebuild bounded by the SP route reuse dirty test."""
        from benchmarks.bench_scale import ksp2_churn_bench

        out = ksp2_churn_bench(120, 3, sp_only=True)
        assert out["bench"].endswith("_sp_churn_rebuild")
        assert out["ksp2_dsts"] == 0
        assert out["events"] == 3
        assert out["incremental_syncs"] == 0  # no engine in play
        assert out["sp_route_reuses_per_event"] > 50
        assert out["median_ms"] > 0
