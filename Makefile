# openr-tpu build/test entry points (Layer 0).
#
# The native SPF core (native/spfcore.cpp) also builds lazily on first
# use (openr_tpu/graph/native_spf.py, which owns the compile command);
# `make native` makes the build explicit for packaging/CI. Python deps
# (jax, numpy, pytest) come from the environment — see pyproject.toml.

# tier1 uses pipefail/PIPESTATUS (bash-only)
SHELL    := /bin/bash

.PHONY: all native test test-fast tier1 lint-analysis race-smoke churn-smoke chaos-smoke tenancy-smoke recovery-smoke integrity-smoke twin-smoke dispatch-smoke pipeline-smoke multichip-smoke serve-smoke obs-smoke replay-smoke fleet-smoke chip-smoke clean install

all: native

# compiles unconditionally; the library is named by the hash of the
# source it was built from, so no stale copy is ever loaded
native:
	python -c "from openr_tpu.graph import native_spf; print(native_spf.build())"

install:
	pip install -e .

# full suite on the virtual 8-device CPU mesh (conftest pins CPU)
test: native
	python -m pytest tests/ -q

test-fast: native
	python -m pytest tests/ -q -x -m "not slow"

# invariant linters (openr_tpu/analysis; --list-rules for the full
# registry). Pure-ast pass, no jax import, a few seconds on the whole
# tree. Exit 1 on any unsuppressed finding OR any stale suppression (a
# directive shielding nothing); suppressions need a reason (see
# docs/RUNBOOK.md "Invariant lint triage").
lint-analysis:
	python -m openr_tpu.analysis --audit-suppressions

# the ROADMAP tier-1 gate, verbatim (CPU-pinned, bounded, dot-counted);
# the invariant linters run first — a finding or a degradation-contract
# regression fails the gate before the test suite spends its budget.
tier1: native lint-analysis race-smoke chaos-smoke tenancy-smoke recovery-smoke integrity-smoke twin-smoke dispatch-smoke pipeline-smoke serve-smoke obs-smoke replay-smoke fleet-smoke
	set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

# fast guard for the incremental churn path: fails if the device
# pipeline regresses to zero incremental syncs / warm solves, or if
# metric churn starts reading the full packed product back per event
# (delta-compacted readback contract, tests/test_route_engine_delta.py).
# The link-churn leg (tests/test_frontier_parity.py) adds the frontier
# regression guard: a localized structural event silently taking the
# full-width path while its frontier is below threshold fails here
churn-smoke: native
	env JAX_PLATFORMS=cpu python -m pytest tests/test_churn_smoke.py tests/test_incremental_parity.py tests/test_route_engine_delta.py tests/test_frontier_parity.py -q -m "not slow"

# thread-provenance race gate (openr_tpu.analysis races/racedep): the
# whole-tree shared-state rule must report zero unsuppressed findings
# with every suppression reasoned and zero stale, the racedep sanitizer
# must convict a seeded two-thread unlocked overlap (and stay silent on
# its lock-guarded twin) under deterministic barrier scheduling, and
# lockdep inversions must carry static role attribution. JSON artifact
# at /tmp/openr_tpu_race_smoke.json. See docs/RUNBOOK.md "Race triage"
# when it fails.
race-smoke:
	env JAX_PLATFORMS=cpu python -m tools.race_smoke --out /tmp/openr_tpu_race_smoke.json

# robustness gate: seeded fault storm through the supervised engine /
# Decision / platform paths; fails if any supervisor fails to
# self-heal, the post-storm product diverges from the fault-free
# oracle, or the fault-coverage floor is missed. JSON artifact at
# /tmp/openr_tpu_chaos_smoke.json (tools/chaos_report.py)
chaos-smoke: native
	env JAX_PLATFORMS=cpu python -m tools.chaos_report --smoke --out /tmp/openr_tpu_chaos_smoke.json

# tenant-plane gate (ops.world_batch): B=8 mixed-size tenants across
# shape buckets — batched-vs-sequential bit parity under churn, a
# zero-compile ceiling after bucket warmup, and the evict->rehydrate
# round trip (warm, not cold). See docs/RUNBOOK.md "Tenant residency
# triage" when it fails.
tenancy-smoke: native
	env JAX_PLATFORMS=cpu python -m tools.tenancy_smoke --out /tmp/openr_tpu_tenancy_smoke.json

# crash-recovery gate (openr_tpu.state): checkpointed warm boot must
# be bit-identical to the cold oracle with zero cold ELL solves and
# zero jit compiles on rehydrate; an injected device.lost must recover
# within the ladder; Fib graceful restart must reconcile with exactly
# one sync and zero deletes (routes never flap). See docs/RUNBOOK.md
# "Crash recovery triage" when it fails.
recovery-smoke: native
	env JAX_PLATFORMS=cpu python -m tools.recovery_smoke --out /tmp/openr_tpu_recovery_smoke.json

# integrity gate (openr_tpu.integrity): seeded bit flips in resident
# device state (ELL, grouped, world-batch) must be convicted within
# one audit pass, quarantined, and healed WARM — bit-identical to the
# host oracle with zero route deletes; a quarantined engine must
# refuse the warm rung. See docs/RUNBOOK.md "Corruption triage" when
# it fails.
integrity-smoke: native
	env JAX_PLATFORMS=cpu python -m tools.integrity_smoke --out /tmp/openr_tpu_integrity_smoke.json

# digital-twin gate (openr_tpu.twin): a 16-vantage fleet must solve
# as ONE batched dispatch wave bit-identical to 16 independently-run
# Decision pipelines, join/warm-churn retrace-free, and the fleet
# analyzer must catch an injected micro-loop and transient blackhole
# (and report clean after the heal wave). See docs/RUNBOOK.md "Fleet
# what-if triage" when it fails.
twin-smoke: native
	env JAX_PLATFORMS=cpu python -m tools.twin_smoke --out /tmp/openr_tpu_twin_smoke.json

# committed-dispatch gate (openr_tpu.ops.route_engine): a warm event
# window must cost at most 2 host touches (one submit run, one reap
# run) with zero blocking syncs, an identical second pass must cost
# zero AOT/jit compiles, and both the incremental result and the
# debounced churn_window batch must be bit-identical to the
# from-scratch oracle. See docs/RUNBOOK.md "Host-overhead triage"
# when it fails.
dispatch-smoke: native
	env JAX_PLATFORMS=cpu python -m tools.dispatch_smoke --out /tmp/openr_tpu_dispatch_smoke.json

# pipelined event-window gate (PR 16): a warm multi-event burst must
# cost at most 2 host touches per pipeline DRAIN (not per window) with
# ops.pipelined_dispatches witnessing depth >= 2, speculation must
# adopt on match and cancel (counted) on mismatch with both paths
# bit-identical to the sequential oracle, and warm bursts at depths
# 1..3 must cost zero AOT/jit compiles. See docs/RUNBOOK.md
# "Speculation-miss storm" when the cancel counters climb.
pipeline-smoke: native
	env JAX_PLATFORMS=cpu python -m tools.pipeline_smoke --out /tmp/openr_tpu_pipeline_smoke.json

# sharded-dispatch gate on the virtual 8-device CPU mesh (conftest
# pins the device count): pipelined==eager bit-identity across a
# shard-boundary event, zero reshards / zero implicit transfers under
# jax.transfer_guard across a 5-event churn run, and the KSP2
# engine's warm dispatches staying on the mesh. Same contracts a real
# multi-chip run must hold.
multichip-smoke: native
	env JAX_PLATFORMS=cpu python -m pytest \
	  tests/test_route_engine_delta.py::TestMeshPipelining \
	  tests/test_route_engine_delta.py::TestShardedNoReshard \
	  tests/test_ksp2_engine.py::TestMeshShardedEngine \
	  -q -m "not slow"

# serving-plane gate (openr_tpu.serve): ONE device-owning solver
# service process serving B>=64 tenants from 4 jax-free client OS
# processes over the ctrl wire — bit parity vs the oracle replay,
# ZERO jit compiles across the whole client storm after warmup,
# per-class p99 under the 100ms CPU-scaled SLO, and premium p99 <=
# standard p99 under a seeded mixed-class storm. See docs/RUNBOOK.md
# "SLO breach triage" when it fails.
serve-smoke: native
	env JAX_PLATFORMS=cpu python -m tools.serve_smoke --out /tmp/openr_tpu_serve_smoke.json

# flight-recorder / device-time-attribution gate: armed-vs-disarmed
# profiling overhead (<5% on a ~1k-event warm churn leg), one forced
# anomaly per trigger class (touch_budget, p99_breach, reshard,
# quarantine, ladder_exhausted, compile_after_warmup) each dumping a
# well-formed post-mortem bundle, and attribution consistency against
# dispatch accounting. See docs/RUNBOOK.md "Post-mortem triage".
obs-smoke: native
	env JAX_PLATFORMS=cpu python -m tools.obs_smoke --out /tmp/openr_tpu_obs_smoke.json

# incident-replay gate (openr_tpu.twin.replay): a seeded flap-free
# churn storm + forced micro-loop must dump a self-contained bundle
# (journal slice + verifying LSDB anchor) that a FRESH OS process
# replays to the same anomaly class with bit-identical per-vantage
# route digests twice in a row and parity vs the live twin at dump
# time. --nodes 1008 is the acceptance-scale run on real hardware.
# See docs/RUNBOOK.md "Replay an incident".
replay-smoke: native
	env JAX_PLATFORMS=cpu python -m tools.replay_smoke --out /tmp/openr_tpu_replay_smoke.json

# fleet-plane gate (openr_tpu.fleet): two-service bring-up with hot
# standbys, a multi-process client storm through SLO-class placement
# (load.multi_client --services mode), a live migration that must land
# WARM (zero cold solves, zero jit compiles on the destination,
# bit-identical SP + FIB products vs the never-migrated oracle), and a
# primary kill mid-schedule whose standby promotion must take exactly
# one reconcile with ZERO route deletes. See docs/RUNBOOK.md
# "Failover and migration triage" when it fails.
fleet-smoke: native
	env JAX_PLATFORMS=cpu python -m tools.fleet_smoke --out /tmp/openr_tpu_fleet_smoke.json

# the quickest proof that the served paths still start on the chip:
# KvStore -> Decision -> Fib at 1008 and 10k nodes, KSP2, the multi-area
# border and SolverService, each checked against the host reference.
# Exits != 0 without a TPU; run it on the machine that holds one.
chip-smoke:
	python chip_smoke.py

clean:
	rm -f native/*.so
	find . -name __pycache__ -type d -exec rm -rf {} +
