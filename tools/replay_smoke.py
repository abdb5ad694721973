#!/usr/bin/env python
"""Incident-replay gate (``make replay-smoke``) and report artifact.

Forces a real incident end to end and proves the post-mortem bundle
is a self-contained, deterministic reproduction
(``openr_tpu/telemetry/flight.py`` event journal +
``openr_tpu/twin/replay.py``):

- INCIDENT: a seeded churn storm (metric + prefix events only — no
  flaps or drains, so the fabric stays connected) over an N-node ring
  twin with the event journal armed, then a forced micro-loop
  (endpoint-only reconvergence after a link flap) that the analyzer
  must convict,
- DUMP: the flight recorder cuts a bundle whose journal slice covers
  the storm and whose LSDB anchor digest self-verifies,
- FRESH-PROCESS REPLAY: a separate OS process
  (``python -m openr_tpu.twin.replay <bundle> --json --twice``)
  ingests ONLY the bundle, reconstructs the LSDB at the anchor,
  re-feeds the captured churn one wave per recorded window, and must
  reproduce the same anomaly class with bit-identical per-vantage
  route digests twice in a row and zero per-window divergence,
- PARENT PARITY: the replay's final per-vantage route digests must
  equal the digests the LIVE twin recorded at dump time.

``--nodes`` scales the storm; the default keeps the CPU-pinned tier-1
run fast, the acceptance-scale run is ``--nodes 1008`` on real
hardware. ``--fixture-out`` additionally copies the dumped bundle to
a path — this is how the ``tests/scenarios/`` regression fixtures are
(re)generated.

Writes a JSON artifact (``--out``, default
``/tmp/openr_tpu_replay_smoke.json``); exit 0 on pass, 1 with a
reason list on fail.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["OPENR_FLIGHT"] = "1"

# allow direct invocation (python tools/replay_smoke.py) in addition
# to module mode (python -m tools.replay_smoke)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--out", default="/tmp/openr_tpu_replay_smoke.json"
    )
    parser.add_argument(
        "--nodes", type=int,
        default=int(os.environ.get("OPENR_REPLAY_NODES", "24")),
    )
    parser.add_argument("--events", type=int, default=30)
    parser.add_argument("--seed", type=int, default=20)
    parser.add_argument(
        "--fixture-out", default=None,
        help="also copy the dumped bundle here (fixture regeneration)",
    )
    args = parser.parse_args(argv)

    from openr_tpu.load.generator import EventMix
    from openr_tpu.models import topologies
    from openr_tpu.telemetry import (
        get_registry,
        load_bundle,
        reset_flight_recorder,
    )
    from openr_tpu.twin import FabricTwin, ScenarioDriver

    reg = get_registry()
    failures: list = []
    report: dict = {
        "gates": {}, "nodes": args.nodes, "events": args.events,
    }
    dump_dir = tempfile.mkdtemp(prefix="openr_tpu_replay_flight_")
    report["dump_dir"] = dump_dir
    fr = reset_flight_recorder(
        dump_dir=dump_dir, min_dump_interval_s=0.0, max_dumps=8
    )

    # flap/drain-free churn keeps the ring connected so the forced
    # endpoint-only reconvergence below reliably forms a cycle
    mix = EventMix(
        metric_churn=0.8, link_flap=0.0,
        prefix_update=0.2, drain_flip=0.0,
    )
    twin = FabricTwin(topologies.ring(args.nodes), record_journal=True)
    drv = ScenarioDriver(twin, seed=args.seed, mix=mix)
    twin.converge()
    drv.run_load(args.events)

    # -- gate 1: the forced incident is convicted live --------------------
    drv.inject_micro_loop("node-0", "node-1")
    live_report = twin.analyze()
    loops = len(live_report.loops())
    report["gates"]["live_micro_loops"] = loops
    if not loops:
        failures.append(
            "forced endpoint-only reconvergence surfaced no live "
            "micro-loop — nothing to replay"
        )
    live_digests = {str(k): v for k, v in twin.route_digests().items()}

    # -- gate 2: the dump is cut and self-verifies -------------------------
    bundle_path = fr.dump_postmortem(
        trigger="replay_smoke",
        reason=f"seeded churn storm + forced micro-loop "
               f"({args.nodes} nodes, {args.events} events)",
    )
    twin.close()
    report["bundle"] = bundle_path
    if not bundle_path:
        failures.append("dump_postmortem produced no bundle path")
    else:
        bundle = load_bundle(bundle_path)
        journal = bundle.get("journal") or {}
        n_recs = len(journal.get("records") or [])
        anchor = journal.get("anchor") or {}
        report["journal_records"] = n_recs
        report["anchor_digest"] = anchor.get("graph_digest")
        report["dump_bytes"] = os.path.getsize(bundle_path)
        if not n_recs:
            failures.append("bundle journal slice is empty")
        if not anchor.get("lsdb"):
            failures.append("bundle LSDB anchor is empty")
        if not reg.snapshot().get("ops.flight.dump_bytes.count"):
            failures.append("ops.flight.dump_bytes histogram never fed")
        if args.fixture_out:
            shutil.copyfile(bundle_path, args.fixture_out)
            report["fixture_out"] = args.fixture_out
    report["gates"]["bundle_cut"] = bool(bundle_path)

    # -- gates 3+4: fresh-process deterministic reproduction ---------------
    verdict = None
    if bundle_path:
        # one process per chip: this parent has touched jax, so a child
        # that needed the accelerator would fail or hang. The child is
        # forced onto the CPU backend — which is all this gate (itself
        # CPU-pinned) checks: the replay is deterministic, not fast
        proc = subprocess.run(
            [sys.executable, "-m", "openr_tpu.twin.replay",
             bundle_path, "--json", "--twice"],
            capture_output=True, text=True, timeout=1200,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        report["replay_rc"] = proc.returncode
        try:
            verdict = json.loads(proc.stdout)
        except ValueError:
            failures.append(
                f"fresh-process replay emitted no JSON verdict "
                f"(rc {proc.returncode}): {proc.stderr[-500:]}"
            )
    if verdict is not None:
        report["verdict"] = {
            k: verdict.get(k)
            for k in ("reproduced", "recorded_classes",
                      "replayed_classes", "windows", "pubs_applied",
                      "trailing_pubs", "anchor_moved", "deterministic",
                      "digests_match_recorded", "errors", "ok")
        }
        if not verdict.get("reproduced"):
            failures.append(
                "fresh-process replay did not reproduce the recorded "
                f"anomaly class (recorded "
                f"{verdict.get('recorded_classes')}, replayed "
                f"{verdict.get('replayed_classes')})"
            )
        if not verdict.get("deterministic"):
            failures.append(
                "two replays of the same bundle were not bit-identical"
            )
        if verdict.get("divergence"):
            failures.append(
                f"per-window divergence vs recorded counters: "
                f"{verdict['divergence'][:4]}"
            )
        if verdict.get("errors"):
            failures.append(f"replay errors: {verdict['errors']}")
        if verdict.get("route_digests") != live_digests:
            failures.append(
                "replayed per-vantage route digests differ from the "
                "live twin's at dump time"
            )
        report["gates"]["reproduced"] = bool(verdict.get("reproduced"))
        report["gates"]["deterministic"] = bool(
            verdict.get("deterministic")
        )
        report["gates"]["parent_parity"] = (
            verdict.get("route_digests") == live_digests
        )
    else:
        report["gates"]["reproduced"] = False
        report["gates"]["deterministic"] = False
        report["gates"]["parent_parity"] = False

    report["counters"] = {
        k: reg.counter_get(k)
        for k in (
            "flight.journal_evictions", "flight.dump_truncations",
            "flight.dump_errors", "twin.replays",
            "twin.replays_reproduced",
        )
    }
    report["failures"] = failures
    report["passed"] = not failures
    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=2, sort_keys=True)
    print(json.dumps(report["gates"], indent=2, sort_keys=True))
    if failures:
        print("REPLAY SMOKE FAILED:", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
        return 1
    print(f"replay smoke passed; report at {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
