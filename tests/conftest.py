"""Test harness config: force an 8-device virtual CPU mesh.

No test needs an accelerator: all sharding tests run on 8 virtual CPU
devices (the standard JAX trick for testing pjit/shard_map topologies
host-side), and `chip_smoke.py` is what runs the same paths on the
chip. The pin lives in openr_tpu.testing so the tools and the driver
entries share one copy.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from openr_tpu.testing import pin_host_cpu  # noqa: E402
from openr_tpu.utils import compile_cache  # noqa: E402

pin_host_cpu(8)
compile_cache.enable()


@pytest.fixture(autouse=True)
def _fresh_integrity_auditor():
    """Resident engines self-register with the process-global
    IntegrityAuditor on construction, and Decision's post-converge
    hook audits EVERY registered engine. Without a per-test reset, one
    test's converge would audit engines still alive from another —
    bumping integrity/tenancy counters and jit-compiling audit kernels
    inside tests that assert exact counter or compile deltas. A
    production process wants the global registry; tests want
    hermeticity."""
    from openr_tpu.integrity import reset_auditor

    reset_auditor()
    yield
    reset_auditor()


@pytest.fixture(autouse=True)
def _fresh_flight_recorder(tmp_path):
    """The flight recorder is a process singleton fed from every event
    window; without a per-test reset one test's anomaly (a Decision
    pipeline installs the default triggers) would freeze the ring or
    write a post-mortem bundle into /tmp mid-way through another
    test's exact-counter assertions. Dumps land under the test's own
    tmp_path; tests that exercise the recorder re-reset with their own
    config."""
    from openr_tpu.telemetry import reset_flight_recorder

    reset_flight_recorder(dump_dir=str(tmp_path / "flight"))
    yield
    reset_flight_recorder()
