"""The invariant lint engine (openr_tpu.analysis): per-rule fixtures
(positive / negative / suppressed), the live-tree meta-test, seeded
mutations of the real route engine, and the runtime lockdep tracker.

Everything here is pure-ast + threading — no jax, no device. The
fixtures are tiny synthetic modules written into tmp_path; the
meta-test and the seeded-mutation tests run on the actual source tree,
so they double as the acceptance gate: the tree must lint clean, and
deleting the ``_build`` drain guard or donating a resident into the
churn dispatch must trip the corresponding rule.
"""

import os
import re
import textwrap
import threading

import pytest

import openr_tpu
from openr_tpu.analysis.core import HYGIENE_RULE, run_analysis
from openr_tpu.analysis.lockdep import (
    LockDepTracker,
    LockOrderError,
    TrackedLock,
    reset_tracker,
)

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.abspath(openr_tpu.__file__))
)
ROUTE_ENGINE = os.path.join(REPO_ROOT, "openr_tpu", "ops", "route_engine.py")


def lint(tmp_path, source, name="snippet.py", rules=None):
    """Run the analysis over one dedented fixture module."""
    (tmp_path / name).write_text(textwrap.dedent(source))
    return run_analysis(str(tmp_path), targets=(name,), rules=rules)


def rule_hits(report, rule):
    return [f for f in report.unsuppressed if f.rule == rule]


# ---------------------------------------------------------------------
# donation-hazard
# ---------------------------------------------------------------------

DONATING_PREAMBLE = """\
    import functools
    import jax
    from openr_tpu.analysis.annotations import (
        donates, requires_drain, resident_buffers,
    )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def consume(buf, other):
        return buf + other
"""


def test_donation_resident_into_donated_position(tmp_path):
    report = lint(tmp_path, DONATING_PREAMBLE + """
    @resident_buffers("res")
    class Engine:
        def step(self, x):
            out = consume(self.res, x)
            return out
    """)
    hits = rule_hits(report, "donation-hazard")
    assert len(hits) == 1
    assert "res" in hits[0].message and "donated" in hits[0].message


def test_donation_alias_taint(tmp_path):
    report = lint(tmp_path, DONATING_PREAMBLE + """
    @resident_buffers("res")
    class Engine:
        def step(self, x):
            prev = self.res
            return consume(prev, x)
    """)
    hits = rule_hits(report, "donation-hazard")
    assert len(hits) == 1
    assert "prev" in hits[0].message


def test_donation_read_after_donation(tmp_path):
    report = lint(tmp_path, DONATING_PREAMBLE + """
    def step(buf, x):
        out = consume(buf, x)
        return out + buf.sum()
    """)
    hits = rule_hits(report, "donation-hazard")
    assert len(hits) == 1
    assert "read after being donated" in hits[0].message


def test_donation_inside_fault_boundary_trips(tmp_path):
    # a ladder rung must not donate ANY argument: a failed rung's
    # deeper rungs re-run against the same inputs
    report = lint(tmp_path, DONATING_PREAMBLE + """
    from openr_tpu.analysis.annotations import fault_boundary

    @fault_boundary
    def rung(buf, x):
        return consume(buf, x)
    """)
    hits = rule_hits(report, "donation-hazard")
    assert len(hits) == 1
    assert "fault_boundary" in hits[0].message
    assert "re-runs deeper rungs" in hits[0].message


def test_donation_outside_fault_boundary_plain_arg_is_clean(tmp_path):
    # same donation without the annotation: a plain (non-resident)
    # value may be donated freely
    report = lint(tmp_path, DONATING_PREAMBLE + """
    def step(buf, x):
        return consume(buf, x)
    """)
    assert rule_hits(report, "donation-hazard") == []


def test_donation_rebind_after_donation_is_clean(tmp_path):
    report = lint(tmp_path, DONATING_PREAMBLE + """
    def step(buf, x):
        buf = consume(buf, x)
        return buf.sum()
    """)
    assert rule_hits(report, "donation-hazard") == []


def test_donation_exclusive_branches_not_read_after(tmp_path):
    # donation in one branch, read in the mutually exclusive other
    report = lint(tmp_path, DONATING_PREAMBLE + """
    def step(buf, x, fast):
        if fast:
            out = consume(buf, x)
        else:
            out = buf.sum()
        return out
    """)
    assert rule_hits(report, "donation-hazard") == []


def test_donation_via_donates_wrapper(tmp_path):
    report = lint(tmp_path, DONATING_PREAMBLE + """
    @donates("d_prev")
    def dispatch(state, d_prev):
        return consume(d_prev, state)

    @resident_buffers("d_dev")
    class Engine:
        def step(self, state):
            return dispatch(state, self.d_dev)
    """)
    hits = rule_hits(report, "donation-hazard")
    assert len(hits) == 1
    assert "d_dev" in hits[0].message


def test_donation_suppressed_with_reason(tmp_path):
    report = lint(tmp_path, DONATING_PREAMBLE + """
    @resident_buffers("res")
    class Engine:
        def step(self, x):
            out = consume(self.res, x)  # openr-lint: disable=donation-hazard -- consumed and rebound
            self.res = out
            return out
    """)
    assert rule_hits(report, "donation-hazard") == []
    suppressed = [f for f in report.findings if f.suppressed]
    assert len(suppressed) == 1
    assert suppressed[0].reason == "consumed and rebound"
    assert rule_hits(report, HYGIENE_RULE) == []


def test_requires_drain_missing_call(tmp_path):
    report = lint(tmp_path, DONATING_PREAMBLE + """
    class Engine:
        @requires_drain("flush")
        def _build(self, ls):
            self._state_dev = compile(ls)
    """)
    hits = rule_hits(report, "donation-hazard")
    assert len(hits) == 1
    assert "never calls flush()" in hits[0].message


def test_requires_drain_write_before_drain(tmp_path):
    report = lint(tmp_path, DONATING_PREAMBLE + """
    class Engine:
        @requires_drain("flush")
        def _build(self, ls):
            self._state_dev = compile(ls)
            self.flush()
    """)
    hits = rule_hits(report, "donation-hazard")
    assert len(hits) == 1
    assert "before calling flush()" in hits[0].message


def test_requires_drain_satisfied(tmp_path):
    report = lint(tmp_path, DONATING_PREAMBLE + """
    class Engine:
        @requires_drain("flush")
        def _build(self, ls):
            self.flush()
            self._state_dev = compile(ls)
    """)
    assert rule_hits(report, "donation-hazard") == []


# ---------------------------------------------------------------------
# host-sync-in-window
# ---------------------------------------------------------------------

SYNC_PREAMBLE = """\
    import numpy as np
    from openr_tpu.analysis.annotations import solve_window
"""


def test_hostsync_flags_annotated_function(tmp_path):
    report = lint(tmp_path, SYNC_PREAMBLE + """
    @solve_window
    def step(rows_dev):
        host = np.asarray(rows_dev)
        rows_dev.block_until_ready()
        return float(rows_dev[0])
    """)
    msgs = [f.message for f in rule_hits(report, "host-sync-in-window")]
    assert len(msgs) == 3
    assert any("np.asarray" in m for m in msgs)
    assert any("block_until_ready" in m for m in msgs)
    assert any("float()" in m for m in msgs)


def test_hostsync_unannotated_function_is_clean(tmp_path):
    report = lint(tmp_path, SYNC_PREAMBLE + """
    def consume(rows_dev):
        return np.asarray(rows_dev)
    """)
    assert rule_hits(report, "host-sync-in-window") == []


def test_hostsync_nested_def_makes_its_own_claim(tmp_path):
    report = lint(tmp_path, SYNC_PREAMBLE + """
    @solve_window
    def step(rows_dev):
        def consume_later():
            return np.asarray(rows_dev)
        return consume_later
    """)
    assert rule_hits(report, "host-sync-in-window") == []


def test_hostsync_suppressed(tmp_path):
    report = lint(tmp_path, SYNC_PREAMBLE + """
    @solve_window
    def step(srcs):
        # openr-lint: disable=host-sync-in-window -- srcs is a host list
        ids = np.asarray(srcs)
        return ids
    """)
    assert rule_hits(report, "host-sync-in-window") == []
    assert any(f.suppressed for f in report.findings)


# ---------------------------------------------------------------------
# committed-dispatch
# ---------------------------------------------------------------------

COMMITTED_PREAMBLE = """\
    import jax
    import numpy as np
    from openr_tpu.analysis.annotations import committed_dispatch
    from openr_tpu.ops import dispatch_accounting as da
"""


def test_committed_flags_raw_syncs(tmp_path):
    report = lint(tmp_path, COMMITTED_PREAMBLE + """
    @committed_dispatch
    def window(rows_dev):
        meta = jax.device_get(rows_dev)
        rows_dev.block_until_ready()
        return int(rows_dev[0])
    """)
    msgs = [f.message for f in rule_hits(report, "committed-dispatch")]
    assert len(msgs) == 3
    assert any("device_get" in m for m in msgs)
    assert any("block_until_ready" in m for m in msgs)
    assert any("int()" in m for m in msgs)


def test_committed_accounted_crossings_are_clean(tmp_path):
    """The sanctioned dispatch_accounting crossings — plus host-list
    numpy prep, which the rule deliberately does not flag inside
    committed bodies (unlike @solve_window ones)."""
    report = lint(tmp_path, COMMITTED_PREAMBLE + """
    @committed_dispatch
    def window(rows_dev, srcs):
        ids = np.asarray(srcs)
        da.count_dispatch()
        da.kick_async(rows_dev)
        return da.reap_read(rows_dev, kicked=True), ids
    """)
    assert rule_hits(report, "committed-dispatch") == []


def test_committed_asarray_on_device_operand_trips(tmp_path):
    report = lint(tmp_path, COMMITTED_PREAMBLE + """
    @committed_dispatch
    def window(rows_dev):
        return np.asarray(rows_dev)
    """)
    hits = rule_hits(report, "committed-dispatch")
    assert len(hits) == 1
    assert "np.asarray" in hits[0].message


def test_committed_unannotated_function_is_clean(tmp_path):
    report = lint(tmp_path, COMMITTED_PREAMBLE + """
    def plain(rows_dev):
        return jax.device_get(rows_dev)
    """)
    assert rule_hits(report, "committed-dispatch") == []


def test_committed_suppressed_with_reason(tmp_path):
    report = lint(tmp_path, COMMITTED_PREAMBLE + """
    @committed_dispatch
    def probe(dev):
        # openr-lint: disable=committed-dispatch -- liveness probe:
        # the blocking sync IS the signal
        return dev.block_until_ready()
    """)
    assert rule_hits(report, "committed-dispatch") == []
    assert any(
        f.rule == "committed-dispatch" and f.suppressed
        for f in report.findings
    )


# ---------------------------------------------------------------------
# host-branch-in-chain
# ---------------------------------------------------------------------


def test_branch_on_reap_read_value_trips(tmp_path):
    report = lint(tmp_path, COMMITTED_PREAMBLE + """
    @committed_dispatch
    def window(meta_dev, rows_dev):
        da.kick_async(meta_dev)
        m = int(da.reap_read(meta_dev, kicked=True))
        if m > 0:
            da.count_dispatch()
        return m
    """)
    hits = rule_hits(report, "host-branch-in-chain")
    assert len(hits) == 1
    assert "'m'" in hits[0].message


def test_branch_taint_flows_through_assignments(tmp_path):
    """``rows = meta[0]`` after ``meta = reap_read(...)`` carries the
    taint; a while on the derived name is the same stall."""
    report = lint(tmp_path, COMMITTED_PREAMBLE + """
    @committed_dispatch
    def window(meta_dev):
        meta = da.reap_read(meta_dev, kicked=True)
        rows = meta[0]
        while rows > 4:
            rows = rows // 2
        return rows
    """)
    hits = rule_hits(report, "host-branch-in-chain")
    assert len(hits) == 1
    assert "while" in hits[0].message


def test_branch_on_untainted_value_is_clean(tmp_path):
    """Branching on host-side inputs (backlog sizes, flags) is fine —
    only readback-derived tests break the chain. Attribute stores of
    a reap must not taint the whole object either."""
    report = lint(tmp_path, COMMITTED_PREAMBLE + """
    @committed_dispatch
    def window(self, events, meta_dev):
        self.meta = da.reap_read(meta_dev, kicked=True)
        if len(events) > 8:
            da.count_dispatch()
        if self.ready:
            da.count_dispatch()
        return events
    """)
    assert rule_hits(report, "host-branch-in-chain") == []


def test_branch_suppressed_with_reason(tmp_path):
    report = lint(tmp_path, COMMITTED_PREAMBLE + """
    @committed_dispatch
    def window(meta_dev):
        m = int(da.reap_read(meta_dev, kicked=True))
        # openr-lint: disable=host-branch-in-chain -- post-reap apply (audited)
        if m:
            return m
        return 0
    """)
    assert rule_hits(report, "host-branch-in-chain") == []
    assert any(
        f.rule == "host-branch-in-chain" and f.suppressed
        for f in report.findings
    )


# ---------------------------------------------------------------------
# lock-order
# ---------------------------------------------------------------------


def test_lockorder_cycle_two_classes(tmp_path):
    # Store.put: Store._lock -> Registry._lock (via reg.bump);
    # Registry.scrape: Registry._lock -> Store._lock (via store.put).
    # Registry's lock is an RLock so the transitive
    # scrape-may-reacquire-its-own-lock self-edge is legal; the
    # cross-class cycle is the one finding.
    report = lint(tmp_path, """
    import threading

    class Registry:
        def __init__(self):
            self._lock = threading.RLock()
            self.store = Store()

        def bump(self):
            with self._lock:
                pass

        def scrape(self, store: "Store"):
            with self._lock:
                store.put(self)

    class Store:
        def __init__(self):
            self._lock = threading.Lock()

        def put(self, reg: "Registry"):
            with self._lock:
                reg.bump()
    """)
    hits = rule_hits(report, "lock-order")
    assert len(hits) == 1
    assert "cycle" in hits[0].message
    assert "Store._lock" in hits[0].message
    assert "Registry._lock" in hits[0].message


def test_lockorder_consistent_order_is_clean(tmp_path):
    report = lint(tmp_path, """
    import threading

    class A:
        def __init__(self):
            self._la = threading.Lock()
            self._lb = threading.Lock()

        def one(self):
            with self._la:
                with self._lb:
                    pass

        def two(self):
            with self._la:
                with self._lb:
                    pass
    """)
    assert rule_hits(report, "lock-order") == []


def test_lockorder_nonreentrant_self_acquire(tmp_path):
    report = lint(tmp_path, """
    import threading

    class A:
        def __init__(self):
            self._l = threading.Lock()

        def outer(self):
            with self._l:
                self.inner()

        def inner(self):
            with self._l:
                pass
    """)
    hits = rule_hits(report, "lock-order")
    assert len(hits) == 1
    assert "non-reentrant" in hits[0].message


def test_lockorder_rlock_reentry_allowed(tmp_path):
    report = lint(tmp_path, """
    import threading

    class A:
        def __init__(self):
            self._l = threading.RLock()

        def outer(self):
            with self._l:
                self.inner()

        def inner(self):
            with self._l:
                pass
    """)
    assert rule_hits(report, "lock-order") == []


def test_lockorder_condition_aliases_its_lock(tmp_path):
    # Condition(self._lock) IS self._lock: taking them "in both orders"
    # across methods is reentrancy on one Lock, not a two-node cycle
    report = lint(tmp_path, """
    import threading

    class Q:
        def __init__(self):
            self._lock = threading.Lock()
            self._cv = threading.Condition(self._lock)

        def push(self):
            with self._lock:
                self.kick()

        def kick(self):
            with self._cv:
                pass
    """)
    hits = rule_hits(report, "lock-order")
    # one self-edge on the non-reentrant lock, no cycle findings
    assert len(hits) == 1
    assert "non-reentrant" in hits[0].message


def test_lockorder_cycle_via_return_annotation(tmp_path):
    # the registry singleton idiom: the Engine->Registry edge is only
    # visible through get_registry()'s return annotation
    report = lint(tmp_path, """
    import threading

    class Registry:
        def __init__(self):
            self._lock = threading.RLock()

        def bump(self):
            with self._lock:
                pass

        def scrape(self, engine: "Engine"):
            with self._lock:
                engine.step()

    def get_registry() -> Registry:
        return Registry()

    class Engine:
        def __init__(self):
            self._mu = threading.Lock()

        def step(self):
            with self._mu:
                pass

        def tick(self):
            with self._mu:
                get_registry().bump()
    """)
    hits = rule_hits(report, "lock-order")
    assert len(hits) == 1
    assert "cycle" in hits[0].message
    assert "Engine._mu" in hits[0].message
    assert "Registry._lock" in hits[0].message


def test_lockorder_unresolved_receiver_is_conservative(tmp_path):
    # an untyped receiver (self.reg = reg, no annotation anywhere)
    # cannot be resolved — the rule stays silent instead of guessing
    report = lint(tmp_path, """
    import threading

    class Store:
        def __init__(self, reg):
            self._lock = threading.Lock()
            self.reg = reg

        def put(self):
            with self._lock:
                self.reg.bump()

    class Registry:
        def __init__(self, store):
            self._lock = threading.Lock()
            self.store = store

        def bump(self):
            with self._lock:
                pass

        def scrape(self):
            with self._lock:
                self.store.put()
    """)
    assert rule_hits(report, "lock-order") == []


# ---------------------------------------------------------------------
# span-discipline
# ---------------------------------------------------------------------

SPAN_PREAMBLE = """\
    from openr_tpu.telemetry import get_registry, get_tracer
"""


def test_span_unclosed(tmp_path):
    report = lint(tmp_path, SPAN_PREAMBLE + """
    def work(tracer):
        span = tracer.span_active("ops.step")
        do_thing()
    """)
    hits = rule_hits(report, "span-discipline")
    assert len(hits) == 1
    assert "never closed" in hits[0].message


def test_span_discarded(tmp_path):
    report = lint(tmp_path, SPAN_PREAMBLE + """
    def work(tracer):
        tracer.span_active("ops.step")
        do_thing()
    """)
    hits = rule_hits(report, "span-discipline")
    assert len(hits) == 1
    assert "discarded" in hits[0].message


def test_span_paired_is_clean(tmp_path):
    report = lint(tmp_path, SPAN_PREAMBLE + """
    def work(tracer):
        span = tracer.span_active("ops.step")
        do_thing()
        tracer.end_span_active(span)
    """)
    assert rule_hits(report, "span-discipline") == []


def test_span_ownership_transfer_to_attribute(tmp_path):
    # the decision.py debounce pattern: the span outlives the function
    report = lint(tmp_path, SPAN_PREAMBLE + """
    class Pending:
        def adopt(self, trace):
            span = trace.begin_span("decision.debounce")
            self._debounce_span = span
    """)
    assert rule_hits(report, "span-discipline") == []


def test_span_early_return_leak(tmp_path):
    report = lint(tmp_path, SPAN_PREAMBLE + """
    def work(tracer, fast):
        span = tracer.span_active("ops.step")
        if fast:
            return None
        out = do_thing()
        tracer.end_span_active(span)
        return out
    """)
    hits = rule_hits(report, "span-discipline")
    assert len(hits) == 1
    assert "return leaks span" in hits[0].message


def test_span_finally_protects_return(tmp_path):
    report = lint(tmp_path, SPAN_PREAMBLE + """
    def work(tracer, fast):
        span = tracer.span_active("ops.step")
        try:
            if fast:
                return None
            return do_thing()
        finally:
            tracer.end_span_active(span)
    """)
    assert rule_hits(report, "span-discipline") == []


def test_span_fault_boundary_close_in_except_is_clean(tmp_path):
    # a degradation-ladder rung closes its span in the catch block and
    # re-raises: protected exit by construction, not via suppression
    report = lint(tmp_path, SPAN_PREAMBLE + """
    from openr_tpu.analysis.annotations import fault_boundary

    @fault_boundary
    def rung(tracer, solver):
        span = tracer.span_active("engine.rung")
        try:
            out = solver.solve()
            tracer.end_span_active(span, ok=True)
            return out
        except Exception:
            tracer.end_span_active(span, ok=False)
            raise
    """)
    assert rule_hits(report, "span-discipline") == []


def test_span_close_in_except_without_fault_boundary_trips(tmp_path):
    # the same shape WITHOUT the annotation still leaks on the success
    # return (close in except has no finally semantics in general code)
    report = lint(tmp_path, SPAN_PREAMBLE + """
    def rung(tracer, solver):
        span = tracer.span_active("engine.rung")
        try:
            do_thing()
            return solver.solve()
        except Exception:
            tracer.end_span_active(span, ok=False)
            raise
    """)
    hits = rule_hits(report, "span-discipline")
    assert len(hits) == 1
    assert "return leaks span" in hits[0].message


def test_span_fb303_name_convention(tmp_path):
    report = lint(tmp_path, SPAN_PREAMBLE + """
    def work(reg, tracer):
        reg.counter_bump("decision.rebuilds")
        reg.counter_bump("BadName")
        reg.observe("noDotsEither", 1.0)
        span = tracer.span_active("Ops.Step")
        tracer.end_span_active(span)
    """)
    msgs = [f.message for f in rule_hits(report, "span-discipline")]
    assert len(msgs) == 3
    assert any("BadName" in m for m in msgs)
    assert any("noDotsEither" in m for m in msgs)
    assert any("Ops.Step" in m for m in msgs)


def test_span_attr_clear_without_close_trips(tmp_path):
    # the overload-path debounce leak: reset() wipes the span attribute
    # while a rebuild is in flight, with no close and no read-out
    report = lint(tmp_path, SPAN_PREAMBLE + """
    class Pending:
        def adopt(self, trace):
            self._debounce_span = trace.begin_span("decision.debounce")

        def reset(self):
            self.count = 0
            self._debounce_span = None
    """)
    hits = rule_hits(report, "span-discipline")
    assert len(hits) == 1
    assert "clearing span attribute" in hits[0].message
    assert "_debounce_span" in hits[0].message


def test_span_attr_clear_after_read_out_is_clean(tmp_path):
    # the fixed shape: read the span into a local (so it can be closed)
    # before clearing the attribute — decision.py's release_trace
    report = lint(tmp_path, SPAN_PREAMBLE + """
    class Pending:
        def adopt(self, trace):
            self._debounce_span = trace.begin_span("decision.debounce")

        def reset(self, trace):
            span = self._debounce_span
            self._debounce_span = None
            if span is not None:
                trace.end_span(span, aborted=True)
    """)
    assert rule_hits(report, "span-discipline") == []


def test_span_attr_clear_init_exempt(tmp_path):
    # declaring the slot in __init__ is not a clear
    report = lint(tmp_path, SPAN_PREAMBLE + """
    class Pending:
        def __init__(self):
            self._debounce_span = None

        def adopt(self, trace):
            self._debounce_span = trace.begin_span("decision.debounce")

        def move_out(self, trace):
            span = self._debounce_span
            self._debounce_span = None
            trace.end_span(span)
            return span
    """)
    assert rule_hits(report, "span-discipline") == []


def test_span_attr_clear_non_span_attr_ignored(tmp_path):
    # only attributes that ever hold spans are tracked
    report = lint(tmp_path, SPAN_PREAMBLE + """
    class State:
        def set(self, value):
            self._value = value

        def reset(self):
            self._value = None
    """)
    assert rule_hits(report, "span-discipline") == []


# ---------------------------------------------------------------------
# retrace-risk
# ---------------------------------------------------------------------

RETRACE_PREAMBLE = """\
    import functools
    import time
    import jax

    @functools.partial(jax.jit, static_argnums=(1,))
    def solve(rows, bucket):
        return rows * bucket
"""


def test_retrace_unhashable_static(tmp_path):
    report = lint(tmp_path, RETRACE_PREAMBLE + """
    def run(rows):
        return solve(rows, [32, 64])
    """)
    hits = rule_hits(report, "retrace-risk")
    assert len(hits) == 1
    assert "unhashable" in hits[0].message


def test_retrace_call_varying_static(tmp_path):
    report = lint(tmp_path, RETRACE_PREAMBLE + """
    def run(rows):
        a = solve(rows, time.perf_counter())
        b = solve(rows, lambda x: x)
        return a, b
    """)
    msgs = [f.message for f in rule_hits(report, "retrace-risk")]
    assert len(msgs) == 2
    assert any("time.perf_counter" in m for m in msgs)
    assert any("lambda" in m for m in msgs)


def test_retrace_static_argnames_kwarg_call(tmp_path):
    report = lint(tmp_path, """
    import functools
    import jax

    @functools.partial(jax.jit, static_argnames=("bands",))
    def solve(rows, bands):
        return rows

    def run(rows):
        return solve(rows, bands=[1, 2])
    """)
    assert len(rule_hits(report, "retrace-risk")) == 1


def test_retrace_stable_static_is_clean(tmp_path):
    report = lint(tmp_path, RETRACE_PREAMBLE + """
    def run(rows, k):
        return solve(rows, k)
    """)
    assert rule_hits(report, "retrace-risk") == []


def test_retrace_jit_in_loop(tmp_path):
    report = lint(tmp_path, """
    import jax

    def run(fns, xs):
        out = []
        for f in fns:
            out.append(jax.jit(f)(xs))
        return out
    """)
    hits = rule_hits(report, "retrace-risk")
    assert len(hits) == 1
    assert "inside a loop" in hits[0].message


# ---------------------------------------------------------------------
# suppression machinery
# ---------------------------------------------------------------------


def test_suppression_without_reason_is_a_finding(tmp_path):
    report = lint(tmp_path, SYNC_PREAMBLE + """
    @solve_window
    def step(rows_dev):
        return np.asarray(rows_dev)  # openr-lint: disable=host-sync-in-window
    """)
    assert rule_hits(report, "host-sync-in-window") == []
    hyg = rule_hits(report, HYGIENE_RULE)
    assert len(hyg) == 1
    assert "no reason" in hyg[0].message


def test_suppression_disable_file(tmp_path):
    report = lint(tmp_path, SYNC_PREAMBLE + """
    # openr-lint: disable-file=host-sync-in-window -- generated shim
    @solve_window
    def step(rows_dev):
        return np.asarray(rows_dev)
    """)
    assert rule_hits(report, "host-sync-in-window") == []


def test_suppression_multiline_reason_shields_next_code_line(tmp_path):
    report = lint(tmp_path, SYNC_PREAMBLE + """
    @solve_window
    def step(rows_dev):
        # openr-lint: disable=host-sync-in-window -- the reason is
        # long and wraps over two comment lines before the code
        return np.asarray(rows_dev)
    """)
    assert rule_hits(report, "host-sync-in-window") == []
    sup = [f for f in report.findings if f.suppressed]
    assert len(sup) == 1
    assert "wraps over two comment lines" in sup[0].reason


def test_exit_code_contract(tmp_path):
    dirty = lint(tmp_path, SYNC_PREAMBLE + """
    @solve_window
    def step(rows_dev):
        return np.asarray(rows_dev)
    """, name="dirty.py")
    assert dirty.exit_code == 1
    clean = lint(tmp_path, "x = 1\n", name="clean.py")
    assert clean.exit_code == 0


def test_parse_error_is_reported(tmp_path):
    report = lint(tmp_path, "def broken(:\n")
    assert any(f.rule == "parse-error" for f in report.findings)
    assert report.exit_code == 1


# ---------------------------------------------------------------------
# meta: the live tree is finding-free, and fast
# ---------------------------------------------------------------------


def test_live_tree_is_finding_free():
    report = run_analysis(REPO_ROOT, targets=("openr_tpu",))
    assert report.unsuppressed == [], "\n".join(
        str(f) for f in report.unsuppressed
    )
    # every suppression in the tree carries a reason
    for f in report.findings:
        if f.suppressed:
            assert f.reason, str(f)
    # the <30s acceptance bound, with heavy margin (it is a pure ast
    # pass; regressing to seconds-per-file would break tier-1 wiring)
    assert report.duration_s < 30.0
    assert report.files_scanned > 50


# ---------------------------------------------------------------------
# seeded mutations of the real engine source
# ---------------------------------------------------------------------


def _lint_mutated_route_engine(tmp_path, mutate):
    with open(ROUTE_ENGINE, "r", encoding="utf-8") as f:
        src = f.read()
    mutated = mutate(src)
    assert mutated != src, "mutation did not apply — source drifted"
    (tmp_path / "route_engine.py").write_text(mutated)
    return run_analysis(str(tmp_path), targets=("route_engine.py",))


def test_seeded_drain_guard_deletion_trips(tmp_path):
    # delete the `self.flush()` drain guard at the top of _build (the
    # line directly above the cold-rebuild compile)
    report = _lint_mutated_route_engine(
        tmp_path,
        lambda src: src.replace(
            "        self.flush()\n",
            "",
            1,
        ),
    )
    hits = rule_hits(report, "donation-hazard")
    assert any(
        "_build" in f.message and "flush" in f.message for f in hits
    ), [str(f) for f in hits]


def test_seeded_donated_resident_trips(tmp_path):
    # donate the resident DR (param 5) into the churn dispatch: the
    # retry ladder would re-dispatch against a freed buffer
    report = _lint_mutated_route_engine(
        tmp_path,
        lambda src: src.replace(
            '@functools.partial(jax.jit, static_argnames=("bands", "n", "k"))',
            '@functools.partial(jax.jit, static_argnames=("bands", "n", "k"),'
            " donate_argnums=(5,))",
            1,
        ),
    )
    hits = rule_hits(report, "donation-hazard")
    assert any(
        "_dr" in f.message and "_churn_step" in f.message for f in hits
    ), [str(f) for f in hits]


def test_unmutated_route_engine_is_clean(tmp_path):
    with open(ROUTE_ENGINE, "r", encoding="utf-8") as f:
        (tmp_path / "route_engine.py").write_text(f.read())
    report = run_analysis(str(tmp_path), targets=("route_engine.py",))
    assert report.unsuppressed == [], "\n".join(
        str(f) for f in report.unsuppressed
    )


# ---------------------------------------------------------------------
# runtime lockdep
# ---------------------------------------------------------------------


def test_lockdep_detects_inversion_single_thread():
    dep = LockDepTracker()
    a = TrackedLock("kvstore.store", tracker=dep)
    b = TrackedLock("telemetry.registry", tracker=dep)
    with a:
        with b:
            pass
    # reversed order: no deadlock strikes (single thread), but the
    # inversion is flagged the moment it is OBSERVED
    with b:
        with a:
            pass
    assert len(dep.violations) == 1
    v = dep.violations[0]
    assert set(v.cycle) == {"kvstore.store", "telemetry.registry"}
    assert "inversion" in str(v)


def test_lockdep_detects_inversion_across_threads():
    dep = LockDepTracker()
    a = TrackedLock("messaging.queue", tracker=dep)
    b = TrackedLock("decision.pending", tracker=dep)

    def t1():
        with a:
            with b:
                pass

    th = threading.Thread(target=t1)
    th.start()
    th.join()

    def t2():
        with b:
            with a:
                pass

    th = threading.Thread(target=t2)
    th.start()
    th.join()
    assert len(dep.violations) == 1
    assert dep.violations[0].witness.thread != ""


def test_lockdep_consistent_order_is_clean():
    dep = LockDepTracker()
    a = TrackedLock("a.lock", tracker=dep)
    b = TrackedLock("b.lock", tracker=dep)
    for _ in range(3):
        with a:
            with b:
                pass
    assert dep.violations == []


def test_lockdep_rlock_reentry_allowed_nonreentrant_flagged():
    dep = LockDepTracker()
    r = TrackedLock("a.rlock", reentrant=True, tracker=dep)
    with r:
        with r:
            pass
    assert dep.violations == []
    dep2 = LockDepTracker()
    l = TrackedLock("a.lock", tracker=dep2, lock=threading.RLock())
    # the backing lock is reentrant so this does not deadlock, but the
    # CLASS is declared non-reentrant: lockdep flags the self-acquire
    with l:
        with l:
            pass
    assert len(dep2.violations) == 1
    assert dep2.violations[0].cycle == ("a.lock",)


def test_lockdep_raise_mode():
    dep = LockDepTracker(raise_on_violation=True)
    a = TrackedLock("x.a", tracker=dep)
    b = TrackedLock("x.b", tracker=dep)
    with a:
        with b:
            pass
    with pytest.raises(LockOrderError):
        with b:
            with a:
                pass


def test_lockdep_global_tracker_reset():
    dep = reset_tracker()
    a = TrackedLock("g.a")  # picks up the global tracker
    with a:
        pass
    assert dep.violations == []
    assert reset_tracker() is not dep


# ---------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------


def test_cli_json_report_and_exit_code(tmp_path, capsys):
    from openr_tpu.analysis.cli import main

    (tmp_path / "mod.py").write_text(textwrap.dedent(SYNC_PREAMBLE + """
    @solve_window
    def step(rows_dev):
        return np.asarray(rows_dev)
    """))
    out_json = tmp_path / "report.json"
    rc = main([
        "--root", str(tmp_path), "mod.py", "--json", str(out_json),
    ])
    assert rc == 1
    import json

    payload = json.loads(out_json.read_text())
    assert payload["findings_total"] == 1
    assert payload["findings_per_rule"]["host-sync-in-window"] == 1
    assert payload["files_scanned"] == 1
    # clean run exits 0
    (tmp_path / "ok.py").write_text("x = 1\n")
    assert main(["--root", str(tmp_path), "ok.py"]) == 0
    capsys.readouterr()


def test_cli_list_rules(capsys):
    from openr_tpu.analysis.cli import main

    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in (
        "donation-hazard",
        "host-sync-in-window",
        "lock-order",
        "span-discipline",
        "retrace-risk",
        "sharding-spec",
    ):
        assert rid in out


# ---------------------------------------------------------------------
# sharding-spec
# ---------------------------------------------------------------------

SHARDING_PREAMBLE = """\
    import functools
    import jax
    from openr_tpu.analysis.annotations import resident_buffers
"""


def lint_ops(tmp_path, source, relpath="openr_tpu/ops/snippet.py"):
    """Fixture module written INSIDE the checked surface (the rule
    only fires under openr_tpu/ops/ and openr_tpu/decision/)."""
    target = tmp_path / relpath
    target.parent.mkdir(parents=True, exist_ok=True)
    target.write_text(textwrap.dedent(source))
    return run_analysis(str(tmp_path), targets=(relpath,))


def test_sharding_bare_jit_taking_resident_trips(tmp_path):
    report = lint_ops(tmp_path, SHARDING_PREAMBLE + """
    @jax.jit
    def step(dr, x):
        return dr + x

    @resident_buffers("_dr")
    class Engine:
        def churn(self, x):
            return step(self._dr, x)
    """)
    hits = rule_hits(report, "sharding-spec")
    assert len(hits) == 1
    assert "_dr" in hits[0].message


def test_sharding_declared_jit_is_clean(tmp_path):
    report = lint_ops(tmp_path, SHARDING_PREAMBLE + """
    @functools.partial(jax.jit, static_argnames=("n",))
    def plain(dr, n):
        return dr * n

    @functools.partial(
        jax.jit, in_shardings=None, out_shardings=None
    )
    def specced(dr, x):
        return dr + x

    def _impl(dr, x):
        return dr + x

    bound = jax.jit(_impl, out_shardings=None)

    @resident_buffers("_dr")
    class Engine:
        def churn(self, x):
            return specced(self._dr, x) + bound(self._dr, x)
    """)
    assert rule_hits(report, "sharding-spec") == []


def test_sharding_shard_map_body_counts_as_declared(tmp_path):
    report = lint_ops(tmp_path, SHARDING_PREAMBLE + """
    from jax import shard_map

    @functools.partial(jax.jit, static_argnames=("mesh",))
    def sharded_step(dr, mesh):
        return shard_map(lambda b: b, mesh=mesh)(dr)

    @resident_buffers("_dr")
    class Engine:
        def churn(self, mesh):
            return sharded_step(self._dr, mesh)
    """)
    assert rule_hits(report, "sharding-spec") == []


def test_sharding_outside_checked_dirs_is_clean(tmp_path):
    report = lint_ops(
        tmp_path,
        SHARDING_PREAMBLE + """
    @jax.jit
    def step(dr, x):
        return dr + x

    @resident_buffers("_dr")
    class Engine:
        def churn(self, x):
            return step(self._dr, x)
    """,
        relpath="openr_tpu/telemetry/snippet.py",
    )
    assert rule_hits(report, "sharding-spec") == []


def test_sharding_sees_through_aot_call(tmp_path):
    """Wrapping the dispatch in the AOT executable cache must not hide
    the resident flow — aot_call(tag, fn, (dyn...), {...}) is unwrapped
    to the virtual call fn(*dyn)."""
    report = lint_ops(tmp_path, SHARDING_PREAMBLE + """
    from openr_tpu.ops.aot_cache import aot_call

    @jax.jit
    def step(dr, x):
        return dr + x

    @resident_buffers("_dr")
    class Engine:
        def churn(self, x):
            return aot_call("tag", step, (self._dr, x), dict(n=4))
    """)
    hits = rule_hits(report, "sharding-spec")
    assert len(hits) == 1
    assert "_dr" in hits[0].message


def test_sharding_suppressed_with_reason(tmp_path):
    report = lint_ops(tmp_path, SHARDING_PREAMBLE + """
    @jax.jit
    def step(dr, x):
        return dr + x

    @resident_buffers("_dr")
    class Engine:
        def churn(self, x):
            # openr-lint: disable=sharding-spec -- single-chip engine
            return step(self._dr, x)
    """)
    assert rule_hits(report, "sharding-spec") == []
    assert any(
        f.rule == "sharding-spec" and f.suppressed
        for f in report.findings
    )


# ---------------------------------------------------------------------
# span-discipline: @flight_callback host-sync ban
# ---------------------------------------------------------------------

FLIGHT_PREAMBLE = """\
    import jax
    import numpy as np
    from openr_tpu.analysis.annotations import flight_callback
    from openr_tpu.telemetry import get_flight_recorder
"""


def test_flight_callback_device_get_flagged(tmp_path):
    report = lint(tmp_path, FLIGHT_PREAMBLE + """
    @flight_callback
    def on_anomaly(arr):
        evidence = jax.device_get(arr)
        get_flight_recorder().note("anomaly", rows=len(evidence))
    """)
    hits = rule_hits(report, "span-discipline")
    assert len(hits) == 1
    assert "flight_callback" in hits[0].message
    assert "never block" in hits[0].message


def test_flight_callback_block_until_ready_flagged(tmp_path):
    report = lint(tmp_path, FLIGHT_PREAMBLE + """
    @flight_callback
    def on_anomaly(arr):
        arr.block_until_ready()
        get_flight_recorder().note("anomaly", ok=True)
    """)
    hits = rule_hits(report, "span-discipline")
    assert len(hits) == 1
    assert "block_until_ready" in hits[0].message


def test_flight_callback_scalar_coercion_flagged(tmp_path):
    report = lint(tmp_path, FLIGHT_PREAMBLE + """
    @flight_callback
    def on_anomaly(count_dev):
        get_flight_recorder().note("anomaly", n=int(count_dev))
    """)
    hits = rule_hits(report, "span-discipline")
    assert len(hits) == 1
    assert "coercion" in hits[0].message


def test_flight_callback_host_work_is_clean(tmp_path):
    report = lint(tmp_path, FLIGHT_PREAMBLE + """
    @flight_callback
    def on_anomaly(rows):
        counts = np.asarray([len(r) for r in rows])
        get_flight_recorder().note(
            "anomaly", total=int(counts.sum())
        )
        get_flight_recorder().check_triggers()
    """)
    assert rule_hits(report, "span-discipline") == []


def test_undecorated_callback_not_policed(tmp_path):
    # the ban rides the decorator: plain helpers keep the normal
    # (window-scoped) host-sync rules only
    report = lint(tmp_path, FLIGHT_PREAMBLE + """
    def not_a_callback(arr):
        return jax.device_get(arr)
    """)
    assert rule_hits(report, "span-discipline") == []


def test_flight_callback_decorator_is_runtime_inert(tmp_path):
    from openr_tpu.analysis.annotations import (
        FLIGHT_CALLBACK_ATTR,
        flight_callback,
    )

    @flight_callback
    def cb(x):
        return x + 1

    assert cb(2) == 3
    assert getattr(cb, FLIGHT_CALLBACK_ATTR)


# ---------------------------------------------------------------------
# vmem-budget
# ---------------------------------------------------------------------

PALLAS_PREAMBLE = """\
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
"""


def test_vmem_budget_undeclared_trips(tmp_path):
    report = lint(tmp_path, PALLAS_PREAMBLE + """
    TILE_S = 8
    TILE_N = 128

    def _kernel(a_ref, o_ref):
        o_ref[...] = a_ref[...]

    def run(a):
        return pl.pallas_call(
            _kernel,
            out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        )(a)
    """)
    hits = rule_hits(report, "vmem-budget")
    assert len(hits) == 1
    assert "vmem_bytes" in hits[0].message


def test_vmem_budget_declared_tracking_tiles_clean(tmp_path):
    report = lint(tmp_path, PALLAS_PREAMBLE + """
    TILE_S = 8
    TILE_N = 128

    def vmem_bytes(k):
        return (TILE_S * TILE_N * k + TILE_S * TILE_N) * 4

    def _kernel(a_ref, o_ref):
        o_ref[...] = a_ref[...]

    def run(a):
        return pl.pallas_call(
            _kernel,
            out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        )(a)
    """)
    assert rule_hits(report, "vmem-budget") == []


def test_vmem_budget_untracked_tile_trips(tmp_path):
    """A tile constant the budget formula never mentions means the
    declared bound and the kernel footprint have diverged."""
    report = lint(tmp_path, PALLAS_PREAMBLE + """
    TILE_S = 8
    TILE_N = 128
    TILE_K = 256

    def vmem_bytes():
        return TILE_S * TILE_N * 4

    def _kernel(a_ref, o_ref):
        o_ref[...] = a_ref[...]

    def run(a):
        return pl.pallas_call(
            _kernel,
            out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        )(a)
    """)
    hits = rule_hits(report, "vmem-budget")
    assert len(hits) == 1
    assert "TILE_K" in hits[0].message


def test_vmem_budget_planner_constant_via_helper_clean(tmp_path):
    """Planner-style modules (no TILE_* constants) satisfy the rule
    through the transitive hop: vmem_bytes -> _pick -> _TEMP_BUDGET."""
    report = lint(tmp_path, PALLAS_PREAMBLE + """
    _TEMP_BUDGET = 1 << 20

    def _pick(n):
        return max(1, _TEMP_BUDGET // n)

    def vmem_bytes(n):
        return _pick(n) * n * 4

    def _kernel(a_ref, o_ref):
        o_ref[...] = a_ref[...]

    def run(a):
        return pl.pallas_call(
            _kernel,
            out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        )(a)
    """)
    assert rule_hits(report, "vmem-budget") == []


def test_vmem_budget_detached_declaration_trips(tmp_path):
    report = lint(tmp_path, PALLAS_PREAMBLE + """
    def vmem_bytes(n):
        return n * 4

    def _kernel(a_ref, o_ref):
        o_ref[...] = a_ref[...]

    def run(a):
        return pl.pallas_call(
            _kernel,
            out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        )(a)
    """)
    hits = rule_hits(report, "vmem-budget")
    assert len(hits) == 1
    assert "detached" in hits[0].message


def test_vmem_budget_non_pallas_module_clean(tmp_path):
    report = lint(tmp_path, """
    TILE_S = 8

    def run(a):
        return a + TILE_S
    """)
    assert rule_hits(report, "vmem-budget") == []


def test_vmem_budget_suppressed_with_reason(tmp_path):
    report = lint(tmp_path, PALLAS_PREAMBLE + """
    def _kernel(a_ref, o_ref):
        o_ref[...] = a_ref[...]

    def run(a):
        # openr-lint: disable=vmem-budget -- scratch prototype kernel
        return pl.pallas_call(
            _kernel,
            out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        )(a)
    """)
    assert rule_hits(report, "vmem-budget") == []
    assert any(
        f.rule == "vmem-budget" and f.suppressed for f in report.findings
    )


# ---------------------------------------------------------------------
# shared-state (static thread-provenance race rule)
# ---------------------------------------------------------------------

SERVICE_PY = os.path.join(REPO_ROOT, "openr_tpu", "serve", "service.py")
SOLVER_PY = os.path.join(REPO_ROOT, "openr_tpu", "ctrl", "solver.py")
REGISTRY_PY = os.path.join(REPO_ROOT, "openr_tpu", "telemetry", "registry.py")
DECISION_PY = os.path.join(REPO_ROOT, "openr_tpu", "decision", "decision.py")

TWO_ROLE_PREAMBLE = """\
    import threading
    from openr_tpu.analysis.annotations import (
        guarded_by, handoff, thread_confined,
    )
"""


def test_sharedstate_cross_role_unlocked_pair_trips(tmp_path):
    # writer thread mutates, drainer thread reads, no lock anywhere:
    # the canonical conviction, naming both inferred roles
    report = lint(tmp_path, TWO_ROLE_PREAMBLE + """
    class Pump:
        def __init__(self):
            self._count = 0
            threading.Thread(target=self._loop, name="worker").start()
            threading.Thread(target=self._drain, name="drainer").start()

        def _loop(self):
            self._count = self._count + 1

        def _drain(self):
            return self._count
    """)
    hits = rule_hits(report, "shared-state")
    assert len(hits) == 1
    assert "Pump._count" in hits[0].message
    assert "worker" in hits[0].message
    assert "drainer" in hits[0].message


def test_sharedstate_common_lock_is_clean(tmp_path):
    report = lint(tmp_path, TWO_ROLE_PREAMBLE + """
    class Pump:
        def __init__(self):
            self._mu = threading.Lock()
            self._count = 0
            threading.Thread(target=self._loop, name="worker").start()
            threading.Thread(target=self._drain, name="drainer").start()

        def _loop(self):
            with self._mu:
                self._count = self._count + 1

        def _drain(self):
            with self._mu:
                return self._count
    """)
    assert rule_hits(report, "shared-state") == []


def test_sharedstate_thread_confined_annotation_is_clean(tmp_path):
    report = lint(tmp_path, TWO_ROLE_PREAMBLE + """
    @thread_confined("worker", "_count")
    class Pump:
        def __init__(self):
            self._count = 0
            threading.Thread(target=self._loop, name="worker").start()
            threading.Thread(target=self._drain, name="drainer").start()

        def _loop(self):
            self._count = self._count + 1

        def _drain(self):
            return self._count
    """)
    assert rule_hits(report, "shared-state") == []


def test_sharedstate_guarded_by_annotation_is_clean(tmp_path):
    # the write path holds the declared lock through a with-block the
    # walker sees; the read path is a callback the declaration covers
    report = lint(tmp_path, TWO_ROLE_PREAMBLE + """
    @guarded_by("Pump._mu", "_count")
    class Pump:
        def __init__(self):
            self._mu = threading.Lock()
            self._count = 0
            threading.Thread(target=self._loop, name="worker").start()
            threading.Thread(target=self._drain, name="drainer").start()

        def _loop(self):
            with self._mu:
                self._count = self._count + 1

        def _drain(self):
            return self._count
    """)
    assert rule_hits(report, "shared-state") == []


def test_sharedstate_handoff_annotation_is_clean(tmp_path):
    report = lint(tmp_path, TWO_ROLE_PREAMBLE + """
    @handoff("_config")
    class Pump:
        def __init__(self):
            self._config = None
            threading.Thread(target=self._loop, name="worker").start()
            threading.Thread(target=self._drain, name="drainer").start()

        def _loop(self):
            self._config = {"a": 1}

        def _drain(self):
            return self._config
    """)
    assert rule_hits(report, "shared-state") == []


def test_sharedstate_suppressed_with_reason(tmp_path):
    report = lint(tmp_path, TWO_ROLE_PREAMBLE + """
    class Pump:
        def __init__(self):
            self._count = 0
            threading.Thread(target=self._loop, name="worker").start()
            threading.Thread(target=self._drain, name="drainer").start()

        def _loop(self):
            # openr-lint: disable=shared-state -- single int, GIL-atomic
            self._count = self._count + 1

        def _drain(self):
            return self._count
    """)
    assert rule_hits(report, "shared-state") == []
    assert any(
        f.rule == "shared-state" and f.suppressed and f.reason
        for f in report.findings
    )


def test_sharedstate_mutator_call_counts_as_write(tmp_path):
    report = lint(tmp_path, TWO_ROLE_PREAMBLE + """
    class Pump:
        def __init__(self):
            self._items = []
            threading.Thread(target=self._loop, name="worker").start()
            threading.Thread(target=self._drain, name="drainer").start()

        def _loop(self):
            self._items.append(1)

        def _drain(self):
            return len(self._items)
    """)
    hits = rule_hits(report, "shared-state")
    assert len(hits) == 1
    assert "Pump._items" in hits[0].message


def test_sharedstate_threadsafe_container_is_clean(tmp_path):
    # a queue.Queue-typed attribute is its own synchronization
    report = lint(tmp_path, TWO_ROLE_PREAMBLE + """
    import queue

    class Pump:
        def __init__(self):
            self._q = queue.Queue()
            threading.Thread(target=self._loop, name="worker").start()
            threading.Thread(target=self._drain, name="drainer").start()

        def _loop(self):
            self._q.put(1)

        def _drain(self):
            return self._q.get()
    """)
    assert rule_hits(report, "shared-state") == []


def test_sharedstate_single_role_is_clean(tmp_path):
    # everything on one thread: no cross-role pair, no finding
    report = lint(tmp_path, TWO_ROLE_PREAMBLE + """
    class Pump:
        def __init__(self):
            self._count = 0
            threading.Thread(target=self._loop, name="worker").start()

        def _loop(self):
            self._count = self._count + 1
            self._use()

        def _use(self):
            return self._count
    """)
    assert rule_hits(report, "shared-state") == []


# ---------------------------------------------------------------------
# shared-state: seeded mutations of the real tree (the fixed races,
# each regression named by the two roles it pairs)
# ---------------------------------------------------------------------


def _lint_mutated(tmp_path, sources, mutate_name, mutate):
    """Copy the given real files into tmp_path flat; apply ``mutate``
    to the one named ``mutate_name``."""
    for abspath in sources:
        name = os.path.basename(abspath)
        with open(abspath, "r", encoding="utf-8") as f:
            src = f.read()
        if name == mutate_name:
            mutated = mutate(src)
            assert mutated != src, "mutation did not apply — source drifted"
            src = mutated
        (tmp_path / name).write_text(src)
    return run_analysis(
        str(tmp_path),
        targets=tuple(os.path.basename(p) for p in sources),
    )


def test_seeded_service_detach_guard_deletion_trips(tmp_path):
    # delete the _cv guard around the detach-side _detached.add: the
    # ctrl-thread register path (discard) races the wave-loop-reachable
    # detach path again — the PR's original SolverService._detached race
    report = _lint_mutated(
        tmp_path,
        [SERVICE_PY, SOLVER_PY],
        "service.py",
        lambda src: src.replace(
            "        with self._cv:\n"
            "            self._detached.add(tenant_id)\n",
            "        self._detached.add(tenant_id)\n",
            1,
        ),
    )
    hits = rule_hits(report, "shared-state")
    assert any("SolverService._detached" in f.message for f in hits), [
        str(f) for f in hits
    ]
    msg = next(
        f.message for f in hits if "SolverService._detached" in f.message
    )
    assert "solver-wave-loop" in msg and "ctrl" in msg, msg


def test_seeded_service_waves_guard_deletion_trips(tmp_path):
    # delete the _cv guard around the wave counter increment: the wave
    # loop's bump races the ctrl-thread waves() read again
    report = _lint_mutated(
        tmp_path,
        [SERVICE_PY, SOLVER_PY],
        "service.py",
        lambda src: src.replace(
            "        with self._cv:\n"
            "            self._waves += len(batches)\n",
            "        self._waves += len(batches)\n",
            1,
        ),
    )
    hits = rule_hits(report, "shared-state")
    assert any("SolverService._waves" in f.message for f in hits), [
        str(f) for f in hits
    ]
    msg = next(
        f.message for f in hits if "SolverService._waves" in f.message
    )
    assert "solver-wave-loop" in msg and "ctrl" in msg, msg


REGISTRY_ROLE_HARNESS = """\
import threading

from registry import Registry


class Driver:
    def __init__(self, reg: Registry):
        self._reg = reg
        threading.Thread(target=self._loop, name="churn-loop").start()
        reg.gauge("x", self._sample)

    def _loop(self):
        self._reg.counter_bump("x")

    def _sample(self):
        return float(self._reg.counter_get("x"))
"""


def test_seeded_registry_lock_deletion_trips(tmp_path):
    # delete the counter_bump lock acquisition: every bump-from-one-
    # role / read-from-another pair on Registry._counters reopens
    (tmp_path / "harness.py").write_text(REGISTRY_ROLE_HARNESS)
    with open(REGISTRY_PY, "r", encoding="utf-8") as f:
        src = f.read()
    mutated = src.replace(
        "        with self._lock:\n"
        "            self._counters[name] = "
        "self._counters.get(name, 0) + delta\n",
        "        self._counters[name] = "
        "self._counters.get(name, 0) + delta\n",
        1,
    )
    assert mutated != src, "mutation did not apply — source drifted"
    (tmp_path / "registry.py").write_text(mutated)
    report = run_analysis(
        str(tmp_path), targets=("registry.py", "harness.py")
    )
    hits = rule_hits(report, "shared-state")
    assert any("Registry._counters" in f.message for f in hits), [
        str(f) for f in hits
    ]
    msg = next(
        f.message for f in hits if "Registry._counters" in f.message
    )
    assert "churn-loop" in msg and "registry.gauge" in msg, msg


def test_seeded_registry_unmutated_is_clean(tmp_path):
    (tmp_path / "harness.py").write_text(REGISTRY_ROLE_HARNESS)
    with open(REGISTRY_PY, "r", encoding="utf-8") as f:
        (tmp_path / "registry.py").write_text(f.read())
    report = run_analysis(
        str(tmp_path), targets=("registry.py", "harness.py")
    )
    assert rule_hits(report, "shared-state") == [], [
        str(f) for f in rule_hits(report, "shared-state")
    ]


def test_seeded_decision_emit_mu_deletion_trips(tmp_path):
    # delete the _emit_mu guard on the emit stage's staleness stamp:
    # the event-base write races the registry gauge read again
    report = _lint_mutated(
        tmp_path,
        [DECISION_PY],
        "decision.py",
        lambda src: src.replace(
            "                with self._emit_mu:\n"
            "                    self._last_good_route_ts = time.monotonic()\n",
            "                self._last_good_route_ts = time.monotonic()\n",
            1,
        ),
    )
    hits = rule_hits(report, "shared-state")
    assert any(
        "Decision._last_good_route_ts" in f.message for f in hits
    ), [str(f) for f in hits]
    msg = next(
        f.message
        for f in hits
        if "Decision._last_good_route_ts" in f.message
    )
    # the convicting pair: the event-base write in _emit_update
    # against the gauge thread's read in _route_staleness_ms
    assert "role evb" in msg and "role registry.gauge" in msg, msg


def test_seeded_decision_emit_worker_fork_trips(tmp_path):
    # fork the emit stage by mode again (worker thread when the
    # executor exists, inline otherwise): route_db then has a writer
    # under two roles and no annotation exempts it, so the fork cannot
    # come back unseen
    def mutate(src):
        src = src.replace(
            "import threading\n",
            "import threading\n"
            "from concurrent.futures import ThreadPoolExecutor\n",
            1,
        )
        src = src.replace(
            "        self._emit_mu = threading.Lock()\n",
            "        self._emit_mu = threading.Lock()\n"
            "        self._emit_pool = ThreadPoolExecutor(max_workers=1)\n",
            1,
        )
        return src.replace(
            "        self._emit_update(payload, trace, rebuild_span, "
            "perf_events)\n",
            "        if self._emit_pool is not None:\n"
            "            self._emit_pool.submit(\n"
            "                self._emit_update, payload, trace,"
            " rebuild_span, perf_events\n"
            "            )\n"
            "        else:\n"
            "            self._emit_update(payload, trace, rebuild_span,"
            " perf_events)\n",
            1,
        )

    report = _lint_mutated(tmp_path, [DECISION_PY], "decision.py", mutate)
    hits = rule_hits(report, "shared-state")
    assert any("Decision.route_db" in f.message for f in hits), [
        str(f) for f in hits
    ]
    msg = next(f.message for f in hits if "Decision.route_db" in f.message)
    assert "ex:Decision._emit_pool" in msg and "evb" in msg, msg


def test_decision_unmutated_is_clean(tmp_path):
    report = _lint_mutated(
        tmp_path,
        [DECISION_PY],
        "decision.py",
        lambda src: src + "\n# trailing comment\n",
    )
    assert rule_hits(report, "shared-state") == [], [
        str(f) for f in rule_hits(report, "shared-state")
    ]


def test_seeded_service_unmutated_is_clean(tmp_path):
    report = _lint_mutated(
        tmp_path,
        [SERVICE_PY, SOLVER_PY],
        "service.py",
        lambda src: src + "\n# trailing comment\n",
    )
    assert rule_hits(report, "shared-state") == [], [
        str(f) for f in rule_hits(report, "shared-state")
    ]


# ---------------------------------------------------------------------
# runtime racedep (barrier-scheduled: deterministic, no sleeps)
# ---------------------------------------------------------------------


def _barrier_schedule(locked, writer_role="solver-wave-loop",
                      reader_role="ctrl"):
    """Two threads, one shared attribute, a Barrier forcing the write
    to land strictly before the read: the overlap is a property of the
    schedule, never of timing, and the tracker must convict (or stay
    silent) without the race striking."""
    from openr_tpu.analysis.lockdep import set_thread_role
    from openr_tpu.analysis.racedep import RaceTracker, SharedState

    dep = LockDepTracker()
    race = RaceTracker(lockdep=dep)
    state = SharedState("SolverService", tracker=race)
    mu = TrackedLock("SolverService._cv", tracker=dep)
    gate = threading.Barrier(2)
    errs = []

    def writer():
        try:
            set_thread_role(writer_role)
            if locked:
                with mu:
                    state.waves = 1
            else:
                state.waves = 1
            gate.wait()
        except Exception as exc:  # pragma: no cover
            errs.append(exc)

    def reader():
        try:
            set_thread_role(reader_role)
            gate.wait()
            if locked:
                with mu:
                    _ = state.waves
            else:
                _ = state.waves
        except Exception as exc:  # pragma: no cover
            errs.append(exc)

    tw = threading.Thread(target=writer)
    tr = threading.Thread(target=reader)
    tw.start()
    tr.start()
    tw.join()
    tr.join()
    assert errs == []
    return race


def test_racedep_convicts_seeded_unlocked_overlap():
    race = _barrier_schedule(locked=False)
    assert len(race.violations) == 1
    v = race.violations[0]
    assert v.attr == "SolverService.waves"
    assert set(v.roles) == {"solver-wave-loop", "ctrl"}
    assert "solver-wave-loop" in str(v) and "ctrl" in str(v)


def test_racedep_silent_on_lock_guarded_twin():
    race = _barrier_schedule(locked=True)
    assert race.violations == []


def test_racedep_same_thread_never_convicts():
    from openr_tpu.analysis.racedep import RaceTracker, SharedState

    race = RaceTracker(lockdep=LockDepTracker())
    state = SharedState("X", tracker=race)
    state.a = 1
    _ = state.a
    state.a = 2
    assert race.violations == []


def test_racedep_read_read_is_clean():
    from openr_tpu.analysis.racedep import RaceTracker, SharedState

    dep = LockDepTracker()
    race = RaceTracker(lockdep=dep)
    state = SharedState("X", tracker=race)
    state.a = 1  # main-thread publish
    gate = threading.Barrier(2)

    def r1():
        gate.wait()
        _ = state.a

    def r2():
        gate.wait()
        _ = state.a

    # the initial write came from the main thread unlocked, so the
    # cross-thread reads DO convict against it — use a fresh tracker
    # to observe only the reads
    race.reset()
    t1 = threading.Thread(target=r1)
    t2 = threading.Thread(target=r2)
    t1.start()
    t2.start()
    t1.join()
    t2.join()
    assert race.violations == []


def test_racedep_mutate_counts_as_write():
    from openr_tpu.analysis.lockdep import set_thread_role
    from openr_tpu.analysis.racedep import RaceTracker, SharedState

    dep = LockDepTracker()
    race = RaceTracker(lockdep=dep)
    state = SharedState("KvStoreDb", tracker=race)
    state.pending = []
    race.reset()  # drop the main-thread publish witness
    gate = threading.Barrier(2)

    def appender():
        set_thread_role("evb")
        state.mutate("pending").append(1)
        gate.wait()

    def reader():
        set_thread_role("ex:KvStoreDb._executor")
        gate.wait()
        _ = state.pending

    t1 = threading.Thread(target=appender)
    t2 = threading.Thread(target=reader)
    t1.start()
    t2.start()
    t1.join()
    t2.join()
    assert len(race.violations) == 1
    assert race.violations[0].attr == "KvStoreDb.pending"
    assert set(race.violations[0].roles) == {
        "evb", "ex:KvStoreDb._executor",
    }


def test_racedep_raise_mode():
    from openr_tpu.analysis.racedep import (
        RaceError,
        RaceTracker,
        SharedState,
    )

    race = RaceTracker(raise_on_violation=True, lockdep=LockDepTracker())
    state = SharedState("X", tracker=race)
    gate = threading.Barrier(2)
    raised = []

    def writer():
        state.x = 1
        gate.wait()

    def reader():
        gate.wait()
        try:
            _ = state.x
        except RaceError as exc:
            raised.append(exc)

    t1 = threading.Thread(target=writer)
    t2 = threading.Thread(target=reader)
    t1.start()
    t2.start()
    t1.join()
    t2.join()
    assert len(raised) == 1


def test_racedep_convicts_once_per_attr():
    from openr_tpu.analysis.racedep import RaceTracker, SharedState

    race = RaceTracker(lockdep=LockDepTracker())
    state = SharedState("X", tracker=race)
    state.x = 1
    done = threading.Barrier(2)

    def other():
        _ = state.x
        _ = state.x
        state.x = 2
        done.wait()

    t = threading.Thread(target=other)
    t.start()
    done.wait()
    t.join()
    assert len(race.violations) == 1


def test_racedep_global_tracker_reset():
    from openr_tpu.analysis import racedep

    t1 = racedep.reset_race_tracker()
    assert racedep.get_race_tracker() is t1
    t2 = racedep.reset_race_tracker()
    assert t2 is not t1
    assert racedep.get_race_tracker() is t2


def test_lockdep_violation_carries_registered_role():
    from openr_tpu.analysis.lockdep import clear_thread_roles, set_thread_role

    dep = LockDepTracker()
    a = TrackedLock("A._x", tracker=dep)
    b = TrackedLock("B._y", tracker=dep)

    def fwd():
        set_thread_role("evb")
        with a:
            with b:
                pass

    def rev():
        set_thread_role("solver-wave-loop")
        with b:
            with a:
                pass

    t1 = threading.Thread(target=fwd)
    t1.start()
    t1.join()
    t2 = threading.Thread(target=rev)
    t2.start()
    t2.join()
    try:
        assert len(dep.violations) == 1
        v = dep.violations[0]
        assert v.witness.role == "solver-wave-loop"
        assert "role solver-wave-loop" in str(v)
    finally:
        clear_thread_roles()


def test_lockdep_unregistered_thread_falls_back_to_name():
    from openr_tpu.analysis.lockdep import clear_thread_roles, current_role

    clear_thread_roles()
    out = []

    def probe():
        out.append(current_role())

    t = threading.Thread(target=probe, name="bare-thread")
    t.start()
    t.join()
    assert out == ["bare-thread"]


# ---------------------------------------------------------------------
# suppression staleness audit
# ---------------------------------------------------------------------


def test_stale_suppression_reported_when_audited(tmp_path):
    # the directive excuses a line that no longer produces a finding
    report = lint(tmp_path, """
    def fine():
        # openr-lint: disable=shared-state -- once excused a race here
        return 1
    """)
    from openr_tpu.analysis.core import STALE_RULE

    assert rule_hits(report, STALE_RULE) == []  # audit off by default
    (tmp_path / "snippet2.py").write_text(
        (tmp_path / "snippet.py").read_text()
    )
    audited = run_analysis(
        str(tmp_path), targets=("snippet2.py",), audit_suppressions=True
    )
    hits = rule_hits(audited, STALE_RULE)
    assert len(hits) == 1
    assert "shared-state" in hits[0].message
    assert audited.exit_code == 1


def test_live_suppression_not_stale(tmp_path):
    from openr_tpu.analysis.core import STALE_RULE

    report = lint(tmp_path, TWO_ROLE_PREAMBLE + """
    class Pump:
        def __init__(self):
            self._count = 0
            threading.Thread(target=self._loop, name="worker").start()
            threading.Thread(target=self._drain, name="drainer").start()

        def _loop(self):
            # openr-lint: disable=shared-state -- single int, GIL-atomic
            self._count = self._count + 1

        def _drain(self):
            return self._count
    """)
    (tmp_path / "keep.py").write_text((tmp_path / "snippet.py").read_text())
    audited = run_analysis(
        str(tmp_path), targets=("keep.py",), audit_suppressions=True
    )
    assert rule_hits(audited, STALE_RULE) == []
    assert rule_hits(audited, "shared-state") == []


def test_stale_audit_skips_rules_that_did_not_run(tmp_path):
    # a rule-subset run cannot judge other rules' directives
    from openr_tpu.analysis.core import STALE_RULE
    from openr_tpu.analysis.rules.races import SharedStateRule

    (tmp_path / "mixed.py").write_text(textwrap.dedent("""
    def fine():
        # openr-lint: disable=donation-hazard -- other rule's business
        return 1
    """))
    audited = run_analysis(
        str(tmp_path),
        targets=("mixed.py",),
        rules=[SharedStateRule()],
        audit_suppressions=True,
    )
    assert rule_hits(audited, STALE_RULE) == []


def test_directive_inside_docstring_is_not_a_directive(tmp_path):
    from openr_tpu.analysis.core import STALE_RULE

    report = lint(tmp_path, '''
    def documented():
        """Example syntax:

            x = 1  # openr-lint: disable=shared-state -- doc example
        """
        return 1
    ''')
    (tmp_path / "doc.py").write_text((tmp_path / "snippet.py").read_text())
    audited = run_analysis(
        str(tmp_path), targets=("doc.py",), audit_suppressions=True
    )
    assert rule_hits(audited, STALE_RULE) == []


def test_live_tree_has_no_stale_suppressions():
    from openr_tpu.analysis.core import STALE_RULE

    report = run_analysis(
        REPO_ROOT, targets=("openr_tpu",), audit_suppressions=True
    )
    assert rule_hits(report, STALE_RULE) == [], "\n".join(
        str(f) for f in rule_hits(report, STALE_RULE)
    )
