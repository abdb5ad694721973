"""Telemetry spine unit tests: registry, histograms, counter shims,
tracer span model, monitor satellites, and export-surface parity."""

import gc
import glob
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from openr_tpu.telemetry import (
    CounterDict,
    Histogram,
    Registry,
    get_registry,
    get_tracer,
)
from openr_tpu.telemetry import install_gc_hook, jax_hooks
from openr_tpu.telemetry.trace import Trace, Tracer


class TestHistogram:
    def test_percentiles_over_window(self):
        h = Histogram("lat_ms", window=100)
        for v in range(1, 101):  # 1..100
            h.observe(float(v))
        s = h.stats()
        assert s["lat_ms.count"] == 100
        assert s["lat_ms.max"] == 100.0
        assert 49 <= s["lat_ms.p50"] <= 52
        assert 94 <= s["lat_ms.p95"] <= 97
        assert 98 <= s["lat_ms.p99"] <= 100
        assert s["lat_ms.avg"] == pytest.approx(50.5)

    def test_sliding_window_forgets_old_samples(self):
        h = Histogram("x", window=4)
        for v in (1000.0, 1000.0, 1000.0, 1000.0, 1.0, 1.0, 1.0, 1.0):
            h.observe(v)
        s = h.stats()
        # percentiles track the window; max/count are lifetime
        assert s["x.p99"] == 1.0
        assert s["x.max"] == 1000.0
        assert s["x.count"] == 8

    def test_empty_histogram_exports_only_count(self):
        s = Histogram("y").stats()
        assert s == {"y.count": 0}


class TestRegistry:
    def test_counter_gauge_histogram_snapshot(self):
        r = Registry()
        r.counter_bump("a.b", 3)
        r.gauge("g.now", lambda: 7.5)
        r.observe("h.ms", 2.0)
        snap = r.snapshot()
        assert snap["a.b"] == 3
        assert snap["g.now"] == 7.5
        assert snap["h.ms.count"] == 1 and snap["h.ms.p50"] == 2.0

    def test_broken_gauge_never_poisons_snapshot(self):
        r = Registry()
        r.counter_bump("ok", 1)
        r.gauge("bad", lambda: 1 / 0)
        assert r.snapshot() == {"ok": 1}

    def test_thread_safety_of_bumps(self):
        r = Registry()

        def bump():
            for _ in range(1000):
                r.counter_bump("n")

        threads = [threading.Thread(target=bump) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert r.counter_get("n") == 8000


class TestCounterDictShim:
    """The legacy SPF_COUNTERS/ELL_COUNTERS idioms must keep working
    verbatim against the registry-backed shim."""

    def test_dict_idioms(self):
        r = Registry()
        d = r.counter_dict(["decision.x", "decision.y"])
        d["decision.x"] += 2
        before = dict(d)
        assert before == {"decision.x": 2, "decision.y": 0}
        d["decision.y"] += 5
        assert d["decision.y"] - before["decision.y"] == 5
        assert sorted(d.items()) == [("decision.x", 2), ("decision.y", 5)]
        assert "decision.x" in d and len(d) == 2

    def test_prefixed_keys_export_under_full_name(self):
        r = Registry()
        d = r.counter_dict(["warm"], prefix="decision.ell_")
        d["warm"] += 1
        assert dict(d) == {"warm": 1}  # bare keys at the call site
        assert r.snapshot()["decision.ell_warm"] == 1  # dotted export

    def test_read_before_write_registers_at_zero(self):
        r = Registry()
        d = r.counter_dict()
        assert d["never.bumped"] == 0
        assert "never.bumped" in dict(d)

    def test_live_shims_share_one_registry(self):
        from openr_tpu.decision.spf_solver import (
            SPF_COUNTERS,
            get_spf_counters,
        )
        from openr_tpu.ops.spf_sparse import ELL_COUNTERS

        b_spf = SPF_COUNTERS["decision.ell_patches"]
        b_ell = ELL_COUNTERS["ell_warm_solves"]
        SPF_COUNTERS["decision.ell_patches"] += 1
        ELL_COUNTERS["ell_warm_solves"] += 1
        merged = get_spf_counters()
        snap = get_registry().snapshot()
        assert merged["decision.ell_patches"] == b_spf + 1
        assert merged["decision.ell_warm_solves"] == b_ell + 1
        # registry and the legacy merged view agree by construction
        assert snap["decision.ell_patches"] == merged["decision.ell_patches"]
        assert (
            snap["decision.ell_warm_solves"]
            == merged["decision.ell_warm_solves"]
        )


class TestTracer:
    def test_nested_spans_complete_trace(self):
        tracer = Tracer()
        t = tracer.start("kvstore.publish", key="adj:a")
        outer = t.begin_span("decision.rebuild")
        inner = t.begin_span("ops.ell_reconverge")
        t.end_span(inner, warm=True)
        t.end_span(outer)
        tracer.finish(t)
        assert t.complete and t.well_formed()
        assert [s.name for s in t.spans] == [
            "kvstore.publish",
            "decision.rebuild",
            "ops.ell_reconverge",
        ]
        assert [s.depth for s in t.spans] == [0, 0, 1]

    def test_unclosed_span_counted_and_marked_incomplete(self):
        tracer = Tracer()
        before = get_registry().counter_get(
            "telemetry.traces_unclosed_spans"
        )
        t = tracer.start()
        t.begin_span("never.closed")
        tracer.finish(t)
        assert not t.complete
        assert (
            get_registry().counter_get("telemetry.traces_unclosed_spans")
            == before + 1
        )

    def test_e2e_feeds_convergence_histogram(self):
        tracer = Tracer()
        before = get_registry().histogram("convergence.e2e_ms").count
        t = tracer.start()
        s = t.begin_span("fib.program")
        time.sleep(0.002)
        t.end_span(s)
        tracer.finish(t)
        assert t.e2e_ms >= 2.0
        assert (
            get_registry().histogram("convergence.e2e_ms").count
            == before + 1
        )

    def test_thread_local_activation(self):
        tracer = Tracer()
        t = tracer.start()
        assert tracer.active() is None
        tracer.activate(t)
        span = tracer.span_active("deep.work")
        tracer.end_span_active(span, hits=3)
        tracer.deactivate()
        assert tracer.active() is None
        assert span.closed and span.attrs["hits"] == 3
        # and from another thread: no active trace, clean no-op
        seen = {}

        def probe():
            seen["span"] = tracer.span_active("other")

        th = threading.Thread(target=probe)
        th.start()
        th.join()
        assert seen["span"] is None

    def test_exports(self):
        tracer = Tracer(ring=4)
        for i in range(6):
            t = tracer.start("kvstore.publish", i=i)
            s = t.begin_span("fib.program")
            t.end_span(s)
            tracer.finish(t)
        assert len(tracer.traces()) == 4  # bounded ring
        doc = tracer.chrome_trace()
        assert doc["traceEvents"] and all(
            e["ph"] == "X" for e in doc["traceEvents"]
        )
        lines = tracer.jsonl(limit=2).splitlines()
        assert len(lines) == 2
        parsed = json.loads(lines[-1])
        assert parsed["complete"] and parsed["spans"]


class TestScopedSpans:
    def test_scoped_span_nests_on_the_active_or_an_explicit_trace(self):
        tracer = Tracer()
        t = tracer.start("kvstore.publish")
        outer = t.begin_span("decision.rebuild")
        tracer.activate(t)
        with tracer.span("decision.route_build", full=True) as build:
            with tracer.span("graph.view_sync", formulation="dense") as sync:
                sync.attrs["rows"] = 3
            deep = tracer.span_active("ops.ell_reconverge")
            tracer.end_span_active(deep)
        tracer.deactivate()
        # no active trace on this thread any more: an explicit one
        with tracer.span("decision.route_diff", trace=t) as diff:
            pass
        t.end_span(outer)
        tracer.finish(t)
        assert t.complete and t.well_formed()
        assert [(s.name, s.depth) for s in t.spans] == [
            ("kvstore.publish", 0),
            ("decision.rebuild", 0),
            ("decision.route_build", 1),
            ("graph.view_sync", 2),
            ("ops.ell_reconverge", 2),
            ("decision.route_diff", 1),
        ]
        assert build.attrs == {"full": True}
        assert sync.attrs == {"formulation": "dense", "rows": 3}
        assert all(s.closed for s in (build, sync, diff))
        assert sync.dur_ms <= build.dur_ms <= outer.dur_ms

    def test_scoped_span_closes_on_exception(self):
        tracer = Tracer()
        t = tracer.start()
        with pytest.raises(RuntimeError):
            with tracer.span("decision.route_build", trace=t) as span:
                with tracer.span("ops.solve_readback", trace=t) as inner:
                    raise RuntimeError("device fell over")
        assert span.closed and inner.closed and not t._stack
        tracer.finish(t)
        assert t.complete and t.well_formed()

    def test_scoped_span_is_a_noop_without_a_trace(self):
        tracer = Tracer()
        ran = []
        with tracer.span("decision.route_build", full=True) as span:
            ran.append(span)
        assert ran == [None]
        # and from a thread other than the one that activated a trace
        t = tracer.start()
        tracer.activate(t)
        seen = []

        def probe():
            with tracer.span("graph.view_sync") as s:
                seen.append(s)

        th = threading.Thread(target=probe)
        th.start()
        th.join(timeout=10)
        tracer.deactivate()
        assert seen == [None]
        assert [s.name for s in t.spans] == ["kvstore.publish"]

    def test_gap_span_runs_from_the_last_close_to_now(self):
        tracer = Tracer()
        t = tracer.start("kvstore.publish")
        time.sleep(0.003)
        wait = t.gap_span("decision.queue_wait")
        publish = t.spans[0]
        assert wait.closed and wait.depth == 0 and wait.dur_ms >= 3.0
        assert wait.ts_ms == publish.ts_ms  # an instant ends where it starts
        work = t.begin_span("decision.emit")
        time.sleep(0.002)
        t.end_span(work)
        handed_off = time.perf_counter()
        time.sleep(0.003)
        hop = t.gap_span("fib.queue_wait", reader="fib")
        assert hop.closed and hop.attrs == {"reader": "fib"}
        assert hop.dur_ms >= 3.0
        # it starts where decision.emit ended, on both clocks
        assert hop.ts_ms == pytest.approx(work.ts_ms + work.dur_ms)
        assert hop._t0 <= handed_off
        tracer.finish(t)
        assert t.complete and t.well_formed()
        assert [s.name for s in t.spans] == [
            "kvstore.publish", "decision.queue_wait", "decision.emit",
            "fib.queue_wait",
        ]
        # nothing has closed yet: nothing to measure from
        assert Trace("kvstore.publish").gap_span("decision.queue_wait") is None

    def test_tracing_never_imports_jax(self):
        """ctrl clients and breeze use this module without jax; a scoped
        span annotates only from a jax the process already has."""
        code = (
            "import sys\n"
            "from openr_tpu.telemetry import get_tracer\n"
            "tracer = get_tracer()\n"
            "t = tracer.start()\n"
            "with tracer.span('decision.route_build', trace=t) as s:\n"
            "    pass\n"
            "assert s.closed\n"
            "assert not [m for m in sys.modules if m.split('.')[0] == 'jax']\n"
        )
        subprocess.run([sys.executable, "-c", code], check=True, timeout=120)

    def test_profiler_session_carries_scoped_spans(self, tmp_path):
        """Inside a ``jax.profiler`` session a scoped span is also an
        event of the session's host plane, on the session's clock; a
        ``begin_span`` / ``end_span`` pair is not."""
        import jax
        from jax.profiler import ProfileData

        tracer = Tracer()
        t = tracer.start()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            plain = t.begin_span("decision.rebuild")
            with tracer.span("decision.route_build", trace=t) as scoped:
                time.sleep(0.005)
            t.end_span(plain)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(
            os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")
        )
        host = [
            ev
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines
            for ev in line.events
        ]
        mine = [ev for ev in host if ev.name == "decision.route_build"]
        assert len(mine) == 1
        assert mine[0].duration_ns / 1e6 == pytest.approx(
            scoped.dur_ms, abs=1.0
        )
        assert not [ev for ev in host if ev.name == "decision.rebuild"]


class TestGcHook:
    def test_counts_full_collections_only(self):
        install_gc_hook()
        install_gc_hook()  # idempotent
        hooks = [cb for cb in gc.callbacks
                 if type(cb).__name__ == "_Gen2Pauses"]
        assert len(hooks) == 1
        reg = get_registry()

        def read():
            return (
                reg.counter_get("process.gc_gen2_collections"),
                reg.counter_get("process.gc_gen2_pause_ms"),
            )

        n0, ms0 = read()
        gc.collect(0)
        gc.collect(1)
        assert read() == (n0, ms0)
        gc.collect(2)
        n1, ms1 = read()
        assert n1 == n0 + 1 and ms1 > ms0
        gc.collect()  # a full collection by its default argument
        assert read()[0] == n0 + 2
        assert "process.gc_gen2_pause_ms" in reg.snapshot()


class TestMonitorSatellites:
    def test_rss_current_vs_peak(self):
        from openr_tpu.monitor.monitor import SystemMetrics

        cur = SystemMetrics.rss_bytes()
        peak = SystemMetrics.rss_peak_bytes()
        assert cur > 0 and peak > 0
        # current RSS can never exceed the kernel-tracked peak
        # (small slack: statm and rusage sample at different instants)
        assert cur <= peak * 1.1

    def test_rss_falls_back_to_peak_when_statm_unreadable(
        self, monkeypatch
    ):
        from openr_tpu.monitor import monitor as monitor_mod

        real_open = open

        def failing_open(path, *a, **kw):
            if path == "/proc/self/statm":
                raise OSError("no procfs")
            return real_open(path, *a, **kw)

        monkeypatch.setattr("builtins.open", failing_open)
        assert (
            monitor_mod.SystemMetrics.rss_bytes()
            == monitor_mod.SystemMetrics.rss_peak_bytes()
        )

    def test_backend_errors_counted_not_swallowed(self):
        from openr_tpu.messaging.queue import ReplicateQueue
        from openr_tpu.monitor.monitor import Monitor

        q = ReplicateQueue(name="logs")
        mon = Monitor(
            "n1", q, backend=lambda s: (_ for _ in ()).throw(RuntimeError)
        )
        mon.start()
        try:
            before = get_registry().counter_get("monitor.backend_errors")
            from openr_tpu.monitor.monitor import push_log_sample

            push_log_sample(q, event="BOOM")
            deadline = time.time() + 5
            while time.time() < deadline:
                if mon.num_processed >= 1:
                    break
                time.sleep(0.01)
            assert mon.num_processed == 1  # drain loop survived
            assert (
                get_registry().counter_get("monitor.backend_errors")
                == before + 1
            )
            counters = mon.get_counters()
            assert counters["monitor.backend_errors"] == before + 1
            assert "process.rss_peak_bytes" in counters
        finally:
            mon.stop()


class TestExportSurfaceParity:
    def test_ctrl_and_monitor_serve_registry_names(self):
        """OpenrCtrl.get_counters == the registry snapshot (plus module
        counters): SPF/ELL names, histogram percentiles, trace health
        all present through both surfaces."""
        from openr_tpu.ctrl.handler import OpenrCtrlHandler
        from openr_tpu.decision.spf_solver import SPF_COUNTERS

        SPF_COUNTERS["decision.ell_patches"] += 1
        get_registry().observe("convergence.e2e_ms", 1.0)
        handler = OpenrCtrlHandler("n1")
        out = handler.get_counters()
        snap = get_registry().snapshot()
        for key in (
            "decision.ell_patches",
            "decision.ell_warm_solves",
            "convergence.e2e_ms.p99",
            "telemetry.traces_finished",
        ):
            assert out[key] == snap[key]

    def test_breeze_monitor_counters_matches_ctrl(self, capsys):
        from openr_tpu.cli.breeze import Breeze, _InProcessClient
        from openr_tpu.ctrl.handler import OpenrCtrlHandler

        handler = OpenrCtrlHandler("n1")
        breeze = Breeze(_InProcessClient(handler))
        breeze.monitor_counters()
        rendered = capsys.readouterr().out
        for key, value in handler.get_counters().items():
            if key.startswith(("decision.ell_", "telemetry.")):
                assert key in rendered

    def test_breeze_monitor_traces_renders_ring(self, capsys):
        from openr_tpu.cli.breeze import Breeze, _InProcessClient
        from openr_tpu.ctrl.handler import OpenrCtrlHandler

        tracer = get_tracer()
        t = tracer.start("kvstore.publish")
        s = t.begin_span("fib.program")
        t.end_span(s)
        tracer.finish(t)
        handler = OpenrCtrlHandler("n1")
        breeze = Breeze(_InProcessClient(handler))
        breeze.monitor_traces(limit=5)
        out = capsys.readouterr().out
        assert "fib.program" in out
        breeze.monitor_traces(limit=5, fmt="chrome")
        doc = json.loads(capsys.readouterr().out)
        assert doc["traceEvents"]

    def test_breeze_monitor_flight_renders_ring_and_attribution(
        self, capsys, tmp_path
    ):
        from openr_tpu.cli.breeze import Breeze, _InProcessClient
        from openr_tpu.ctrl.handler import OpenrCtrlHandler
        from openr_tpu.telemetry import (
            get_flight_recorder,
            reset_flight_recorder,
            reset_profiler,
        )

        reset_flight_recorder(
            dump_dir=str(tmp_path / "flight"), min_dump_interval_s=0.0
        )
        prof = reset_profiler(sample_every=1)
        try:
            prof.on_dispatch("t_breeze_stage", None, 1.5)
            get_flight_recorder().note("engine", path="cold_build")
            handler = OpenrCtrlHandler("n1")
            breeze = Breeze(_InProcessClient(handler))
            breeze.monitor_flight(limit=5)
            out = capsys.readouterr().out
            assert "cold_build" in out
            assert "t_breeze_stage" in out
            breeze.monitor_flight(limit=5, fmt="json")
            doc = json.loads(capsys.readouterr().out)
            assert doc["records"] and "t_breeze_stage" in doc["attribution"]
            breeze.monitor_flight(dump=True)
            out = capsys.readouterr().out
            assert "postmortem-manual-" in out
        finally:
            reset_profiler()

    def test_solver_handler_flight_surface_matches_ctrl(self, tmp_path):
        # the solver process serves the same flight surface so breeze
        # monitor flight works against it too; neither method touches
        # self, so exercise them without a full SolverService
        from openr_tpu.ctrl.handler import OpenrCtrlHandler
        from openr_tpu.ctrl.solver import SolverCtrlHandler
        from openr_tpu.telemetry import reset_flight_recorder

        reset_flight_recorder(
            dump_dir=str(tmp_path / "flight"), min_dump_interval_s=0.0
        )
        a = OpenrCtrlHandler("n1").get_flight_record()
        b = SolverCtrlHandler.get_flight_record(None)
        assert set(a) == set(b) == {
            "records", "triggers", "attribution", "host_overhead_ratio",
        }


class TestJaxHooks:
    def test_install_idempotent(self):
        assert jax_hooks.install()
        assert jax_hooks.install()
        assert get_registry().counter_get("jax.hooks_installed") == 1

    @pytest.mark.slow
    def test_compile_event_counted(self):
        import jax
        import jax.numpy as jnp

        jax_hooks.install()
        before = get_registry().counter_get("jax.compile_count")

        @jax.jit
        def f(x):
            return x * 2 + 1

        f(jnp.arange(7)).block_until_ready()
        assert get_registry().counter_get("jax.compile_count") > before


class TestConcurrentPercentiles:
    """The serve plane reads ``Registry.percentile`` between waves and
    the flight triggers read ``histogram_if_exists(...).percentile``
    per retired window — both race live ``observe`` streams from
    dispatch threads. The sliding-window ring must stay readable (no
    exceptions, values inside the observed range) under that churn."""

    def test_histogram_observe_vs_percentile_race(self):
        h = Histogram("race_ms", window=128)
        stop = threading.Event()
        errors = []

        def writer():
            v = 0
            while not stop.is_set():
                h.observe(float(v % 1000))
                v += 1

        def reader():
            while not stop.is_set():
                for q in (0.5, 0.95, 0.99):
                    p = h.percentile(q)
                    if not (0.0 <= p <= 999.0):
                        errors.append((q, p))
                s = h.stats()
                if s["race_ms.count"] and not (
                    0.0 <= s["race_ms.p50"] <= 999.0
                ):
                    errors.append(("stats", s["race_ms.p50"]))

        threads = [threading.Thread(target=writer) for _ in range(4)] + [
            threading.Thread(target=reader) for _ in range(3)
        ]
        for t in threads:
            t.start()
        time.sleep(0.2)
        stop.set()
        for t in threads:
            t.join()
        assert not errors
        assert h.count >= 128

    def test_registry_percentile_vs_observe_and_snapshot_race(self):
        r = Registry()
        stop = threading.Event()
        errors = []

        def writer(k):
            v = 0
            while not stop.is_set():
                r.observe(f"lat.{k}", float(v % 100))
                v += 1

        def reader():
            while not stop.is_set():
                p = r.percentile("lat.0", 0.99)
                if not (0.0 <= p <= 99.0):
                    errors.append(p)
                r.snapshot()
                if r.histogram_if_exists("lat.never") is not None:
                    errors.append("materialized lat.never")

        threads = [
            threading.Thread(target=writer, args=(k,)) for k in range(3)
        ] + [threading.Thread(target=reader) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.2)
        stop.set()
        for t in threads:
            t.join()
        assert not errors
        # readers never created histograms the writers did not observe
        assert set(r.histograms()) == {"lat.0", "lat.1", "lat.2"}

    def test_histogram_if_exists_returns_live_histogram(self):
        r = Registry()
        assert r.histogram_if_exists("x.ms") is None
        r.observe("x.ms", 3.0)
        h = r.histogram_if_exists("x.ms")
        assert h is not None and h.percentile(0.5) == 3.0


class TestProfiler:
    """Device-time attribution plane (telemetry/profiler.py)."""

    def _fresh(self, **kw):
        from openr_tpu.telemetry import reset_profiler

        return reset_profiler(**kw)

    def teardown_method(self):
        from openr_tpu.telemetry import reset_profiler

        reset_profiler()

    def test_sampling_cadence_and_histograms(self):
        """Outside any accounting window nothing was deferred behind
        the dispatch: a sampled call blocks for its result and the
        sample lands at once."""
        reg = get_registry()
        prof = self._fresh(sample_every=4)
        h0 = reg.histogram_if_exists("ops.host_ms.t_stage")
        host0 = h0.count if h0 else 0
        d0 = reg.histogram_if_exists("ops.device_ms.t_stage")
        dev0 = d0.count if d0 else 0
        for _ in range(8):
            prof.on_dispatch("t_stage", None, 0.5)
        h = reg.histogram_if_exists("ops.host_ms.t_stage")
        d = reg.histogram_if_exists("ops.device_ms.t_stage")
        assert h.count - host0 == 8  # every call carries host time
        assert d.count - dev0 == 2  # calls 1 and 5 sampled

    def test_a_sample_inside_a_window_is_a_mark_the_next_reap_closes(
        self, monkeypatch
    ):
        """Inside a window the profiler waits for nothing: a sampled
        dispatch leaves a mark, the window's next ``reap_read`` closes
        it as dispatch start -> end of that read, and a mark whose
        output is not ready then waits for the read after."""
        import jax
        import jax.numpy as jnp

        from openr_tpu.ops import dispatch_accounting as da
        from openr_tpu.ops.aot_cache import aot_call

        reg = get_registry()
        self._fresh(sample_every=1)
        waits = []
        real = jax.block_until_ready
        monkeypatch.setattr(
            jax, "block_until_ready", lambda x: waits.append(x) or real(x)
        )

        def dev(tag):
            h = reg.histogram_if_exists(f"ops.device_ms.{tag}")
            return h.count if h else 0

        samples = reg.counter_get("ops.profile_samples")
        fn = jax.jit(lambda x: x + 1)
        with da.event_window("t_mark_win") as win:
            out = aot_call("t_mark", fn, (jnp.arange(4),), {})
            assert [m[0] for m in win.marks] == ["t_mark"]
            assert dev("t_mark") == 0 and not waits
            real(out)
            da.reap_read(out)
            assert not win.marks and dev("t_mark") == 1
            # a batch dispatched behind the one being read
            aot_call("t_mark", fn, (jnp.arange(4),), {})
            (mark,) = win.marks

            class NotYet:
                def is_ready(self):
                    return False

            win.marks[0] = mark[:3] + (NotYet(),)
            da.reap_read(out)
            assert len(win.marks) == 1 and dev("t_mark") == 1
            win.marks[0] = mark
            da.reap_read(out)
            assert not win.marks and dev("t_mark") == 2
        assert not waits
        assert reg.counter_get("ops.profile_samples") - samples == 2
        h = reg.histogram_if_exists("ops.device_ms.t_mark")
        assert h.percentile(0.5) > 0.0

    def test_a_mark_no_read_closes_is_dropped_with_its_window(self):
        import jax
        import jax.numpy as jnp

        from openr_tpu.ops import dispatch_accounting as da
        from openr_tpu.ops.aot_cache import aot_call

        reg = get_registry()
        self._fresh(sample_every=1)
        samples = reg.counter_get("ops.profile_samples")
        fn = jax.jit(lambda x: x * 2)
        with da.event_window("t_drop_win") as win:
            aot_call("t_drop", fn, (jnp.arange(4),), {})
            assert len(win.marks) == 1
        with da.event_window("t_drop_win") as win:
            assert not win.marks
            da.reap_read(jnp.arange(2))
        assert reg.histogram_if_exists("ops.device_ms.t_drop") is None
        assert reg.histogram_if_exists("ops.host_ms.t_drop").count == 1
        assert reg.counter_get("ops.profile_samples") == samples

    def test_a_dispatch_nobody_reads_is_never_sampled(self, monkeypatch):
        """``unread`` (the KSP2 engine's deferred matrix solve): host
        time on every call, no mark, no wait, in a window or out."""
        import jax
        import jax.numpy as jnp

        from openr_tpu.ops import dispatch_accounting as da
        from openr_tpu.ops.aot_cache import aot_call

        reg = get_registry()
        self._fresh(sample_every=1)
        waits = []
        real = jax.block_until_ready
        monkeypatch.setattr(
            jax, "block_until_ready", lambda x: waits.append(x) or real(x)
        )
        fn = jax.jit(lambda x: x - 1)
        aot_call("t_unread", fn, (jnp.arange(4),), {}, unread=True)
        with da.event_window("t_unread_win") as win:
            aot_call("t_unread", fn, (jnp.arange(4),), {}, unread=True)
            assert not win.marks
            assert "t_unread" in win.stages
        assert not waits
        assert reg.histogram_if_exists("ops.host_ms.t_unread").count == 2
        assert reg.histogram_if_exists("ops.device_ms.t_unread") is None

    def test_labels_land_sampled_device_time_per_dimension(self):
        reg = get_registry()
        prof = self._fresh(sample_every=1)
        with prof.labels(bucket="8x128x4", slo="Premium!"):
            prof.on_dispatch("t_lbl", None, 1.0)
        assert reg.histogram_if_exists(
            "ops.device_ms.by_bucket.8x128x4"
        ) is not None
        # label values sanitized to fb303-safe strings
        assert reg.histogram_if_exists(
            "ops.device_ms.by_slo.premium"
        ) is not None

    def test_attribution_excludes_label_histograms(self):
        prof = self._fresh(sample_every=1)
        with prof.labels(bucket="b1"):
            prof.on_dispatch("t_attr", None, 2.0)
        attr = prof.attribution()
        assert "t_attr" in attr
        row = attr["t_attr"]
        assert row["calls"] >= 1 and row["device_samples"] >= 1
        assert not any(tag.startswith("by_") for tag in attr)

    def test_host_overhead_ratio_from_window_pairs(self):
        prof = self._fresh()
        prof.on_window("w", 10.0, 5.0)
        prof.on_window("w", 30.0, 15.0)
        assert prof.host_overhead_ratio() == 2.0

    def test_disabled_profiler_observes_nothing(self):
        reg = get_registry()
        prof = self._fresh(enabled=False)
        prof.on_dispatch("t_off", None, 1.0)
        prof.on_window("t_off", 10.0, 5.0)
        assert reg.histogram_if_exists("ops.host_ms.t_off") is None
        assert prof.host_overhead_ratio() == 0.0

    def test_profiled_aot_call_feeds_window_stage_table(self):
        import jax
        import jax.numpy as jnp

        from openr_tpu.ops import dispatch_accounting as da
        from openr_tpu.ops.aot_cache import aot_call

        self._fresh(sample_every=1)
        fn = jax.jit(lambda x: x + 1)
        with da.event_window("t_prof_win") as win:
            aot_call("t_prof_stage", fn, (jnp.arange(4),), {})
        assert "t_prof_stage" in win.stages
        calls, host_ms, device_ms = win.stages["t_prof_stage"]
        assert calls == 1 and host_ms > 0.0 and device_ms > 0.0
        assert win.device_ms >= device_ms
