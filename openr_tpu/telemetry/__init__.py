"""Process-wide observability spine: counters, gauges, latency
histograms, and end-to-end trace spans.

Three pieces, one export surface:

- ``registry.py``: a thread-safe fb303-style metric registry. Modules
  register dotted-name counters/gauges/histograms; ``snapshot()``
  flattens everything (histograms expand to ``.p50/.p95/.p99/.max/
  .avg/.sum/.count``) into the dict served by ``OpenrCtrl.get_counters``
  and ``breeze monitor counters``.
- ``trace.py``: structured spans over the PerfEvents chain. A trace is
  born at KvStore publication, rides the Publication/RouteUpdate
  objects through Decision and Fib, and lands in a bounded ring
  exportable as Chrome-trace JSON or JSONL.
- ``jax_hooks.py``: jax.monitoring listeners mapping jit compiles to
  ``jax.compile_count`` / ``jax.compile_ms`` so compile-cache
  regressions show up as counters, not silent latency cliffs.
- ``profiler.py``: always-on device-time attribution — measured
  ``ops.device_ms.<tag>`` / ``ops.host_ms.<tag>`` per dispatch tag and
  a live ``ops.host_overhead_ratio`` gauge.
- ``gc_pauses.py``: generation-2 garbage collections as the counters
  ``process.gc_gen2_collections`` / ``process.gc_gen2_pause_ms``.
- ``flight.py``: the flight recorder — a lock-cheap activity ring that
  survives trace-ring overflow, with anomaly triggers that freeze it
  and dump post-mortem bundles.
"""

from openr_tpu.telemetry.registry import (  # noqa: F401
    CounterDict,
    Histogram,
    Registry,
    get_registry,
)
from openr_tpu.telemetry.trace import (  # noqa: F401
    Span,
    Trace,
    Tracer,
    get_tracer,
)
from openr_tpu.telemetry.gc_pauses import (  # noqa: F401
    install_gc_hook,
    settle_heap,
)
from openr_tpu.telemetry.profiler import (  # noqa: F401
    Profiler,
    get_profiler,
    reset_profiler,
)
from openr_tpu.telemetry.flight import (  # noqa: F401
    BUNDLE_SCHEMA,
    CompileAfterWarmupTrigger,
    CounterDeltaTrigger,
    FlightRecorder,
    P99BreachTrigger,
    fnv1a,
    get_flight_recorder,
    install_default_triggers,
    load_bundle,
    reset_flight_recorder,
)

__all__ = [
    "BUNDLE_SCHEMA",
    "CompileAfterWarmupTrigger",
    "CounterDeltaTrigger",
    "CounterDict",
    "FlightRecorder",
    "Histogram",
    "P99BreachTrigger",
    "Profiler",
    "Registry",
    "Span",
    "Trace",
    "Tracer",
    "fnv1a",
    "get_flight_recorder",
    "get_profiler",
    "get_registry",
    "get_tracer",
    "install_default_triggers",
    "install_gc_hook",
    "load_bundle",
    "reset_flight_recorder",
    "reset_profiler",
    "settle_heap",
]
