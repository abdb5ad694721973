"""JAX runtime hooks: surface jit compiles as registry metrics.

The persistent compile cache (PR 1) makes first-dispatch latency
bimodal: a cache hit costs a disk read, a miss costs a full XLA
compile (seconds at 10k shapes). Without a counter, a cache
regression reads as an unexplained latency cliff in the churn bench.
These listeners map ``jax.monitoring`` backend-compile events to:

- ``jax.compile_count``          — number of backend compiles
- ``jax.compile_ms`` histogram   — per-compile wall time distribution
- ``jax.events.<suffix>``        — count per distinct monitoring event

"""

from __future__ import annotations

import threading

from openr_tpu.telemetry.registry import get_registry

_INSTALL_LOCK = threading.Lock()
_installed = False

# jax.monitoring event keys are paths like "/jax/core/compile" —
# anything mentioning compile/lower/trace on the duration channel is a
# stage of program building worth a histogram sample.
_COMPILE_MARKERS = ("compile", "lowering", "tracing", "jaxpr")


def _suffix(event: str) -> str:
    return event.strip("/").replace("/", ".")


def _on_event(event: str, **_kw) -> None:
    get_registry().counter_bump("jax.events." + _suffix(event))


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    reg = get_registry()
    low = event.lower()
    if any(m in low for m in _COMPILE_MARKERS):
        reg.counter_bump("jax.compile_count")
        reg.observe("jax.compile_ms", duration_secs * 1000.0)
    reg.observe("jax.duration_ms." + _suffix(event), duration_secs * 1000.0)


def install() -> bool:
    """Register the listeners once per process; returns True once the
    hooks are live."""
    global _installed
    with _INSTALL_LOCK:
        if _installed:
            return True
        from jax import monitoring

        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True
        get_registry().counter_set("jax.hooks_installed", 1)
        return True
