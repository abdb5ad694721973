"""Backend compiles of this process, counted off ``jax.monitoring`` by
the benchmark itself. A hit in the persistent cache still reports a
backend-compile event whose duration is the retrieval."""

from __future__ import annotations

import threading
from typing import List, Tuple

_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._seconds = 0.0
        self._names: List[str] = []

    def install(self) -> "CompileCounter":
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def _on_duration(self, event: str, duration_secs: float,
                     fun_name: str = "?", **_kw) -> None:
        if event == _EVENT:
            with self._lock:
                self._names.append(fun_name)
                self._seconds += duration_secs

    def read(self) -> Tuple[int, float]:
        """(compiles so far, their seconds)"""
        with self._lock:
            return len(self._names), self._seconds

    def names_since(self, count: int) -> List[str]:
        """What was compiled after the first ``count`` compiles."""
        with self._lock:
            return self._names[count:]
