"""The traced part of a ``--trace 1`` run.

A helper thread opens a ``jax.profiler`` session for the last seconds of
the window and marks the steady part with ``TraceAnnotation`` (so the
reduction finds it on the trace's own clock). The session closes when
the window does; in a cell whose traffic never reaches the device it
stays open, under a second marker, until the driver says the closing
probe has drained. The thread that sends events never waits for the
profiler.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from chipbench import xplane


class TracedTail:
    def __init__(self, trace_dir: str, start_at: float, steady_until: float,
                 probe: bool = False):
        """Times are on ``time.monotonic``."""
        self.trace_dir = trace_dir
        self._start_at = start_at
        self._steady_until = steady_until
        self._probe = probe
        self._probe_done = threading.Event()
        self._error: Optional[BaseException] = None
        # wall clock (``time.time``) at which the steady marker opened:
        # what puts the program's spans on the trace's clock
        self.steady_wall_s = 0.0
        self._thread = threading.Thread(
            target=self._run, name="chipbench-profiler", daemon=True
        )
        self._thread.start()

    def _run(self) -> None:
        import jax

        try:
            time.sleep(max(0.0, self._start_at - time.monotonic()))
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(
                self.trace_dir, profiler_options=options
            )
            try:
                self.steady_wall_s = time.time()
                with jax.profiler.TraceAnnotation(xplane.STEADY):
                    time.sleep(
                        max(0.0, self._steady_until - time.monotonic())
                    )
                if self._probe:
                    with jax.profiler.TraceAnnotation(xplane.PROBE):
                        self._probe_done.wait(timeout=180.0)
            finally:
                jax.profiler.stop_trace()
        except BaseException as exc:  # noqa: BLE001 - re-raised by finish()
            self._error = exc

    def finish(self) -> "xplane.DeviceTrace":
        """The window, and the probe if any, have drained: wait for the
        session to close and reduce it."""
        self._probe_done.set()
        self._thread.join(timeout=300.0)
        if self._thread.is_alive():
            raise RuntimeError("the profiler session did not close")
        if self._error is not None:
            raise self._error
        return xplane.reduce(xplane.newest_xplane(self.trace_dir))
