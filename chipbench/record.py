"""What one run hands to the metric readers.

A served path's driver fills a ``RunRecord``; each file under
``chipbench/end_to_end/`` and ``chipbench/layer_metrics/`` reads one
number out of it. A reader that finds nothing to read returns ``None``
and its metric is left out of the line.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from chipbench import stats
from chipbench.xplane import DeviceTrace


@dataclass
class Span:
    """One span of the program's own trace, on the wall clock."""

    trace_id: int
    name: str
    ts_ms: float
    dur_ms: float
    attrs: Dict[str, Any]


@dataclass
class RunRecord:
    # end to end, on the benchmark's clock
    samples_ms: List[float] = field(default_factory=list)
    lateness_ms: List[float] = field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    # why the run is not correct; empty == correct
    problems: List[str] = field(default_factory=list)
    # set-up, by phase, in seconds (``setup_s`` is the whole)
    setup: Dict[str, float] = field(default_factory=dict)
    # the program's counters, as deltas over the window
    counters: Dict[str, float] = field(default_factory=dict)
    # the program's spans of every trace retired in the window
    spans: List[Span] = field(default_factory=list)
    # the sizes the operations-and-bytes functions need
    shapes: Dict[str, Any] = field(default_factory=dict)
    memory_peak_bytes: int = 0
    device_kind: str = ""
    # the reduced profiler trace (``--trace 1`` only), and the wall clock
    # (``time.time``) at which its steady part began
    device: Optional[DeviceTrace] = None
    steady_wall_s: float = 0.0

    def span_median(self, name: str) -> Optional[float]:
        """Median duration (ms) of the program's span ``name``."""
        durations = [s.dur_ms for s in self.spans if s.name == name]
        return stats.median(durations) if durations else None

    def steady_rebuilds(self) -> int:
        """Rebuild windows that began in the steady part of the traced
        window (the program's spans are on the wall clock)."""
        dev = self.device
        t0 = self.steady_wall_s * 1e3
        t1 = t0 + (dev.steady[1] - dev.steady[0]) / 1e6
        return sum(
            1 for s in self.spans
            if s.name == "decision.rebuild" and t0 <= s.ts_ms < t1
        )

    def counter(self, name: str) -> float:
        return float(self.counters.get(name, 0))
