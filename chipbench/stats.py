"""Percentiles and the sample rule.

``percentile`` interpolates linearly between order statistics, as
``openr_tpu/load/harness.percentiles`` does (copied). A percentile is
reported only when at least ``BEYOND`` samples lie beyond it: a 95th
percentile needs 200 samples, or it is a maximum under another name.
"""

from __future__ import annotations

import math
from typing import Sequence

BEYOND = 10


class TooFewSamples(ValueError):
    """The window produced fewer samples than the percentile needs."""


def needed(q: float) -> int:
    return 1 if q <= 0.5 else math.ceil(BEYOND / (1.0 - q))


def percentile(samples: Sequence[float], q: float) -> float:
    if len(samples) < needed(q):
        raise TooFewSamples(
            f"p{q * 100:g} needs {needed(q)} samples, the window gave "
            f"{len(samples)}"
        )
    s = sorted(samples)
    pos = q * (len(s) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def median(samples: Sequence[float]) -> float:
    return percentile(samples, 0.5)
