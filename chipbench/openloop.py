"""The open-loop clock: events go out when they are due, not when the
system is ready for them. One thread, which sleeps and sends."""

from __future__ import annotations

import time
from typing import Callable, List, Sequence, Tuple


def run(
    offsets: Sequence[float],
    send: Callable[[int], None],
    seconds: float,
    clock: Callable[[], float] = time.monotonic,
) -> Tuple[List[float], List[float]]:
    """Send event ``i`` at ``t0 + offsets[i]`` and stop at ``t0 +
    seconds``: an event still unsent then is due but never published.

    Returns each sent event's due time and how late it left (seconds,
    on ``clock``). ``send`` may block; the events behind it
    then leave late, and their samples are timed from when they were
    due, so a stall charges everything it delayed."""
    t0 = clock() + 0.05
    end = t0 + seconds
    due: List[float] = []
    late: List[float] = []
    for i, off in enumerate(offsets):
        at = t0 + off
        if at >= end:
            break
        wait = at - clock()
        if wait > 0:
            time.sleep(wait)
        now = clock()
        send(i)
        due.append(at)
        late.append(now - at)
    wait = end - clock()
    if wait > 0:
        time.sleep(wait)
    return due, late
