"""What a debounce window waited for, and the slowest decile's account.

Two readers of the program's spans for the metrics under
``conv_p95_ms``, which every other per-layer metric leaves without a
layer: they are medians.

``window_terms`` reads the attributes with which the program closes
``decision.debounce`` (``policy_ms``, ``busy_ms``, ``slack_ms``,
``timer_late_ms``): one value per window that has the attribute,
nothing from a program that does not write it.

``tail`` ranks the window's traces by their own extent
(``kvstore.publish`` to the end of their last span: the program's spans
only, never the benchmark's samples), takes the slowest tenth, and for
each stage gives the decile's median minus the median over all traces.
The stages tile a trace:

- ingest: ``kvstore.publish`` -> start of ``decision.debounce``;
- debounce: that span;
- rebuild: its end -> end of the last ``decision.rebuild``;
- fib: from there -> end of the trace's last span (``decision.emit``,
  ``fib.queue_wait``, ``fib.program``).

The decile's median sits at the 95th percentile of the traces, so the
four excesses are the layers of ``conv_p95_ms`` - ``conv_p50_ms``, less
what the spans cannot hold (the generator's lateness, the benchmark's
own ends). Under 200 traces there is no 95th percentile (``stats``'
rule) and no tail.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

from chipbench import spantree, stats

STAGES = ("ingest", "debounce", "rebuild", "fib")


def window_terms(record, attr: str) -> List[float]:
    return [
        float(s.attrs[attr]) for s in record.spans
        if s.name == "decision.debounce" and attr in s.attrs
    ]


def overrun_share(slacks) -> Optional[float]:
    """% of ``slacks`` (``slack_ms`` of some windows) below 0: windows
    whose work outlasted the policy wait. None of none."""
    if not slacks:
        return None
    return 100.0 * sum(1 for x in slacks if x < 0) / len(slacks)


@dataclass
class Tail:
    traces: int
    decile: int
    # per stage: the decile's median minus the median over all traces
    excess_ms: Dict[str, float]
    # % of the decile's windows whose work outlasted the policy wait;
    # None from a program whose windows do not say
    overrun_share: Optional[float]


def _stages(spans) -> Optional[dict]:
    """One trace's stages in ms; None unless it has every boundary and
    reached ``fib.program``."""
    born = debounce = rebuilt = None
    programmed, end = False, 0.0
    for s in spans:
        end = max(end, s.ts_ms + s.dur_ms)
        if s.name == "kvstore.publish":
            born = s.ts_ms
        elif s.name == "decision.debounce" and debounce is None:
            debounce = s
        elif s.name == "decision.rebuild":
            rebuilt = max(rebuilt or 0.0, s.ts_ms + s.dur_ms)
        elif s.name == "fib.program":
            programmed = True
    if born is None or debounce is None or rebuilt is None or not programmed:
        return None
    waited = debounce.ts_ms + debounce.dur_ms
    return {
        "extent": end - born,
        "ingest": debounce.ts_ms - born,
        "debounce": debounce.dur_ms,
        "rebuild": rebuilt - waited,
        "fib": end - rebuilt,
        "slack": debounce.attrs.get("slack_ms"),
    }


def tail(record) -> Optional[Tail]:
    rows = [
        row for row in map(_stages, spantree.by_trace(record).values())
        if row is not None
    ]
    if len(rows) < stats.needed(0.95):
        return None
    rows.sort(key=lambda row: row["extent"])
    slow = rows[-(len(rows) // 10):]
    excess = {
        stage: stats.median([r[stage] for r in slow])
        - stats.median([r[stage] for r in rows])
        for stage in STAGES
    }
    share = overrun_share(
        [r["slack"] for r in slow if r["slack"] is not None])
    return Tail(len(rows), len(slow), excess, share)


def excess_ms(record, stage: str) -> Optional[float]:
    found = tail(record)
    return None if found is None else found.excess_ms[stage]
