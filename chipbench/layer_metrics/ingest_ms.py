"""Ingest: ``kvstore.publish`` instant -> ``decision.debounce`` start,
median over the window's traces (KvStore merge, queue, Decision's
publication handling)."""
from chipbench import stats


def read(record):
    born, adopted = {}, {}
    for s in record.spans:
        if s.name == "kvstore.publish":
            born[s.trace_id] = s.ts_ms
        elif s.name == "decision.debounce":
            adopted[s.trace_id] = s.ts_ms
    waits = [adopted[t] - born[t] for t in adopted if t in born]
    return stats.median(waits) if waits else None
