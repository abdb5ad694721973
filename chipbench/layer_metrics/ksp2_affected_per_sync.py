"""KSP2 engine: destinations an incremental sync named as affected, per
sync, over the window (``decision.ksp2_affected_dsts`` over
``decision.ksp2_incremental_syncs``): what the engine re-derives where
a cold build re-derives all. Nothing where no sync was incremental."""


def read(record):
    syncs = record.counter("decision.ksp2_incremental_syncs")
    if "decision.ksp2_affected_dsts" not in record.counters or not syncs:
        return None
    return record.counter("decision.ksp2_affected_dsts") / syncs
