"""KSP2 engine: the share of the window's engine syncs that rebuilt cold
(``decision.ksp2_cold_builds`` over cold builds plus
``decision.ksp2_incremental_syncs``), in percent: the rebuilds that
re-solved and re-traced every destination. Nothing where the engine
never ran."""


def read(record):
    cold = record.counter("decision.ksp2_cold_builds")
    syncs = cold + record.counter("decision.ksp2_incremental_syncs")
    if not syncs:
        return None
    return 100.0 * cold / syncs
