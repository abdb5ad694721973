"""Rebuild, host side: median of the program's ``decision.ksp2_routes``
span, the per-prefix pass of a rebuild in which the KSP2 engine ran:
label-stack routes re-derived for the destinations the engine named,
the rest (the span's ``reused``) served from the cache. Nothing from a
program that has no such span."""


def read(record):
    return record.span_median("decision.ksp2_routes")
