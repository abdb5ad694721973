"""Rebuild, host side: per rebuild window, ``decision.rebuild`` minus the
spans inside it: what no finer span names (the ladder, the event
window, the hand-off between solve and emit); median. Nothing from a
program whose rebuild has no ``decision.route_build`` inside."""
from chipbench import spantree


def read(record):
    if record.span_median("decision.route_build") is None:
        return None
    return spantree.median_self_ms(record, "decision.rebuild")
