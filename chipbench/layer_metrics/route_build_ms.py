"""Rebuild, host side: per rebuild window, ``decision.route_build``
minus the spans nested in it (view sync, solve dispatch, readback): its
self time, which is route materialisation in Python; median."""
from chipbench import spantree


def read(record):
    return spantree.median_self_ms(record, "decision.route_build")
