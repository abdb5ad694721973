"""Rebuild, host side: median of the program's ``graph.view_sync`` span
(LinkState -> device-resident arrays: snapshot or ELL band sync, the
source batch, the row patch's launch)."""


def read(record):
    return record.span_median("graph.view_sync")
