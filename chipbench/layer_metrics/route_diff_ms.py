"""Rebuild, host side: median of the program's ``decision.route_diff``
span (the full-db diff against the installed routes)."""


def read(record):
    return record.span_median("decision.route_diff")
