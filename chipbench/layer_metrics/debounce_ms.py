"""Median duration of the program's ``decision.debounce`` span over the window."""


def read(record):
    return record.span_median("decision.debounce")
