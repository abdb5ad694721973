"""Median duration of the program's ``decision.rebuild`` span over the window."""


def read(record):
    return record.span_median("decision.rebuild")
