"""Median duration of the program's ``fib.program`` span over the window."""


def read(record):
    return record.span_median("fib.program")
