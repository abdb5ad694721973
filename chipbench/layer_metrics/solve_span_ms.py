"""Median duration of the program's ``ops.ell_reconverge`` span over the window."""


def read(record):
    return record.span_median("ops.ell_reconverge")
