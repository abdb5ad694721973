"""Device solve: median of the program's ``ops.solve_readback`` span,
the host blocked until the solve's result is on the host (the rest of
the device time after the dispatch returned, and the transfer)."""


def read(record):
    return record.span_median("ops.solve_readback")
