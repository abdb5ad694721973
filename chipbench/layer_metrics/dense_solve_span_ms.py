"""Device solve: median of the program's ``ops.spf_view_batch`` span, the
dense solve's dispatch (the twin of ``solve_span_ms`` on the ELL side)."""


def read(record):
    return record.span_median("ops.spf_view_batch")
