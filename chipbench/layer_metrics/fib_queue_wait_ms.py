"""Route programming: median of the program's ``fib.queue_wait`` span,
the end of ``decision.emit`` -> Fib's thread picks the update up (the
queue push, the hop and the wake-up)."""


def read(record):
    return record.span_median("fib.queue_wait")
