"""Event loop: the share of the window that Decision's thread spent in
callbacks and timer functions (``evb.decision.busy_ms`` over busy +
``evb.decision.idle_ms``, the loop's own account), in percent. Its
inverse times the offered rate is where the loop saturates. The loop
flushes its account as it goes idle, so the window's edges are soft by
the last idle stretch before each reading (under half a second)."""


def read(record):
    if "evb.decision.busy_ms" not in record.counters:
        return None
    busy = record.counter("evb.decision.busy_ms")
    whole = busy + record.counter("evb.decision.idle_ms")
    return 100.0 * busy / whole if whole > 0 else None
