"""Process runtime: milliseconds the window spent in generation-2
garbage collections (``process.gc_gen2_pause_ms``); 0 in a window that
had none, nothing from a program that does not count them."""


def read(record):
    return record.counters.get("process.gc_gen2_pause_ms")
