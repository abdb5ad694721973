"""Process runtime: how many of the window's traces carry a
``process.gc_pause`` span: samples that a full garbage collection
stopped. 0 in a window that had none; nothing from a program that does
not put pauses on its traces (no ``telemetry.traces_paused`` counter)."""


def read(record):
    if "telemetry.traces_paused" not in record.counters:
        return None
    return len({
        s.trace_id for s in record.spans if s.name == "process.gc_pause"
    })
