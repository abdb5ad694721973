"""Rebuild, host side: programs launched on the device per rebuild,
counted in the steady part of the profiler's trace (one event of the
``XLA Modules`` line is one launch). The program's own
``ops.host_dispatches`` does not see the shipped solve paths: it counts
``aot_cache`` and route-engine calls, which the default pipeline never
makes."""


def read(record):
    if record.device is None or not record.steady_rebuilds():
        return None
    dev = record.device
    launches = sum(n for _, n in dev.module_seconds(dev.steady).values())
    return launches / record.steady_rebuilds()
