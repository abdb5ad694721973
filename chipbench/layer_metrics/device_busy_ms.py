"""Device solve: milliseconds an operation ran on the device per
rebuild, over the steady part of the traced window (profiler trace)."""


def read(record):
    if record.device is None or not record.steady_rebuilds():
        return None
    dev = record.device
    return dev.busy_s(dev.steady) * 1e3 / record.steady_rebuilds()
