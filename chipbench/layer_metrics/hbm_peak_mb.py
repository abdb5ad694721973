"""Device: peak bytes in use on the fullest chip, in megabytes."""


def read(record):
    return record.memory_peak_bytes / 1e6
