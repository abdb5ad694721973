"""Median publication -> Fib convergence over the window's samples."""
from chipbench import stats


def read(record):
    return stats.median(record.samples_ms)
