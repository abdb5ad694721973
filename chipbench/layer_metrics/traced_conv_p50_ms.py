"""The traced run's own median convergence: against ``conv_p50_ms`` of
an untraced run it is what the profiler's session costs."""
from chipbench import stats


def read(record):
    return stats.median(record.samples_ms) if record.samples_ms else None
