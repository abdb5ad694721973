"""95th percentile of the same samples; refuses under 200 of them."""
from chipbench import stats


def read(record):
    return stats.percentile(record.samples_ms, 0.95)
