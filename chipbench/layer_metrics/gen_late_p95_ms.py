"""Load generator: how late events left (sent - due), 95th percentile."""
from chipbench import stats


def read(record):
    if not record.lateness_ms:
        return None
    return stats.percentile(record.lateness_ms, 0.95)
