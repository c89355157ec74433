"""served_p95_s: 95th percentile of the latency, submission to
retirement, of every request completed in the window."""
from chipbench import stats


def read(record):
    if not record.requests:
        return None
    return stats.percentile([r.latency_s for r in record.requests], 95)
