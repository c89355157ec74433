"""queue_wait_p50_s.serve: median telemetry.queue_wait_s (submission to
first residence in the engine's block) of the requests retired in the
window."""
from chipbench import stats


def read(record):
    if not record.requests:
        return None
    return stats.median([r.queue_wait_s for r in record.requests])
