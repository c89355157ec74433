"""served_rps: verified requests retired per second of the window."""
from chipbench import stats


def read(record):
    if record.requests is None:
        return None
    return stats.rate(sum(r.converged for r in record.requests),
                      record.window_s)
