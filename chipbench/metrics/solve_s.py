"""solve_s: seconds of the window per verified right-hand side, the
benchmark's check and any refinement included."""
from chipbench import stats


def read(record):
    if record.solves is None:
        return None
    verified = sum(s.verified for s in record.solves)
    return 1.0 / stats.rate(verified, record.window_s) if verified else None
