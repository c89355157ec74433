"""idle.serve: 1 - device-busy time over the traced window."""


def read(record):
    if record.trace is None or record.requests is None:
        return None
    return record.trace.idle_frac
