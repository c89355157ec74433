"""iters.serve: mean RequestResult.iterations of the requests retired in
the window."""


def read(record):
    if not record.requests:
        return None
    return sum(r.iterations for r in record.requests) / len(record.requests)
