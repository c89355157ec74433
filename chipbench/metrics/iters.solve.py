"""iters.solve: solver iterations per verified right-hand side, the
refinement's included (SolveResult.iterations)."""


def read(record):
    if record.solves is None:
        return None
    verified = sum(s.verified for s in record.solves)
    return record.iterations / verified if verified else None
