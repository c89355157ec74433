"""refines.solve: refinement solves per verified right-hand side; 0 once
the solver loop verifies its own convergence."""


def read(record):
    if record.solves is None:
        return None
    verified = sum(s.verified for s in record.solves)
    return sum(s.refines for s in record.solves) / verified \
        if verified else None
