"""repro — communication-hiding pipelined BiCGSafe, grown production-shaped.

Front door (:mod:`repro.api`): bind an operator once, solve many times —

    import repro

    solver = repro.make_solver("p-bicgsafe", op, precond="block_jacobi",
                               substrate="pallas")
    res = solver.solve(b)                  # compiled program cached
    res = solver.solve_many([b1, b2, b3])  # ONE (9, m) reduction/iter
    dist = solver.on_mesh(mesh)            # sharded, same session

    res = repro.solve(op, b)               # one-shot convenience

Sessions are content-addressed: equal-content operators share one
session (built preconditioner + compiled programs), whether they arrive
via :func:`make_solver`, :func:`solve`, or the continuous-batching
solve service (:mod:`repro.service`), whose registry consumes the same
cache.

The whole regression matrix — operator class x method x substrate x
precond x guard x batch — is declarative data (:mod:`repro.scenarios`):
register a :class:`Scenario` once and it becomes a cached session
(``make_solver(scenario="poisson-jacobi")``), a contract-audit row, and
a ``python -m repro.scenarios sweep`` cell.

Layers underneath: :mod:`repro.core` (the paper's solvers, operators,
batched/distributed drivers), :mod:`repro.kernels` (Pallas hot-loop
kernels), :mod:`repro.precond` (preconditioners inside the overlap
window), :mod:`repro.service` (continuous batching),
:mod:`repro.observe` (zero-sync iteration traces, span timelines,
metrics — ``solver.solve(b, trace=True)``).  The historical
free-function entry points keep working as deprecated shims.
"""
from repro.api import (DistributedSolver, LinearSolver, make_solver,
                       operator_fingerprint, solve)
from repro.core import (SOLVERS, CSROperator, DenseOperator, ELLOperator,
                        Preconditioner, SolveResult, SolverConfig,
                        Stencil7Operator, Stencil27Operator, SUBSTRATES,
                        get_substrate)
from repro.observe import ConvergenceTrace
from repro.resilience import GuardedSolver, RecoveryPolicy, SolveStatus
from repro.scenarios import (OperatorSpec, Scenario, register_operator_class,
                             register_scenario)

__all__ = [
    # the front door
    "make_solver", "solve", "LinearSolver", "DistributedSolver",
    "operator_fingerprint",
    # the vocabulary types the front door speaks
    "SolverConfig", "SolveResult", "SOLVERS",
    "DenseOperator", "CSROperator", "ELLOperator", "Stencil7Operator",
    "Stencil27Operator",
    "Preconditioner",
    "SUBSTRATES", "get_substrate",
    # the scenario registry (repro.scenarios; make_solver(scenario=...))
    "Scenario", "OperatorSpec", "register_scenario",
    "register_operator_class",
    # guarded solves (repro.resilience; make_solver(recovery=...))
    "SolveStatus", "RecoveryPolicy", "GuardedSolver",
    # observability (repro.observe; solve(trace=True))
    "ConvergenceTrace",
]
