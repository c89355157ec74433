"""repro.core — the paper's contribution: pipelined BiCGSafe solvers.

FRONT DOOR: :mod:`repro.api` — bind an operator once with
``repro.make_solver(method, op, precond=..., substrate=...)`` and solve
many times from the session (``.solve`` / ``.solve_many`` /
``.init``/``.step_chunk``/``.splice`` / ``.on_mesh``); compiled
programs and built preconditioners are cached by operator content.  The
free functions below keep working verbatim but are deprecated as direct
entry points (one DeprecationWarning per process each).

Public API:

* Solvers (all ``(matvec, b, x0=None, *, config, r0_star, dot_reduce)``):
  - :func:`bicgstab_solve`        BiCGStab            (Alg. 2.1, 2 syncs)
  - :func:`pbicgstab_solve`       pipelined BiCGStab  (Cools-Vanroose, 2 overlapped)
  - :func:`gpbicg_solve`          GPBi-CG             (Alg. 2.2, 3 syncs)
  - :func:`ssbicgsafe2_solve`     ssBiCGSafe2         (Alg. 2.3, 1 sync)
  - :func:`pbicgsafe_solve`       p-BiCGSafe          (Alg. 3.1, 1 overlapped sync)
  - :func:`pbicgsafe_rr_solve`    p-BiCGSafe-rr       (Alg. 4.1)
* Operators: Dense/CSR/ELL/Stencil7/Stencil27.
* Problem generators: :mod:`repro.core.matrices`.
* Distributed driver: :mod:`repro.core.distributed`.
* Compute substrates: every solver takes ``substrate="jnp"|"pallas"``
  (:mod:`repro.core.substrate`) selecting who computes the fused dot /
  vector-update / SpMV phases of the hot loop.
* Multi-RHS: :func:`solve_batched` solves ``A X = B`` for ``(n, m)``
  right-hand sides with per-RHS convergence (per-column ``tol=``
  vectors supported), one reduction per iteration; the iteration is
  also exposed open-loop as :func:`init_state` / :func:`step_chunk` /
  :func:`splice_columns`, which the continuous-batching solve service
  (:mod:`repro.service`) drives.
* Preconditioning: every solver entry point (including the batched and
  distributed drivers) takes ``precond=`` — a name or a
  :class:`repro.precond.Preconditioner` (Jacobi / block-Jacobi / Neumann
  polynomial / SSOR) — running the left-preconditioned system with the
  M^{-1}-apply routed through the substrate and, for the pipelined
  solvers, scheduled inside the overlap window of the single reduction
  (:mod:`repro.precond`).
"""
from repro.precond import (BlockJacobiPreconditioner, JacobiPreconditioner,
                           NeumannPreconditioner, Preconditioner,
                           SSORPreconditioner, block_jacobi, jacobi, neumann,
                           ssor)
import functools as _functools

from ._deprecation import warn_legacy as _warn_legacy
from .types import SolveResult, SolverConfig, identity_reduce
from .linear_operator import (CSROperator, DenseOperator, ELLOperator,
                              Stencil7Operator, Stencil27Operator,
                              as_matvec)
from .substrate import (SUBSTRATES, JnpSubstrate, PallasSubstrate, Substrate,
                        get_substrate)
from .bicgstab import bicgstab_solve
from .cgs import cgs_solve
from .pipelined_bicgstab import pbicgstab_solve
from .gpbicg import gpbicg_solve
from .ssbicgsafe import ssbicgsafe2_solve
from .pipelined_bicgsafe import pbicgsafe_solve, pbicgsafe_rr_solve
from .multirhs import (init_state, solve_batched, splice_columns,
                       step_chunk)


def _legacy_shim(fn, name: str, replacement: str):
    """Wrap a free-function entry point as a deprecated shim.

    The wrapped function keeps working verbatim (the session layer in
    :mod:`repro.api` delegates to the SAME underlying implementation),
    but a direct call announces the front door with one
    DeprecationWarning per process.  Internal/delegating callers are
    silent: the session layer runs under ``internal_use()`` and
    intra-package callers import from the defining modules, which stay
    unwrapped.
    """
    @_functools.wraps(fn)
    def shim(*args, **kwargs):
        _warn_legacy(name, replacement)
        return fn(*args, **kwargs)
    return shim


bicgstab_solve = _legacy_shim(
    bicgstab_solve, "bicgstab_solve", 'repro.make_solver("bicgstab", A)')
cgs_solve = _legacy_shim(
    cgs_solve, "cgs_solve", 'repro.make_solver("cgs", A)')
pbicgstab_solve = _legacy_shim(
    pbicgstab_solve, "pbicgstab_solve", 'repro.make_solver("p-bicgstab", A)')
gpbicg_solve = _legacy_shim(
    gpbicg_solve, "gpbicg_solve", 'repro.make_solver("gpbicg", A)')
ssbicgsafe2_solve = _legacy_shim(
    ssbicgsafe2_solve, "ssbicgsafe2_solve",
    'repro.make_solver("ssbicgsafe2", A)')
pbicgsafe_solve = _legacy_shim(
    pbicgsafe_solve, "pbicgsafe_solve", 'repro.make_solver("p-bicgsafe", A)')
pbicgsafe_rr_solve = _legacy_shim(
    pbicgsafe_rr_solve, "pbicgsafe_rr_solve",
    'repro.make_solver("p-bicgsafe-rr", A)')
solve_batched = _legacy_shim(
    solve_batched, "solve_batched",
    'repro.make_solver("p-bicgsafe", A).solve_many(B)')


def __getattr__(name: str):
    # deprecated alias, same PEP 562 treatment as its twin in
    # core/linear_operator.py: superseded by precond= on a bound session
    if name == "preconditioned_matvec":
        _warn_legacy("repro.core.preconditioned_matvec",
                     "precond= on repro.make_solver(...) "
                     "(or repro.precond.preconditioned_matvec)")
        from repro.precond.base import preconditioned_matvec
        return preconditioned_matvec
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")

SOLVERS = {
    "bicgstab": bicgstab_solve,
    "p-bicgstab": pbicgstab_solve,
    "gpbicg": gpbicg_solve,
    "cgs": cgs_solve,
    "ssbicgsafe2": ssbicgsafe2_solve,
    "p-bicgsafe": pbicgsafe_solve,
    "p-bicgsafe-rr": pbicgsafe_rr_solve,
}

__all__ = [
    "SolveResult", "SolverConfig", "identity_reduce",
    "CSROperator", "DenseOperator", "ELLOperator",
    "Stencil7Operator", "Stencil27Operator", "as_matvec",
    "preconditioned_matvec",
    "Preconditioner", "JacobiPreconditioner", "BlockJacobiPreconditioner",
    "NeumannPreconditioner", "SSORPreconditioner",
    "jacobi", "block_jacobi", "neumann", "ssor",
    "Substrate", "JnpSubstrate", "PallasSubstrate", "SUBSTRATES",
    "get_substrate",
    "bicgstab_solve", "pbicgstab_solve", "gpbicg_solve",
    "ssbicgsafe2_solve", "pbicgsafe_solve", "pbicgsafe_rr_solve",
    "solve_batched", "init_state", "step_chunk", "splice_columns",
    "SOLVERS",
]
