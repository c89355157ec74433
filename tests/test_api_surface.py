"""Snapshot of the public ``repro`` namespace.

The front-door namespace is a contract: accidental export drift (a new
helper leaking to ``repro.*``, a re-export vanishing during a refactor)
must fail loudly here, with the fix being an intentional edit of BOTH
the package ``__all__`` and this snapshot.
"""
import repro

# the intended public surface of `import repro` — keep sorted
PUBLIC_API = [
    "CSROperator",
    "ConvergenceTrace",
    "DenseOperator",
    "DistributedSolver",
    "ELLOperator",
    "GuardedSolver",
    "LinearSolver",
    "OperatorSpec",
    "Preconditioner",
    "RecoveryPolicy",
    "SOLVERS",
    "SUBSTRATES",
    "Scenario",
    "SolveResult",
    "SolveStatus",
    "SolverConfig",
    "Stencil27Operator",
    "Stencil7Operator",
    "get_substrate",
    "make_solver",
    "operator_fingerprint",
    "register_operator_class",
    "register_scenario",
    "solve",
]

# submodules that legitimately appear as attributes after import
# (importing repro.api pulls these in); NOT part of the call surface
_SUBMODULES = {"api", "core", "precond", "kernels", "resilience",
               "observe", "scenarios"}


def test_all_matches_snapshot():
    assert sorted(repro.__all__) == PUBLIC_API, (
        "public repro namespace drifted; if intentional, update BOTH "
        "repro/__init__.__all__ and tests/test_api_surface.PUBLIC_API")


def test_exports_exist_and_nothing_leaks():
    for name in PUBLIC_API:
        assert hasattr(repro, name), f"declared export {name!r} missing"
    leaked = {n for n in dir(repro)
              if not n.startswith("_")
              and n not in set(PUBLIC_API) | _SUBMODULES
              and type(getattr(repro, n)).__name__ != "module"}
    assert not leaked, (
        f"unexported public names leaked into repro.*: {sorted(leaked)}")


def test_solver_registry_matches_methods():
    """SOLVERS is the method registry make_solver resolves from — its
    key set is part of the public contract."""
    assert sorted(repro.SOLVERS) == [
        "bicgstab", "cgs", "gpbicg", "p-bicgsafe", "p-bicgsafe-rr",
        "p-bicgstab", "ssbicgsafe2"]


def test_front_door_docstrings_point_home():
    """The layer docs route newcomers to the front door."""
    import repro.core
    assert "repro.api" in (repro.core.__doc__ or "")
    assert "make_solver" in (repro.__doc__ or "")
