"""Correctness tests for the Krylov solver core (paper Algs. 2.1-4.1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.analysis.jaxpr_tools import find_prim_eqns, find_while_body
from repro.core import (SOLVERS, SolverConfig, Stencil7Operator, as_matvec,
                        bicgstab_solve, gpbicg_solve, pbicgsafe_rr_solve,
                        pbicgsafe_solve, pbicgstab_solve, ssbicgsafe2_solve)
from repro.core import matrices as M
from repro.core._common import SyncCounter
from repro.core.types import identity_reduce

PROBLEMS = {
    "nonsym_dense": lambda: M.nonsym_dense(150),
    "spd_dense": lambda: M.spd_dense(120, cond=1e3),
    "poisson3d": lambda: M.poisson3d(10),
    "convdiff": lambda: M.convection_diffusion(10, peclet=1.0),
    "random_csr": lambda: M.random_nonsym(1200, 7, diag_dominance=1.1),
    "random_ell": lambda: M.random_nonsym(800, 7, fmt="ell"),
    "aniso": lambda: M.anisotropic3d(10, eps=1e-2),
}


@pytest.mark.parametrize("prob", list(PROBLEMS))
@pytest.mark.parametrize("sname", list(SOLVERS))
def test_converges_to_true_solution(x64, prob, sname):
    op, b, xt = PROBLEMS[prob]()
    mv = as_matvec(op)
    res = SOLVERS[sname](mv, b, config=SolverConfig(tol=1e-8, maxiter=4000))
    assert bool(res.converged), f"{sname} failed on {prob}"
    true_res = jnp.linalg.norm(b - mv(res.x)) / jnp.linalg.norm(b)
    # recurred residual matched by true residual (no silent drift at tol)
    assert float(true_res) < 1e-6
    assert float(jnp.linalg.norm(res.x - xt) / jnp.linalg.norm(xt)) < 1e-5


def test_pipelined_equiv_ssbicgsafe2(x64):
    """Paper §3: Alg 3.1 == Alg 2.3 in exact arithmetic.

    In fp64 the residual histories must agree to high precision over the
    first dozens of iterations (paper Fig. 5.1 observation).
    """
    op, b, _ = M.convection_diffusion(12, peclet=1.0)
    cfg = SolverConfig(tol=1e-10, maxiter=300, record_history=True)
    r1 = ssbicgsafe2_solve(op.matvec, b, config=cfg)
    r2 = pbicgsafe_solve(op.matvec, b, config=cfg)
    n = min(int(r1.iterations), int(r2.iterations), 40)
    h1, h2 = np.asarray(r1.residual_history)[:n], np.asarray(r2.residual_history)[:n]
    # Identical until round-off takes over (paper: histories "nearly
    # identical for the several dozen initial iterations", then diverge in
    # finite precision — that divergence is the motivation for §4).
    pre_roundoff = h1 > 1e-5
    np.testing.assert_allclose(h1[pre_roundoff], h2[pre_roundoff], rtol=1e-3)


def test_pipelined_equiv_bicgstab(x64):
    """p-BiCGStab (Cools-Vanroose) == BiCGStab in exact arithmetic."""
    op, b, _ = M.nonsym_dense(200)
    cfg = SolverConfig(tol=1e-9, maxiter=300, record_history=True)
    r1 = bicgstab_solve(op.matvec, b, config=cfg)
    r2 = pbicgstab_solve(op.matvec, b, config=cfg)
    assert abs(int(r1.iterations) - int(r2.iterations)) <= 1
    n = min(int(r1.iterations), int(r2.iterations), 30)
    np.testing.assert_allclose(np.asarray(r1.residual_history)[:n],
                               np.asarray(r2.residual_history)[:n], rtol=1e-5)


SYNC_COUNTS = {
    # init reductions + per-iteration reduction phases (while body traces once)
    "ssbicgsafe2": (1, 1),
    "p-bicgsafe": (1, 1),
    "p-bicgsafe-rr": (1, 1),
    "bicgstab": (1, 2),
    "p-bicgstab": (1, 2),
    "gpbicg": (1, 3),
}


@pytest.mark.parametrize("sname", list(SYNC_COUNTS))
def test_synchronization_phase_count(x64, sname):
    """The paper's central claim surface: reductions per iteration.

    ssBiCGSafe2 / p-BiCGSafe: ONE fused phase; BiCGStab family: two;
    GPBi-CG: three.  Counted at trace time (while_loop body traces once).
    """
    op, b, _ = M.nonsym_dense(64)
    counter = SyncCounter(identity_reduce)
    jax.make_jaxpr(
        lambda bb: SOLVERS[sname](op.matvec, bb,
                                  config=SolverConfig(maxiter=10),
                                  dot_reduce=counter))(b)
    init, per_iter = SYNC_COUNTS[sname]
    assert counter.calls == init + per_iter, (
        f"{sname}: {counter.calls} reduce calls traced, "
        f"expected {init}+{per_iter}")


def test_single_fused_message_is_nine_scalars(x64):
    """p-BiCGSafe's one reduction carries all 9 inner products at once."""
    op, b, _ = M.nonsym_dense(64)
    sizes = []

    def spy(partials):
        sizes.append(partials.shape)
        return partials

    jax.make_jaxpr(lambda bb: pbicgsafe_solve(
        op.matvec, bb, config=SolverConfig(maxiter=5), dot_reduce=spy))(b)
    assert sizes[0] == (1,)       # init ||r0||
    assert sizes[1] == (9,)       # the fused phase


def test_nonzero_initial_guess(x64):
    op, b, xt = M.poisson3d(8)
    x0 = jnp.full_like(b, 0.37)
    res = pbicgsafe_solve(op.matvec, b, x0, config=SolverConfig())
    assert bool(res.converged)
    assert float(jnp.linalg.norm(res.x - xt)) < 1e-5


def test_custom_r0_star(x64):
    op, b, xt = M.nonsym_dense(100)
    key = jax.random.PRNGKey(0)
    rstar = jax.random.normal(key, b.shape, dtype=b.dtype)
    res = pbicgsafe_solve(op.matvec, b, r0_star=rstar, config=SolverConfig())
    assert bool(res.converged)


def test_maxiter_cap(x64):
    op, b, _ = M.poisson3d(10)
    res = pbicgsafe_solve(op.matvec, b, config=SolverConfig(maxiter=3))
    assert int(res.iterations) == 3
    assert not bool(res.converged)


def test_history_recording(x64):
    op, b, _ = M.poisson3d(8)
    cfg = SolverConfig(maxiter=500, record_history=True)
    res = pbicgsafe_solve(op.matvec, b, config=cfg)
    h = np.asarray(res.residual_history)
    it = int(res.iterations)
    assert np.isfinite(h[:it + 1]).all()
    assert h[0] == pytest.approx(1.0)
    assert h[it] <= 1e-8
    assert np.isnan(h[it + 1:]).all()


def test_rr_matches_pipelined_on_easy_problem(x64):
    """With convergence before the first replacement epoch, -rr == plain.

    Same algebra on both paths; the -rr solver's ``lax.cond`` is a
    compilation boundary whose fusion/FMA choices differ at the ulp level
    on CPU, so "equal" means identical iteration counts and iterates that
    agree far below the solve tolerance (not bitwise).
    """
    op, b, _ = M.poisson3d(10)
    cfg = SolverConfig(maxiter=500, rr_epoch=1000)
    r1 = pbicgsafe_solve(op.matvec, b, config=cfg)
    r2 = pbicgsafe_rr_solve(op.matvec, b, config=cfg)
    assert int(r1.iterations) == int(r2.iterations)
    np.testing.assert_allclose(np.asarray(r1.x), np.asarray(r2.x), rtol=1e-9)


def test_rr_replacement_executes_and_converges(x64):
    op, b, xt = M.convection_diffusion(12, peclet=1.0)
    cfg = SolverConfig(maxiter=1000, rr_epoch=5, rr_maxiter=500)
    res = pbicgsafe_rr_solve(op.matvec, b, config=cfg)
    assert bool(res.converged)
    assert float(jnp.linalg.norm(res.x - xt) / jnp.linalg.norm(xt)) < 1e-5


def test_solvers_jit_compatible(x64):
    op, b, _ = M.poisson3d(8)
    fn = jax.jit(lambda bb: pbicgsafe_solve(op.matvec, bb,
                                            config=SolverConfig()))
    res = fn(b)
    assert bool(res.converged)


def test_float32_operation():
    """Solvers are dtype-generic; fp32 converges at a looser tolerance."""
    op, b, xt = M.poisson3d(8, dtype=jnp.float32)
    res = pbicgsafe_solve(op.matvec, b, config=SolverConfig(tol=1e-5))
    assert bool(res.converged)
    assert res.x.dtype == jnp.float32


def test_breakdown_on_singular_system(x64):
    a = jnp.zeros((16, 16), dtype=jnp.float64)
    b = jnp.ones((16,), dtype=jnp.float64)
    res = pbicgsafe_solve(lambda x: a @ x, b, config=SolverConfig(maxiter=50))
    assert bool(res.breakdown)
    assert not bool(res.converged)
    assert np.isfinite(np.asarray(res.x)).all()


# --- the single-vector p-BiCGSafe loop's stop: x_i is kept, every other
# loop-carried vector is written once an iteration (no whole-state select)

PIPELINED = ("p-bicgsafe", "p-bicgsafe-rr")


def _small_stencil():
    c = jnp.asarray([6.75, -1.5, -1.0, -1.25, -1.0, -1.0, -1.0], jnp.float32)
    op = Stencil7Operator(c, 12, 10, 6)
    b = jnp.asarray(np.random.default_rng(7).standard_normal(op.shape[0]),
                    jnp.float32)
    return op, b


@pytest.mark.parametrize("method", PIPELINED)
def test_tol_stop_returns_the_iterate_it_measured(method):
    """A solve stopped by tol at iteration k returns x_k, k and relres_k:
    x and k bitwise those of a solve capped at maxiter=k, relres bitwise
    that of a solve capped at k + 1 (relres is measured at the top of an
    iteration, so the capped-at-k solve reports relres_{k-1})."""
    op, b = _small_stencil()

    def solve(tol, maxiter):
        return repro.make_solver(method, op, config=SolverConfig(
            tol=tol, maxiter=maxiter, rr_epoch=5)).solve(b)

    stopped = solve(1e-4, 500)
    k = int(stopped.iterations)
    assert bool(stopped.converged) and not bool(stopped.breakdown)
    assert k > 5                              # past a replacement step
    capped = solve(1e-30, k)
    assert int(capped.iterations) == k and not bool(capped.converged)
    np.testing.assert_array_equal(np.asarray(stopped.x),
                                  np.asarray(capped.x))
    assert float(stopped.relres) <= 1e-4
    assert float(stopped.relres) == float(solve(1e-30, k + 1).relres)


@pytest.mark.parametrize("method", PIPELINED)
def test_zero_rhs_converges_at_step_zero(method):
    op, b = _small_stencil()
    res = repro.make_solver(method, op).solve(jnp.zeros_like(b))
    assert bool(res.converged) and not bool(res.breakdown)
    assert int(res.iterations) == 0
    assert float(res.relres) == 0.0
    assert not np.asarray(res.x).any()


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
@pytest.mark.parametrize("method", PIPELINED)
def test_while_body_selects_at_most_one_vector(method, substrate):
    """In the solve program's while body, cond branches included, at most
    one select has a full (n,) vector output: the stop keeps x_i, and no
    whole-state select carries the other vectors."""
    op, b = _small_stencil()
    n = b.shape[0]
    cfg = SolverConfig(rr_epoch=5, record_history=True, trace_cap=8)
    body = find_while_body(jax.make_jaxpr(
        lambda v: SOLVERS[method](op, v, config=cfg, substrate=substrate))(b)
        .jaxpr)
    assert body is not None
    assert find_prim_eqns(body, "cond") or method == "p-bicgsafe"
    vector_selects = [e for e in find_prim_eqns(body, "select_n")
                      if tuple(e.outvars[0].aval.shape) == (n,)]
    assert len(vector_selects) <= 1, vector_selects
