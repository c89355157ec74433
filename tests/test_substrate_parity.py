"""Compute-substrate tests: jnp/pallas parity, sync counts, the structural
overlap invariant, and the batched multi-RHS path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from conftest import enable_x64
from repro.analysis import (BindingSpec, count_prim as _count_prim,
                            find_prim_eqn as _find_prim_eqn,
                            find_while_body as _find_while_body,
                            reduction_consumes_matvec, tag_matvec,
                            tag_reduce, trace_fn)
from repro.core import (SOLVERS, SolverConfig, get_substrate, pbicgsafe_solve,
                        solve_batched, ssbicgsafe2_solve)
from repro.core import matrices as M
from repro.core._common import SyncCounter
from repro.core.types import identity_reduce
from repro.scenarios import build_problem

# built through the scenario registry's operator plugins (one shared
# definition per family; cached per spec content)
SEED_PROBLEMS = {
    "poisson3d": lambda: build_problem("poisson3d", nx=8),
    "convdiff": lambda: build_problem("convection_diffusion", nx=10,
                                      peclet=1.0),
}


# ---------------------------------------------------------------------------
# substrate resolution
# ---------------------------------------------------------------------------

def test_get_substrate_resolution():
    assert get_substrate(None).name == "jnp"
    assert get_substrate("jnp").name == "jnp"
    assert get_substrate("pallas").name == "pallas"
    sub = get_substrate("pallas")
    assert get_substrate(sub) is sub
    with pytest.raises(ValueError, match="unknown substrate"):
        get_substrate("cuda")


# ---------------------------------------------------------------------------
# jnp <-> pallas parity (interpret mode on CPU: same kernel bodies as TPU)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prob", list(SEED_PROBLEMS))
@pytest.mark.parametrize("sname", ["p-bicgsafe", "ssbicgsafe2"])
def test_pallas_substrate_iterate_parity(x64, prob, sname):
    """Both substrates run the same algorithm: same iterate trajectory up
    to fp64 summation-order noise.  On the SPD seed problem the iteration
    counts are identical and the iterates bitwise-close; on the
    convection-diffusion problem the tol check may flip by a couple of
    iterations (the kernel accumulates block-wise, jnp.vdot pairwise,
    and the crossing point lands differently per XLA build), so there
    we assert the drift bound and solution-level parity instead."""
    op, b, xt = SEED_PROBLEMS[prob]()
    cfg = SolverConfig(tol=1e-8, maxiter=2000)
    r_jnp = SOLVERS[sname](op.matvec, b, config=cfg, substrate="jnp")
    r_pal = SOLVERS[sname](op.matvec, b, config=cfg, substrate="pallas")
    assert bool(r_jnp.converged) and bool(r_pal.converged)
    if prob == "poisson3d":
        assert int(r_jnp.iterations) == int(r_pal.iterations), (
            f"{sname}/{prob}: substrate changed the iteration count")
        np.testing.assert_allclose(np.asarray(r_pal.x), np.asarray(r_jnp.x),
                                   rtol=1e-9, atol=1e-10)
        np.testing.assert_allclose(float(r_pal.relres), float(r_jnp.relres),
                                   rtol=1e-6)
    else:
        assert abs(int(r_jnp.iterations) - int(r_pal.iterations)) <= 2
        for res in (r_jnp, r_pal):
            true = float(jnp.linalg.norm(b - op.matvec(res.x))
                         / jnp.linalg.norm(b))
            assert true < 1e-6
        np.testing.assert_allclose(np.asarray(r_pal.x), np.asarray(r_jnp.x),
                                   rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("sname", ["bicgstab", "p-bicgstab", "gpbicg",
                                   "p-bicgsafe-rr", "cgs"])
def test_all_entry_points_accept_substrate(x64, sname):
    """Every solver entry point takes substrate= and still converges."""
    op, b, xt = M.poisson3d(8)
    res = SOLVERS[sname](op.matvec, b, config=SolverConfig(tol=1e-8),
                         substrate="pallas")
    assert bool(res.converged)
    assert float(jnp.linalg.norm(res.x - xt) / jnp.linalg.norm(xt)) < 1e-5


def test_pallas_substrate_dispatches_banded_ell_spmv(x64):
    """An ELLOperator with banded structure routes through the Pallas SpMV
    when passed (as an operator) to a solver on the pallas substrate."""
    n = 1024
    rng = np.random.default_rng(0)
    offs = np.array([-2, -1, 0, 1, 2])
    cols = np.clip(np.arange(n)[:, None] + offs[None, :], 0, n - 1)
    vals = rng.standard_normal((n, 5))
    vals[:, 2] = 1.0 + 1.2 * np.abs(vals).sum(axis=1)
    from repro.core import ELLOperator
    ell = ELLOperator(jnp.asarray(vals), jnp.asarray(cols, np.int32), n)
    xt = jnp.ones((n,), jnp.float64)
    b = ell.matvec(xt)
    res = pbicgsafe_solve(ell, b, config=SolverConfig(tol=1e-10),
                          substrate="pallas")
    assert bool(res.converged)
    assert float(jnp.linalg.norm(res.x - xt) / jnp.linalg.norm(xt)) < 1e-7


# ---------------------------------------------------------------------------
# communication structure survives the refactor
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
@pytest.mark.parametrize("sname,per_iter", [("ssbicgsafe2", 1),
                                            ("p-bicgsafe", 1)])
def test_sync_count_per_substrate(x64, substrate, sname, per_iter):
    """The substrate refactor keeps ONE reduction/iter for the safes."""
    op, b, _ = M.nonsym_dense(64)
    counter = SyncCounter(identity_reduce)
    jax.make_jaxpr(
        lambda bb: SOLVERS[sname](op.matvec, bb,
                                  config=SolverConfig(maxiter=10),
                                  dot_reduce=counter,
                                  substrate=substrate))(b)
    assert counter.calls == 1 + per_iter


def _reduction_sees_matvec(solve, op, b, substrate, precond=None) -> bool:
    """Structural overlap probe via the repro.analysis contract core.

    The matvec output and the fused-dot partials are tagged
    (``tag_matvec`` / ``tag_reduce``); ``reduction_consumes_matvec``
    then walks the while-body jaxpr for a path from the reduction back
    to the matvec tag.  False == no dependency edge == the reduction
    may overlap the matvec.

    Works for the single-RHS solvers ((9,) partials) and for
    ``solve_batched`` ((9, m) partial blocks; ``b`` is then (n, m), and
    the tag wraps the block matvec — optimization_barrier has no vmap
    batching rule, so the barrier must sit outside the column lift).
    """
    if b.ndim == 2:
        mv = tag_matvec(jax.vmap(op.matvec, in_axes=1, out_axes=1))
        solve_kw = {"blocked": True}
        binding = "batched"
    else:
        mv = tag_matvec(op.matvec)
        solve_kw = {}
        binding = "single"

    if precond is not None:
        # instances only: the probe hands the solver a tagged CALLABLE,
        # which a name spec could not build from.  The matvec tag sits
        # inside the M^{-1} ∘ A composition, so "reduction needs the tag"
        # still captures any edge to the in-flight precond+matvec (the
        # apply is strictly downstream of the tag).
        solve_kw["precond"] = precond
    spec = BindingSpec(method="probe", substrate=str(substrate),
                      binding=binding)
    tb = trace_fn(lambda bb: solve(
        mv, bb, config=SolverConfig(maxiter=10), dot_reduce=tag_reduce,
        substrate=substrate, **solve_kw), b, spec=spec)
    edge, _, _ = reduction_consumes_matvec(tb)
    return edge


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
def test_overlap_edge_survives_substrate_refactor(x64, substrate):
    """p-BiCGSafe's fused dots read only {s, y, r, t_prev, rs}: no path
    from the in-flight matvec to the reduction (the paper's overlap
    property), on EITHER substrate; ssBiCGSafe2's reduction consumes the
    fresh matvec, so there the edge must exist."""
    op, b, _ = M.nonsym_dense(64)
    assert not _reduction_sees_matvec(pbicgsafe_solve, op, b, substrate)
    assert _reduction_sees_matvec(ssbicgsafe2_solve, op, b, substrate)


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
@pytest.mark.parametrize("pname", ["jacobi", "block_jacobi", "neumann"])
def test_overlap_edge_survives_preconditioning(x64, substrate, pname):
    """The tentpole invariant of the preconditioned pipelined method: the
    M^{-1}-apply joins the in-flight matvec INSIDE the overlap window, so
    the fused reduction still has no dependency path to it — while
    preconditioned ssBiCGSafe2 (whose dots consume the fresh
    preconditioned matvec) must keep the edge."""
    from repro.precond import resolve_precond
    op, b, _ = M.nonsym_dense(64)
    pc = resolve_precond(pname, op)
    assert not _reduction_sees_matvec(pbicgsafe_solve, op, b, substrate,
                                      precond=pc)
    assert _reduction_sees_matvec(ssbicgsafe2_solve, op, b, substrate,
                                  precond=pc)


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
def test_overlap_edge_survives_precond_batching(x64, substrate):
    """Preconditioned + batched: the (9, m) block reduction keeps no path
    from the in-flight preconditioned BLOCK matvec."""
    from repro.precond import block_jacobi
    op, b, _ = M.nonsym_dense(64)
    B = jnp.stack([b, 0.5 * b, b + 1.0], axis=1)
    assert not _reduction_sees_matvec(solve_batched, op, B, substrate,
                                      precond=block_jacobi(op, 16))


# the per-solver reduction-phase table of test_solvers (single source of
# truth), which preconditioning must NOT change (no preconditioner
# computes an inner product); cgs only appears here because its
# unpreconditioned count is asserted by test_converges_* instead
from test_solvers import SYNC_COUNTS as _SYNC_COUNTS  # noqa: E402

PRECOND_SYNC_COUNTS = dict(_SYNC_COUNTS, cgs=(1, 2))


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
@pytest.mark.parametrize("sname", list(PRECOND_SYNC_COUNTS))
def test_sync_count_preconditioned(x64, substrate, sname):
    """Preconditioning leaves every solver's synchronization count
    untouched, on either substrate (all preconditioned paths)."""
    op, b, _ = M.nonsym_dense(64)
    counter = SyncCounter(identity_reduce)
    jax.make_jaxpr(
        lambda bb: SOLVERS[sname](op, bb,
                                  config=SolverConfig(maxiter=10),
                                  dot_reduce=counter,
                                  substrate=substrate,
                                  precond="block_jacobi"))(b)
    init, per_iter = PRECOND_SYNC_COUNTS[sname]
    assert counter.calls == init + per_iter, (
        f"{sname}: preconditioning changed the reduce count "
        f"({counter.calls} != {init}+{per_iter})")


def test_sync_count_preconditioned_batched(x64):
    """solve_batched with precond: still exactly one (9, m) reduction per
    iteration for any m."""
    op, b, _ = M.poisson3d(8)
    for m in (1, 3):
        counter = SyncCounter(identity_reduce)
        jax.make_jaxpr(lambda bb: solve_batched(
            op, bb, config=SolverConfig(maxiter=10),
            dot_reduce=counter, precond="ssor"))(_rhs_block(b, m))
        assert counter.calls == 2, (m, counter.calls)   # init + 1/iter


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
def test_overlap_edge_survives_batching(x64, substrate):
    """The (9, m) fused block reduction of solve_batched still has no
    dependency path from the in-flight BLOCK matvec — batching the
    reduction must not serialize it behind the SpMV, on either substrate."""
    op, b, _ = M.nonsym_dense(64)
    B = jnp.stack([b, 0.5 * b, b + 1.0], axis=1)
    assert not _reduction_sees_matvec(solve_batched, op, B, substrate)


# ---------------------------------------------------------------------------
# sharded batched solve: one psum/iter, no edge to the halo exchange
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
@pytest.mark.parametrize("m", [1, 4])
@pytest.mark.parametrize("precond", [None, "block_jacobi"])
def test_sharded_batched_single_psum_per_iter(x64, substrate, m, precond):
    """The sharded batched solve lowers to EXACTLY ONE psum per iteration
    — the (9, m) block — for any m and either substrate (the paper's
    one-synchronization property).  A 1-device mesh suffices for the
    count (the psum is mesh-size independent); the multi-device halo /
    dependency-edge structure is asserted in tests/_distributed_check.py
    and benchmarks/bench_overlap.py on 8 fake devices."""
    from repro.launch.mesh import make_mesh
    from repro.core.distributed import distributed_stencil_solve_batched

    op, b, _ = M.convection_diffusion(8, peclet=1.0)
    B_grid = jnp.stack([b * (j + 1) for j in range(m)],
                       axis=1).reshape(8, 8, 8, m)
    mesh = make_mesh((1,), ("rows",))
    jaxpr = jax.make_jaxpr(lambda BB: distributed_stencil_solve_batched(
        op, BB, mesh, config=SolverConfig(maxiter=10),
        substrate=substrate, precond=precond, jit=False))(B_grid)
    body = _find_while_body(jaxpr.jaxpr)
    assert body is not None, "no while loop in the sharded batched solve"
    assert _count_prim(body, "psum") == 1, "must be ONE reduction/iter"
    psum_eqn = _find_prim_eqn(body, "psum")
    assert psum_eqn.invars[0].aval.shape == (9, m), \
        "the one reduction must carry the whole (9, m) partial block"


# ---------------------------------------------------------------------------
# batched multi-RHS path
# ---------------------------------------------------------------------------

def _rhs_block(b, m, seed=3):
    keys = jax.random.split(jax.random.PRNGKey(seed), m)
    cols = [b] + [jax.random.normal(k, b.shape, b.dtype) for k in keys[1:]]
    return jnp.stack(cols, axis=1)


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
def test_batched_matches_looped(x64, substrate):
    """Each batched column solves its system (true residual at tol) and
    needs essentially the per-column iteration counts of looped solves."""
    op, b, _ = M.convection_diffusion(10, peclet=1.0)
    B = _rhs_block(b, 4)
    cfg = SolverConfig(tol=1e-8, maxiter=2000)
    res = solve_batched(op.matvec, B, config=cfg, substrate=substrate)
    assert bool(np.asarray(res.converged).all())
    for j in range(B.shape[1]):
        true = float(jnp.linalg.norm(B[:, j] - op.matvec(res.x[:, j]))
                     / jnp.linalg.norm(B[:, j]))
        assert true < 1e-6, (j, true)
        rj = pbicgsafe_solve(op.matvec, B[:, j], config=cfg)
        # same algorithm per column; allow a couple iters of fp drift
        assert abs(int(res.iterations[j]) - int(rj.iterations)) <= 3


def test_batched_single_reduction_any_m(x64):
    """Exactly one dot_reduce per iteration regardless of m."""
    op, b, _ = M.poisson3d(8)
    for m in (1, 3, 17):
        counter = SyncCounter(identity_reduce)
        jax.make_jaxpr(lambda bb: solve_batched(
            op.matvec, bb, config=SolverConfig(maxiter=10),
            dot_reduce=counter))(_rhs_block(b, m))
        assert counter.calls == 2, (m, counter.calls)   # init + 1/iter


def test_batched_reduction_is_one_9xm_block(x64):
    """The per-iteration message is a single (9, m) partial block."""
    op, b, _ = M.poisson3d(8)
    m = 5
    sizes = []

    def spy(partials):
        sizes.append(partials.shape)
        return partials

    jax.make_jaxpr(lambda bb: solve_batched(
        op.matvec, bb, config=SolverConfig(maxiter=5),
        dot_reduce=spy))(_rhs_block(b, m))
    assert sizes[0] == (1, m)     # init ||r0|| per column
    assert sizes[1] == (9, m)     # the fused phase, all m systems at once


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
def test_batched_per_rhs_masking(x64, substrate):
    """Columns converge at their own iteration; early columns freeze (on
    the pallas substrate the freeze happens in-kernel via the convergence
    mask the update-phase kernel consumes)."""
    op, b, _ = M.poisson3d(8)
    # power-of-two scaling keeps the fp trajectory bitwise identical
    B = jnp.stack([b, (2.0 ** -20) * b, jax.random.normal(
        jax.random.PRNGKey(0), b.shape, b.dtype)], axis=1)
    cfg = SolverConfig(tol=1e-8, maxiter=2000)
    res = solve_batched(op.matvec, B, config=cfg, substrate=substrate)
    iters = np.asarray(res.iterations)
    assert bool(np.asarray(res.converged).all())
    # scaled column converges in the same iterations as its parent
    assert iters[1] == iters[0]
    assert np.asarray(res.relres).max() <= 1e-8


def test_batched_pallas_jnp_parity_per_column(x64):
    """solve_batched(substrate="pallas") == substrate="jnp" column by
    column: same per-column iteration counts and fp64-tolerance iterates
    (interpret mode on CPU runs the same kernel bodies as TPU)."""
    op, b, _ = M.convection_diffusion(10, peclet=1.0)
    B = _rhs_block(b, 4)
    cfg = SolverConfig(tol=1e-8, maxiter=2000)
    r_jnp = solve_batched(op.matvec, B, config=cfg, substrate="jnp")
    r_pal = solve_batched(op.matvec, B, config=cfg, substrate="pallas")
    assert bool(np.asarray(r_jnp.converged).all())
    assert bool(np.asarray(r_pal.converged).all())
    for j in range(B.shape[1]):
        assert int(r_jnp.iterations[j]) == int(r_pal.iterations[j]), (
            f"column {j}: substrate changed the iteration count")
        np.testing.assert_allclose(
            np.asarray(r_pal.x[:, j]), np.asarray(r_jnp.x[:, j]),
            rtol=1e-6, atol=1e-8, err_msg=f"column {j}")
    # relres sits at ~tol where block-wise vs pairwise summation order is
    # visible; the iterates themselves are asserted tight above
    np.testing.assert_allclose(np.asarray(r_pal.relres),
                               np.asarray(r_jnp.relres),
                               rtol=5e-2, atol=1e-10)


def test_batched_pallas_block_ell_spmv(x64):
    """A banded ELLOperator handed to solve_batched on the pallas
    substrate routes through the BLOCK ELL kernel (matrix tiles read once
    for all m columns) and reproduces the jnp path."""
    n, m = 1024, 3
    rng = np.random.default_rng(0)
    offs = np.array([-2, -1, 0, 1, 2])
    cols = np.clip(np.arange(n)[:, None] + offs[None, :], 0, n - 1)
    vals = rng.standard_normal((n, 5))
    vals[:, 2] = 1.0 + 1.2 * np.abs(vals).sum(axis=1)
    from repro.core import ELLOperator, get_substrate
    ell = ELLOperator(jnp.asarray(vals), jnp.asarray(cols, np.int32), n)

    # dispatch check: the block matvec is the kernel, not a vmap
    bmv = get_substrate("pallas").as_block_matvec(ell)
    X = jnp.asarray(rng.standard_normal((n, m)))
    np.testing.assert_allclose(np.asarray(bmv(X)),
                               np.stack([np.asarray(ell.matvec(X[:, j]))
                                         for j in range(m)], axis=1),
                               rtol=1e-10)

    Xt = jnp.ones((n, m), jnp.float64) * jnp.arange(1., m + 1.)
    B = bmv(Xt)
    res = solve_batched(ell, B, config=SolverConfig(tol=1e-10),
                        substrate="pallas")
    assert bool(np.asarray(res.converged).all())
    err = float(jnp.linalg.norm(res.x - Xt) / jnp.linalg.norm(Xt))
    assert err < 1e-7


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
def test_batched_per_column_tol(x64, substrate):
    """solve_batched accepts an (m,) tol vector: each column converges
    against its OWN tolerance (what heterogeneous service requests need),
    matching a standalone solve at that tolerance, on both substrates."""
    op, b, _ = M.poisson3d(8)
    B = _rhs_block(b, 3)
    tols = jnp.asarray([1e-4, 1e-8, 1e-10])
    cfg = SolverConfig(tol=1e-8, maxiter=2000)
    res = solve_batched(op.matvec, B, config=cfg, substrate=substrate,
                        tol=tols)
    assert bool(np.asarray(res.converged).all())
    relres = np.asarray(res.relres)
    iters = np.asarray(res.iterations)
    for j, tol in enumerate(np.asarray(tols)):
        assert relres[j] <= tol, (j, relres[j], tol)
        solo = solve_batched(op.matvec, B[:, j:j + 1],
                             config=SolverConfig(tol=float(tol),
                                                 maxiter=2000),
                             substrate=substrate)
        assert int(iters[j]) == int(solo.iterations[0]), (
            f"column {j}: per-column tol changed the trajectory")
    # looser columns stop earlier than tighter ones
    assert iters[0] < iters[1] < iters[2]


def test_batched_per_column_tol_shape_is_loud(x64):
    """A wrong-length tol vector must not silently broadcast."""
    op, b, _ = M.poisson3d(8)
    with pytest.raises(ValueError, match="per-column tol"):
        solve_batched(op.matvec, _rhs_block(b, 3),
                      tol=jnp.asarray([1e-8, 1e-8]))


def test_batched_history_and_x0(x64):
    op, b, _ = M.poisson3d(8)
    B = _rhs_block(b, 3)
    X0 = jnp.full_like(B, 0.37)
    cfg = SolverConfig(tol=1e-8, maxiter=500, record_history=True)
    res = solve_batched(op.matvec, B, X0, config=cfg)
    assert bool(np.asarray(res.converged).all())
    h = np.asarray(res.residual_history)
    assert h.shape == (501, 3)
    for j in range(3):
        it = int(res.iterations[j])
        assert np.isfinite(h[:it + 1, j]).all()
        assert np.isnan(h[it + 1:, j]).all()


def test_batched_rejects_1d_rhs(x64):
    op, b, _ = M.poisson3d(8)
    with pytest.raises(ValueError, match=r"\(n, m\)"):
        solve_batched(op.matvec, b)


def test_masked_normalizes_m1_degenerate_shapes(x64):
    """multirhs._masked accepts coefficients whose trailing m=1 axis was
    squeezed away (e.g. by a dot_reduce that collapses the (9, 1) partial
    block to (9,)) instead of raising / producing mis-shaped state."""
    from repro.core.multirhs import _masked
    mask = jnp.asarray([True])
    # scalar new vs (1,) old — the squeezed-coefficient case
    out = _masked(mask, jnp.asarray(2.0), jnp.asarray([1.0]))
    assert out.shape == (1,) and float(out[0]) == 2.0
    # (n,) new vs (n, 1) old
    out = _masked(jnp.asarray([False]), jnp.arange(4.0),
                  jnp.zeros((4, 1)))
    assert out.shape == (4, 1)
    np.testing.assert_array_equal(np.asarray(out), np.zeros((4, 1)))
    # matching ranks stay the fast path
    out = _masked(jnp.asarray([True, False]), jnp.ones((3, 2)),
                  jnp.zeros((3, 2)))
    np.testing.assert_array_equal(np.asarray(out),
                                  np.stack([np.ones(3), np.zeros(3)], 1))
    # m>1 rank collapse stays a LOUD failure (a dot_reduce that sums away
    # a real RHS axis must not silently broadcast one column to all m)
    with pytest.raises(ValueError, match="rank mismatch"):
        _masked(jnp.asarray([True, True]), jnp.ones(()), jnp.zeros((2,)))


# ---------------------------------------------------------------------------
# deprecation hygiene: the init_state/step_chunk refactor of solve_batched
# (PR 4) must be BYTE-identical to the historical monolithic while_loop
# ---------------------------------------------------------------------------

def _solve_batched_pre_refactor(matvec, B, *, config):
    """Verbatim copy of the pre-refactor ``solve_batched`` hot loop
    (git 9a6cb8c): one closed ``lax.while_loop`` with a scalar global
    iteration counter, closure-carried RS/norm_r0, and a scalar tol.
    The refactored open-loop wrapper must reproduce it bit for bit."""
    from repro.core._common import (bicgsafe_coefficients,
                                    pipelined_recurrence_tail)
    from repro.core.multirhs import _masked
    from repro.core.substrate import get_substrate
    from repro.core.types import SolveResult

    sub = get_substrate("jnp")
    bmv = sub.as_block_matvec(matvec)
    n, m = B.shape
    eps = config.breakdown_threshold(B.dtype)
    X = jnp.zeros_like(B)
    R0 = B
    RS = R0
    S0 = bmv(R0)
    norm_r0 = jnp.sqrt(sub.dots([(R0, R0)]))[0]
    Z0 = jnp.zeros_like(B)
    ones_m = jnp.ones((m,), B.dtype)
    if config.record_history:
        hist = jnp.full((config.maxiter + 1, m), jnp.nan, norm_r0.dtype)
    else:
        hist = jnp.zeros((0, m), norm_r0.dtype)
    state = dict(
        x=X, r=R0, s=S0, p=Z0, u=Z0, t=Z0, y=Z0, z=Z0, w=Z0, l=Z0, g=Z0,
        alpha=jnp.zeros((m,), B.dtype), zeta=ones_m, f=ones_m,
        i=jnp.zeros((), jnp.int32),
        iterations=jnp.zeros((m,), jnp.int32),
        relres=jnp.ones((m,), norm_r0.dtype),
        converged=jnp.zeros((m,), bool), breakdown=jnp.zeros((m,), bool),
        hist=hist)

    def cond(st):
        active = (~st["converged"]) & (~st["breakdown"])
        return jnp.any(active) & (st["i"] < config.maxiter)

    def body(st):
        r, s, y, t_prev = st["r"], st["s"], st["y"], st["t"]
        active = (~st["converged"]) & (~st["breakdown"])
        As = bmv(s)
        dots = sub.bicgsafe_dots(s, y, r, t_prev, RS)
        beta, alpha, zeta, eta, f, rr, bad = bicgsafe_coefficients(
            dots, st["i"], st["alpha"], st["zeta"], st["f"], eps)
        relres = jnp.sqrt(jnp.abs(rr)) / norm_r0
        done = relres <= config.tol
        advance = active & ~done & ~bad
        upd = sub.axpy_phase(
            dict(r=r, p=st["p"], u=st["u"], t=t_prev, y=y, z=st["z"],
                 s=s, l=st["l"], g=st["g"], w=st["w"], x=st["x"], As=As),
            (alpha, beta, zeta, eta), mask=advance)
        p, u, q, w, t = (upd[k] for k in ("p", "u", "q", "w", "t"))
        z, y_next, x_next, r_next = (
            upd[k] for k in ("z", "y", "x", "r"))
        Aw = bmv(w)
        l, g_next, s_next = pipelined_recurrence_tail(
            q, s, As, st["g"], Aw, alpha, zeta, eta)
        upd = lambda new, old: _masked(advance, new, old)  # noqa: E731
        relres_out = _masked(active, relres, st["relres"])
        if config.record_history:
            hist_i = st["hist"].at[st["i"]].set(
                jnp.where(active, relres_out.astype(st["hist"].dtype),
                          st["hist"][st["i"]]))
        else:
            hist_i = st["hist"]
        return dict(
            x=x_next, r=r_next, s=upd(s_next, s),
            p=p, u=u, t=t, y=y_next, z=z, w=w,
            l=upd(l, st["l"]), g=upd(g_next, st["g"]),
            alpha=upd(alpha, st["alpha"]), zeta=upd(zeta, st["zeta"]),
            f=upd(f, st["f"]),
            i=st["i"] + 1,
            iterations=jnp.where(advance, st["i"] + 1, st["iterations"]),
            relres=relres_out,
            converged=st["converged"] | (active & done),
            breakdown=st["breakdown"] | (active & bad & ~done),
            hist=hist_i)

    st = jax.lax.while_loop(cond, body, state)
    return SolveResult(st["x"], st["iterations"], st["relres"],
                       st["converged"], st["breakdown"], st["hist"])


REGRESSION_PROBLEMS = {
    "stencil7": lambda: M.poisson3d(8),                     # Stencil7
    "dense": lambda: M.nonsym_dense(64),                    # Dense
    "csr": lambda: M.random_nonsym(300, seed=2),            # CSR
    "ell": lambda: M.random_nonsym(300, seed=2, fmt="ell"),  # ELL
}


@pytest.mark.parametrize("prob", list(REGRESSION_PROBLEMS))
def test_solve_batched_bitwise_pre_refactor_regression(x64, prob):
    """Fixed-seed before/after regression on all four operator classes:
    the open-loop refactor (state-carried rs/norm_r0, per-column tol and
    first-iteration logic) keeps ``solve_batched`` BYTE-identical to the
    pre-refactor monolithic loop — every result field, including the
    recorded residual history."""
    op, b, _ = REGRESSION_PROBLEMS[prob]()
    B = _rhs_block(b, 3, seed=11)
    cfg = SolverConfig(tol=1e-8, maxiter=300, record_history=True)
    old = _solve_batched_pre_refactor(op.matvec, B, config=cfg)
    new = solve_batched(op.matvec, B, config=cfg)
    assert bool(np.asarray(new.converged).all()), (
        f"{prob}: regression baseline did not converge")
    for field in ("x", "iterations", "relres", "converged", "breakdown",
                  "residual_history"):
        a = np.asarray(getattr(old, field))
        c = np.asarray(getattr(new, field))
        assert np.array_equal(a, c, equal_nan=True), (
            f"{prob}: solve_batched.{field} changed bitwise after the "
            "init_state/step_chunk refactor")


# ---------------------------------------------------------------------------
# the session path (repro.api) preserves the structural invariants on
# every binding: single, batched, distributed (PR 5 acceptance)
# ---------------------------------------------------------------------------

def _session_reduction_sees_matvec(method, op, b, substrate) -> bool:
    """The overlap probe of _reduction_sees_matvec, through a bound
    session: tag the matvec and the fused-dot partials
    (repro.analysis tags), then walk the while-body (inside the
    session's jitted program — find_while_body recurses through pjit)
    for a path from the reduction back to the matvec tag."""
    import repro
    if b.ndim == 2:
        mv = tag_matvec(jax.vmap(op.matvec, in_axes=1, out_axes=1))
        session = repro.make_solver(method, mv, substrate=substrate,
                                    config=SolverConfig(maxiter=10),
                                    dot_reduce=tag_reduce, blocked=True)
        run, binding = (lambda bb: session.solve_many(bb)), "batched"
    else:
        mv = tag_matvec(op.matvec)
        session = repro.make_solver(method, mv, substrate=substrate,
                                    config=SolverConfig(maxiter=10),
                                    dot_reduce=tag_reduce)
        run, binding = (lambda bb: session.solve(bb)), "single"
    spec = BindingSpec(method=method, substrate=str(substrate),
                      binding=binding)
    tb = trace_fn(run, b, spec=spec)
    edge, _, _ = reduction_consumes_matvec(tb)
    return edge


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
def test_session_overlap_edge_single(x64, substrate):
    """p-BiCGSafe through a session keeps the no-dependency-edge overlap
    (and ssBiCGSafe2 keeps the edge) — the jitted session program does
    not serialize the reduction behind the matvec."""
    op, b, _ = M.nonsym_dense(64)
    assert not _session_reduction_sees_matvec("p-bicgsafe", op, b, substrate)
    assert _session_reduction_sees_matvec("ssbicgsafe2", op, b, substrate)


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
def test_session_overlap_edge_batched(x64, substrate):
    """solve_many through a session: the (9, m) block reduction keeps no
    path from the in-flight block matvec."""
    op, b, _ = M.nonsym_dense(64)
    B = jnp.stack([b, 0.5 * b, b + 1.0], axis=1)
    assert not _session_reduction_sees_matvec("p-bicgsafe", op, B, substrate)


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
@pytest.mark.parametrize("sname,per_iter", [("ssbicgsafe2", 1),
                                            ("p-bicgsafe", 1)])
def test_session_sync_count(x64, substrate, sname, per_iter):
    """ONE reduction per iteration through the session path — and zero
    NEW reductions on the repeat solve (the program is reused, which is
    the amortization the API redesign exists for)."""
    import repro
    op, b, _ = M.nonsym_dense(64)
    counter = SyncCounter(identity_reduce)
    session = repro.make_solver(sname, op, substrate=substrate,
                                config=SolverConfig(maxiter=10),
                                dot_reduce=counter)
    session.solve(b)
    assert counter.calls == 1 + per_iter
    session.solve(2.0 * b)
    assert counter.calls == 1 + per_iter, "repeat solve must not retrace"


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
@pytest.mark.parametrize("m", [1, 4])
@pytest.mark.parametrize("precond", [None, "block_jacobi"])
def test_session_sharded_batched_single_psum_per_iter(x64, substrate, m,
                                                      precond):
    """The mesh-bound session lowers to EXACTLY ONE psum per iteration —
    the (9, m) block — matching the legacy distributed driver probe
    above (the session path must not add or split reductions)."""
    import repro
    from repro.launch.mesh import make_mesh

    op, b, _ = M.convection_diffusion(8, peclet=1.0)
    B_grid = jnp.stack([b * (j + 1) for j in range(m)],
                       axis=1).reshape(8, 8, 8, m)
    mesh = make_mesh((1,), ("rows",))
    dist = repro.make_solver(
        "p-bicgsafe", op, precond=precond,
        substrate=substrate, config=SolverConfig(maxiter=10)).on_mesh(mesh)
    jaxpr = jax.make_jaxpr(lambda BB: dist.solve_many(BB))(B_grid)
    body = _find_while_body(jaxpr.jaxpr)
    assert body is not None, "no while loop in the sharded batched solve"
    assert _count_prim(body, "psum") == 1, "must be ONE reduction/iter"
    psum_eqn = _find_prim_eqn(body, "psum")
    assert psum_eqn.invars[0].aval.shape == (9, m), \
        "the one reduction must carry the whole (9, m) partial block"


def test_batched_m1_with_squeezing_dot_reduce(x64):
    """End-to-end m=1 regression: a dot_reduce that squeezes the
    degenerate RHS axis (returning (9,) for the (9, 1) block) must still
    solve — this was reachable and raised the _masked rank check."""
    op, b, xt = M.poisson3d(8)
    B = b[:, None]

    def squeezing_reduce(partials):
        return partials.reshape(partials.shape[0])   # (k, 1) -> (k,)

    cfg = SolverConfig(tol=1e-8, maxiter=2000)
    res = solve_batched(op.matvec, B, config=cfg,
                        dot_reduce=squeezing_reduce)
    assert bool(np.asarray(res.converged).all())
    ref = solve_batched(op.matvec, B, config=cfg)
    assert int(res.iterations[0]) == int(ref.iterations[0])
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(ref.x),
                               rtol=1e-10, atol=1e-12)
