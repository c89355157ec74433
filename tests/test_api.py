"""Tests for the repro.api front door: bind-once LinearSolver sessions.

Pins the three contracts of the API redesign (PR 5):

* parity — a session solve runs the SAME traced program as the legacy
  free function: bitwise-identical SolveResult for all 7 methods x 2
  substrates x {precond on/off} (and within fp-fusion noise of the
  un-jitted legacy call);
* caching — repeat solves against one session never retrace; equal-
  content operators share one session (built preconditioner included),
  across make_solver, repro.solve, and the service registry;
* deprecation hygiene — legacy shims warn once per process, the
  linear_operator re-exports warn on attribute access, and the session
  path never warns.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from conftest import enable_x64  # noqa: F401  (x64 fixture dependency)
from repro.core import SOLVERS, SolverConfig, solve_batched
from repro.core import matrices as M
from repro.core._common import SyncCounter
from repro.core.types import identity_reduce


def _fields_equal(a, b):
    for x, y in zip(a, b):
        if x is None or y is None:       # optional fields (status, trace)
            if x is not y:
                return False
        elif isinstance(x, dict) or isinstance(y, dict):
            if not (isinstance(x, dict) and isinstance(y, dict)
                    and x.keys() == y.keys()
                    and _fields_equal([x[k] for k in x], [y[k] for k in x])):
                return False
        elif not np.array_equal(np.asarray(x), np.asarray(y),
                                equal_nan=True):
            return False
    return True


# ---------------------------------------------------------------------------
# session vs legacy parity: 7 methods x 2 substrates x {precond on/off}
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
@pytest.mark.parametrize("method", list(SOLVERS))
@pytest.mark.parametrize("precond", [None, "jacobi"])
def test_session_matches_legacy_bitwise(x64, method, substrate, precond):
    """session.solve == the legacy free function, bitwise.

    The session traces the SAME program the legacy entry point runs, so
    under a common execution regime (one jit wrapper — what the session
    does) every SolveResult field is bitwise-identical.  The un-jitted
    legacy call is additionally asserted to fp-fusion noise (XLA fuses
    the init phase differently eagerly; the while-loop program is the
    same).
    """
    op, b, _ = M.convection_diffusion(8, peclet=1.0)
    cfg = SolverConfig(tol=1e-8, maxiter=500)
    session = repro.make_solver(method, op, precond=precond,
                                substrate=substrate, config=cfg)
    res = session.solve(b)
    assert bool(res.converged)

    legacy_fn = SOLVERS[method]
    legacy_jit = jax.jit(lambda bb: legacy_fn(
        op, bb, config=cfg, substrate=substrate,
        precond=session.precond))(b)
    assert _fields_equal(res, legacy_jit), (
        f"{method}/{substrate}/precond={precond}: session result is not "
        "bitwise-identical to the (jitted) legacy entry point")

    legacy_eager = legacy_fn(op, b, config=cfg, substrate=substrate,
                             precond=precond)
    assert int(legacy_eager.iterations) == int(res.iterations)
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(legacy_eager.x),
                               rtol=1e-9, atol=1e-10)


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
@pytest.mark.parametrize("precond", [None, "jacobi"])
def test_solve_many_matches_legacy_bitwise(x64, substrate, precond):
    """session.solve_many == legacy solve_batched, bitwise per field."""
    op, b, _ = M.poisson3d(8)
    B = jnp.stack([b, 0.5 * b, b + 1.0], axis=1)
    cfg = SolverConfig(tol=1e-8, maxiter=500)
    session = repro.make_solver("p-bicgsafe", op, precond=precond,
                                substrate=substrate, config=cfg)
    res = session.solve_many(B)
    assert bool(np.asarray(res.converged).all())
    # the session runs the SAME program as the legacy entry point: under
    # a common execution regime (one jit wrapper, the session's built
    # preconditioner instance — binding it once is the point of the
    # redesign) every field is bitwise-identical
    legacy_jit = jax.jit(lambda BB: solve_batched(
        op, BB, config=cfg, substrate=substrate,
        precond=session.precond))(B)
    assert _fields_equal(res, legacy_jit), (
        f"solve_many/{substrate}/precond={precond}: not bitwise-identical "
        "to the (jitted) legacy solve_batched")
    # and the plain eager name-spec legacy call agrees to fp-fusion noise
    named = solve_batched(op, B, config=cfg, substrate=substrate,
                          precond=precond)
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(named.x),
                               rtol=1e-9, atol=1e-10)
    np.testing.assert_array_equal(np.asarray(res.iterations),
                                  np.asarray(named.iterations))


def test_solve_many_accepts_column_vectors_and_per_column_settings(x64):
    op, b, _ = M.poisson3d(8)
    session = repro.make_solver("p-bicgsafe", op,
                                config=SolverConfig(maxiter=2000))
    tols = jnp.asarray([1e-4, 1e-8, 1e-10])
    res = session.solve_many([b, 0.5 * b, b + 1.0], tol=tols)
    assert bool(np.asarray(res.converged).all())
    relres = np.asarray(res.relres)
    for j, tol in enumerate(np.asarray(tols)):
        assert relres[j] <= tol
    iters = np.asarray(res.iterations)
    assert iters[0] < iters[1] < iters[2]
    # heterogeneous tol batches share ONE compiled program (tol is a
    # runtime argument, not baked into the trace)
    before = session.stats["traces"]
    session.solve_many([b, b, b], tol=jnp.asarray([1e-3, 1e-6, 1e-9]))
    assert session.stats["traces"] == before


def test_open_loop_handles_match_solve_many(x64):
    """init + step_chunk through the session == solve_many (same k)."""
    op, b, _ = M.poisson3d(8)
    cfg = SolverConfig(tol=1e-8, maxiter=300)
    session = repro.make_solver("p-bicgsafe", op, precond="jacobi",
                                config=cfg)
    B = jnp.stack([b, 2.0 * b], axis=1)
    st = session.init(B)
    st = session.step_chunk(st, cfg.maxiter)
    res = session.result(st)
    ref = session.solve_many(B)
    assert _fields_equal(res, ref)


def test_session_splice_resets_columns(x64):
    """Splicing a fresh rhs into a converged block restarts that column
    (the service's refill path, via the session handle)."""
    op, b, _ = M.poisson3d(8)
    session = repro.make_solver("p-bicgsafe", op,
                                config=SolverConfig(tol=1e-8, maxiter=300))
    B = jnp.stack([b, 0.5 * b], axis=1)
    st = session.step_chunk(session.init(B), 300)
    assert bool(np.asarray(st["converged"]).all())
    fresh = jax.random.normal(jax.random.PRNGKey(0), b.shape, b.dtype)
    st = session.splice(st, jnp.asarray([False, True]),
                        jnp.stack([b, fresh], axis=1))
    assert not bool(st["converged"][1])
    assert bool(st["converged"][0])
    st = session.step_chunk(st, 300)
    res = session.result(st)
    assert bool(np.asarray(res.converged).all())
    solo = session.solve_many(fresh[:, None])
    assert int(res.iterations[1]) == int(solo.iterations[0])


# ---------------------------------------------------------------------------
# caching: no retrace on repeat solves; content-keyed session reuse
# ---------------------------------------------------------------------------

def test_second_solve_does_not_retrace(x64):
    """The headline amortization: solve #2 with a NEW b reuses the
    compiled program (trace count stays 1) and the built preconditioner."""
    op, b, _ = M.poisson3d(8)
    session = repro.make_solver("p-bicgsafe", op, precond="block_jacobi")
    pc = session.precond
    assert pc is not None                     # built at bind time, once
    session.solve(b)
    assert session.stats["traces"] == 1
    for i in range(3):
        session.solve(b + float(i + 1))
    assert session.stats["traces"] == 1, "repeat solves must not retrace"
    assert session.precond is pc
    # a different static override compiles its own program, once
    session.solve(b, tol=1e-4)
    session.solve(2.0 * b, tol=1e-4)
    assert session.stats["traces"] == 2


def test_make_solver_content_cache_hit(x64):
    """Equal-content operators (fresh objects) return the SAME session:
    the fingerprint promoted out of service/registry.py is the key."""
    s1 = repro.make_solver("p-bicgsafe", M.poisson3d(8)[0],
                           precond="block_jacobi")
    s1.solve(M.poisson3d(8)[1])
    traces = s1.stats["traces"]
    s2 = repro.make_solver("p-bicgsafe", M.poisson3d(8)[0],
                           precond="block_jacobi")
    assert s2 is s1                            # fingerprint hit
    assert s2.precond is s1.precond
    s2.solve(2.0 * M.poisson3d(8)[1])
    assert s1.stats["traces"] == traces        # compiled program reused

    # distinct content / spec / method / substrate: distinct sessions
    assert repro.make_solver("p-bicgsafe", M.poisson3d(10)[0],
                             precond="block_jacobi") is not s1
    assert repro.make_solver("p-bicgsafe", M.poisson3d(8)[0],
                             precond="jacobi") is not s1
    assert repro.make_solver("bicgstab", M.poisson3d(8)[0],
                             precond="block_jacobi") is not s1
    assert repro.make_solver("p-bicgsafe", M.poisson3d(8)[0],
                             precond="block_jacobi",
                             substrate="pallas") is not s1


def test_repro_solve_one_shot_hits_session_cache(x64):
    op, b, xt = M.poisson3d(8)
    r1 = repro.solve(op, b, tol=1e-8)
    assert bool(r1.converged)
    s = repro.make_solver("p-bicgsafe", M.poisson3d(8)[0],
                          config=SolverConfig())
    traces = s.stats["traces"]
    r2 = repro.solve(M.poisson3d(8)[0], 2.0 * b, tol=1e-8)
    assert bool(r2.converged)
    assert s.stats["traces"] == traces, (
        "repeat repro.solve against equal content must reuse the session")


def test_service_registry_consumes_api_cache(x64):
    """The service registry is a thin consumer: registering an operator
    shares the session with a direct make_solver of the same content."""
    from repro.service import ServiceConfig, SolveEngine
    scfg = ServiceConfig(max_batch=2, chunk=8, tol=1e-8, maxiter=250)
    eng = SolveEngine(scfg)
    name = eng.register(M.poisson3d(8)[0], precond="jacobi")
    entry = eng.registry[name]
    direct = repro.make_solver(
        "p-bicgsafe", M.poisson3d(8)[0], precond="jacobi",
        config=SolverConfig(tol=scfg.tol, maxiter=scfg.maxiter))
    assert entry.session is direct
    assert entry.precond is direct.precond


def test_uncacheable_sessions_are_fresh(x64):
    """Bare matvec callables are not content-addressable: sessions are
    built fresh (no id-aliasing risk), and still solve correctly."""
    op, b, xt = M.poisson3d(8)
    s1 = repro.make_solver("p-bicgsafe", op.matvec)
    s2 = repro.make_solver("p-bicgsafe", op.matvec)
    assert s1 is not s2
    assert s1.fingerprint is None
    res = s1.solve(b)
    assert bool(res.converged)
    err = float(jnp.linalg.norm(res.x - xt) / jnp.linalg.norm(xt))
    assert err < 1e-5
    # name-spec preconds need an operator object — loud, as before
    with pytest.raises(TypeError, match="operator"):
        repro.make_solver("p-bicgsafe", op.matvec, precond="jacobi")


def test_custom_dot_reduce_skips_cache_and_counts_syncs(x64):
    """dot_reduce callables are honored (sessions just aren't cached):
    the session path keeps ONE reduction per iteration."""
    op, b, _ = M.nonsym_dense(64)
    counter = SyncCounter(identity_reduce)
    s = repro.make_solver("p-bicgsafe", op, dot_reduce=counter,
                          config=SolverConfig(maxiter=10))
    assert repro.make_solver("p-bicgsafe", op,
                             config=SolverConfig(maxiter=10)) is not s
    s.solve(b)
    assert counter.calls == 2                  # init ||r0|| + 1/iter
    s.solve(2.0 * b)
    assert counter.calls == 2                  # no retrace, no new syncs


# ---------------------------------------------------------------------------
# distributed binding
# ---------------------------------------------------------------------------

def test_on_mesh_matches_legacy_distributed(x64):
    """session.on_mesh(mesh) == the legacy distributed drivers, bitwise,
    and repeat solves reuse the built shard_map program."""
    from repro.launch.mesh import make_mesh
    from repro.core.distributed import (distributed_stencil_solve,
                                        distributed_stencil_solve_batched)
    op, b, _ = M.convection_diffusion(8, peclet=1.0)
    mesh = make_mesh((1,), ("rows",))
    cfg = SolverConfig(tol=1e-8, maxiter=500)
    session = repro.make_solver("p-bicgsafe", op, precond="jacobi",
                                config=cfg)
    dist = session.on_mesh(mesh)

    b_grid = b.reshape(8, 8, 8)
    res = dist.solve(b_grid)
    ref = distributed_stencil_solve(SOLVERS["p-bicgsafe"], op, b_grid, mesh,
                                    config=cfg, precond="jacobi")
    assert _fields_equal(res, ref)

    B_grid = jnp.stack([b, 2.0 * b], axis=1).reshape(8, 8, 8, 2)
    resb = dist.solve_many(B_grid)
    refb = distributed_stencil_solve_batched(op, B_grid, mesh, config=cfg,
                                             precond="jacobi")
    assert _fields_equal(resb, refb)

    programs = session.stats["programs"]
    dist.solve(2.0 * b_grid)
    dist.solve_many(3.0 * B_grid)
    assert session.stats["programs"] == programs, (
        "repeat distributed solves must reuse the built programs")
    # the binding itself is memoized, so the literal loop idiom the
    # deprecation message recommends (.on_mesh(mesh).solve(b) per call)
    # also reuses the built shard_map programs
    assert session.on_mesh(mesh) is dist
    session.on_mesh(mesh).solve(b_grid)
    assert session.stats["programs"] == programs


def test_on_mesh_requires_stencil_operator(x64):
    from repro.launch.mesh import make_mesh
    op, _, _ = M.nonsym_dense(16)
    with pytest.raises(TypeError, match="Stencil7"):
        repro.make_solver("p-bicgsafe", op).on_mesh(
            make_mesh((1,), ("rows",)))


def test_on_mesh_rejects_custom_dot_reduce(x64):
    """The sharded driver supplies its own single-psum reduction; a
    session-bound dot_reduce must fail loudly, not be silently dropped."""
    from repro.launch.mesh import make_mesh
    op, _, _ = M.convection_diffusion(8, peclet=1.0)
    session = repro.make_solver("p-bicgsafe", op,
                                dot_reduce=lambda p: p)
    with pytest.raises(ValueError, match="dot_reduce"):
        session.on_mesh(make_mesh((1,), ("rows",)))


def test_on_mesh_only_session_skips_global_precond_build(x64):
    """A session used only via .on_mesh never pays the global
    preconditioner build — the distributed binding rebuilds the name
    spec shard-locally (the legacy drivers' cost model, kept)."""
    from repro.launch.mesh import make_mesh
    op, b, _ = M.convection_diffusion(8, peclet=1.0)
    session = repro.make_solver(
        "p-bicgsafe", M.convection_diffusion(8, peclet=1.0)[0],
        precond="block_jacobi", config=SolverConfig(maxiter=300))
    dist = session.on_mesh(make_mesh((1,), ("rows",)))
    res = dist.solve(b.reshape(8, 8, 8))
    assert bool(res.converged)
    assert not session._precond_built, (
        "mesh-only usage must not build the global preconditioner")
    # first LOCAL use builds it, once
    assert session.precond is not None
    assert session._precond_built


# ---------------------------------------------------------------------------
# deprecation hygiene
# ---------------------------------------------------------------------------

def test_legacy_shims_warn_once_per_process(x64):
    """Each legacy entry point emits a single DeprecationWarning per
    process — not per call — and the session path emits none."""
    from repro.core import _deprecation, pbicgsafe_solve
    op, b, _ = M.poisson3d(8)
    _deprecation.reset_for_testing()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        pbicgsafe_solve(op, b, config=SolverConfig(maxiter=5))
        pbicgsafe_solve(op, b, config=SolverConfig(maxiter=5))
        dep = [x for x in w if issubclass(x.category, DeprecationWarning)
               and "pbicgsafe_solve" in str(x.message)]
    assert len(dep) == 1, "legacy shim must warn exactly once per process"

    _deprecation.reset_for_testing()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        s = repro.make_solver("p-bicgsafe", op,
                              config=SolverConfig(maxiter=50))
        s.solve(b)
        s.solve_many(jnp.stack([b, b], axis=1))
        dep = [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert not dep, f"session path must never warn: {[str(d.message) for d in dep]}"


def test_linear_operator_reexports_warn_but_preserve_identity(x64):
    """The historical repro.core.linear_operator aliases warn on access
    (no more silent aliasing) and still return the repro.precond
    objects themselves."""
    import repro.precond as P
    from repro.core import _deprecation
    from repro.core import linear_operator as LO
    _deprecation.reset_for_testing()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert LO.JacobiPreconditioner is P.JacobiPreconditioner
        assert LO.preconditioned_matvec is P.preconditioned_matvec
        dep = [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert len(dep) == 2
    with pytest.raises(AttributeError):
        LO.not_a_thing
    # the repro.core package-level alias gets the same treatment
    import repro.core as C
    _deprecation.reset_for_testing()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert C.preconditioned_matvec is P.preconditioned_matvec
        dep = [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert len(dep) == 1


def test_service_accepts_substrate_instance(x64):
    """ServiceConfig.substrate documents Substrate instances; a fresh
    instance must register fine (the session just is not globally
    cached) — regression for the fingerprint-skip on uncached
    substrates."""
    from repro.core import JnpSubstrate
    from repro.service import ServiceConfig, SolveEngine
    eng = SolveEngine(ServiceConfig(max_batch=2, chunk=8, maxiter=200,
                                    substrate=JnpSubstrate()))
    name = eng.register(M.poisson3d(8)[0], precond="jacobi", name="p")
    entry = eng.registry[name]
    assert entry.fingerprint is not None
    # equal-content re-registration still dedups within the engine
    n2 = eng.register(M.poisson3d(8)[0], precond="jacobi")
    assert eng.registry[n2] is entry
    op, b, _ = M.poisson3d(8)
    rid = eng.submit("p", b)
    res = {r.rid: r for r in eng.run()}
    assert res[rid].converged


# ---------------------------------------------------------------------------
# construction errors are loud
# ---------------------------------------------------------------------------

def test_make_solver_errors(x64):
    op, b, _ = M.poisson3d(8)
    with pytest.raises(ValueError, match="unknown method"):
        repro.make_solver("bicgfoo", op)
    with pytest.raises(TypeError, match="requires an operator"):
        repro.make_solver("p-bicgsafe")
    blocked = repro.make_solver(
        "p-bicgsafe", jax.vmap(op.matvec, in_axes=1, out_axes=1),
        blocked=True)
    with pytest.raises(ValueError, match="blocked"):
        blocked.solve(b)
    res = blocked.solve_many(jnp.stack([b, 2.0 * b], axis=1))
    assert bool(np.asarray(res.converged).all())
    with pytest.raises(ValueError, match=r"\(n, m\)"):
        repro.make_solver("p-bicgsafe", op).solve_many(b)


def test_session_cache_is_bounded(x64):
    """The content-keyed cache is LRU-bounded: churning operator content
    (time-stepping one-shots) must not pin every historical session."""
    from repro import api
    api.clear_session_cache()
    for i in range(api._SESSION_CACHE_MAX + 8):
        a = jnp.eye(4) * (2.0 + i)
        repro.make_solver("p-bicgsafe", repro.DenseOperator(a))
    assert api.session_cache_info()["sessions"] == api._SESSION_CACHE_MAX
    api.clear_session_cache()


def test_service_rejects_bare_callable_operator(x64):
    """The engine needs op.shape/dtype and content addressing; a bare
    matvec is rejected loudly at registration, not deep in submit."""
    from repro.service import ServiceConfig, SolveEngine
    op, _, _ = M.poisson3d(8)
    eng = SolveEngine(ServiceConfig())
    with pytest.raises(TypeError, match="content-addressable"):
        eng.register(op.matvec)


def test_batched_paths_require_pbicgsafe(x64):
    """The batched/open-loop iteration IS p-BiCGSafe; a session bound to
    another method must fail loudly on those entry points instead of
    silently running the wrong algorithm."""
    from repro.launch.mesh import make_mesh
    op, b, _ = M.convection_diffusion(8, peclet=1.0)
    session = repro.make_solver("bicgstab", op,
                                config=SolverConfig(maxiter=200))
    assert bool(session.solve(b).converged)        # single-RHS: fine
    B = jnp.stack([b, 2.0 * b], axis=1)
    with pytest.raises(ValueError, match="p-bicgsafe"):
        session.solve_many(B)
    with pytest.raises(ValueError, match="p-bicgsafe"):
        session.init(B)
    with pytest.raises(ValueError, match="p-bicgsafe"):
        session.on_mesh(make_mesh((1,), ("rows",))).solve_many(
            B.reshape(8, 8, 8, 2))


def test_mutable_operator_sessions_not_served_stale(x64):
    """A session over a writeable-numpy-backed operator must not stay
    findable after the backing array is mutated in place: such sessions
    are simply never cached (same immutability bar as the digest memo)."""
    a = np.diag(np.full(8, 2.0))
    s1 = repro.make_solver("p-bicgsafe", repro.DenseOperator(a))
    a *= 50.0                                  # mutate under the cache
    fresh = repro.DenseOperator(np.diag(np.full(8, 2.0)))
    s2 = repro.make_solver("p-bicgsafe", fresh)
    assert s2 is not s1, "stale session served for mutated content"
    x = np.asarray(s2.solve(jnp.ones(8)).x)
    np.testing.assert_allclose(x, 0.5)         # solves 2*x = 1, not 100*x


def test_fingerprint_not_memoized_for_mutable_operators(x64):
    """An operator backed by a writeable numpy array can be mutated in
    place under the caller's feet: its fingerprint must be re-hashed per
    call (no stale memo serving results for the OLD content)."""
    a = np.eye(6) * 3.0
    op = repro.DenseOperator(a)
    fp1 = repro.operator_fingerprint(op)
    a *= 2.0                                   # in-place mutation
    fp2 = repro.operator_fingerprint(op)
    assert fp1 != fp2, "mutated content must change the fingerprint"
    # immutable (jax-array-backed) operators ARE memoized: same digest,
    # and the repeat call is a dict hit (covered by the O(1) claim)
    op_j = repro.DenseOperator(jnp.asarray(a))
    assert repro.operator_fingerprint(op_j) == repro.operator_fingerprint(op_j)


def test_fingerprint_rejects_non_array_content(x64):
    with pytest.raises(TypeError, match="fingerprint"):
        repro.operator_fingerprint(lambda x: x)
    # the precond/base delegate keeps the historical import path alive
    from repro.precond import operator_fingerprint as legacy_fp
    op = M.poisson3d(8)[0]
    assert legacy_fp(op, "jacobi") == repro.operator_fingerprint(op, "jacobi")
