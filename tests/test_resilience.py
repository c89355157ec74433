"""Guarded solves: detection, recovery, and service resilience.

Pins the three layers of :mod:`repro.resilience`:

* detection — the guarded fused phase is ONE (11, m) reduction per
  iteration with NO dependency edge to the in-flight block matvec, on
  both substrates and (via subprocess) sharded across 8 devices with a
  single psum; the clean guarded path is numerically identical to the
  unguarded program;
* recovery — typed per-column :class:`~repro.core.SolveStatus` codes,
  restart-from-current-x, on-trigger residual replacement, substrate
  degradation, method fallback, and a finite-output guarantee, all
  driven by deterministic fault injection (:mod:`repro.resilience
  .inject`);
* serving — guarded engines retire typed statuses, scrub poisoned
  columns before the slot is reused, and re-enqueue failed requests
  with capped backoff.

Also the satellite regressions: zero right-hand sides across every
registered method, and typed statuses on the legacy shim results.
"""
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.analysis import (BindingSpec, find_while_body as _find_while_body,
                            reduction_consumes_matvec, tag_matvec,
                            tag_reduce, trace_fn)
import repro
from repro.core import SOLVERS, SolverConfig
from repro.core import matrices as M
from repro.core._common import SyncCounter
from repro.core.multirhs import GUARD_FIELDS, init_state, step_chunk
from repro.core.substrate import get_substrate
from repro.core.types import SolveStatus, identity_reduce
from repro.resilience import (ChunkFaultInjector, GuardedSolver,
                              RecoveryPolicy, SimulatedKernelFailure,
                              TickingClock, corrupt_engine_block,
                              near_singular_dense, orthogonal_shadow)
from repro.service import ServiceConfig, SolveEngine

HERE = os.path.dirname(__file__)


def _normalized_problem(n=64):
    """Well-conditioned dense problem with a unit-norm rhs (recovery
    scenarios anchor tolerances to ||b||)."""
    op, b, xt = M.nonsym_dense(n)
    b = b / jnp.linalg.norm(b)
    return op, b, xt


def _guarded(op, policy, *, substrate="jnp", config=SolverConfig(),
             inject=None):
    gs = repro.make_solver("p-bicgsafe", op, substrate=substrate,
                           config=config, recovery=policy)
    gs.inject = inject
    return gs


# ---------------------------------------------------------------------------
# detection: the guarded fused phase
# ---------------------------------------------------------------------------

def test_make_solver_recovery_returns_guarded(x64):
    op, _, _ = M.nonsym_dense(32)
    gs = repro.make_solver("p-bicgsafe", op, recovery=True)
    assert isinstance(gs, GuardedSolver)
    assert gs.session.config.guard
    assert isinstance(gs.policy, RecoveryPolicy)
    with pytest.raises(TypeError):
        repro.make_solver("p-bicgsafe", op, recovery="yes")


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
def test_guarded_single_reduction_per_iter(x64, substrate):
    """The guarded step body still traces exactly ONE dot_reduce — the
    fused phase widened from (9, m) to (11, m), not a second sync."""
    op, b, _ = M.nonsym_dense(64)
    m = 3
    B = jnp.stack([b, 0.5 * b, b + 1.0], axis=1)
    counter = SyncCounter(identity_reduce)
    sub = get_substrate(substrate)
    cfg = SolverConfig(guard=True)
    bmv = jax.vmap(op.matvec, in_axes=1, out_axes=1)
    state = init_state(bmv, B, config=cfg, substrate=sub)
    jaxpr = jax.make_jaxpr(lambda st: step_chunk(
        bmv, st, 8, config=cfg, dot_reduce=counter, substrate=sub))(state)
    assert counter.calls == 1, "guarded step must trace ONE dot_reduce"
    assert _find_while_body(jaxpr.jaxpr) is not None


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
def test_guarded_overlap_edge(x64, substrate):
    """Overlap invariant survives the guard: the (11, m) fused reduction
    has NO dependency path from the in-flight block matvec."""
    op, b, _ = M.nonsym_dense(64)
    sub = get_substrate(substrate)
    m = 3
    B = jnp.stack([b, 0.5 * b, b + 1.0], axis=1)
    bmv = tag_matvec(jax.vmap(op.matvec, in_axes=1, out_axes=1))
    cfg = SolverConfig(guard=True)

    state = init_state(bmv, B, config=cfg, substrate=sub)
    spec = BindingSpec(method="p-bicgsafe", substrate=str(substrate),
                      binding="open_loop", guard=True, m=m,
                      guard_effective=True)
    tb = trace_fn(lambda st: step_chunk(
        bmv, st, 8, config=cfg, dot_reduce=tag_reduce, substrate=sub),
        state, spec=spec)
    assert tb.body is not None
    reds = tb.reduce_eqns()
    assert len(reds) == 1, "fused (11, m) phase not found in step body"
    assert reds[0].invars[0].aval.shape == (11, m)
    edge, detail, _ = reduction_consumes_matvec(tb)
    assert not edge, (
        "the guarded fused reduction must keep NO dependency edge to "
        f"the in-flight block matvec (health rows ride the overlap): "
        f"{detail}")


@pytest.mark.slow
def test_guarded_sharded_single_psum():
    """8-way sharded guarded solve: still ONE psum/iter — the (11, m)
    block — with no edge to the halo exchange (subprocess probe)."""
    env = dict(os.environ)
    src = os.path.abspath(os.path.join(HERE, os.pardir, "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "_distributed_check.py"),
         "guarded"],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, \
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "GUARDED DISTRIBUTED SMOKE PASSED" in proc.stdout


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
def test_guarded_kernel_parity(x64, substrate):
    """The guarded state advanced on either substrate agrees: same
    iterates AND same health scalars (the pallas (11, m) kernel computes
    the same probe rows as the jnp reference)."""
    op, b, _ = M.nonsym_dense(64)
    B = jnp.stack([b, 2.0 * b], axis=1)
    cfg = SolverConfig(guard=True)
    bmv = jax.vmap(op.matvec, in_axes=1, out_axes=1)
    sub = get_substrate(substrate)
    ref = get_substrate("jnp")
    st = step_chunk(bmv, init_state(bmv, B, config=cfg, substrate=sub),
                    12, config=cfg, substrate=sub)
    rf = step_chunk(bmv, init_state(bmv, B, config=cfg, substrate=ref),
                    12, config=cfg, substrate=ref)
    for k in ("x", "r") + GUARD_FIELDS:
        np.testing.assert_allclose(
            np.asarray(st[k], dtype=np.float64),
            np.asarray(rf[k], dtype=np.float64),
            rtol=1e-10, atol=1e-12, err_msg=f"field {k}")


def test_guarded_clean_path_identical(x64):
    """A clean guarded solve takes the unguarded numerical path (the
    health rows observe, never write): same iteration count per column,
    same iterate up to XLA fusion-reordering round-off, zero recovery
    events, CONVERGED stamped everywhere."""
    op, b, _ = M.nonsym_dense(64)
    B = jnp.stack([b, 0.5 * b, b + 1.0], axis=1)
    cfg = SolverConfig(tol=1e-10, maxiter=400)
    plain = repro.make_solver("p-bicgsafe", op, config=cfg)
    gs = _guarded(op, RecoveryPolicy(), config=cfg)
    ref = plain.solve_many(B)
    res = gs.solve_many(B)
    assert gs.events == []
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(ref.x),
                               rtol=1e-12, atol=1e-13)
    assert np.array_equal(np.asarray(res.iterations),
                          np.asarray(ref.iterations))
    assert all(SolveStatus(int(s)) == SolveStatus.CONVERGED
               for s in np.asarray(res.status))


# ---------------------------------------------------------------------------
# recovery policies
# ---------------------------------------------------------------------------

def test_policy_validation():
    with pytest.raises(ValueError):
        RecoveryPolicy(chunk=0)
    with pytest.raises(ValueError):
        RecoveryPolicy(method_fallback="not-a-method")
    with pytest.raises(ValueError):
        RecoveryPolicy(max_restarts=-1)


def test_guarded_solver_rejects_wrong_sessions(x64):
    op, _, _ = M.nonsym_dense(32)
    with pytest.raises(ValueError, match="guarded session"):
        GuardedSolver(repro.make_solver("p-bicgsafe", op))
    with pytest.raises(ValueError, match="bicgstab"):
        GuardedSolver(repro.make_solver(
            "bicgstab", op, config=SolverConfig(guard=True)))


def test_nan_injection_restart_recovers(x64):
    """Poisoned residual mid-solve: the finiteness probe flags NONFINITE,
    the policy restarts from current x, and the recovered solution
    matches the clean solve."""
    op, b, _ = _normalized_problem()
    B = jnp.stack([b, 0.7 * b], axis=1)
    cfg = SolverConfig(tol=1e-8, maxiter=400)
    clean = repro.make_solver("p-bicgsafe", op, config=cfg).solve_many(B)
    inj = ChunkFaultInjector(nan_at={1: (0,)})
    gs = _guarded(op, RecoveryPolicy(chunk=8), config=cfg, inject=inj)
    res = gs.solve_many(B)
    assert inj.fired, "injector never fired"
    assert any(e["event"] == "restart" for e in gs.events)
    assert bool(np.asarray(res.converged).all())
    assert np.isfinite(np.asarray(res.x)).all()
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(clean.x),
                               rtol=1e-6, atol=1e-8)
    assert int(np.asarray(res.status)[0]) == SolveStatus.CONVERGED


def test_nan_without_recovery_is_typed_failure(x64):
    """With the restart budget at zero the poison surfaces as a typed
    NONFINITE failure — and x is STILL finite (sanitized, never NaN)."""
    op, b, _ = _normalized_problem()
    cfg = SolverConfig(tol=1e-8, maxiter=200)
    inj = ChunkFaultInjector(nan_at={1: (0,)})
    gs = _guarded(op, RecoveryPolicy(chunk=8, max_restarts=0,
                                     method_fallback=None),
                  config=cfg, inject=inj)
    res = gs.solve(b)
    assert SolveStatus(int(np.asarray(res.status))) == SolveStatus.NONFINITE
    assert not bool(np.asarray(res.converged))
    assert np.isfinite(np.asarray(res.x)).all()


def test_breakdown_restart_recovers(x64):
    """Orthogonal shadow residual: rho = (r0*, r0) = 0 trips the typed
    in-reduction BREAKDOWN_RHO at the first iteration; a restart (which
    re-seeds r0* = r0) then converges to the clean answer."""
    op, b, _ = _normalized_problem()
    cfg = SolverConfig(tol=1e-2, maxiter=300, breakdown_eps=1e-12)
    shadow = orthogonal_shadow(b)
    gs = _guarded(op, RecoveryPolicy(chunk=16, method_fallback=None),
                  config=cfg)
    res = gs.solve(b, r0_star=shadow)
    assert any(e["event"] == "restart" for e in gs.events)
    assert bool(np.asarray(res.converged))
    assert SolveStatus(int(np.asarray(res.status))) == SolveStatus.CONVERGED
    x = np.asarray(res.x)
    relres = float(np.linalg.norm(np.asarray(b) - np.asarray(
        op.matvec(jnp.asarray(x)))) / np.linalg.norm(np.asarray(b)))
    assert relres <= 1e-2 * 1.01


def test_breakdown_without_recovery_is_typed(x64):
    """Same scenario, no recovery: the result reports WHICH denominator
    broke (typed BREAKDOWN_RHO), finite x, no silent NaN."""
    op, b, _ = _normalized_problem()
    cfg = SolverConfig(tol=1e-2, maxiter=300, breakdown_eps=1e-12)
    gs = _guarded(op, RecoveryPolicy(chunk=16, max_restarts=0,
                                     method_fallback=None),
                  config=cfg)
    res = gs.solve(b, r0_star=orthogonal_shadow(b))
    assert SolveStatus(int(np.asarray(res.status))) == \
        SolveStatus.BREAKDOWN_RHO
    assert bool(np.asarray(res.breakdown))
    assert np.isfinite(np.asarray(res.x)).all()


def test_method_fallback_rescues_exhausted_column(x64):
    """Restart budget zero + shadow-induced breakdown: the per-column
    method fallback (BiCGSTAB) rescues the solve and logs the handoff."""
    op, b, _ = _normalized_problem()
    cfg = SolverConfig(tol=1e-2, maxiter=300, breakdown_eps=1e-12)
    gs = _guarded(op, RecoveryPolicy(chunk=16, max_restarts=0,
                                     method_fallback="bicgstab"),
                  config=cfg)
    res = gs.solve(b, r0_star=orthogonal_shadow(b))
    fb = [e for e in gs.events if e["event"] == "method_fallback"]
    assert fb and fb[0]["method"] == "bicgstab"
    assert fb[0]["from_status"] == "BREAKDOWN_RHO"
    assert bool(np.asarray(res.converged))
    assert SolveStatus(int(np.asarray(res.status))) == SolveStatus.CONVERGED


def test_kernel_failure_degrades_substrate(x64):
    """A kernel-level failure on the pallas path degrades the session to
    the jnp substrate and finishes from the SAME state pytree."""
    op, b, _ = _normalized_problem()
    cfg = SolverConfig(tol=1e-8, maxiter=400)
    inj = ChunkFaultInjector(fail_at=(1,))
    gs = _guarded(op, RecoveryPolicy(chunk=8), substrate="pallas",
                  config=cfg, inject=inj)
    res = gs.solve(b)
    deg = [e for e in gs.events if e["event"] == "substrate_degraded"]
    assert deg and deg[0]["detail"]["to"] == "jnp"
    assert gs._active.sub.name == "jnp"
    assert bool(np.asarray(res.converged))
    assert np.isfinite(np.asarray(res.x)).all()


def test_compile_failure_raises_instead_of_degrading(x64):
    """A step program the backend refuses to build is a defect: it
    propagates, where a runtime fault of a compiled program degrades."""
    from repro.core.substrate import PallasSubstrate

    class Refused(PallasSubstrate):
        def axpy_phase(self, vecs, scalars, mask=None):
            raise RuntimeError("Mosaic refused the update kernel")

    op, b, _ = _normalized_problem()
    gs = _guarded(op, RecoveryPolicy(chunk=8), substrate=Refused(),
                  config=SolverConfig(tol=1e-8, maxiter=100))
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        gs.solve(b)
    assert not [e for e in gs.events if e["event"] == "substrate_degraded"]


def test_kernel_failure_without_fallback_raises(x64):
    op, b, _ = _normalized_problem()
    inj = ChunkFaultInjector(fail_at=(0,))
    gs = _guarded(op, RecoveryPolicy(substrate_fallback=False),
                  substrate="pallas",
                  config=SolverConfig(tol=1e-8, maxiter=100), inject=inj)
    with pytest.raises(SimulatedKernelFailure):
        gs.solve(b)


def test_drift_trigger_replaces_residual(x64):
    """An artificially tight drift threshold fires the on-trigger
    replacement (r <- B - A x, recomputed derived vectors); the solve
    still converges and the events are audited per column."""
    op, b, _ = _normalized_problem()
    cfg = SolverConfig(tol=1e-8, maxiter=400)
    gs = _guarded(op, RecoveryPolicy(chunk=8, drift_scale=1e-12),
                  config=cfg)
    res = gs.solve(b)
    rep = [e for e in gs.events if e["event"] == "replace"]
    assert rep, "tightened drift bound must trigger replacement"
    assert all(e["columns"] == [0] for e in rep)
    assert bool(np.asarray(res.converged))


def test_stagnation_gives_up_typed(x64):
    """A column that cannot reach tol: stagnation restarts burn out, then
    the driver stamps typed STAGNATION instead of spinning forever."""
    op = near_singular_dense(48, sigma_min=1e-14)
    b = jnp.ones((48,), jnp.float64)
    b = b / jnp.linalg.norm(b)
    cfg = SolverConfig(tol=1e-13, maxiter=4000)
    gs = _guarded(op, RecoveryPolicy(chunk=32, stagnation_window=64,
                                     max_restarts=1, method_fallback=None),
                  config=cfg)
    res = gs.solve(b)
    sts = SolveStatus(int(np.asarray(res.status)))
    assert sts.is_failure or bool(np.asarray(res.converged))
    assert np.isfinite(np.asarray(res.x)).all()
    assert np.isfinite(float(np.asarray(res.relres))) or \
        float(np.asarray(res.relres)) == np.inf
    if sts == SolveStatus.STAGNATION:
        assert any(e["event"] == "stagnation_giveup" for e in gs.events)


def test_near_singular_never_silent_nan(x64):
    """Near-singular operator, no recovery: whatever the typed outcome,
    the guarded surface never leaks NaN."""
    op = near_singular_dense(48, sigma_min=1e-15)
    b = jnp.ones((48,), jnp.float64)
    gs = _guarded(op, RecoveryPolicy(max_restarts=0, method_fallback=None,
                                     chunk=16),
                  config=SolverConfig(tol=1e-12, maxiter=500,
                                      breakdown_eps=1e-12))
    res = gs.solve(b)
    assert np.isfinite(np.asarray(res.x)).all()
    assert SolveStatus(int(np.asarray(res.status))).is_terminal


# ---------------------------------------------------------------------------
# satellites: zero rhs across every method, legacy shim statuses
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", sorted(SOLVERS))
def test_zero_rhs_regression(x64, method):
    """b = 0 must return x = 0, converged in 0 iterations, relres 0 —
    not a 0/0 NaN out of the ||r0|| normalization (regression: every
    registered method)."""
    op, b, _ = M.nonsym_dense(48)
    res = repro.make_solver(method, op).solve(jnp.zeros_like(b))
    assert bool(np.asarray(res.converged))
    assert int(np.asarray(res.iterations)) == 0
    assert float(np.abs(np.asarray(res.x)).max()) == 0.0
    assert float(np.asarray(res.relres)) == 0.0
    assert SolveStatus(int(np.asarray(res.status))) == SolveStatus.CONVERGED


def test_zero_rhs_batched_mixed_columns(x64):
    """A zero column riding next to live columns converges instantly
    without perturbing its neighbours."""
    op, b, _ = M.nonsym_dense(48)
    B = jnp.stack([b, jnp.zeros_like(b), 2.0 * b], axis=1)
    sess = repro.make_solver("p-bicgsafe", op,
                             config=SolverConfig(tol=1e-8, maxiter=300))
    res = sess.solve_many(B)
    assert bool(np.asarray(res.converged).all())
    assert int(np.asarray(res.iterations)[1]) == 0
    assert float(np.abs(np.asarray(res.x)[:, 1]).max()) == 0.0
    ref = sess.solve_many(b[:, None])
    np.testing.assert_allclose(np.asarray(res.x[:, 0]),
                               np.asarray(ref.x[:, 0]), rtol=1e-8)


def test_legacy_shims_carry_typed_status(x64):
    """The deprecated free-function entry points fill SolveResult.status
    (satellite: typed statuses are universal, not guarded-only)."""
    from repro import core as C
    op, b, _ = M.nonsym_dense(48)
    for name in ("pbicgsafe_solve", "bicgstab_solve", "cgs_solve",
                 "gpbicg_solve", "pbicgstab_solve", "ssbicgsafe2_solve",
                 "pbicgsafe_rr_solve"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            res = getattr(C, name)(op.matvec, b)
        sts = SolveStatus(int(np.asarray(res.status)))
        assert sts == SolveStatus.CONVERGED, (name, sts)


# ---------------------------------------------------------------------------
# service-level resilience
# ---------------------------------------------------------------------------

def _guarded_engine(op, *, recovery=RecoveryPolicy(), clock=None,
                    max_batch=3, chunk=8, tol=1e-8, maxiter=600):
    kw = {} if clock is None else dict(clock=clock)
    eng = SolveEngine(ServiceConfig(max_batch=max_batch, chunk=chunk,
                                    tol=tol, maxiter=maxiter,
                                    recovery=recovery), **kw)
    name = eng.register(op)
    return eng, name


def test_engine_clean_guarded_traffic(x64):
    """Guarded serving on clean traffic: every result typed CONVERGED,
    zero retries, same answers as standalone."""
    op, b, _ = _normalized_problem()
    eng, name = _guarded_engine(op)
    rids = [eng.submit(name, np.asarray(v))
            for v in (b, 0.5 * b, b + 0.1, 2.0 * b)]
    out = {r.rid: r for r in eng.run()}
    assert sorted(out) == sorted(rids)
    for r in out.values():
        assert r.status == SolveStatus.CONVERGED
        assert r.retries == 0
        assert r.converged
        assert np.isfinite(r.x).all()


def test_engine_corruption_scrub_and_retry(x64):
    """Mid-flight NaN corruption: the guarded chunk surfaces NONFINITE,
    the poisoned column is scrubbed before reuse, the victim request is
    re-enqueued and completes on retry — and the resident block stays
    finite throughout."""
    op, b, _ = _normalized_problem()
    eng, name = _guarded_engine(op, recovery=RecoveryPolicy(max_retries=1))
    rids = [eng.submit(name, np.asarray(v)) for v in (b, 0.6 * b)]
    first = eng.poll()                       # block resident, one chunk in
    assert not first
    corrupt_engine_block(eng, name, cols=[0])
    out = {r.rid: r for r in eng.run()}
    assert sorted(out) == sorted(rids)
    retried = [r for r in out.values() if r.retries > 0]
    assert retried, "corrupted request must be retried"
    for r in out.values():
        assert r.converged, (r.rid, r.status)
        assert r.status == SolveStatus.CONVERGED
        assert np.isfinite(r.x).all()
    blk = eng._blocks[name]
    if blk is not None and blk.state is not None:
        assert np.isfinite(np.asarray(
            jax.device_get(blk.state["x"]))).all(), \
            "resident block must stay finite after the scrub"


def test_engine_corruption_retries_exhausted_is_typed(x64):
    """max_retries=0: the corrupted request retires once with its typed
    NONFINITE status and a finite (sanitized) iterate."""
    op, b, _ = _normalized_problem()
    eng, name = _guarded_engine(op, recovery=RecoveryPolicy(max_retries=0))
    rid = eng.submit(name, np.asarray(b))
    assert not eng.poll()
    corrupt_engine_block(eng, name, cols=[0])
    out = {r.rid: r for r in eng.run()}
    r = out[rid]
    assert r.status == SolveStatus.NONFINITE
    assert not r.converged
    assert r.retries == 0
    assert np.isfinite(r.x).all()


def test_engine_deadline_is_typed(x64):
    """Deadline expiry under a virtual clock retires with the typed
    DEADLINE status (queued-only AND mid-flight)."""
    op, b, _ = _normalized_problem()
    clock = TickingClock(dt=0.05)
    eng, name = _guarded_engine(op, clock=clock, maxiter=2000, tol=1e-14)
    rid_ok = eng.submit(name, np.asarray(b), tol=1e-6)
    rid_dead = eng.submit(name, np.asarray(0.5 * b), deadline=0.01)
    clock.advance(1.0)
    out = {r.rid: r for r in eng.run()}
    assert out[rid_dead].status == SolveStatus.DEADLINE
    assert out[rid_dead].telemetry.deadline_exceeded
    assert out[rid_ok].status == SolveStatus.CONVERGED


def test_engine_retry_backoff_window(x64):
    """A re-enqueued request inside its backoff window rotates at the
    back of the queue instead of being dropped, and still completes."""
    op, b, _ = _normalized_problem()
    clock = TickingClock(dt=0.001)
    eng, name = _guarded_engine(
        op, clock=clock,
        recovery=RecoveryPolicy(max_retries=2, retry_backoff_s=0.5,
                                retry_backoff_cap_s=2.0))
    rid = eng.submit(name, np.asarray(b))
    assert not eng.poll()
    corrupt_engine_block(eng, name, cols=[0])
    out = {r.rid: r for r in eng.run()}
    r = out[rid]
    assert r.retries >= 1
    assert r.converged and r.status == SolveStatus.CONVERGED
