"""Tests for the continuous-batching solve service (repro.service).

The engine multiplexes heterogeneous requests onto one resident
(n, max_batch) block; these tests pin its three contracts:

* correctness — every multiplexed request returns the same
  x / iterations / converged (to tolerance) as a standalone
  ``solve_batched`` call, including requests that enter via mid-flight
  refill, on both substrates (deterministic + hypothesis property tests);
* communication — the engine's step program issues exactly ONE
  ``dot_reduce`` per iteration with NO dependency edge from the fused
  (9, m) reduction to the in-flight block matvec, on both substrates
  (contract probes via repro.analysis);
* retirement — a request retires CONVERGED only once its true residual
  ``b - A x`` meets its tol; a column the recurrence fooled is
  residual-replaced and resumes;
* caching — re-registering an operator with equal content reuses the
  built preconditioner AND the compiled step programs (fingerprint
  cache).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.analysis import (BindingSpec, find_while_body as _find_while_body,
                            reduction_consumes_matvec, tag_matvec,
                            tag_reduce, trace_fn)
from repro.api import make_solver
from repro.core import SolverConfig, solve_batched
from repro.core import matrices as M
from repro.core._common import SyncCounter
from repro.core.multirhs import init_state, step_chunk
from repro.core.substrate import get_substrate
from repro.core.types import SolveStatus, identity_reduce
from repro.service import ServiceConfig, SolveEngine


def _standalone(op, b, tol, maxiter, substrate="jnp", precond=None):
    return solve_batched(
        op, jnp.asarray(b)[:, None],
        config=SolverConfig(tol=tol, maxiter=maxiter),
        substrate=substrate, precond=precond)


def _check_request(r, ref, *, rtol=1e-6, atol=1e-8, iter_slack=1):
    """Engine column == standalone solve_batched column, to tolerance."""
    assert r.converged == bool(ref.converged[0]), (
        f"rid {r.rid}: engine converged={r.converged}, "
        f"standalone={bool(ref.converged[0])}")
    assert abs(r.iterations - int(ref.iterations[0])) <= iter_slack, (
        f"rid {r.rid}: iterations {r.iterations} vs "
        f"{int(ref.iterations[0])}")
    np.testing.assert_allclose(r.x, np.asarray(ref.x[:, 0]),
                               rtol=rtol, atol=atol,
                               err_msg=f"rid {r.rid}")


# ---------------------------------------------------------------------------
# engine == standalone, with mid-flight refill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
def test_engine_matches_standalone_with_refill(x64, substrate):
    """More requests than slots, heterogeneous tolerances and budgets,
    two operators (one preconditioned): every request must reproduce its
    standalone solve — including the ones that entered via mid-flight
    splice (N > max_batch and staggered finish times force refills)."""
    op1, b1, _ = M.poisson3d(8)
    op2, b2, _ = M.convection_diffusion(8, peclet=1.0)
    eng = SolveEngine(ServiceConfig(max_batch=3, chunk=4, tol=1e-8,
                                    maxiter=400, substrate=substrate))
    eng.register(op1, name="poisson")
    eng.register(op2, precond="jacobi", name="convdiff")

    rng = np.random.default_rng(7)
    tols = [1e-4, 1e-8, 1e-10]
    reqs = []
    for i in range(8):
        opn = "poisson" if i % 2 == 0 else "convdiff"
        b = jnp.asarray(rng.standard_normal(512))
        tol = tols[i % 3]
        rid = eng.submit(opn, b, tol=tol, maxiter=300)
        reqs.append((rid, opn, b, tol))

    results = {r.rid: r for r in eng.run()}
    assert len(results) == len(reqs)
    assert not eng.has_work()
    # 8 requests through 3+3 slots: refills necessarily happened
    for rid, opn, b, tol in reqs:
        op = op1 if opn == "poisson" else op2
        pc = None if opn == "poisson" else "jacobi"
        ref = _standalone(op, b, tol, 300, substrate=substrate, precond=pc)
        _check_request(results[rid], ref)


def test_engine_per_request_maxiter_and_deadline(x64):
    """Per-request budgets: a maxiter-capped request retires unconverged
    at exactly its budget (device-enforced); a deadline-blown request
    retires at the next chunk boundary with the partial iterate and the
    telemetry flag set; a queued request whose deadline lapses before a
    slot frees never occupies one."""
    t = [0.0]
    op, b, _ = M.hard_nonsym(200)       # slow enough to outlive deadlines
    eng = SolveEngine(ServiceConfig(max_batch=2, chunk=4, maxiter=10_000),
                      clock=lambda: t[0])
    eng.register(op, name="hard")
    rid_budget = eng.submit("hard", b, maxiter=50)
    rid_deadline = eng.submit("hard", b, deadline=0.5)
    rid_expired = eng.submit("hard", 2.0 * b, deadline=0.1)  # queued-only

    out = []
    while eng.has_work():
        out.extend(eng.poll())
        t[0] += 0.2
    res = {r.rid: r for r in out}
    assert len(res) == 3

    assert not res[rid_budget].converged
    assert res[rid_budget].iterations == 50
    assert not res[rid_budget].telemetry.deadline_exceeded

    assert not res[rid_deadline].converged
    assert res[rid_deadline].telemetry.deadline_exceeded
    assert res[rid_deadline].iterations > 0          # partial progress

    assert res[rid_expired].telemetry.deadline_exceeded
    assert res[rid_expired].iterations == 0
    assert res[rid_expired].telemetry.chunks_resident == 0


def test_engine_telemetry(x64):
    """Telemetry fields are populated and consistent."""
    op, b, _ = M.poisson3d(8)
    eng = SolveEngine(ServiceConfig(max_batch=2, chunk=8, maxiter=200))
    eng.register(op, name="p")
    rids = [eng.submit("p", jnp.asarray(v))
            for v in np.random.default_rng(1).standard_normal((5, 512))]
    res = {r.rid: r for r in eng.run()}
    assert len(res) == 5
    for rid in rids:
        tel = res[rid].telemetry
        assert tel.chunks_resident >= 1
        assert tel.queue_wait_s >= 0.0
        assert tel.wall_s >= tel.service_s >= 0.0
        assert not tel.deadline_exceeded
    # 5 requests / 2 slots: the late ones waited in the queue
    waits = sorted(res[r].telemetry.queue_wait_s for r in rids)
    assert waits[-1] > waits[0]


# (the hypothesis property test over random request streams lives in
# tests/test_service_properties.py so this module still runs when
# hypothesis is absent — same split as test_precond_properties.py)


# ---------------------------------------------------------------------------
# communication structure of the engine's step program
# ---------------------------------------------------------------------------

def _engine_entry(op, substrate, max_batch=3, chunk=8, precond=None):
    eng = SolveEngine(ServiceConfig(max_batch=max_batch, chunk=chunk,
                                    substrate=substrate))
    name = eng.register(op, precond=precond)
    return eng.registry[name]


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
def test_engine_step_single_reduction_per_iter(x64, substrate):
    """The engine's step program performs exactly ONE dot_reduce in its
    iteration body — the (9, m) fused block — for any resident mix."""
    op, b, _ = M.nonsym_dense(64)
    entry = _engine_entry(op, substrate)
    m = 3
    B = jnp.stack([b, 0.5 * b, b + 1.0], axis=1)
    counter = SyncCounter(identity_reduce)
    sub = get_substrate(substrate)
    bmv = entry.bmv
    state = init_state(bmv, B, substrate=sub)
    jaxpr = jax.make_jaxpr(lambda st: step_chunk(
        bmv, st, 8, dot_reduce=counter, substrate=sub))(state)
    assert counter.calls == 1, "step body must trace ONE dot_reduce"
    body = _find_while_body(jaxpr.jaxpr)
    assert body is not None, "step program must be one while_loop"


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
@pytest.mark.parametrize("precond", [None, "block_jacobi"])
def test_engine_step_overlap_edge(x64, substrate, precond):
    """The engine step program keeps the paper's overlap invariant: the
    (9, m) fused reduction has NO dependency path from the in-flight
    block matvec (preconditioned or not) — multiplexing requests must not
    serialize the reduction behind the SpMV."""
    from repro.precond import resolve_precond
    op, b, _ = M.nonsym_dense(64)
    sub = get_substrate(substrate)
    m = 3
    B = jnp.stack([b, 0.5 * b, b + 1.0], axis=1)

    # the engine's composed block matvec (M^{-1} ∘ A), tagged with the
    # repro.analysis probe tags
    base = jax.vmap(op.matvec, in_axes=1, out_axes=1)
    tagged = tag_matvec(base)
    pc = resolve_precond(precond, op)
    if pc is not None:
        papply = sub.as_precond_apply(pc)
        bmv = lambda X: papply(tagged(X))  # noqa: E731
        Bp = papply(B)
    else:
        bmv, Bp = tagged, B

    state = init_state(bmv, Bp, substrate=sub)
    spec = BindingSpec(method="p-bicgsafe", substrate=str(substrate),
                      binding="open_loop", precond=precond, m=m)
    tb = trace_fn(lambda st: step_chunk(
        bmv, st, 8, dot_reduce=tag_reduce, substrate=sub), state, spec=spec)
    assert tb.body is not None
    reds = tb.reduce_eqns()
    assert len(reds) == 1, "fused (9, m) phase not found in step body"
    assert reds[0].invars[0].aval.shape == (9, m)
    edge, detail, _ = reduction_consumes_matvec(tb)
    assert not edge, (
        "the engine step's fused reduction must keep NO dependency edge "
        f"to the in-flight block matvec (comm-hiding under load): {detail}")


def test_engine_kernel_backed_assertion(x64):
    """The pallas-substrate service path is kernel-backed."""
    op, _, _ = M.poisson3d(8)
    entry = _engine_entry(op, "pallas")
    assert entry.kernel_backed
    assert not _engine_entry(op, "jnp").kernel_backed


# ---------------------------------------------------------------------------
# registry: fingerprint-keyed reuse
# ---------------------------------------------------------------------------

def test_registry_fingerprint_reuses_precond_and_programs(x64):
    """Re-registering equal content returns the SAME entry: the built
    preconditioner and the compiled step programs are reused (repeat
    traffic against the same A must not rebuild/retrace)."""
    eng = SolveEngine(ServiceConfig(max_batch=2))
    op_a = M.poisson3d(8)[0]
    op_b = M.poisson3d(8)[0]            # equal content, fresh object
    assert op_a is not op_b
    n1 = eng.register(op_a, precond="block_jacobi", name="A")
    n2 = eng.register(op_b, precond="block_jacobi")       # cache hit
    e1, e2 = eng.registry[n1], eng.registry[n2]
    assert e1 is e2
    assert e1.precond is e2.precond
    assert e1.step_fn is e2.step_fn
    assert len(eng.registry.entries()) == 1

    # different precond spec or different content: distinct entries
    n3 = eng.register(op_a, precond="jacobi")
    assert eng.registry[n3] is not e1
    n4 = eng.register(M.poisson3d(10)[0], precond="block_jacobi")
    assert eng.registry[n4] is not e1
    assert len(eng.registry.entries()) == 3

    # name collision with different content is loud
    with pytest.raises(ValueError, match="different content"):
        eng.register(M.convection_diffusion(8)[0], name="A")


def test_registry_unknown_operator_is_loud(x64):
    eng = SolveEngine(ServiceConfig())
    with pytest.raises(KeyError, match="unknown operator"):
        eng.submit("nope", jnp.ones((8,)))


def test_submit_validates_rhs_shape(x64):
    eng = SolveEngine(ServiceConfig())
    name = eng.register(M.poisson3d(8)[0], name="p")
    with pytest.raises(ValueError, match="shape"):
        eng.submit(name, jnp.ones((7,)))


# ---------------------------------------------------------------------------
# retirement: a converged column's true residual is verified
# ---------------------------------------------------------------------------

def test_verify_columns_replaces_only_the_fooled_column(x64):
    """A column whose recurrence claims convergence while its true
    residual misses tol is un-converged and residual-replaced; the other
    columns pass through bitwise; resumed, it converges for real."""
    op, _, _ = M.poisson3d(8)
    sess = make_solver("p-bicgsafe", op, config=SolverConfig(tol=1e-8))
    B = jnp.asarray(np.random.default_rng(3).standard_normal((op.n, 3)))
    st = sess.step_chunk(sess.init(B), 400)
    assert np.asarray(st["converged"]).all()
    # what a drifted recurrence leaves behind: x off, r still "converged"
    st["x"] = st["x"].at[:, 1].add(1e-3)
    mask = np.ones(3, bool)
    out, true = sess.verify(st, B, mask)
    true = np.asarray(true)
    assert (true[[0, 2]] <= 1e-8).all() and true[1] > 1e-8
    np.testing.assert_array_equal(np.asarray(out["converged"]),
                                  [True, False, True])
    assert float(out["relres"][1]) == pytest.approx(true[1])
    r1 = np.asarray(B[:, 1] - op.matvec(st["x"][:, 1]))
    np.testing.assert_allclose(np.asarray(out["r"][:, 1]), r1,
                               rtol=1e-12, atol=1e-15)
    for k in ("x", "r", "s", "l", "g", "w", "p", "u"):
        np.testing.assert_array_equal(np.asarray(out[k][:, [0, 2]]),
                                      np.asarray(st[k][:, [0, 2]]))

    out = sess.step_chunk(out, 400)
    out, true = sess.verify(out, B, mask)
    assert np.asarray(out["converged"]).all()
    assert (np.asarray(true) <= 1e-8).all()
    x1 = np.asarray(out["x"][:, 1])
    assert np.linalg.norm(np.asarray(B[:, 1]) - np.asarray(
        op.matvec(jnp.asarray(x1)))) <= 1e-8 * np.linalg.norm(B[:, 1])


@pytest.mark.parametrize("guarded", [False, True])
def test_engine_retires_converged_only_with_verified_residual(x64,
                                                              guarded):
    """A request whose recurrence converged with a bad iterate is not
    handed back CONVERGED: the engine replaces its residual, keeps it
    resident, and retires it once b - A x truly meets tol."""
    from repro.observe import metrics as _metrics
    from repro.resilience import RecoveryPolicy

    op, _, _ = M.poisson3d(8)
    scfg = ServiceConfig(max_batch=2, chunk=8, tol=1e-8, maxiter=400,
                         recovery=RecoveryPolicy() if guarded else None)
    eng = SolveEngine(scfg)
    name = eng.register(op, name="p")
    entry = eng.registry[name]
    step = entry.step_fn
    fooled = []

    def step_then_drift(st):
        st = step(st)
        if not fooled and bool(st["converged"][0]):
            fooled.append(True)           # once: column 0 drifts off
            st = dict(st, x=st["x"].at[:, 0].add(1e-3))
        return st

    entry.step_fn = step_then_drift
    rng = np.random.default_rng(5)
    bs = [rng.standard_normal(op.n) for _ in range(2)]
    rids = [eng.submit(name, b) for b in bs]
    before = _metrics.ENGINE_REPLACEMENTS.value()
    res = {r.rid: r for r in eng.run()}
    assert fooled
    assert _metrics.ENGINE_REPLACEMENTS.value() == before + 1
    for rid, b in zip(rids, bs):
        r = res[rid]
        assert r.status == SolveStatus.CONVERGED and r.converged
        true = np.linalg.norm(b - np.asarray(op.matvec(jnp.asarray(r.x))))
        true /= np.linalg.norm(b)
        assert true <= 1e-8
        assert r.relres == pytest.approx(true, rel=1e-6)
