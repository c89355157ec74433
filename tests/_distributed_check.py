"""Multi-device solver checks — run in a subprocess with 8 fake devices.

Invoked by tests/test_distributed.py.  Exits nonzero on any failure.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (SolverConfig, bicgstab_solve, gpbicg_solve,  # noqa: E402
                        pbicgsafe_rr_solve, pbicgsafe_solve, pbicgstab_solve,
                        solve_batched, ssbicgsafe2_solve)
from repro.core import matrices as M  # noqa: E402
from repro.core.distributed import (distributed_stencil_solve,  # noqa: E402
                                    distributed_stencil_solve_batched)
from repro.launch.mesh import make_mesh  # noqa: E402


def check(mesh_shape, axis_names, solver, op, b_grid, ref_iters, xt):
    mesh = make_mesh(mesh_shape, axis_names)
    res = distributed_stencil_solve(solver, op, b_grid, mesh,
                                    config=SolverConfig(tol=1e-8))
    it = int(res.iterations)
    assert bool(res.converged), f"{axis_names}: not converged"
    err = float(jnp.linalg.norm(res.x.reshape(-1) - xt) / jnp.linalg.norm(xt))
    assert err < 1e-6, f"{axis_names}: err {err}"
    # Same math => same iteration count modulo rounding: sharded partial
    # sums reduce in a different order than a single global sum, which can
    # shift the stopping iteration by a few when relres hovers at tol.
    assert abs(it - ref_iters) <= max(3, int(0.2 * ref_iters)), \
        f"{axis_names}: iters {it} vs {ref_iters}"
    print(f"  ok mesh={mesh_shape} axes={axis_names} "
          f"solver={solver.__module__.split('.')[-1]} iters={it} err={err:.1e}")


def check_batched(mesh_shape, axis_names, op, b, substrate):
    """Sharded multi-RHS solve: every column reproduces the local batched
    solve; one (9, m) psum per iteration (asserted in-process by
    tests/test_substrate_parity.py; here we check the numbers)."""
    m = 3
    keys = jax.random.split(jax.random.PRNGKey(11), m)
    B = jnp.stack([b] + [jax.random.normal(k, b.shape, b.dtype)
                         for k in keys[1:]], axis=1)
    cfg = SolverConfig(tol=1e-8, maxiter=2000)
    ref = solve_batched(op.matvec, B, config=cfg)
    mesh = make_mesh(mesh_shape, axis_names)
    res = distributed_stencil_solve_batched(
        op, B.reshape(op.nx, op.ny, op.nz, m), mesh, config=cfg,
        substrate=substrate)
    assert bool(np.asarray(res.converged).all()), \
        f"batched {axis_names}/{substrate}: not converged"
    for j in range(m):
        xj = res.x.reshape(-1, m)[:, j]
        true = float(jnp.linalg.norm(B[:, j] - op.matvec(xj))
                     / jnp.linalg.norm(B[:, j]))
        assert true < 1e-6, (j, true)
        assert abs(int(res.iterations[j]) - int(ref.iterations[j])) \
            <= max(3, int(0.2 * int(ref.iterations[j])))
    print(f"  ok batched mesh={mesh_shape} axes={axis_names} "
          f"substrate={substrate} iters={np.asarray(res.iterations)}")


from repro.analysis import eqn_needs_ppermute as _eqn_needs_ppermute  # noqa: E402
from repro.analysis import find_while_body as _find_while_body  # noqa: E402


def check_batched_structure(op, b):
    """8-way sharded batched solve, jaxpr level: the while body holds
    EXACTLY ONE psum (the (9, m) block), halo ppermutes are present, and
    the psum's transitive inputs contain NO ppermute — the reduction has
    no dependency edge to the in-flight block matvec, so the overlap
    survives batching+sharding."""
    m = 3
    B_grid = jnp.stack([b * (j + 1) for j in range(m)],
                       axis=1).reshape(op.nx, op.ny, op.nz, m)
    mesh = make_mesh((8,), ("rows",))
    jaxpr = jax.make_jaxpr(lambda BB: distributed_stencil_solve_batched(
        op, BB, mesh, config=SolverConfig(maxiter=10), jit=False))(B_grid)
    body = _find_while_body(jaxpr.jaxpr)
    assert body is not None, "no while loop found"

    psums = [e for e in body.eqns if e.primitive.name == "psum"]
    assert len(psums) == 1, f"want ONE psum/iter, got {len(psums)}"
    psum_eqn = psums[0]
    assert psum_eqn.invars[0].aval.shape == (9, m), \
        psum_eqn.invars[0].aval.shape

    permute_outs, needs = _eqn_needs_ppermute(body, psum_eqn)
    assert permute_outs, "no halo ppermutes in the loop body"
    assert not needs, \
        "the (9, m) reduction transitively consumes the halo exchange"
    print(f"  ok batched structure: 1 psum/iter of (9, {m}), "
          f"{len(permute_outs)} halo ppermute outputs, no edge to psum")


def check_precond_structure(op, b):
    """Preconditioning must not change the communication structure: the
    8-way sharded p-BiCGSafe while body with shard-local block-Jacobi
    still holds EXACTLY ONE psum (the (9,) stacked partials) and the
    psum's transitive inputs contain NO ppermute — the M^{-1}-apply rides
    inside the overlap window without adding or serializing collectives."""
    mesh = make_mesh((8,), ("rows",))
    bodies = {}
    for pc in (None, "block_jacobi"):
        jaxpr = jax.make_jaxpr(lambda bb: distributed_stencil_solve(
            pbicgsafe_solve, op, bb, mesh, config=SolverConfig(maxiter=10),
            precond=pc, jit=False))(b.reshape(op.nx, op.ny, op.nz))
        body = _find_while_body(jaxpr.jaxpr)
        assert body is not None, f"no while loop (precond={pc})"
        bodies[pc] = body

    counts = {}
    for pc, body in bodies.items():
        psums = [e for e in body.eqns if e.primitive.name == "psum"]
        counts[pc] = len(psums)
        assert len(psums) == 1, \
            f"precond={pc}: want ONE psum/iter, got {len(psums)}"
        psum_eqn = psums[0]
        assert psum_eqn.invars[0].aval.shape == (9,), \
            psum_eqn.invars[0].aval.shape
        permute_outs, needs = _eqn_needs_ppermute(body, psum_eqn)
        assert permute_outs, f"precond={pc}: no halo ppermutes in body"
        assert not needs, \
            f"precond={pc}: the reduction consumes the halo exchange"
    assert counts[None] == counts["block_jacobi"], counts
    print("  ok precond structure: single-psum-per-iteration count "
          f"unchanged by block-Jacobi ({counts[None]} == "
          f"{counts['block_jacobi']}), no edge to the halo exchange")


def check_precond_numeric(mesh_shape, axis_names, op, b_grid, xt):
    """Shard-local block-Jacobi converges in <= the unpreconditioned
    iterations and still solves the ORIGINAL system."""
    mesh = make_mesh(mesh_shape, axis_names)
    cfg = SolverConfig(tol=1e-8)
    plain = distributed_stencil_solve(pbicgsafe_solve, op, b_grid, mesh,
                                      config=cfg)
    prec = distributed_stencil_solve(pbicgsafe_solve, op, b_grid, mesh,
                                     config=cfg, precond="block_jacobi")
    assert bool(prec.converged), f"{axis_names}: preconditioned not converged"
    err = float(jnp.linalg.norm(prec.x.reshape(-1) - xt)
                / jnp.linalg.norm(xt))
    assert err < 1e-6, f"{axis_names}: err {err}"
    assert int(prec.iterations) <= int(plain.iterations), \
        (int(prec.iterations), int(plain.iterations))
    print(f"  ok precond mesh={mesh_shape} axes={axis_names} "
          f"block-Jacobi iters={int(prec.iterations)} <= "
          f"plain {int(plain.iterations)}, err={err:.1e}")


def check_guarded_structure(op, b):
    """Guarded + sharded: the health rows widen the fused block from
    (9, m) to (11, m) but the communication structure is untouched —
    EXACTLY ONE psum per iteration, halo ppermutes present, and the
    reduction's transitive inputs contain NO ppermute (the in-reduction
    breakdown detection costs zero extra synchronizations even across
    8 devices)."""
    m = 3
    B_grid = jnp.stack([b * (j + 1) for j in range(m)],
                       axis=1).reshape(op.nx, op.ny, op.nz, m)
    mesh = make_mesh((8,), ("rows",))
    cfg = SolverConfig(maxiter=10, guard=True)
    jaxpr = jax.make_jaxpr(lambda BB: distributed_stencil_solve_batched(
        op, BB, mesh, config=cfg, jit=False))(B_grid)
    body = _find_while_body(jaxpr.jaxpr)
    assert body is not None, "no while loop found"

    psums = [e for e in body.eqns if e.primitive.name == "psum"]
    assert len(psums) == 1, f"want ONE psum/iter, got {len(psums)}"
    psum_eqn = psums[0]
    assert psum_eqn.invars[0].aval.shape == (11, m), \
        psum_eqn.invars[0].aval.shape

    permute_outs, needs = _eqn_needs_ppermute(body, psum_eqn)
    assert permute_outs, "no halo ppermutes in the loop body"
    assert not needs, \
        "the guarded (11, m) reduction transitively consumes the halo " \
        "exchange"
    print(f"  ok guarded structure: 1 psum/iter of (11, {m}), "
          f"{len(permute_outs)} halo ppermute outputs, no edge to psum")


def check_guarded_numeric(op, b):
    """Guarded sharded solve == unguarded sharded solve (same iteration
    counts, iterates equal to fusion round-off): the health rows
    observe, never steer."""
    m = 2
    B = jnp.stack([b, 0.5 * b], axis=1)
    B_grid = B.reshape(op.nx, op.ny, op.nz, m)
    mesh = make_mesh((8,), ("rows",))
    plain = distributed_stencil_solve_batched(
        op, B_grid, mesh, config=SolverConfig(tol=1e-8, maxiter=2000))
    guard = distributed_stencil_solve_batched(
        op, B_grid, mesh,
        config=SolverConfig(tol=1e-8, maxiter=2000, guard=True))
    assert bool(np.asarray(guard.converged).all())
    np.testing.assert_allclose(np.asarray(guard.x), np.asarray(plain.x),
                               rtol=1e-12, atol=1e-13)
    assert np.array_equal(np.asarray(guard.iterations),
                          np.asarray(plain.iterations))
    print("  ok guarded numeric: sharded guarded == unguarded, "
          f"iters={np.asarray(guard.iterations)}")


def guarded_smoke():
    """CI/pytest smoke entry (``python tests/_distributed_check.py
    guarded``): sharded guarded structure + parity assertions."""
    assert jax.device_count() == 8, jax.device_count()
    op, b, _ = M.convection_diffusion(16, peclet=1.0)
    check_guarded_structure(op, b)
    check_guarded_numeric(op, b)
    print("GUARDED DISTRIBUTED SMOKE PASSED")


def precond_smoke():
    """CI smoke entry (``python tests/_distributed_check.py precond``):
    block-Jacobi-enabled distributed solve with the psum-count assertion."""
    assert jax.device_count() == 8, jax.device_count()
    op, b, xt = M.convection_diffusion(16, peclet=1.0)
    check_precond_structure(op, b)
    check_precond_numeric((8,), ("rows",), op, b.reshape(16, 16, 16), xt)
    print("PRECOND DISTRIBUTED SMOKE PASSED")


def main():
    assert jax.device_count() == 8, jax.device_count()
    op, b, xt = M.convection_diffusion(16, peclet=1.0)
    b_grid = b.reshape(16, 16, 16)

    solvers = [pbicgsafe_solve, ssbicgsafe2_solve, bicgstab_solve,
               pbicgstab_solve, gpbicg_solve, pbicgsafe_rr_solve]
    refs = {s: int(s(op.matvec, b, config=SolverConfig(tol=1e-8)).iterations)
            for s in solvers}

    # 1-axis ring, 2-axis (data, model), 3-axis (pod, data, model)
    for mesh_shape, axes in [((8,), ("rows",)),
                             ((4, 2), ("data", "model")),
                             ((2, 2, 2), ("pod", "data", "model"))]:
        for s in solvers:
            check(mesh_shape, axes, s, op, b_grid, refs[s], xt)

    # batched multi-RHS: row-sharded (n, m) block, one (9, m) psum/iter
    check_batched_structure(op, b)
    check_batched((8,), ("rows",), op, b, "jnp")
    check_batched((4, 2), ("data", "model"), op, b, "jnp")
    check_batched((8,), ("rows",), op, b, "pallas")

    # shard-local preconditioning: psum count unchanged, numerics hold
    check_precond_structure(op, b)
    check_precond_numeric((8,), ("rows",), op, b_grid, xt)
    check_precond_numeric((4, 2), ("data", "model"), op, b_grid, xt)
    print("ALL DISTRIBUTED CHECKS PASSED")


if __name__ == "__main__":
    if "precond" in sys.argv[1:]:
        precond_smoke()
    elif "guarded" in sys.argv[1:]:
        guarded_smoke()
    else:
        main()
