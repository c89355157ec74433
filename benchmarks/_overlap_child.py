"""Child process for bench_overlap: lowers the distributed solvers on an
8-device mesh and reports collective/matvec dependency structure as JSON.

Thin consumer of :func:`repro.analysis.hlo.overlap_report` — the HLO
backend of the contract analyzer owns the dependency analysis; this
child only builds the compiled texts on the fake 8-device mesh.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import json  # noqa: E402

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402

from repro.analysis.hlo import overlap_report  # noqa: E402
from repro.core import (SolverConfig, pbicgsafe_solve,  # noqa: E402
                        ssbicgsafe2_solve)
from repro.core import matrices as M  # noqa: E402
from repro.core.distributed import (distributed_stencil_solve,  # noqa: E402
                                    distributed_stencil_solve_batched)


def analyze(solver, op, b_grid, mesh, precond=None):
    fn = jax.jit(lambda b: distributed_stencil_solve(
        solver, op, b, mesh, config=SolverConfig(maxiter=100),
        precond=precond, jit=False))
    return overlap_report(fn.lower(b_grid).compile().as_text())


def analyze_batched(op, B_grid, mesh):
    """Batched+sharded p-BiCGSafe: the (9, m) block all-reduce must keep
    the no-dependency edge to the in-flight block matvec's halo permutes —
    batching the reduction must not serialize it behind the SpMV."""
    fn = jax.jit(lambda B: distributed_stencil_solve_batched(
        op, B, mesh, config=SolverConfig(maxiter=100), jit=False))
    return overlap_report(fn.lower(B_grid).compile().as_text())


def main():
    op, b, _ = M.convection_diffusion(16, peclet=1.0)
    b_grid = b.reshape(16, 16, 16)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("rows",))
    m = 4
    keys = jax.random.split(jax.random.PRNGKey(0), m)
    B_grid = jnp.stack([b] + [jax.random.normal(k, b.shape, b.dtype)
                              for k in keys[1:]], axis=1).reshape(16, 16, 16, m)
    out = {
        "p-bicgsafe": analyze(pbicgsafe_solve, op, b_grid, mesh),
        "ssbicgsafe2": analyze(ssbicgsafe2_solve, op, b_grid, mesh),
        "p-bicgsafe-batched": analyze_batched(op, B_grid, mesh),
        # preconditioned pipelined solve: the shard-local block-Jacobi
        # M^{-1}-apply joins the in-flight matvec inside the overlap
        # window — the all-reduce must STILL not depend on any halo
        # permute (reduction_needs_permutes == 0)
        "p-bicgsafe-block-jacobi": analyze(pbicgsafe_solve, op, b_grid,
                                           mesh, precond="block_jacobi"),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
