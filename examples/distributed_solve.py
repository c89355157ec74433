"""End-to-end distributed solve (the paper's own workload).

Solves a 1.1M-row convection-diffusion system with all solvers on an
8-device (data, model) mesh — the same shard_map + halo-exchange + single
fused psum runtime that the 512-chip dry-run exercises.

  PYTHONPATH=src python examples/distributed_solve.py [--n 104]
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402

import repro  # noqa: E402
from repro.core import SolverConfig  # noqa: E402
from repro.core import matrices as M  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=104,
                    help="grid size (n^3 unknowns; must be divisible by 8)")
    args = ap.parse_args()
    n = args.n
    op, b, xt = M.convection_diffusion(n, peclet=1.0)
    print(f"convection-diffusion, {n}^3 = {n**3:,} unknowns, "
          f"{jax.device_count()} devices, mesh (4, 2) = (data, model)")
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    b_grid = b.reshape(n, n, n)
    for name in ("p-bicgsafe", "ssbicgsafe2", "bicgstab", "p-bicgstab"):
        t0 = time.perf_counter()
        # bind-once front door: the mesh-bound session builds the
        # shard_map program once; repeat solves would reuse it
        dist = repro.make_solver(name, op,
                                 config=SolverConfig(tol=1e-8)).on_mesh(mesh)
        res = dist.solve(b_grid)
        jax.block_until_ready(res.x)
        dt = time.perf_counter() - t0
        err = float(jnp.linalg.norm(res.x.reshape(-1) - xt)
                    / jnp.linalg.norm(xt))
        print(f"  {name:12s} iters={int(res.iterations):4d} "
              f"conv={bool(res.converged)} err={err:.1e} "
              f"wall={dt:.2f}s (incl. compile)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
