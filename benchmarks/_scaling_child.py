"""Child for bench_scaling: times distributed solves on N fake devices.

Wall-clock on fake (single-core) devices measures per-iteration WORK, not
parallel speedup — the honest quantity here is the p-BiCGSafe vs
ssBiCGSafe2 per-iteration cost ratio at zero network latency (the paper's
Table 3.1 overhead, measured end-to-end).
"""
import os
import sys

n_dev = sys.argv[1] if len(sys.argv) > 1 else "4"
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"

import json  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402

from repro.core import (SolverConfig, pbicgsafe_solve,  # noqa: E402
                        ssbicgsafe2_solve)
from repro.core import matrices as M  # noqa: E402
from repro.core.distributed import distributed_stencil_solve  # noqa: E402


def main():
    nd = int(n_dev)
    op, b, _ = M.convection_diffusion(32, peclet=1.0)   # 32^3 = 32768 rows
    b_grid = b.reshape(32, 32, 32)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((nd,), ("rows",))
    out = {"devices": nd}
    for name, solver in (("ssbicgsafe2", ssbicgsafe2_solve),
                         ("p-bicgsafe", pbicgsafe_solve)):
        cfg = SolverConfig(tol=1e-30, maxiter=60)   # fixed 60 iterations
        fn = jax.jit(lambda bb: distributed_stencil_solve(
            solver, op, bb, mesh, config=cfg, jit=False))
        r = fn(b_grid)
        jax.block_until_ready(r.x)                  # compile + warm
        t0 = time.perf_counter()
        reps = 3
        for _ in range(reps):
            r = fn(b_grid)
            jax.block_until_ready(r.x)
        dt = (time.perf_counter() - t0) / reps
        out[name] = {"time_s": dt, "iters": int(r.iterations),
                     "per_iter_us": dt / max(int(r.iterations), 1) * 1e6}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
