"""Child process for bench_overlap's measured-overlap mesh leg: run one
profiled distributed p-BiCGSafe solve on an 8-fake-device mesh and print
the :class:`repro.observe.ProfileReport` as JSON (last stdout line).

A subprocess because the fake-device XLA_FLAGS must be set before jax
initializes, and the parent bench process has already initialized jax on
the real (single) device.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import json  # noqa: E402
import sys   # noqa: E402

import jax   # noqa: E402

jax.config.update("jax_enable_x64", True)

import repro  # noqa: E402
from repro.core import SolverConfig  # noqa: E402
from repro.core import matrices as M  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def main():
    out_dir = sys.argv[1]
    op, b, _ = M.convection_diffusion(16, peclet=1.0)
    solver = repro.make_solver(
        "p-bicgsafe", op, config=SolverConfig(tol=1e-8, maxiter=200))
    dist = solver.on_mesh(make_mesh((8,), ("rows",)))
    res = dist.solve(b.reshape(16, 16, 16), profile=out_dir)
    rep = solver.last_profile
    doc = rep.to_json()
    doc["converged"] = bool(res.converged)
    print(json.dumps(doc))


if __name__ == "__main__":
    main()
