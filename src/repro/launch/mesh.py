"""Production meshes.

Functions, never module-level constants — importing this module must not
touch jax device state (device count is locked on first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` with Auto axes: the sharded solvers place their
    operands through ``shard_map`` specs and let eager code outside it
    run on sharded results, which Explicit axes (the default) refuse."""
    return jax.make_mesh(shape, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_solver_mesh(n_devices: int | None = None, *,
                     axes=("data", "model")):
    """Mesh for the distributed solver examples/tests: whatever devices are
    available, folded into the requested axes (row-major)."""
    n = n_devices or jax.device_count()
    if len(axes) == 1:
        shape = (n,)
    else:
        a = 1
        while (a * 2) * (a * 2) <= n * 0:  # pragma: no cover
            a *= 2
        # largest power-of-two split n = d * m with d >= m
        m = 1
        while (m * 2) ** 2 <= n:
            m *= 2
        d = n // m
        shape = (d, m)
    return make_mesh(shape, axes[:len(shape)])


def make_multirhs_mesh(n_devices: int | None = None):
    """Mesh for sharded batched (multi-RHS) solves: one flat ``rows`` axis
    over all devices.  The (n, m) block is row-sharded over it while the m
    columns stay local to every shard, so the batched solver's single
    (9, m) psum reduces over exactly this axis
    (:func:`repro.core.distributed.distributed_stencil_solve_batched`).
    Shard-local preconditioning (``precond=`` on the distributed drivers,
    e.g. block-Jacobi) adds no traffic on any axis of this mesh — the
    psum stays the only per-iteration collective besides the halo
    ppermutes."""
    n = n_devices or jax.device_count()
    return make_mesh((n,), ("rows",))
