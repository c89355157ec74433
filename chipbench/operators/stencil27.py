"""The 27-point stencil on a 3-D grid with zero Dirichlet boundaries: a
row keeps only its in-domain neighbours (HPCG 3.1's operator,
``GenerateProblem_ref.cpp``).

The configuration's ``operator`` group states ``kind: "stencil27"``,
``grid: [nx, ny, nz]``, ``dtype`` and ``coeffs``, nested 3 x 3 x 3:
``coeffs[dx + 1][dy + 1][dz + 1]`` couples point (i, j, k) to
(i + dx, j + dy, k + dz).

Three forms of the one operator, each found by the configuration's kind
(:func:`chipbench.cells.operator`):

- :func:`build`, the program's operator through its public API;
- :func:`apply_f32`, the benchmark's own device check in jnp;
- :func:`apply_f64`, the float64 host operator of the reference, which
  imports nothing of the program.
"""
from __future__ import annotations

import itertools

import numpy as np

#: the offsets (dx, dy, dz) of the 27 terms
OFFSETS = list(itertools.product((-1, 0, 1), repeat=3))


def params(operator_cfg: dict) -> np.ndarray:
    """What :func:`apply_f32` and :func:`apply_f64` take first: the
    configuration's 27 coefficients as a (3, 3, 3) float64 array."""
    c = np.asarray(operator_cfg["coeffs"], np.float64)
    if c.shape != (3, 3, 3):
        raise ValueError(f"a 27-point stencil needs 3 x 3 x 3 coefficients; "
                         f"got shape {c.shape}")
    return c


def build(operator_cfg: dict):
    """The program's operator, in the configuration's ``dtype``."""
    import jax.numpy as jnp

    from repro import Stencil27Operator

    return Stencil27Operator(jnp.asarray(params(operator_cfg),
                                         operator_cfg["dtype"]),
                             *operator_cfg["grid"])


def apply_f32(c, u):
    """A u on ``u``'s 3-D grid in jnp, in ``u``'s type: the 27 terms
    summed over the zero-padded grid."""
    import jax.numpy as jnp

    nx, ny, nz = u.shape
    p = jnp.pad(u, 1)
    out = jnp.zeros_like(u)
    for dx, dy, dz in OFFSETS:
        out = out + c[dx + 1, dy + 1, dz + 1] * p[1 + dx:1 + dx + nx,
                                                  1 + dy:1 + dy + ny,
                                                  1 + dz:1 + dz + nz]
    return out


def apply_f64(coeffs, u: np.ndarray) -> np.ndarray:
    """A u in float64 numpy on ``u``'s 3-D grid, one x-plane of the
    result at a time from its own plane and the two beside it (planes
    of a few megabytes stay in the host's cache)."""
    c = np.asarray(coeffs, np.float64)
    if c.shape != (3, 3, 3) or u.ndim != 3:
        raise ValueError("a 27-point stencil needs 3 x 3 x 3 coefficients "
                         f"and a 3-D grid; got {c.shape} and {u.shape}")
    nx, ny, nz = u.shape
    au = np.zeros(u.shape, np.float64)
    plane = np.zeros((ny + 2, nz + 2))      # zero-padded in y and z
    for i in range(nx):
        for dx in (-1, 0, 1):
            if not 0 <= i + dx < nx:
                continue
            plane[1:-1, 1:-1] = u[i + dx]
            for dy, dz in itertools.product((-1, 0, 1), repeat=2):
                au[i] += c[dx + 1, dy + 1, dz + 1] * plane[
                    1 + dy:1 + dy + ny, 1 + dz:1 + dz + nz]
    return au
