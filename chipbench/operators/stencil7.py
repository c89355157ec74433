"""The 7-point stencil on a 3-D grid with zero Dirichlet boundaries.

The configuration's ``operator`` group states ``kind: "stencil7"``,
``grid: [nx, ny, nz]``, ``dtype`` and ``coeffs`` = [center, x-, x+, y-,
y+, z-, z+] (the neighbour at i-1 along x carries x-, the one at i+1
carries x+).

Three forms of the one operator, each found by the configuration's kind
(:func:`chipbench.cells.operator`):

- :func:`build`, the program's operator through its public API;
- :func:`apply_f32`, the benchmark's own device check in jnp;
- :func:`apply_f64`, the float64 host operator of the reference, which
  imports nothing of the program.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np


def params(operator_cfg: dict) -> Sequence[float]:
    """What :func:`apply_f32` and :func:`apply_f64` take first: the
    configuration's seven coefficients."""
    return [float(v) for v in operator_cfg["coeffs"]]


def build(operator_cfg: dict):
    """The program's operator, in the configuration's ``dtype``."""
    import jax.numpy as jnp

    from repro.core import Stencil7Operator

    return Stencil7Operator(jnp.asarray(operator_cfg["coeffs"],
                                        operator_cfg["dtype"]),
                            *operator_cfg["grid"])


def apply_f32(c, u):
    """A u on ``u``'s 3-D grid in jnp, in ``u``'s type."""
    import jax.numpy as jnp

    p = jnp.pad(u, 1)
    return (c[0] * u + c[1] * p[:-2, 1:-1, 1:-1] + c[2] * p[2:, 1:-1, 1:-1]
            + c[3] * p[1:-1, :-2, 1:-1] + c[4] * p[1:-1, 2:, 1:-1]
            + c[5] * p[1:-1, 1:-1, :-2] + c[6] * p[1:-1, 1:-1, 2:])


def apply_f64(coeffs: Sequence[float], u: np.ndarray) -> np.ndarray:
    """A u in float64 numpy on ``u``'s 3-D grid."""
    c = [float(v) for v in coeffs]
    if len(c) != 7 or u.ndim != 3:
        raise ValueError("a 7-point stencil needs 7 coefficients and a 3-D "
                         f"grid; got {len(c)} and shape {u.shape}")
    u = np.asarray(u, np.float64)
    au = c[0] * u
    au[1:] += c[1] * u[:-1]
    au[:-1] += c[2] * u[1:]
    au[:, 1:] += c[3] * u[:, :-1]
    au[:, :-1] += c[4] * u[:, 1:]
    au[:, :, 1:] += c[5] * u[:, :, :-1]
    au[:, :, :-1] += c[6] * u[:, :, 1:]
    return au
