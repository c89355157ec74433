"""The plain reference that decides ``correct``: the configuration's 7-point
operator applied in float64 on the host, written from the configuration's
coefficients alone (it imports nothing of the program and reads nothing
the program made).

A solve's answer x for a right-hand side b is right when its true
relative residual ``||b - A x|| / ||b||`` meets the tolerance the request
asked for.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np


def stencil_matvec(coeffs: Sequence[float], u: np.ndarray) -> np.ndarray:
    """A u for the zero-Dirichlet 7-point stencil on ``u``'s 3-D grid;
    ``coeffs`` = [center, x-, x+, y-, y+, z-, z+] (the neighbour at i-1
    along x carries x-, the one at i+1 carries x+)."""
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


def relative_residual(coeffs: Sequence[float], grid: Sequence[int],
                      b: np.ndarray, x: np.ndarray) -> float:
    """``||b - A x|| / ||b||`` in float64 (inf where x is not finite)."""
    shape = tuple(int(g) for g in grid)
    x = np.asarray(x, np.float64).reshape(shape)
    if not np.isfinite(x).all():
        return float("inf")
    b = np.asarray(b, np.float64).reshape(shape)
    return float(np.linalg.norm(b - stencil_matvec(coeffs, x))
                 / np.linalg.norm(b))
