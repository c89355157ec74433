"""The plain reference that decides ``correct``: the configuration's
operator applied in float64 on the host (the ``apply_f64`` of its kind's
module, :func:`chipbench.cells.operator`), written from the configuration
alone (it imports nothing of the program and reads nothing the program
made).

A solve's answer x for a right-hand side b is right when its true
relative residual ``||b - A x|| / ||b||`` meets the tolerance the request
asked for.
"""
from __future__ import annotations

import math

import numpy as np

from .cells import operator

#: unknowns of one x-slab: the host holds a few float64 slabs, never the
#: whole grid in float64
SLAB_UNKNOWNS = 1 << 24


def relative_residual(operator_cfg: dict, b: np.ndarray,
                      x: np.ndarray) -> float:
    """``||b - A x|| / ||b||`` in float64 (inf where x is not finite), for
    the configuration's ``operator`` group, evaluated in x-slabs of whole
    planes holding at most :data:`SLAB_UNKNOWNS` (the last may be
    thinner), each with its neighbouring plane on either side."""
    op = operator(operator_cfg["kind"])
    params = op.params(operator_cfg)
    shape = tuple(int(g) for g in operator_cfg["grid"])
    nx, plane = shape[0], math.prod(shape[1:])
    slab_planes = max(1, SLAB_UNKNOWNS // plane)
    b = np.asarray(b).reshape(shape)
    x = np.asarray(x).reshape(shape)
    rr = bb = 0.0
    for i0 in range(0, nx, slab_planes):
        i1 = min(i0 + slab_planes, nx)
        lo, hi = max(i0 - 1, 0), min(i1 + 1, nx)
        u = np.asarray(x[lo:hi], np.float64)
        if not np.isfinite(u).all():
            return float("inf")
        bs = np.asarray(b[i0:i1], np.float64).ravel()
        r = bs - op.apply_f64(params, u)[i0 - lo:i1 - lo].ravel()
        rr += float(r @ r)
        bb += float(bs @ bs)
    return math.sqrt(rr / bb)
