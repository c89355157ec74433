"""The least HBM traffic one solver iteration needs, from its shapes.

Each vector the iteration updates is read and written once, and each
read-only vector it uses is read once; matvec results, scalars and the
occasional residual-replacement step are left out.  The count is fixed
per method, so every implementation of an iteration is read against the
same work.

p-BiCGSafe (paper Alg. 3.1) and its residual-replacement form (Alg. 4.1)
update eleven vectors per iteration (x, r, s, p, u, t, y, z, w, l, g) and
read one more (the shadow residual r0*): 23 vector passes.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

import jax.numpy as jnp

#: method -> (vectors updated per iteration, read-only vectors read)
ITERATION_VECTORS: Dict[str, Tuple[int, int]] = {
    "p-bicgsafe": (11, 1),
    "p-bicgsafe-rr": (11, 1),
}


def floor_bytes(grid: Sequence[int], dtype, method: str,
                shards: int = 1) -> int:
    """Bytes one iteration must move through one device's HBM when the
    ``grid`` is split evenly over ``shards`` devices."""
    try:
        updated, read_only = ITERATION_VECTORS[method]
    except KeyError:
        raise KeyError(f"no floor for method {method!r} (known: "
                       f"{sorted(ITERATION_VECTORS)})") from None
    n = math.prod(int(g) for g in grid)
    if n % shards:
        raise ValueError(f"{n} unknowns do not split over {shards} shards")
    passes = 2 * updated + read_only
    return passes * (n // shards) * jnp.dtype(dtype).itemsize
