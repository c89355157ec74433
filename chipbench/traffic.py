"""The one traffic generator: a mix file of parameters plus ``--seed`` gives
the stream of requests, and nothing else shapes the work.

A mix (``chipbench/traffic/<mix>.json``) names

- ``entry``: ``"solve"`` (one right-hand side per call through
  ``LinearSolver.solve``, or ``DistributedSolver.solve`` where the
  configuration is sharded; a call returns before the next is made) or
  ``"serve"`` (requests through ``SolveEngine.submit`` / ``poll``);
- ``clients`` (serve): the closed loop's clients, each of which sends its
  next request when its last one has been answered;
- ``tols``: the tolerances the requests ask for;
- ``rhs_set``: how many base right-hand sides the requests cycle
  through.  The set is the same for every seed: the seed orders it (a
  fresh permutation of every (base, tol) pair on each pass) and scales
  each request's vector by a sign and a power of two, which floating
  point carries exactly through a solve, so every seed asks for the same
  work in another order while no two requests need send the same bytes.
  Fresh vectors per request made the work differ from seed to seed: a
  few in a thousand stall the float32 solver at tol 1e-4 (``PERF.md``);
- ``refine_tol`` (solve): the relative tolerance of the one refinement
  solve a right-hand side gets when its answer misses its tol;
- ``max_batch`` (serve): slots of the engine's resident block;
- ``check_sample``: verified answers compared with the reference after
  the window;
- ``drain_s`` (serve): how long past the window's close the benchmark
  waits for the answers still in flight.

Base right-hand sides are standard normal vectors, made on the device
from their index; the same seed gives the same stream.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Iterator

import numpy as np

ENTRIES = ("solve", "serve")
REQUIRED = {"solve": ("entry", "tols", "rhs_set", "refine_tol",
                      "check_sample"),
            "serve": ("entry", "clients", "tols", "rhs_set", "max_batch",
                      "check_sample", "drain_s")}
OPTIONAL = ("why",)
#: a request's vector is its base vector times +-2**e, |e| <= MAX_EXP
MAX_EXP = 4
#: the right-hand side set-up warms on; no request sends it
WARM_RHS = 2 ** 31 - 1


@dataclasses.dataclass(frozen=True)
class Request:
    index: int      # order of submission
    rhs: int        # which base right-hand side
    scale: float    # +-2**e, the factor on the base vector
    tol: float


def load(path: Path) -> dict:
    """Read and check one mix file."""
    mix = json.loads(Path(path).read_text())
    entry = mix.get("entry")
    if entry not in ENTRIES:
        raise ValueError(f"{path}: entry {entry!r} is not one of {ENTRIES}")
    missing = [k for k in REQUIRED[entry] if k not in mix]
    extra = sorted(set(mix) - set(REQUIRED[entry]) - set(OPTIONAL))
    if missing or extra:
        raise ValueError(f"{path}: missing keys {missing}, unknown keys "
                         f"{extra}")
    if not mix["tols"] or int(mix.get("clients", 1)) < 1 or \
            not 0 < int(mix["rhs_set"]) < WARM_RHS:
        raise ValueError(f"{path}: needs a client, a tol and a base vector")
    return mix


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """An independent host stream of the seed (0: requests, 1: the
    reference's sample)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed),
                                                         int(stream)]))


def requests(mix: dict, seed: int) -> Iterator[Request]:
    """The endless request stream of ``mix`` under ``seed``."""
    rng = host_rng(seed, 0)
    kinds = [(j, float(t)) for j in range(int(mix["rhs_set"]))
             for t in mix["tols"]]
    i = 0
    while True:
        for k in rng.permutation(len(kinds)):
            rhs, tol = kinds[k]
            sign = 1.0 if rng.integers(2) else -1.0
            exp = int(rng.integers(-MAX_EXP, MAX_EXP + 1))
            yield Request(i, rhs, sign * 2.0 ** exp, tol)
            i += 1


def make_rhs_fn(shape, dtype, sharding=None):
    """A jitted ``f(index, scale) -> scale * b_index``: base right-hand
    side number ``index`` (standard normal, made on the device, laid out
    by ``sharding`` where given), times ``scale``.  ``index`` may also be
    a vector, giving a block of them along a leading axis."""
    import jax
    import jax.numpy as jnp

    base = jax.random.key(0)

    def one(i):
        return jax.random.normal(jax.random.fold_in(base, i), shape, dtype)

    def rhs(index, scale):
        index = jnp.asarray(index, jnp.uint32)
        b = one(index) if index.ndim == 0 else jax.vmap(one)(index)
        return (b * scale).astype(dtype)

    return jax.jit(rhs, out_shardings=sharding)
