"""Quickstart: the paper's solver behind one front door + a tiny LM step.

  PYTHONPATH=src python examples/quickstart.py

The front door (``repro.api``)
------------------------------
Bind the operator ONCE, solve many times:

    solver = repro.make_solver("p-bicgsafe", op, precond="block_jacobi",
                               substrate="pallas")
    res = solver.solve(b)                 # traces + compiles once
    res = solver.solve(b2)                # replays the compiled program
    res = solver.solve_many([b3, b4])     # ONE (9, m) reduction/iter
    dist = solver.on_mesh(mesh)           # sharded, same session

or one-shot: ``repro.solve(op, b)`` (which still hits the content-keyed
session cache, so a second call against an equal-content operator reuses
the compiled program and the built preconditioner).

Everything is set at bind time and never re-threaded per call:

* ``method``  — any of ``repro.SOLVERS``: "bicgstab", "p-bicgstab",
  "gpbicg", "cgs", "ssbicgsafe2", "p-bicgsafe" (the paper's Alg. 3.1),
  "p-bicgsafe-rr" (Alg. 4.1).
* ``substrate`` — ``"jnp"`` (reference; 9 separate reductions for the
  fused phase) or ``"pallas"`` (hand-tiled kernels: one HBM pass for
  the 9-dot phase, one for the whole vector-update phase, block-ELL
  SpMV; compiled Mosaic on TPU, interpret mode elsewhere — use off-TPU
  to validate numerics, not for speed).
* ``precond`` — ``"jacobi" | "block_jacobi" | "neumann" | "ssor"`` or a
  Preconditioner instance; built ONCE at bind time, applied inside the
  overlap window (the single reduction per iteration keeps no
  dependency edge to the in-flight M^{-1}-applied matvec, on every
  binding — asserted at the jaxpr level in the test suite).

The historical free functions (``pbicgsafe_solve``, ``solve_batched``,
``distributed_stencil_solve*``) keep working verbatim but are deprecated
shims now: they re-trace the whole solver on every call, which is
exactly the cost the session amortizes (benchmarks/bench_api.py measures
~10x on 10 repeat solves — larger the more you repeat).
"""
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402

import repro  # noqa: E402
from repro.core import SolverConfig  # noqa: E402
from repro.core import matrices as M  # noqa: E402


def solver_demo():
    print("== p-BiCGSafe vs baselines on a convection-diffusion system ==")
    op, b, x_true = M.convection_diffusion(24, peclet=1.0)  # 13824 rows
    for method in ("bicgstab", "ssbicgsafe2", "p-bicgsafe"):
        solver = repro.make_solver(method, op,
                                   config=SolverConfig(tol=1e-8))
        res = solver.solve(b)
        err = float(jnp.linalg.norm(res.x - x_true)
                    / jnp.linalg.norm(x_true))
        print(f"  {method:12s} iterations={int(res.iterations):4d} "
              f"relres={float(res.relres):.2e} x_err={err:.2e}")
    # repeat solves against the bound operator replay the compiled
    # program — no retracing (solver.stats counts traces)
    solver.solve(2.0 * b)
    print(f"  repeat solve reused the program: {solver.stats}")


def precond_demo():
    print("\n== preconditioned p-BiCGSafe (precond= at bind time) ==")
    # hard_nonsym: badly row-scaled — plain p-BiCGSafe stagnates, the
    # preconditioned solve converges in a few dozen iterations with the
    # M^{-1}-apply hidden inside the overlap window.
    op, b, x_true = M.hard_nonsym(n=600)
    cfg = SolverConfig(tol=1e-8, maxiter=3000)
    plain = repro.solve(op, b, config=cfg)
    prec = repro.make_solver("p-bicgsafe", op, precond="block_jacobi",
                             substrate="pallas", config=cfg).solve(b)
    err = float(jnp.linalg.norm(prec.x - x_true) / jnp.linalg.norm(x_true))
    print(f"  unpreconditioned: converged={bool(plain.converged)} "
          f"iterations={int(plain.iterations)}")
    print(f"  block-Jacobi (pallas apply): converged={bool(prec.converged)} "
          f"iterations={int(prec.iterations)} x_err={err:.2e}")
    # SSOR on the stencil family: same front door, name spec
    op, b, _ = M.anisotropic3d(10, eps=1e-2)
    plain = repro.solve(op, b, config=cfg)
    prec = repro.solve(op, b, precond="ssor", config=cfg)
    print(f"  anisotropic3d: {int(plain.iterations)} iters -> "
          f"{int(prec.iterations)} with precond='ssor'")


def multirhs_demo():
    print("\n== batched multi-RHS p-BiCGSafe (one (9, m) reduction/iter) ==")
    op, b, _ = M.poisson3d(10)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    cols = [b] + [jax.random.normal(k, b.shape, b.dtype) for k in keys]
    solver = repro.make_solver("p-bicgsafe", op,
                               config=SolverConfig(tol=1e-8))
    res = solver.solve_many(cols)            # per-column vectors accepted
    for j in range(len(cols)):
        print(f"  rhs {j}: iterations={int(res.iterations[j]):4d} "
              f"relres={float(res.relres[j]):.2e} "
              f"converged={bool(res.converged[j])}")
    # same solve on the hand-tiled (n, m) block kernels (compiled on TPU,
    # interpret mode elsewhere) — same trajectory column by column; the
    # stopping iteration may flip by one where relres hovers at tol (the
    # kernel accumulates block-wise, jnp pairwise)
    res_k = repro.make_solver("p-bicgsafe", op, substrate="pallas",
                              config=SolverConfig(tol=1e-8)).solve_many(cols)
    same = [abs(int(res_k.iterations[j]) - int(res.iterations[j])) <= 1
            for j in range(len(cols))]
    print(f"  substrate='pallas' block kernels: converged="
          f"{bool(res_k.converged.all())}, per-column iteration "
          f"counts within +-1 of jnp: {all(same)}")
    # heterogeneous tolerances are per-column runtime arguments — one
    # compiled program serves every mix (what repro.service rides on)
    het = solver.solve_many(cols[:3], tol=jnp.asarray([1e-4, 1e-8, 1e-10]))
    print(f"  per-column tol [1e-4, 1e-8, 1e-10]: iterations="
          f"{[int(i) for i in het.iterations]}")


def lm_demo():
    print("\n== 5 training steps of a reduced qwen3 config ==")
    from repro.configs import smoke_config
    from repro.data import DataConfig
    from repro.optim import AdamWConfig
    from repro.train import TrainConfig, train

    cfg = smoke_config("qwen3-8b")
    out = train(cfg,
                DataConfig(batch_size=2, seq_len=32,
                           vocab_size=cfg.vocab_size),
                TrainConfig(steps=5, ckpt_every=100,
                            ckpt_dir="/tmp/repro-quickstart",
                            opt=AdamWConfig(lr=1e-3)))
    for h in out["history"]:
        print(f"  step {h['step']}: loss {h['loss']:.4f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    solver_demo()
    precond_demo()
    multirhs_demo()
    lm_demo()
