"""Quickstart: serving solve requests with continuous batching.

  PYTHONPATH=src python examples/serve_solver.py

The library-call way to solve ``A x = b`` is a bound session
(``repro.make_solver(...).solve(b)``) per right-hand side.  A service
multiplexes instead: :class:`repro.service.SolveEngine` keeps one resident
``(n, max_batch)`` block per registered operator, steps ALL resident
requests with ONE compiled program (one (9, m) fused reduction per
iteration — the paper's single synchronization phase, amortized over
every resident request), retires converged columns at chunk boundaries,
and splices queued requests into the freed slots mid-flight.

This demo registers TWO operators (a Poisson stencil, and a
block-Jacobi-preconditioned convection-diffusion stencil), enqueues a
mixed stream of requests with heterogeneous tolerances and budgets
against both, drains the engine, and prints per-request telemetry.
Re-registering an operator with the same content is a fingerprint cache
hit: the engine's registry consumes the :mod:`repro.api` session cache,
so the built preconditioner and the compiled step programs are reused —
even across engines, or with a direct ``repro.make_solver`` of the same
operator.

The engine serves with ``trace_cap`` set, so every retirement carries a
per-iteration :class:`repro.observe.ConvergenceTrace` (harvested with
the one host read the engine already does), and the whole run lands in
the observe layer: spans are dumped as Chrome trace-event JSON, metrics
as a Prometheus snapshot, one request's trace as convergence JSON —
render them with ``python -m repro.observe report``.
"""
import numpy as np

import jax
import jax.numpy as jnp

jax.config.update("jax_enable_x64", True)

from repro.core import matrices as M          # noqa: E402
from repro.observe import RECORDER, prometheus  # noqa: E402
from repro.service import ServiceConfig, SolveEngine  # noqa: E402

OUT = "experiments/runtime/observe"


def main():
    op_a, b_a, _ = M.poisson3d(8)                        # n = 512, SPD
    op_b, b_b, _ = M.convection_diffusion(8, peclet=1.0)  # non-symmetric

    eng = SolveEngine(ServiceConfig(max_batch=8, chunk=12,
                                    tol=1e-8, maxiter=2000,
                                    trace_cap=128))
    eng.register(op_a, name="poisson")
    eng.register(op_b, precond="block_jacobi", name="convdiff")

    # same content, fresh objects -> cache hit, nothing rebuilt
    assert eng.register(M.poisson3d(8)[0], name="poisson") == "poisson"
    assert len(eng.registry.entries()) == 2

    rng = np.random.default_rng(0)
    n_req = 20
    print(f"submitting {n_req} requests against 2 operators "
          f"(slots: {eng.scfg.max_batch}/operator, heterogeneous tol)")
    for i in range(n_req):
        name = "poisson" if i % 2 == 0 else "convdiff"
        b = jnp.asarray(rng.standard_normal(512))
        tol = float(rng.choice([1e-6, 1e-8, 1e-10]))
        eng.submit(name, b, tol=tol, maxiter=500)

    results = eng.run()

    print(f"\n{'rid':>3} {'operator':<9} {'conv':<5} {'iters':>5} "
          f"{'relres':>9} {'wait ms':>8} {'wall ms':>8} {'chunks':>6}")
    for r in sorted(results, key=lambda r: r.rid):
        t = r.telemetry
        print(f"{r.rid:>3} {r.operator:<9} {str(r.converged):<5} "
              f"{r.iterations:>5} {r.relres:>9.1e} "
              f"{t.queue_wait_s * 1e3:>8.1f} {t.wall_s * 1e3:>8.1f} "
              f"{t.chunks_resident:>6}")

    conv = sum(r.converged for r in results)
    chunks = np.mean([r.telemetry.chunks_resident for r in results])
    print(f"\n{conv}/{n_req} converged; mean chunks resident "
          f"{chunks:.1f}; every iteration of a resident block is ONE "
          "(9, m) reduction for all its requests")

    # -- dump the observe artifacts for the report CLI -------------------
    import os
    os.makedirs(OUT, exist_ok=True)
    RECORDER.save_chrome_trace(f"{OUT}/spans.trace.json")
    with open(f"{OUT}/metrics.prom", "w") as fh:
        fh.write(prometheus())
    slowest = max(results, key=lambda r: r.iterations)
    slowest.trace.save(f"{OUT}/convergence.json")
    print(f"\nslowest request (rid {slowest.rid}): "
          f"{slowest.trace.summary()}")
    print(f"observe artifacts in {OUT}/ — render the timeline with:\n"
          f"  PYTHONPATH=src python -m repro.observe report --dir {OUT}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
