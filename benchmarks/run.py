"""Benchmark harness: one module per paper table/figure.

  python -m benchmarks.run [--quick] [--only a,b]

  bench_convergence   Table 5.2 + Fig 5.1  (iteration counts, histories)
  bench_rr            §5.2 / Fig 5.2       (residual replacement)
  bench_cost          Table 3.1            (per-iteration op counts)
  bench_overlap       §3 Fig 3.1 + Fig 5.3 (HLO overlap proof + model +
                                            measured overlap)
  bench_scaling       Fig 5.3 companion    (measured per-iter work)
  bench_roofline      §Roofline            (terms from dry-run artifacts)
  bench_multirhs      multi-RHS            (batched vs looped solves)
  bench_precond       preconditioning      (precond vs not, per solver)
  bench_service       solve service        (continuous batching vs
                                            sequential / static batch)
  bench_api           bind-once sessions   (repeat-solve amortization vs
                                            legacy free functions)
  bench_robustness    guarded solves       (clean-path overhead budget +
                                            fault-injection recovery)
  bench_observe       observability        (trace/metrics overhead
                                            budget, session + engine)
  bench_scenarios     scenario registry    (declarative matrix sweep:
                                            oracle + contract claims)

Artifacts land in experiments/*.json; stdout is the human summary.

``REGISTRY`` below is the single source of truth the perf-trajectory
gate (:mod:`repro.observe.trajectory`, ``python -m repro.observe
trajectory``) reads: each benchmark declares, next to its registration,
which artifact values are tracked over git history and how much
regression its noise profile tolerates.  ``gate=True`` metrics fail CI
when the current value is worse than the median of the last committed
points by more than ``rel_tol``; ``gate=False`` ("watch") metrics are
wall-clock/throughput numbers that vary machine to machine — trended
and flagged in the report, never fatal.
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache
from repro.observe.trajectory import BenchSpec, Metric

REGISTRY = (
    BenchSpec(
        "api", "benchmarks.bench_api", "bench_api.json",
        metrics=(
            Metric("results/jnp/speedup", "higher", 0.5, gate=True,
                   note="session amortization vs legacy free functions"),
            Metric("results/jnp/session_dot_reduce_traces", "lower", 0.0,
                   gate=True,
                   note="retraces of the fused reduction per session"),
        )),
    BenchSpec(
        "robustness", "benchmarks.bench_robustness",
        "bench_robustness.json",
        metrics=(
            Metric("overhead/overhead_ratio", "lower", 0.25, gate=False,
                   note="guarded vs unguarded wall clock (machine noise)"),
        )),
    BenchSpec(
        "observe", "benchmarks.bench_observe", "bench_observe.json",
        metrics=(
            Metric("session/overhead_ratio", "lower", 0.25, gate=False,
                   note="traced vs untraced session solve (wall clock)"),
            Metric("engine/overhead_ratio", "lower", 0.25, gate=False,
                   note="traced vs untraced engine drain (wall clock)"),
        )),
    BenchSpec(
        "convergence", "benchmarks.bench_convergence",
        "bench_convergence.json",
        metrics=(
            Metric("claims/equivalence_ok", "higher", 0.0, gate=True,
                   note="p-BiCGSafe matches BiCGSafe iteration counts"),
            Metric("claims/safe_beats_stab", "higher", 0.25, gate=True,
                   note="#matrices where BiCGSafe beats BiCGSTAB"),
        )),
    BenchSpec(
        "rr", "benchmarks.bench_rr", "bench_rr.json",
        metrics=(
            Metric("claims/hard_sr3.0/rr_truthful", "higher", 0.0,
                   gate=True,
                   note="residual replacement keeps the recursion honest"),
        )),
    BenchSpec(
        "cost", "benchmarks.bench_cost", "bench_cost.json",
        metrics=(
            Metric("p-bicgsafe/measured/sync_phases", "lower", 0.0,
                   gate=True,
                   note="the paper's headline: ONE reduction per iter"),
            Metric("p-bicgsafe/measured/mul_n", "lower", 0.1, gate=True,
                   note="Table 3.1 per-iteration multiplies"),
            Metric("p-bicgsafe/measured/carry_vectors", "lower", 0.0,
                   gate=True, note="loop-carried vector count"),
        )),
    BenchSpec(
        "overlap", "benchmarks.bench_overlap", "bench_overlap.json",
        metrics=(
            Metric("claim_ok", "higher", 0.0, gate=True,
                   note="structural proof: reduction independent of A s_i"),
            Metric("batched_claim_ok", "higher", 0.0, gate=True),
            Metric("precond_claim_ok", "higher", 0.0, gate=True),
            Metric("measured/session_jnp/overlap_efficiency", "higher",
                   0.5, gate=False,
                   note="measured overlap is 0 on a serial CPU device; "
                        "trended so a real-overlap substrate shows up"),
            Metric("measured/session_jnp/exposed_per_iter_us", "lower",
                   0.5, gate=False,
                   note="exposed reduction time per iteration (wall "
                        "clock; machine-sensitive)"),
            Metric("measured/mesh/overlap_efficiency", "higher", 0.5,
                   gate=False,
                   note="the 8-device mesh leg DOES overlap (threads "
                        "run concurrently): the paper's claim, measured"),
        )),
    BenchSpec(
        "scaling", "benchmarks.bench_scaling", "bench_scaling.json",
        metrics=(
            Metric("1/p-bicgsafe/per_iter_us", "lower", 0.5, gate=False,
                   note="single-RHS per-iteration wall clock"),
        )),
    BenchSpec(
        "roofline", "benchmarks.bench_roofline", "bench_roofline.json",
        metrics=(
            Metric("claims/pipelined_hides_reduction", "higher", 0.0,
                   gate=True,
                   note="roofline model: reduction latency hidden when "
                        "overlap term is active"),
        )),
    BenchSpec(
        "multirhs", "benchmarks.bench_multirhs", "bench_multirhs.json",
        metrics=(
            Metric("pallas_kernel_path/x_err", "lower", 9.0, gate=True,
                   note="fused-kernel path accuracy — order-of-magnitude "
                        "guard against silent kernel breakage"),
        )),
    BenchSpec(
        "precond", "benchmarks.bench_precond", "bench_precond.json",
        metrics=(
            Metric("trajectory/block_jacobi/converged", "higher", 0.0,
                   gate=True),
            Metric("trajectory/block_jacobi/iterations", "lower", 0.25,
                   gate=True,
                   note="preconditioned iteration count (fp-drift slack)"),
        )),
    BenchSpec(
        "scenarios", "benchmarks.bench_scenarios", "scenario_sweep.json",
        metrics=(
            Metric("summary/n_cells", "higher", 0.0, gate=True,
                   note="registered scenario coverage never shrinks"),
            Metric("claims/all_oracle_ok", "higher", 0.0, gate=True,
                   note="every cell's solution verified by its operator "
                        "plugin's oracle"),
            Metric("claims/all_contracts_ok", "higher", 0.0, gate=True,
                   note="every cell matches the expected contract "
                        "matrix (+ plugin deltas)"),
            Metric("summary/wall_s", "lower", 0.5, gate=False,
                   note="whole-sweep wall clock (machine noise)"),
        )),
    BenchSpec(
        "service", "benchmarks.bench_service", "bench_service.json",
        metrics=(
            Metric("capacity_burst/engine/throughput_rps", "higher", 0.5,
                   gate=False,
                   note="burst throughput (quick mode under-batches; "
                        "wall clock — watch only)"),
        )),
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller problem set (CI-sized)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of bench names")
    args = ap.parse_args()
    enable_compile_cache()

    specs = list(REGISTRY)
    if args.only:
        keep = set(args.only.split(","))
        specs = [s for s in specs if s.name in keep]

    failures = []
    for spec in specs:
        t0 = time.time()
        print(f"\n################ {spec.name} ################")
        try:
            importlib.import_module(spec.module).run(quick=args.quick)
            print(f"[{spec.name}] done in {time.time() - t0:.1f}s")
        except Exception:  # noqa: BLE001
            failures.append(spec.name)
            traceback.print_exc()
            print(f"[{spec.name}] FAILED")
    if failures:
        print(f"\nFAILED benches: {failures}")
        sys.exit(1)
    print("\nall benches ok")


if __name__ == "__main__":
    main()
