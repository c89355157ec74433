"""On-chip smoke run of the p-BiCGSafe solve path and the solve service.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # the sharded solve on a 4-chip mesh

One chip (the default) runs, in one process, through the entry points a
user calls (all float32, x64 off):

1. single solve: the 256^3 = 16,777,216-unknown convection-diffusion
   system of ``core.matrices.convection_diffusion`` through
   ``repro.make_solver("p-bicgsafe-rr", op, substrate="pallas").solve(b)``
   (see :data:`METHOD` for why with residual replacement), one warm-up
   and one timed solve; its true residual and its error against the
   generator's ``x_true`` are recomputed on the host in float64 by an
   independent numpy stencil;
2. kernels: the compiled solve program must contain the Pallas kernels
   (``tpu_custom_call``) — interpret mode or the jnp reference fails here;
3. banded ELL: the Pallas SpMV (``kernels/spmv_ell.py``) against a
   float64 host product, for one vector and an 8-column block, and a
   short pallas solve of that ELL system (see :data:`ELL_GRID`);
4. reference: the 256^3 Poisson system (``core.matrices.poisson3d``)
   solved on ``substrate="pallas"`` and on ``substrate="jnp"`` must both
   converge, verified, within :func:`iteration_margin` of each other
   (see :data:`REF_OPERATOR` for why not the convection-diffusion one);
5. solve service: ``SolveEngine(ServiceConfig(max_batch=8,
   substrate="pallas"))`` with one 128^3 convection-diffusion operator
   answers 16 requests of mixed ``tol``, refilling slots mid-flight;
   every request must retire CONVERGED with its true residual at its
   tol (recomputed on the host in float64; see
   :data:`SERVICE_RES_FACTOR`).

``--chips 4`` runs only the sharded x-slab solve
(``LinearSolver.on_mesh``) of the 512x256x256 Poisson system on a 4-chip
mesh (a warm-up and a timed solve), and one single-device session solve
of the same system it is compared with.

Every check raises on failure (exit code 1) and no phase is retried or
skipped.  Without a TPU the script exits 2 before any phase.  The last
line of stdout is ``{"ok": true, "device": {...}}`` and nothing else.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro  # noqa: E402
from repro.core import SolverConfig  # noqa: E402
from repro.core import matrices as M  # noqa: E402
from repro.core.linear_operator import ELLOperator  # noqa: E402
from repro.core.types import SolveStatus  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

SINGLE_GRID = (256, 256, 256)
SERVICE_GRID = (128, 128, 128)
MESH_GRID = (512, 256, 256)
SEED = 0

#: the solves run p-BiCGSafe with residual replacement every RR_EPOCH
#: iterations: in float32 the plain pipelined recurrences drift from the
#: true residual until they stagnate (CPU, 128^3 convection-diffusion:
#: recurrence residual stuck at 2e-3 while the true residual grows past
#: 1); replacing them with true values every 20 iterations keeps the two
#: within a small factor
METHOD = "p-bicgsafe-rr"
RR_EPOCH = 20
#: stopping tolerance (recurrence residual).  In float32 the Gram
#: determinant of p-BiCGSafe's zeta/eta step cancels to zero once the
#: 256^3 convection-diffusion residual nears 6.6e-5 (breakdown, v5e)
SOLVE_TOL = 1e-4
#: acceptance bound on the float64 true residual: between replacements
#: the float32 recurrences drift, and the 256^3 convection-diffusion
#: solve ends with true residual 4.2e-4 at recurrence 9.2e-5 (v5e)
TRUE_RES_BOUND = 1e-3
#: acceptance bound on ||x - x_true|| / ||x_true|| of the convection-
#: diffusion solve (8.95e-4 measured on a v5e)
ERROR_BOUND = 1e-2
#: the reference and sharded comparisons use the SPD Poisson operator:
#: a comparison needs two float32 trajectories that both converge, and
#: convection-diffusion at 256^3 is unstable in float32 — residual
#: peaks of 50 and more; on a v5e the jnp trajectory ends in NaN by
#: iteration 203 while the pallas one converges, and perturbing b by
#: 1e-6 relative makes pallas fail and jnp converge — while Poisson
#: converges smoothly
REF_OPERATOR = "poisson3d"
#: service requests cycle through these tolerances.  In float32 the
#: batched recurrences drift: a tol-1e-3 request once claimed
#: convergence at recurrence 9.3e-4 with true residual 5.5e-3 (v5e,
#: 128^3).  The engine verifies the true residual before it retires a
#: request CONVERGED, and residual-replaces a column that missed
SERVICE_TOLS = (1e-2, 3e-3, 1e-3)
#: a request's float64 host residual may exceed its tol by at most this
#: factor: the engine verifies in float32 on the device, and the two
#: evaluations of ||b - A x|| / ||b|| agreed to 1e-5 relative (CPU,
#: 48^3 and 96^3 float32 service runs)
SERVICE_RES_FACTOR = 1.01
#: banded-ELL check: the convection-diffusion stencil on a long
#: 1024x16x16 grid (262,144 unknowns; the short axes keep the solve
#: short), stored as an ELLOperator: its offsets 1, 16 and 256 lie
#: inside the band of the Pallas SpMV (kernels/spmv_ell.py), so the
#: pallas substrate runs the matvec as that kernel
ELL_GRID = (1024, 16, 16)
#: bound on the SpMV's relative error against the float64 host product
ELL_SPMV_BOUND = 1e-5
#: the sharded and single-device programs are compared on early
#: iterates: the rounding differences of their reduction orders grow
#: with every iteration (CPU, 4 devices, 128x64x64 Poisson float32:
#: 5.4e-7 after 5 iterations, 2.7e-2 after 15; the converged solutions
#: 5.7e-4 apart), so the converged solutions are each verified and the
#: programs must agree after EARLY_ITERS iterations, where a wrong halo
#: or reduction is off by orders of magnitude more than EARLY_BOUND
EARLY_ITERS = 5
EARLY_BOUND = 1e-4
#: the sharded program may need at most this share of the single-device
#: program's memory per device (the compiler's figures for a v5e at
#: 512x256x256: 1,850,621,952 against 6,174,880,256 bytes)
MESH_MEMORY_SHARE = 0.5
MAXITER = 8000


def iteration_margin(iters: int) -> int:
    """Allowed iteration-count difference between two solves of one system
    whose reductions sum in a different order (float32)."""
    return max(10, int(0.1 * iters))


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def say(*parts) -> None:
    print(*parts, flush=True)


class CompileClock:
    """Seconds XLA spent compiling, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration


def host_matvec(op, x) -> np.ndarray:
    """A x in float64 on the host, written independently of the
    operator's jnp matvec: a numpy 7-point stencil, or the ELL row sums."""
    x = np.asarray(x, np.float64)
    if isinstance(op, ELLOperator):
        vals = np.asarray(op.values, np.float64)
        return np.einsum("rk,rk...->r...", vals, x[np.asarray(op.cols)])
    c = np.asarray(op.c, np.float64)
    u = x.reshape(op.nx, op.ny, op.nz)
    au = c[0] * u
    au[1:] += c[1] * u[:-1]
    au[:-1] += c[2] * u[1:]
    au[:, 1:] += c[3] * u[:, :-1]
    au[:, :-1] += c[4] * u[:, 1:]
    au[:, :, 1:] += c[5] * u[:, :, :-1]
    au[:, :, :-1] += c[6] * u[:, :, 1:]
    return au.reshape(-1)


def true_residual(op, b, x) -> float:
    """||b - A x|| / ||b|| in float64 on the host (:func:`host_matvec`)."""
    bb = np.asarray(b, np.float64).reshape(-1)
    return float(np.linalg.norm(bb - host_matvec(op, x))
                 / np.linalg.norm(bb))


def rel_error(x, x_true) -> float:
    x = np.asarray(x, np.float64).reshape(-1)
    xt = np.asarray(x_true, np.float64).reshape(-1)
    return float(np.linalg.norm(x - xt) / np.linalg.norm(xt))


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def bytes_in_use(device) -> int:
    return int(device.memory_stats()["bytes_in_use"])


def timed_solves(solve, b):
    """(first-call seconds, warm result, warm seconds)."""
    t0 = time.perf_counter()
    jax.block_until_ready(solve(b))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = solve(b)
    jax.block_until_ready(res)
    return first, res, time.perf_counter() - t0


def compiled_program(programs: dict, kind: str, *args):
    """The compiled cached session program of ``kind`` (a cache hit: it
    is the program that just ran)."""
    (fn,) = [fn for key, fn in programs.items() if key[0] == kind]
    return fn.lower(*args).compile()


def program_bytes(compiled) -> int:
    """Device bytes a compiled program needs per device: arguments,
    outputs and temporaries, as the compiler lays them out.  The
    allocator's ``peak_bytes_in_use`` does not show a program's
    temporaries (on four v5e chips it stayed at 74,007,040 bytes on the
    devices that ran the 1.78 GB-temp sharded solve)."""
    ma = compiled.memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes)


def verify_solution(label: str, op, b, x_true, res) -> None:
    """Converged, and the float64 host residual within TRUE_RES_BOUND."""
    iters = int(res.iterations)
    check(bool(res.converged), f"{label}: not converged after {iters} "
          f"iterations (relres {float(res.relres):.3e})")
    true = true_residual(op, b, res.x)
    say(f"{label}: iterations {iters}  relres {float(res.relres):.6e}  "
        f"true residual (host f64) {true:.6e}  error vs x_true "
        f"{rel_error(res.x, x_true):.6e}")
    check(true <= TRUE_RES_BOUND,
          f"{label}: true residual {true:.3e} > {TRUE_RES_BOUND:.0e}")


def solver_config() -> SolverConfig:
    return SolverConfig(tol=SOLVE_TOL, maxiter=MAXITER, rr_epoch=RR_EPOCH)


def kernel_count(hlo: str) -> int:
    return hlo.count('custom_call_target="tpu_custom_call"')


def check_iterations(label: str, a, b) -> None:
    gap = abs(int(a.iterations) - int(b.iterations))
    margin = iteration_margin(int(b.iterations))
    say(f"{label}: iterations differ by {gap} (margin {margin})")
    check(gap <= margin, f"{label}: iterations differ by {gap} "
          f"(margin {margin})")


def phase_single(device, clock: CompileClock) -> None:
    op, b, x_true = M.convection_diffusion(*SINGLE_GRID, dtype=jnp.float32)
    say(f"single solve: convection-diffusion {SINGLE_GRID}, n={op.n:,}, "
        f"float32, {METHOD} (rr_epoch {RR_EPOCH}), tol {SOLVE_TOL:.0e}")
    solver = repro.make_solver(METHOD, op, substrate="pallas",
                               config=solver_config())
    c0 = clock.seconds
    first, res, warm = timed_solves(solver.solve, b)
    say(f"pallas: first call {first:.3f} s (compile {clock.seconds - c0:.3f}"
        f" s), warm solve {warm:.6f} s  peak bytes {peak_bytes(device):,}")
    verify_solution("pallas", op, b, x_true, res)
    err = rel_error(res.x, x_true)
    check(err <= ERROR_BOUND,
          f"pallas: error vs x_true {err:.3e} > {ERROR_BOUND:.0e}")

    prog = compiled_program(solver._programs, "solve", b, None, None)
    n_kernels = kernel_count(prog.as_text())
    say(f"pallas: compiled solve program holds {n_kernels} tpu_custom_call"
        f", needs {program_bytes(prog):,} device bytes (compiler)")
    check(n_kernels >= 2, "the compiled pallas solve program holds no "
          "Pallas kernels (fused dots + fused axpy expected)")


def stencil_ell(grid) -> ELLOperator:
    """The float32 convection-diffusion stencil of ``grid`` as an
    ELLOperator (one slot per stencil point, zero slots off the
    boundary)."""
    stencil, _, _ = M.convection_diffusion(*grid, dtype=jnp.float32)
    c = np.asarray(stencil.c, np.float32)
    idx = np.arange(stencil.n).reshape(grid)
    pos = np.indices(grid)
    vals = np.zeros((stencil.n, 7), np.float32)
    cols = np.zeros((stencil.n, 7), np.int32)
    vals[:, 0], cols[:, 0] = c[0], idx.reshape(-1)
    # slots in Stencil7Operator's order: x-, x+, y-, y+, z-, z+
    for q, (ax, d) in enumerate(((0, -1), (0, 1), (1, -1), (1, 1),
                                 (2, -1), (2, 1)), start=1):
        inside = ((pos[ax] + d >= 0) & (pos[ax] + d < grid[ax])).reshape(-1)
        nb = np.roll(idx, -d, axis=ax).reshape(-1)   # idx[p + d] along ax
        vals[:, q] = np.where(inside, c[q], 0.0)
        cols[:, q] = np.where(inside, nb, 0)
    return ELLOperator(jnp.asarray(vals), jnp.asarray(cols), stencil.n)


def phase_ell(device, clock: CompileClock) -> None:
    from repro.kernels import ops

    op = stencil_ell(ELL_GRID)
    say(f"banded ELL: convection-diffusion {ELL_GRID} as ELL (k={op.k}), "
        f"n={op.n:,}, float32")
    check(ops.ell_is_banded(op), "the ELL operator is outside the band of "
          "the Pallas SpMV")
    rng = np.random.default_rng(SEED)
    spmv = jax.jit(lambda x: ops.spmv_ell(op, x))
    for m in (None, 8):
        shape = (op.n,) if m is None else (op.n, m)
        x = rng.standard_normal(shape).astype(np.float32)
        y = np.asarray(spmv(jnp.asarray(x)), np.float64)
        want = host_matvec(op, x)
        err = float(np.linalg.norm(y - want) / np.linalg.norm(want))
        n_kernels = kernel_count(spmv.lower(jnp.asarray(x)).compile()
                                 .as_text())
        say(f"spmv_ell {shape}: relative error vs host f64 {err:.6e}, "
            f"{n_kernels} tpu_custom_call")
        check(n_kernels >= 1, f"spmv_ell {shape} ran no Pallas kernel")
        check(err <= ELL_SPMV_BOUND, f"spmv_ell {shape}: relative error "
              f"{err:.3e} > {ELL_SPMV_BOUND:.0e}")

    x_true = np.ones(op.n, np.float32)
    b = jnp.asarray(host_matvec(op, x_true).astype(np.float32))
    solver = repro.make_solver(METHOD, op, substrate="pallas",
                               config=solver_config())
    c0 = clock.seconds
    first, res, warm = timed_solves(solver.solve, b)
    say(f"ELL pallas: first call {first:.3f} s (compile "
        f"{clock.seconds - c0:.3f} s), warm solve {warm:.6f} s")
    verify_solution("ELL pallas", op, b, x_true, res)
    n_kernels = kernel_count(compiled_program(solver._programs, "solve", b,
                                              None, None).as_text())
    say(f"ELL pallas: compiled solve program holds {n_kernels} "
        f"tpu_custom_call")
    check(n_kernels >= 3, "the ELL pallas solve program misses a kernel "
          "(fused dots + fused axpy + SpMV expected)")


def phase_reference(device, clock: CompileClock) -> None:
    op, b, x_true = getattr(M, REF_OPERATOR)(*SINGLE_GRID,
                                             dtype=jnp.float32)
    say(f"reference: {REF_OPERATOR} {SINGLE_GRID}, n={op.n:,}, float32, "
        f"pallas vs jnp")
    results = {}
    for sub in ("pallas", "jnp"):
        solver = repro.make_solver(METHOD, op, substrate=sub,
                                   config=solver_config())
        c0 = clock.seconds
        first, res, warm = timed_solves(solver.solve, b)
        say(f"{sub}: first call {first:.3f} s (compile "
            f"{clock.seconds - c0:.3f} s), warm solve {warm:.6f} s")
        verify_solution(sub, op, b, x_true, res)
        results[sub] = res
    check_iterations("pallas vs jnp", results["pallas"], results["jnp"])


def phase_service(device, clock: CompileClock) -> None:
    from repro.observe import metrics
    from repro.service import ServiceConfig, SolveEngine

    op, _, _ = M.convection_diffusion(*SERVICE_GRID, dtype=jnp.float32)
    scfg = ServiceConfig(max_batch=8, substrate="pallas", maxiter=MAXITER)
    n_req = 16
    say(f"service: convection-diffusion {SERVICE_GRID}, n={op.n:,}, "
        f"float32, {n_req} requests, max_batch {scfg.max_batch}, "
        f"tols {SERVICE_TOLS}")
    eng = SolveEngine(scfg)
    eng.register(op, name="convdiff")
    rng = np.random.default_rng(SEED)
    rhs, tols = {}, {}
    for i in range(n_req):
        b = rng.standard_normal(op.n).astype(np.float32)
        tol = SERVICE_TOLS[i % len(SERVICE_TOLS)]
        rid = eng.submit("convdiff", b, tol=tol)
        rhs[rid], tols[rid] = b, tol
    c0 = clock.seconds
    replaced = metrics.ENGINE_REPLACEMENTS.value()
    t0 = time.perf_counter()
    results = eng.run()
    wall = time.perf_counter() - t0
    replaced = metrics.ENGINE_REPLACEMENTS.value() - replaced
    check(sorted(r.rid for r in results) == sorted(rhs),
          f"service answered {len(results)} of {n_req} requests")
    # 16 requests through 8 slots: every answer past the eighth came
    # through a slot that a retirement freed mid-flight
    say(f"service: drained in {wall:.3f} s (compile "
        f"{clock.seconds - c0:.3f} s); {n_req - scfg.max_batch} requests "
        f"entered freed slots; {replaced:g} residual replacements at "
        f"retirement  peak bytes {peak_bytes(device):,}")
    for r in sorted(results, key=lambda r: r.rid):
        true = true_residual(op, rhs[r.rid], r.x)
        say(f"  request {r.rid:2d}: tol {tols[r.rid]:.0e}  "
            f"{r.status.name}  iterations {r.iterations}  verified "
            f"residual (device) {r.relres:.6e}  true residual (host f64) "
            f"{true:.6e} ({true / tols[r.rid]:.4f} x tol)  queue wait "
            f"{r.telemetry.queue_wait_s:.3f} s  chunks "
            f"{r.telemetry.chunks_resident}")
        check(r.status == SolveStatus.CONVERGED,
              f"request {r.rid} retired {r.status.name}")
        check(true <= SERVICE_RES_FACTOR * tols[r.rid],
              f"request {r.rid}: true residual {true:.3e} > "
              f"{SERVICE_RES_FACTOR:g} x tol {tols[r.rid]:.0e}")


def phase_mesh(devices, clock: CompileClock) -> None:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_multirhs_mesh

    mesh = make_multirhs_mesh(len(devices))
    op, b, x_true = getattr(M, REF_OPERATOR)(*MESH_GRID, dtype=jnp.float32)
    # host copies, so that device 0 holds nothing the other devices do not
    b, x_true = np.asarray(b), np.asarray(x_true)
    say(f"sharded solve: {REF_OPERATOR} {MESH_GRID}, n={op.n:,}, float32, "
        f"{METHOD} (rr_epoch {RR_EPOCH}), tol {SOLVE_TOL:.0e}, "
        f"mesh {dict(mesh.shape)}")
    session = repro.make_solver(METHOD, op, substrate="pallas",
                                config=solver_config())
    dist = session.on_mesh(mesh)
    b_grid = jax.device_put(b.reshape(MESH_GRID),
                            NamedSharding(mesh, P(*mesh.axis_names)))
    c0 = clock.seconds
    first, mres, warm = timed_solves(dist.solve, b_grid)
    held = [bytes_in_use(d) for d in devices]
    say(f"sharded: first call {first:.3f} s (compile "
        f"{clock.seconds - c0:.3f} s), warm solve {warm:.6f} s")
    say(f"sharded: per device, bytes in use {held} (the rhs and the "
        f"solution), peak bytes {[peak_bytes(d) for d in devices]}")
    verify_solution("sharded", op, b, x_true, mres)
    mesh_prog = compiled_program(dist._programs, "dsolve", b_grid)
    n_kernels = kernel_count(mesh_prog.as_text())
    say(f"sharded: compiled program holds {n_kernels} tpu_custom_call")
    check(n_kernels >= 2, "the sharded pallas program holds no kernels")
    check(min(held) > 0.5 * max(held), f"the rhs and solution are not "
          f"spread over the mesh (bytes in use per device {held})")

    b_dev = jnp.asarray(b)
    c0 = clock.seconds
    t0 = time.perf_counter()
    sres = session.solve(b_dev)
    jax.block_until_ready(sres)
    say(f"single device: one solve {time.perf_counter() - t0:.3f} s "
        f"(compile {clock.seconds - c0:.3f} s)  "
        f"peak bytes {peak_bytes(devices[0]):,}")
    mesh_bytes = program_bytes(mesh_prog)
    single_bytes = program_bytes(compiled_program(
        session._programs, "solve", b_dev, None, None))
    say(f"program memory per device (compiler): sharded {mesh_bytes:,} "
        f"bytes, single device {single_bytes:,} bytes")
    check(mesh_bytes <= MESH_MEMORY_SHARE * single_bytes, "the sharded "
          f"program needs {mesh_bytes:,} bytes per device, over "
          f"{MESH_MEMORY_SHARE:g} x the single-device {single_bytes:,}")
    check_iterations("sharded vs single device", mres, sres)
    diff = rel_error(np.asarray(mres.x).reshape(-1), sres.x)
    say(f"sharded vs single device: converged solutions differ by "
        f"{diff:.6e} (relative)")

    early_m = dist.solve(b_grid, tol=0.0, maxiter=EARLY_ITERS)
    early_s = session.solve(b_dev, tol=0.0, maxiter=EARLY_ITERS)
    early = rel_error(np.asarray(early_m.x).reshape(-1), early_s.x)
    say(f"sharded vs single device: iterates after {EARLY_ITERS} "
        f"iterations differ by {early:.6e} (relative)")
    check(int(early_m.iterations) == int(early_s.iterations) == EARLY_ITERS
          and early <= EARLY_BOUND, f"sharded and single-device iterates "
          f"after {EARLY_ITERS} iterations differ by {early:.3e} (bound "
          f"{EARLY_BOUND:.0e})")
    verify_solution("single device", op, b, x_true, sres)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: single solve + service (default); 4: the "
                    "sharded solve and its single-device comparison")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices; JAX sees {len(devices)}", file=sys.stderr)
        return 2
    if jax.config.jax_enable_x64:
        print("chip_smoke: runs in float32; unset JAX_ENABLE_X64",
              file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    dev = devices[0]
    say(f"device_kind {dev.device_kind!r}  platform {dev.platform}  "
        f"count {len(devices)}  jax {jax.__version__}  compile cache {cache}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(devices[:4], clock)
    else:
        phase_single(dev, clock)
        phase_ell(dev, clock)
        phase_reference(dev, clock)
        phase_service(dev, clock)
    say(f"all phases passed in {time.perf_counter() - t0:.3f} s "
        f"(compile {clock.seconds:.3f} s)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
