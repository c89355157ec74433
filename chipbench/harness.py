"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the record the metric readers read.

Set-up builds the operator and the session (or the engine) through the
public API with its defaults, and warms every program the window calls.
The window then drives the timed path for ``seconds``:

- ``solve``: one right-hand side at a time through ``LinearSolver.solve``
  (``DistributedSolver.solve`` where the configuration's ``process_grid``
  spans more than one chip: a ``("x", "y", "z")`` mesh of that shape, the
  right-hand sides laid out in its blocks).  The
  benchmark checks every answer's true residual with a matvec of its own;
  an answer that misses its tol gets one refinement solve at the mix's
  ``refine_tol`` (``solve(b, x0=x)`` on one chip; on a mesh, whose solve
  takes no ``x0``, a solve of A d = b - A x and x += d), and one still
  over tol after it counts as failed.  Only verified answers count.
- ``serve``: a closed loop of ``clients`` requests through
  ``SolveEngine.submit`` / ``poll``; the engine verifies each answer
  before it retires it.

Requests cycle a fixed set of base right-hand sides, in an order and
with scales drawn from the seed (see :mod:`chipbench.traffic`).

After the window the answers still in flight are awaited, peak device
memory is read, and a sample of the verified answers, drawn from the
seed, is compared with the float64 reference (:mod:`chipbench.reference`),
pulled to the host one at a time.
The numbers compared, each with its limit, decide ``correct``.
"""
from __future__ import annotations

import dataclasses
import math
import shutil
import statistics
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import cells, reference, traffic as traffic_mod
from .floor import floor_bytes
from .peaks import ChipPeaks

ROOT = Path(__file__).resolve().parent.parent
#: JAX's persistent compilation cache: one fixed path inside the
#: checkout, so that every run after a cell's first finds its programs
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_traces"
TRACE_SECONDS = 2.0


def enable_compile_cache() -> None:
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Programs lowered and compiled while it is armed (a warmed window
    has none of either)."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.armed = False
        self.lowered = 0
        self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.armed:
            if event == self.LOWER:
                self.lowered += 1
            elif event == self.COMPILE:
                self.compiled += 1


class Tracer:
    """Profiles the last :data:`TRACE_SECONDS` of a window, from the first
    boundary between calls after ``seconds - TRACE_SECONDS``.  The
    profiler's trace grows by tens of megabytes a second, and writing it
    out blocks the host, so it covers a few seconds at the window's end;
    ``bench.window`` spans the traced part."""

    def __init__(self, out_dir: Optional[str]):
        self.out_dir, self.on, self.t_on = out_dir, False, 0.0
        self._span = None

    def go(self, elapsed: float, seconds: float) -> bool:
        """Whether the window goes on at this boundary (``elapsed``
        seconds into it); starts the profiler when its part begins."""
        from . import trace as trace_mod

        if self.out_dir is None:
            return elapsed < seconds
        if not self.on and elapsed >= seconds - TRACE_SECONDS:
            trace_mod.start(self.out_dir)
            self._span = _span("bench.window")
            self._span.__enter__()
            self.on, self.t_on = True, elapsed
        return not (self.on and elapsed >= max(seconds,
                                               self.t_on + TRACE_SECONDS))

    def close(self) -> None:
        from . import trace as trace_mod

        if self.on:
            self._span.__exit__(None, None, None)
            trace_mod.stop()
            self.on = False


class Sample:
    """A uniform sample of at most ``k`` items of a stream (reservoir
    sampling, drawn from the seed)."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.seen, self.items = k, rng, 0, []

    def add(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(self.seen))
            if j < self.k:
                self.items[j] = item


@dataclasses.dataclass
class SolveRec:
    iterations: int     # solver iterations, the refinement's included
    refines: int
    verified: bool
    traced: bool        # ran inside the profiled part of the window


@dataclasses.dataclass
class RequestRec:
    latency_s: float    # submission to retirement, host clock
    iterations: int
    queue_wait_s: float
    chunks_resident: int
    converged: bool


@dataclasses.dataclass
class Record:
    """What one run measured; the metric readers read nothing else."""
    setup_s: float
    window_s: float
    peaks: ChipPeaks
    floor_bytes: Optional[int] = None     # per device and iteration
    solves: Optional[List[SolveRec]] = None
    requests: Optional[List[RequestRec]] = None
    chunks: Optional[int] = None          # engine chunks stepped
    max_batch: Optional[int] = None
    trace: Optional[object] = None        # trace.TraceSummary

    @property
    def iterations(self) -> int:
        return sum(s.iterations for s in self.solves or ())

    @property
    def traced_iterations(self) -> int:
        return sum(s.iterations for s in self.solves or () if s.traced)


@dataclasses.dataclass
class Outcome:
    record: Record
    attempted: int
    failed: int
    #: the numbers compared, each with its limit: name -> (value, limit)
    checks: Dict[str, Tuple[float, float]]
    memory_peak_bytes: int
    lowered_in_window: int
    compiled_in_window: int

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.checks.values())


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# the benchmark's own device check: the configuration's operator in jnp
# ---------------------------------------------------------------------------

def _checks(operator_cfg: dict, sharding):
    """Jitted ``residual(c, b, x) -> b - A x`` (in b's type),
    ``relres(c, b, x) -> ||b - A x|| / ||b||`` and ``add(x, d)``, on
    vectors shaped like the program's (flat on one chip, the grid on a
    mesh), with ``c`` the operator's float32 parameters; the arithmetic
    is float32 whatever the program's type."""
    import jax
    import jax.numpy as jnp

    shape = tuple(operator_cfg["grid"])
    apply_f32 = cells.operator(operator_cfg["kind"]).apply_f32

    def r32(c, b, x):
        b = b.reshape(shape).astype(jnp.float32)
        x = x.reshape(shape).astype(jnp.float32)
        if sharding is not None:
            # the answer in b's blocks, whatever layout the solve left it
            # in: the operator's halos then pass between neighbouring
            # blocks, where a mixed layout would gather the whole grid
            x = jax.lax.with_sharding_constraint(x, sharding)
        return b, b - apply_f32(c, x)

    def residual(c, b, x):
        return r32(c, b, x)[1].reshape(b.shape).astype(b.dtype)

    def relres(c, b, x):
        b, r = r32(c, b, x)
        return jnp.sqrt(jnp.sum(r * r) / jnp.sum(b * b))

    return (jax.jit(residual, out_shardings=sharding), jax.jit(relres),
            jax.jit(lambda x, d: x + d, out_shardings=sharding))


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def _operator(operator_cfg: dict):
    return cells.operator(operator_cfg["kind"]).build(operator_cfg)


def _session(config: dict, tol: float):
    import repro
    from repro.core import SolverConfig

    s = config["solver"]
    return repro.make_solver(
        s["method"], _operator(config["operator"]),
        config=SolverConfig(tol=tol, maxiter=s["maxiter"],
                            rr_epoch=s["rr_epoch"]))


def _mesh(devices, process_grid):
    """The ``("x", "y", "z")`` mesh of ``process_grid`` over the first
    devices, one mesh axis per grid axis."""
    import jax
    from jax.sharding import AxisType

    shape = tuple(int(p) for p in process_grid)
    return jax.make_mesh(shape, ("x", "y", "z"),
                         axis_types=(AxisType.Auto,) * 3,
                         devices=devices[:math.prod(shape)])


# ---------------------------------------------------------------------------
# the solve entry
# ---------------------------------------------------------------------------

def _solve_entry(cell: cells.Cell, seed: int, seconds: float, devices, tracer,
                 counter, t_start):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg, mix = cell.config, cell.traffic
    o = cfg["operator"]
    grid, dtype = tuple(o["grid"]), o["dtype"]
    chips = cells.chips(cfg)
    tols = sorted({float(t) for t in mix["tols"]})
    if len(tols) != 1:
        raise ValueError("a solve mix has one tol: the solver's tol is "
                         "static, so another would compile in the window")
    tol, refine_tol = tols[0], float(mix["refine_tol"])
    session = _session(cfg, tol)
    c = jnp.asarray(cells.operator(o["kind"]).params(o), jnp.float32)
    if chips > 1:
        sharding = NamedSharding(_mesh(devices, cfg["process_grid"]),
                                 P("x", "y", "z"))
        dist = session.on_mesh(sharding.mesh)
        shape = grid
    else:
        sharding, dist, shape = None, None, (int(np.prod(grid)),)
    make_b = traffic_mod.make_rhs_fn(shape, dtype, sharding)
    residual, relres, add = _checks(o, sharding)

    def solve(b):
        with _span("bench.solve"):
            res = (dist or session).solve(b)
            return res.x, int(res.iterations)

    def refine(b, x):
        with _span("bench.refine"):
            if dist is None:
                res = session.solve(b, x0=x, tol=refine_tol)
                return res.x, int(res.iterations)
            res = dist.solve(residual(c, b, x), tol=refine_tol)
            return add(x, res.x), int(res.iterations)

    def check(b, x) -> float:
        with _span("bench.verify"):
            return float(relres(c, b, x))

    def one(req, traced: bool = False):
        b = make_b(req.rhs, req.scale)
        x, its = solve(b)
        rel, refines = check(b, x), 0
        if not rel <= tol:
            x, more = refine(b, x)
            rel, its, refines = check(b, x), its + more, 1
        return x, SolveRec(its, refines, bool(rel <= tol), traced)

    say(f"set-up: session built at {time.perf_counter() - t_start:.3f} s")
    # warm every program the window calls, the refinement included, on
    # right-hand sides the window never draws; the check is warmed on the
    # layouts of both the solve's and the refinement's answers, which
    # differ on a mesh whose blocks are not the program's x-slabs
    b = make_b(traffic_mod.WARM_RHS, 1.0)
    x, _ = solve(b)
    check(b, x)
    check(b, refine(b, x)[0])

    say(f"set-up: warm at {time.perf_counter() - t_start:.3f} s")
    sample = Sample(int(mix["check_sample"]), traffic_mod.host_rng(seed, 1))
    recs: List[SolveRec] = []
    stream = traffic_mod.requests(mix, seed)
    counter.armed = True
    t0 = time.perf_counter()
    while tracer.go(time.perf_counter() - t0, seconds):
        req = next(stream)
        x, rec = one(req, tracer.on)
        recs.append(rec)
        if rec.verified:
            sample.add((req, x))
    window = time.perf_counter() - t0
    counter.armed = False
    tracer.close()
    peak = _peak_bytes(devices[:chips])

    def checked():
        # one answer and its b on the host at a time
        for req, x in sample.items:
            yield (np.asarray(jax.device_get(make_b(req.rhs, req.scale))),
                   np.asarray(jax.device_get(x)), req.tol)

    record = Record(
        setup_s=t0 - t_start, window_s=window, peaks=None,
        floor_bytes=floor_bytes(grid, dtype, cfg["solver"]["method"],
                                chips),
        solves=recs)
    n_failed = sum(not r.verified for r in recs)
    return record, len(recs), n_failed, 0, checked(), peak


# ---------------------------------------------------------------------------
# the serve entry
# ---------------------------------------------------------------------------

def _serve_entry(cell: cells.Cell, seed: int, seconds: float, devices, tracer,
                 counter, t_start):
    import jax

    from repro.service import ServiceConfig, SolveEngine

    cfg, mix = cell.config, cell.traffic
    if cells.chips(cfg) != 1:
        raise ValueError("the serve entry runs its engine on one chip; "
                         f"process_grid is {cfg['process_grid']}")
    o = cfg["operator"]
    grid, dtype = tuple(o["grid"]), o["dtype"]
    n = int(np.prod(grid))
    make_b = traffic_mod.make_rhs_fn((n,), dtype)
    # the base right-hand sides, on the host where the engine takes them
    pool = np.asarray(jax.device_get(
        make_b(np.arange(int(mix["rhs_set"])), 1.0)))

    def rhs(req):
        return pool[req.rhs] * pool.dtype.type(req.scale)

    max_batch = int(mix["max_batch"])
    engine = SolveEngine(ServiceConfig(max_batch=max_batch,
                                       maxiter=cfg["solver"]["maxiter"]))
    name = engine.register(_operator(o), name=cfg["name"])
    tols = [float(t) for t in mix["tols"]]
    say(f"set-up: pool and engine built at "
        f"{time.perf_counter() - t_start:.3f} s")

    # warm every program (init, step, splice + step, verify) by serving
    # one request more than the block holds, on a right-hand side the
    # window never sends
    warm = np.asarray(jax.device_get(make_b(traffic_mod.WARM_RHS, 1.0)))
    warming = {engine.submit(name, warm, tol=tols[j % len(tols)])
               for j in range(max_batch + 1)}
    deadline = time.perf_counter() + float(mix["drain_s"])
    while warming and time.perf_counter() < deadline:
        warming -= {r.rid for r in engine.poll()}
    say(f"set-up: warm at {time.perf_counter() - t_start:.3f} s")

    stream = traffic_mod.requests(mix, seed)
    sample = Sample(int(mix["check_sample"]), traffic_mod.host_rng(seed, 1))
    longest = None
    pending = {}                    # rid -> (request, submit time)
    recs: List[RequestRec] = []
    chunks = 0

    def submit():
        req = next(stream)
        with _span("bench.submit"):
            rid = engine.submit(name, rhs(req), tol=req.tol)
        pending[rid] = (req, time.perf_counter())

    def keep(req, r):
        nonlocal longest
        if not r.converged:
            return
        item = (req, r.x)
        sample.add(item)
        if longest is None or r.iterations > longest[0]:
            longest = (r.iterations, item)

    counter.armed = True
    t0 = now = time.perf_counter()
    for _ in range(int(mix["clients"])):
        submit()
    while tracer.go(now - t0, seconds):
        with _span("bench.poll"):
            done = engine.poll()
        now = time.perf_counter()
        chunks += 1
        for r in done:
            if r.rid not in pending:    # a warm-up request
                continue
            req, t_sub = pending.pop(r.rid)
            recs.append(RequestRec(now - t_sub, int(r.iterations),
                                   r.telemetry.queue_wait_s,
                                   r.telemetry.chunks_resident,
                                   bool(r.converged)))
            keep(req, r)
            submit()
    window = now - t0
    counter.armed = False
    tracer.close()
    # the requests sent last are still in flight: late is not wrong, but
    # an answer that never comes is missing
    deadline = time.perf_counter() + float(mix["drain_s"])
    while pending and engine.has_work() and time.perf_counter() < deadline:
        for r in engine.poll():
            if r.rid in pending:
                keep(pending.pop(r.rid)[0], r)
    peak = _peak_bytes(devices[:1])
    items = list(sample.items)
    if longest is not None and all(it is not longest[1] for it in items):
        items.append(longest[1])
    checked = ((rhs(req), x, req.tol) for req, x in items)
    record = Record(setup_s=t0 - t_start, window_s=window, peaks=None,
                    requests=recs, chunks=chunks, max_batch=max_batch)
    n_failed = sum(not r.converged for r in recs)
    # a warm-up request that never came back is missing too
    return (record, len(recs), n_failed, len(pending) + len(warming),
            checked, peak)


ENTRIES = {"solve": _solve_entry, "serve": _serve_entry}


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool, *,
             devices, peaks: ChipPeaks, t_start: float) -> Outcome:
    """Set up, measure and check one run of ``cell``."""
    from . import trace as trace_mod

    if devices[0].platform != "cpu":
        enable_compile_cache()
    counter = CompileCounter()
    tdir = str(TRACE_DIR / cell.name)
    tracer = Tracer(tdir if trace else None)
    cfg = cell.config
    entry = ENTRIES[cell.traffic["entry"]]
    try:
        record, attempted, failed, missing, checked, peak = entry(
            cell, seed, seconds, devices, tracer, counter, t_start)
        record.peaks = peaks
        if trace:
            record.trace = trace_mod.summarize(
                trace_mod.load_xplane(tdir),
                [d.id for d in devices[:cells.chips(cfg)]])
    finally:
        if trace:
            shutil.rmtree(tdir, ignore_errors=True)
    ratios = [reference.relative_residual(cfg["operator"], b, x) / tol
              for b, x, tol in checked]
    say(f"checked {len(ratios)} verified answers against the float64 "
        f"reference; residual / tol: median "
        f"{statistics.median(ratios) if ratios else float('nan'):.6g}")
    checks = {
        # every verified answer that was checked meets its tol
        "worst_residual_over_tol": (
            max(ratios, default=0.0),
            1.0 + float(cfg["verify"]["float64_allowance"])),
        # answers that missed their tol, of those due in the window
        "failed_share": (failed / attempted if attempted else 1.0,
                         float(cfg["verify"]["failed_share_limit"])),
        # answers that never came
        "answers_missing": (float(missing), 0.0),
    }
    return Outcome(record, attempted, failed, checks, peak, counter.lowered,
                   counter.compiled)
