"""Tests for repro.observe: traces, spans, metrics, clock, report CLI.

The load-bearing contract is NON-PERTURBATION: turning observability on
must not change a single bit of the numerical answer and must not add a
synchronization or a dependency edge to the in-flight matvec.  The
bitwise-parity tests pin the first half; the contract-verifier tests
(one fused reduction per iteration, overlap-edge freedom — run on
TRACED bindings) pin the second.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from conftest import enable_x64  # noqa: F401  (x64 fixture dependency)
from repro.core import SolverConfig
from repro.core import matrices as M
from repro.core.types import TRACE_CHANNELS, SolveStatus
from repro.observe import (RECORDER, REGISTRY, ConvergenceTrace,
                           MetricsRegistry, SpanRecorder, TickingClock,
                           wrap_trace)
from repro.observe.clock import SYSTEM_CLOCK, Clock
from repro.service import ServiceConfig, SolveEngine


def _problem(nx=6):
    return M.poisson3d(nx)


def _same(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b), equal_nan=True)


# ---------------------------------------------------------------------------
# non-perturbation: trace on == trace off, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
def test_trace_bitwise_parity_single(x64, substrate):
    op, b, _ = _problem()
    s = repro.make_solver("p-bicgsafe", op, substrate=substrate,
                          config=SolverConfig(tol=1e-8, maxiter=300))
    bare = s.solve(b)
    traced = s.solve(b, trace=True)
    assert bare.trace is None and traced.trace is not None
    for field in ("x", "iterations", "relres", "converged", "breakdown",
                  "status"):
        assert _same(getattr(bare, field), getattr(traced, field)), field


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
def test_trace_bitwise_parity_batched(x64, substrate):
    op, b, _ = _problem()
    rng = np.random.default_rng(3)
    B = jnp.stack([b, jnp.asarray(rng.standard_normal(b.shape))], axis=1)
    s = repro.make_solver("p-bicgsafe", op, substrate=substrate,
                          config=SolverConfig(tol=1e-8, maxiter=300))
    bare = s.solve_many(B)
    traced = s.solve_many(B, trace=True)
    assert traced.trace.batched and traced.trace.m == 2
    for field in ("x", "iterations", "relres", "converged", "breakdown",
                  "status"):
        assert _same(getattr(bare, field), getattr(traced, field)), field


def test_trace_bitwise_parity_open_loop(x64):
    """Open-loop chunk stepping: a traced config solves the same system
    to the same bits as an untraced one (tracing is config-driven on
    this path — the ring rides in the state pytree)."""
    op, b, _ = _problem()
    B = b[:, None]
    cfgs = [SolverConfig(tol=1e-8, maxiter=300),
            SolverConfig(tol=1e-8, maxiter=300, trace_cap=64)]
    states = []
    for cfg in cfgs:
        s = repro.make_solver("p-bicgsafe", op, config=cfg)
        st = s.init(B)
        for _ in range(6):
            st = s.step_chunk(st, 16)
        states.append(s.result(st))
    bare, traced = states
    assert bare.trace is None and traced.trace is not None
    for field in ("x", "iterations", "relres", "converged"):
        assert _same(getattr(bare, field), getattr(traced, field)), field


# ---------------------------------------------------------------------------
# trace content
# ---------------------------------------------------------------------------

def test_trace_records_convergence_trajectory(x64):
    op, b, _ = _problem()
    s = repro.make_solver("p-bicgsafe", op,
                          config=SolverConfig(tol=1e-8, maxiter=300))
    res = s.solve(b, trace=True)
    tr = res.trace
    assert isinstance(tr, ConvergenceTrace) and not tr.batched
    rows = tr.per_iteration()
    it = rows[:, TRACE_CHANNELS.index("iteration")]
    relres = rows[:, TRACE_CHANNELS.index("relres")]
    # completed-update convention: first row is (0, 1.0), last row is
    # (T, final_relres, CONVERGED)
    assert it[0] == 0 and relres[0] == 1.0
    assert it[-1] == int(res.iterations)
    assert np.isclose(relres[-1], float(res.relres), rtol=1e-12)
    assert int(rows[-1, TRACE_CHANNELS.index("status")]) \
        == SolveStatus.CONVERGED.value
    assert (np.diff(it) == 1).all()
    s2 = tr.summary()
    assert s2["status"] == "CONVERGED"
    assert s2["iterations"] == int(res.iterations)


def test_trace_ring_wraparound(x64):
    """An int trace cap keeps the LAST cap iterations."""
    op, b, _ = _problem()
    s = repro.make_solver("p-bicgsafe", op,
                          config=SolverConfig(tol=1e-8, maxiter=300))
    full = s.solve(b, trace=True).trace
    ringed = s.solve(b, trace=4).trace
    assert ringed.cap == 4 and ringed.steps == full.steps
    it_full = full.per_iteration()[:, TRACE_CHANNELS.index("iteration")]
    it_ring = ringed.per_iteration()[:, TRACE_CHANNELS.index("iteration")]
    assert list(it_ring) == list(it_full[-len(it_ring):])


def test_engine_splice_resets_reused_slot_trace(x64):
    """A request admitted into a reused slot must not see its
    predecessor's rows: splice NaNs the column, per_iteration drops
    them, so the harvested trace starts at the new request's iter 0."""
    op, b, _ = _problem(5)
    eng = SolveEngine(ServiceConfig(max_batch=2, chunk=8, tol=1e-8,
                                    maxiter=500, trace_cap=256))
    name = eng.register(op)
    rng = np.random.default_rng(5)
    for k in range(5):                    # 5 requests through 2 slots
        eng.submit(name, rng.standard_normal(op.shape[0]))
    results = eng.run()
    assert len(results) == 5
    for r in results:
        assert r.status == SolveStatus.CONVERGED
        rows = r.trace.per_iteration()
        it = rows[:, TRACE_CHANNELS.index("iteration")]
        assert it[0] == 0, "reused slot leaked the previous trajectory"
        assert it[-1] == r.iterations
        assert (np.diff(it) == 1).all()


def test_guarded_solve_carries_trace(x64):
    from repro.resilience import RecoveryPolicy
    op, b, _ = _problem()
    s = repro.make_solver(
        "p-bicgsafe", op,
        config=SolverConfig(tol=1e-8, maxiter=300, trace_cap=64),
        recovery=RecoveryPolicy())
    res = s.solve(b)
    assert isinstance(res.trace, ConvergenceTrace) and not res.trace.batched
    assert res.trace.summary()["status"] == "CONVERGED"


# ---------------------------------------------------------------------------
# the communication contracts hold on TRACED bindings
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
def test_contracts_hold_with_tracing(x64, substrate):
    op, _, _ = _problem()
    s = repro.make_solver(
        "p-bicgsafe", op, substrate=substrate,
        config=SolverConfig(tol=1e-8, maxiter=300, trace_cap=50))
    reports = s.verify_contracts(raise_on_violation=True)
    contracts = {f.contract: f.status for r in reports for f in r.findings}
    assert contracts["one_reduction_per_iteration"] == "ok"
    assert contracts["overlap_edge_free"] == "ok"


def test_contracts_hold_with_tracing_mesh(x64):
    """The traced mesh binding (replicated ring in the out_specs) still
    passes the sharded contract cell — no extra collective from the
    trace payload."""
    from jax.sharding import Mesh
    op, _, _ = _problem()
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("x",))
    s = repro.make_solver(
        "p-bicgsafe", op,
        config=SolverConfig(tol=1e-8, maxiter=300, trace_cap=50))
    reports = s.verify_contracts(bindings=["mesh"], mesh=mesh,
                                 raise_on_violation=True)
    contracts = {f.contract for r in reports for f in r.findings}
    assert "single_psum_sharded" in contracts


# ---------------------------------------------------------------------------
# ConvergenceTrace plumbing
# ---------------------------------------------------------------------------

def test_wrap_trace_passthrough_and_validation():
    assert wrap_trace(None) is None
    buf = np.full((4, len(TRACE_CHANNELS)), np.nan)
    tr = wrap_trace({"buffer": buf, "steps": 2})
    assert isinstance(tr, ConvergenceTrace)
    assert wrap_trace(tr) is tr
    with pytest.raises(ValueError, match="trace buffer"):
        ConvergenceTrace(np.zeros((4, 3)), 1)


def test_trace_json_roundtrip(tmp_path):
    rng = np.random.default_rng(0)
    buf = rng.standard_normal((5, len(TRACE_CHANNELS), 2))
    buf[0, :, :] = np.nan                 # never-written slot
    tr = ConvergenceTrace(buf, 12)
    payload = json.loads(json.dumps(tr.to_json()))   # JSON-able
    back = ConvergenceTrace.from_json(payload)
    assert back.steps == 12 and back.batched and back.m == 2
    assert _same(back.buffer, buf)
    p = tmp_path / "t.json"
    tr.column(1).save(p)
    single = ConvergenceTrace.from_json(json.loads(p.read_text()))
    assert not single.batched
    assert _same(single.buffer, buf[:, :, 1])


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_metrics_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help", labels=("kind",))
    c.inc(kind="a")
    c.inc(2, kind="a")
    c.inc(kind="b")
    assert c.value(kind="a") == 3 and c.value(kind="b") == 1
    with pytest.raises(ValueError, match="labels"):
        c.inc(wrong="x")
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1, kind="a")

    g = reg.gauge("g", "help")
    g.set(5)
    g.dec(2)
    assert g.value() == 3

    h = reg.histogram("h_seconds", "help", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count() == 3 and h.sum() == 55.5
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("h_seconds")

    text = reg.prometheus()
    assert '# TYPE c_total counter' in text
    assert 'c_total{kind="a"} 3' in text
    assert 'h_seconds_bucket{le="1"} 1' in text
    assert 'h_seconds_bucket{le="10"} 2' in text
    assert 'h_seconds_bucket{le="+Inf"} 3' in text
    assert 'h_seconds_count 3' in text

    snap = json.loads(json.dumps(reg.snapshot()))    # JSON-able
    assert snap["h_seconds"]["values"][0]["count"] == 3

    reg.reset()
    assert c.value(kind="a") == 0 and h.count() == 0
    assert reg.get("c_total") is c                   # instruments survive


def test_api_layer_records_metrics(x64):
    from repro.observe.metrics import SESSION_CACHE, SOLVES
    op, b, _ = _problem()
    before_miss = SESSION_CACHE.value(outcome="miss")
    s = repro.make_solver("p-bicgsafe", op,
                          config=SolverConfig(tol=1e-6, maxiter=200,
                                              stagnation_window=17))
    assert SESSION_CACHE.value(outcome="miss") == before_miss + 1
    before_hit = SESSION_CACHE.value(outcome="hit")
    repro.make_solver("p-bicgsafe", op,
                      config=SolverConfig(tol=1e-6, maxiter=200,
                                          stagnation_window=17))
    assert SESSION_CACHE.value(outcome="hit") == before_hit + 1
    before = SOLVES.value(method="p-bicgsafe", substrate="jnp",
                          entry="solve")
    s.solve(b)
    assert SOLVES.value(method="p-bicgsafe", substrate="jnp",
                        entry="solve") == before + 1


def test_engine_records_metrics(x64):
    from repro.observe.metrics import ENGINE_REQUESTS, REQUEST_CHUNKS
    op, b, _ = _problem(5)
    before = ENGINE_REQUESTS.value(status="CONVERGED")
    n_before = REQUEST_CHUNKS.count()
    eng = SolveEngine(ServiceConfig(max_batch=2, chunk=16, tol=1e-8,
                                    maxiter=500))
    name = eng.register(op)
    eng.submit(name, np.asarray(b))
    results = eng.run()
    assert results[0].trace is None       # trace_cap unset: no harvest
    assert ENGINE_REQUESTS.value(status="CONVERGED") == before + 1
    assert REQUEST_CHUNKS.count() == n_before + 1


# ---------------------------------------------------------------------------
# spans + clock
# ---------------------------------------------------------------------------

def test_span_recorder_with_virtual_clock():
    clk = TickingClock(dt=0.0)
    rec = SpanRecorder(clock=clk)
    with rec.span("outer", operator="p"):
        clk.advance(2.0)
        with rec.span("inner"):
            clk.advance(0.5)
    names = [s.name for s in rec.spans()]
    assert names == ["inner", "outer"]    # closed in completion order
    inner, outer = rec.spans()
    assert inner.duration == pytest.approx(0.5)
    assert outer.duration == pytest.approx(2.5)
    assert outer.args == {"operator": "p"}

    ct = rec.chrome_trace()
    ev = ct["traceEvents"]
    assert all(e["ph"] == "X" for e in ev)
    by_name = {e["name"]: e for e in ev}
    assert by_name["inner"]["dur"] == pytest.approx(0.5e6)   # µs
    json.dumps(ct)                                           # serializable

    rec.clear()
    assert rec.spans() == []


def test_span_recorder_disabled_records_nothing():
    rec = SpanRecorder(clock=TickingClock(dt=1.0))
    rec.enabled = False
    with rec.span("quiet"):
        pass
    assert rec.spans() == []


def test_clock_protocol_and_inject_shim():
    from repro.resilience.inject import TickingClock as LegacyClock
    assert LegacyClock is TickingClock
    assert isinstance(TickingClock(), Clock)
    assert isinstance(SYSTEM_CLOCK, Clock)
    c = TickingClock(dt=0.25, t0=1.0)
    assert c() == 1.25 and c() == 1.5
    c.advance(10)
    assert c() == pytest.approx(11.75)


def test_engine_emits_spans(x64):
    op, b, _ = _problem(5)
    RECORDER.clear()
    eng = SolveEngine(ServiceConfig(max_batch=2, chunk=16, tol=1e-8,
                                    maxiter=500))
    name = eng.register(op)
    eng.submit(name, np.asarray(b))
    eng.run()
    kinds = {s.name for s in RECORDER.spans()}
    assert {"engine.chunk", "engine.retire"} <= kinds
    RECORDER.clear()


def _drain_with_refills(enabled=True):
    """Five float32 requests through two slots, so that the block is
    filled once and refilled by splices; the spans and the host-byte
    counter's growth come back with the results."""
    from repro.observe.metrics import ENGINE_HOST_BYTES
    op, b, _ = _problem(5)
    eng = SolveEngine(ServiceConfig(max_batch=2, chunk=8, tol=1e-4,
                                    maxiter=400))
    name = eng.register(op)
    rng = np.random.default_rng(7)
    for _ in range(5):
        eng.submit(name, rng.standard_normal(b.shape[0]).astype(np.float32))
    before = {d: ENGINE_HOST_BYTES.value(direction=d)
              for d in ("h2d", "d2h")}
    RECORDER.clear()
    RECORDER.enabled = enabled
    try:
        results = eng.run()
    finally:
        RECORDER.enabled = True
    moved = {d: ENGINE_HOST_BYTES.value(direction=d) - before[d]
             for d in before}
    spans = RECORDER.spans()
    RECORDER.clear()
    return op.shape[0], results, spans, moved


def test_engine_spans_the_host_round_trip():
    n, results, spans, _ = _drain_with_refills()
    assert len(results) == 5 and all(r.converged for r in results)
    names = [s.name for s in spans]
    assert {"engine.admit", "engine.put", "engine.harvest",
            "engine.splice_step"} <= set(names)
    # one put after each admission that spliced or filled, and none
    # inside the dispatch spans
    puts = [s for s in spans if s.name == "engine.put"]
    assert len(puts) == names.count("engine.splice_step") \
        + names.count("engine.init_fill")
    for d in (s for s in spans if s.name in ("engine.splice_step",
                                              "engine.init_fill")):
        assert not any(d.start <= p.start < d.end for p in puts)
    # one harvest in each chunk that retired a request, at most
    assert 0 < names.count("engine.harvest") <= names.count("engine.retire")


def test_engine_host_bytes_are_the_arithmetic():
    """float32 blocks: a put moves the (n, m) block and the (m,) tol and
    maxiter vectors (and the (m,) mask when it splices); each chunk reads
    five (m,) flags (two bool, int32 iterations, float32 relres, int32
    budget), a verify two (bool, float32), a harvest the (n, m) block."""
    n, _, spans, moved = _drain_with_refills()
    m = 2
    count = {k: sum(s.name == k for s in spans) for k in (
        "engine.init_fill", "engine.splice_step", "engine.retire",
        "engine.verify", "engine.harvest")}
    assert all(count.values())            # every term below is counted
    assert moved["h2d"] == (count["engine.init_fill"] * (n * m * 4 + 8 * m)
                            + count["engine.splice_step"]
                            * (n * m * 4 + 9 * m))
    assert moved["d2h"] == (count["engine.retire"] * 14 * m
                            + count["engine.verify"] * 5 * m
                            + count["engine.harvest"] * n * m * 4)


def test_engine_results_bitwise_with_spans_on_and_off():
    _, on, spans_on, moved_on = _drain_with_refills(enabled=True)
    _, off, spans_off, moved_off = _drain_with_refills(enabled=False)
    assert spans_on and not spans_off
    assert moved_on == moved_off          # the counter is not a span
    assert [r.rid for r in on] == [r.rid for r in off]
    for a, b in zip(on, off):
        assert _same(a.x, b.x)
        assert a.iterations == b.iterations and a.relres == b.relres


# ---------------------------------------------------------------------------
# report CLI
# ---------------------------------------------------------------------------

def test_report_cli_smoke_and_render(x64, tmp_path, capsys):
    from repro.observe.report import main
    out = tmp_path / "observe"
    assert main(["smoke", "--out", str(out)]) == 0
    wrote = {p.name for p in out.iterdir()}
    assert {"convergence.json", "spans.trace.json", "metrics.prom",
            "metrics.json"} <= wrote
    conv = json.loads((out / "convergence.json").read_text())
    assert conv["schema"] == "repro.observe/convergence-trace/v1"
    assert conv["summary"]["status"] == "CONVERGED"
    spans = json.loads((out / "spans.trace.json").read_text())
    assert spans["metadata"]["schema"] == "repro.observe/chrome-trace/v1"
    assert any(e["name"] == "engine.chunk" for e in spans["traceEvents"])
    prom = (out / "metrics.prom").read_text()
    assert "repro_engine_requests_total" in prom

    capsys.readouterr()
    assert main(["report", "--dir", str(out)]) == 0
    text = capsys.readouterr().out
    assert "engine.chunk" in text          # timeline rendered
    assert "repro_engine_requests_total" in text
    assert "CONVERGED" in text
