"""Continuous-batching solve engine.

Slot-based design, the solver-side sibling of the LM serving engine in
:mod:`repro.serve.engine` (vLLM-style at the batch level): a fixed
``(n, max_batch)`` block of right-hand-side *slots* is stepped in chunks
of k iterations by ONE compiled program per registered operator,
regardless of which request mix occupies the slots.  Padding
unification: empty slots ride along as frozen columns (per-column budget
0), so the step program's shapes never change and nothing recompiles
under load.

Between chunks the engine retires finished columns — converged (once
their true residual ``b - A x`` is verified on the device: a column
whose drifting recurrence claimed convergence too early is
residual-replaced and keeps iterating), broken down, past their
per-request ``maxiter`` budget (enforced on-device by the per-column
mask), or past their wall-clock ``deadline`` — and
refills the freed slots mid-flight by splicing fresh right-hand sides
and reset per-column Krylov state into the live state pytree (the
``splice_step`` handle of the operator's bound
:class:`repro.api.LinearSolver` session — admission fused into the
chunk as ONE compiled program).  Columns are independent
in "individual" blocked mode, so multiplexing is *exact*: a request's
trajectory is the one it would have had in a standalone
``solve_batched`` call (property-tested in tests/test_service.py).

What makes the batched p-BiCGSafe iteration the right substrate for a
solver service is the paper's own production property: every iteration
of the resident block issues ONE ``dot_reduce`` of a ``(9, m)`` partial
block — the single synchronization phase, amortized over every resident
request (Krasnopolsky, arXiv:1907.12874) — and that reduction keeps no
dependency edge to the in-flight block matvec, so the comm-hiding
overlap (Cools & Vanroose, arXiv:1612.01395) is intact under load
(asserted on the engine's step program in tests/test_service.py).

Throughput/latency against sequential and static-batch serving:
``benchmarks/bench_service.py``.

Resilience (``ServiceConfig.recovery``; see :mod:`repro.resilience`):
with a :class:`~repro.resilience.RecoveryPolicy` bound, the resident
blocks step guarded — the fused reduction carries the (11, m) health
rows, so breakdown/NaN detection costs zero extra synchronization —
and every retirement carries a typed :class:`~repro.core.SolveStatus`.
Columns that went non-finite are scrubbed (freeze-spliced) before their
slot is reused, and failed requests are re-enqueued with capped
exponential backoff up to ``recovery.max_retries`` times (stable rid
across retries).  Fault-injection chaos tests:
tests/test_resilience.py via :mod:`repro.resilience.inject`.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import SolveStatus
from repro.observe import metrics as _metrics
from repro.observe.spans import span as _span
from repro.observe.trace import ConvergenceTrace

from .registry import OperatorRegistry, RegisteredOperator
from .types import (RequestResult, RequestTelemetry, ServiceConfig,
                    SolveRequest)


@jax.jit
def _merge_columns(mask, new, old):
    return jnp.where(mask[None, :], new, old)


@dataclasses.dataclass
class _Block:
    """One operator's resident (n, max_batch) block + host slot table."""

    state: dict
    slots: List[Optional[SolveRequest]]
    #: the raw right-hand sides of the resident columns, on the device —
    #: what a converged column's true residual is verified against
    B: Optional[jax.Array] = None
    #: slots whose device column is still iterating but whose request was
    #: retired host-side (deadline) — they must be freeze-spliced
    orphans: set = dataclasses.field(default_factory=set)

    def live(self) -> bool:
        return any(s is not None for s in self.slots)


class SolveEngine:
    """Multiplex heterogeneous solve requests onto resident blocks.

    One resident block per registered operator; :meth:`poll` services one
    operator for one chunk (round-robin over operators with work) and
    returns the requests that completed; :meth:`run` drains everything.

    ``clock`` is injectable (tests and benchmarks drive deadlines with a
    virtual clock); it must be monotonic seconds.
    """

    def __init__(self, scfg: ServiceConfig = ServiceConfig(),
                 clock=time.monotonic):
        self.scfg = scfg
        self.registry = OperatorRegistry(scfg)
        self._clock = clock
        self._queues: Dict[str, Deque[SolveRequest]] = {}
        self._blocks: Dict[str, Optional[_Block]] = {}
        self._next_rid = 0
        self._rr = 0                     # round-robin cursor
        self._expired: List[RequestResult] = []
        #: ProfileReport of the most recent profiled run()
        #: (``ServiceConfig.profile_dir``); None otherwise
        self.last_profile = None

    # -- registration / submission ---------------------------------------
    def register(self, op, precond=None, name: Optional[str] = None) -> str:
        """Register an operator (idempotent by content; see registry)."""
        name = self.registry.register(op, precond, name)
        canon = self.registry[name].name
        self._queues.setdefault(canon, deque())
        self._blocks.setdefault(canon, None)
        return name

    def register_scenario(self, scenario,
                          name: Optional[str] = None) -> str:
        """Register a scenario (name or :class:`repro.scenarios
        .Scenario`): its plugin-built operator + precond become a
        resident block under the scenario's name."""
        name = self.registry.register_scenario(scenario, name)
        canon = self.registry[name].name
        self._queues.setdefault(canon, deque())
        self._blocks.setdefault(canon, None)
        return name

    def submit(self, operator: str, b, *, tol: Optional[float] = None,
               maxiter: Optional[int] = None,
               deadline: Optional[float] = None) -> int:
        """Enqueue one right-hand side; returns the request id."""
        entry = self.registry[operator]
        # host-side staging: the rhs is only ever consumed when the host
        # assembles an admission block, so keeping it as np avoids a
        # device put here AND a device pull per request at refill time
        b = np.asarray(b, dtype=np.dtype(entry.dtype))
        if b.shape != (entry.n,):
            raise ValueError(
                f"operator {operator!r} expects rhs of shape "
                f"({entry.n},); got {b.shape}")
        req = SolveRequest(operator=entry.name, b=b, tol=tol,
                           maxiter=maxiter, deadline=deadline,
                           rid=self._next_rid, t_submit=self._clock())
        self._next_rid += 1
        self._queues[entry.name].append(req)
        _metrics.ENGINE_QUEUE_DEPTH.set(len(self._queues[entry.name]),
                                        operator=entry.name)
        return req.rid

    # -- serving ---------------------------------------------------------
    def has_work(self) -> bool:
        return any(q for q in self._queues.values()) or \
            any(b is not None and b.live() for b in self._blocks.values())

    def run(self) -> List[RequestResult]:
        """Drain all queues and blocks; completed requests in retirement
        order.

        With ``ServiceConfig.profile_dir`` set, the whole drain runs
        inside a :mod:`repro.observe.profile` capture window: the step/
        splice programs the chunks execute are noted for HLO phase
        mapping, and the analyzed report lands on ``self.last_profile``
        + ``profile_dir/profile.json``.  Results are identical.
        """
        if self.scfg.profile_dir:
            return self._run_profiled()
        return self._drain()

    def _drain(self) -> List[RequestResult]:
        out: List[RequestResult] = []
        while self.has_work():
            out.extend(self.poll())
        out.extend(self._take_expired())
        return out

    def _run_profiled(self) -> List[RequestResult]:
        import os

        import jax

        from repro.observe import profile as _profile

        with _profile.capture(self.scfg.profile_dir) as cap:
            out = self._drain()
            # the ONE host read per chunk already synchronized; this
            # only fences stragglers before the window closes
            for blk in self._blocks.values():
                if blk is not None:
                    jax.block_until_ready(blk.state)
        rep = cap.analyze(label=f"engine/{self.scfg.substrate}")
        rep.save(os.path.join(self.scfg.profile_dir, "profile.json"))
        cap.save_hlo_map()
        self.last_profile = rep
        return out

    def poll(self) -> List[RequestResult]:
        """Service ONE operator for one chunk; returns newly completed
        requests (possibly none).  No-op when nothing has work."""
        entries = self.registry.entries()
        for off in range(len(entries)):
            entry = entries[(self._rr + off) % len(entries)]
            if self._entry_has_work(entry):
                self._rr = (self._rr + off + 1) % len(entries)
                done = self._service_chunk(entry)
                return self._take_expired() + done
        return self._take_expired()

    # -- internals -------------------------------------------------------
    def _entry_has_work(self, entry: RegisteredOperator) -> bool:
        blk = self._blocks[entry.name]
        return bool(self._queues[entry.name]) or \
            (blk is not None and blk.live())

    def _take_expired(self) -> List[RequestResult]:
        out, self._expired = self._expired, []
        return out

    def _next_request(self, q: Deque[SolveRequest]
                      ) -> Optional[SolveRequest]:
        """Pop the next serviceable request; requests whose deadline
        elapsed while queued are retired immediately (never occupy a
        slot), and retried requests still inside their backoff window
        (``not_before``) rotate to the back of the queue."""
        for _ in range(len(q)):
            req = q.popleft()
            if req.deadline is not None and \
                    self._clock() - req.t_submit > req.deadline:
                now = self._clock()
                self._expired.append(RequestResult(
                    rid=req.rid, operator=req.operator,
                    x=np.zeros((req.b.shape[0],), req.b.dtype),
                    iterations=0, relres=float("inf"),
                    converged=False, breakdown=False,
                    telemetry=RequestTelemetry(
                        queue_wait_s=now - req.t_submit, service_s=0.0,
                        wall_s=now - req.t_submit, chunks_resident=0,
                        deadline_exceeded=True),
                    status=SolveStatus.DEADLINE, retries=req.retries))
                self._observe_result(self._expired[-1])
                continue
            if req.not_before and self._clock() < req.not_before:
                q.append(req)            # backing off: not eligible yet
                continue
            return req
        return None

    def _fill_vectors(self, entry, slot_iter, B, tolv, mitv, mask=None):
        """Assign queued requests (then freeze-dummies) to the given free
        slots, writing the rhs block and per-column tol/maxiter in place.
        ``mask=None`` marks the initial fill (every slot is written);
        otherwise only masked columns are spliced."""
        q = self._queues[entry.name]
        blk = self._blocks[entry.name]
        for j in slot_iter:
            req = self._next_request(q)
            if req is not None:
                req.t_start = self._clock()
                B[:, j] = req.b
                tolv[j] = self.scfg.tol if req.tol is None else req.tol
                mitv[j] = self.scfg.maxiter if req.maxiter is None \
                    else req.maxiter
                blk.slots[j] = req
                blk.orphans.discard(j)
                if mask is not None:
                    mask[j] = True
            elif mask is not None and j in blk.orphans:
                # no request for this slot: freeze-splice the orphan
                # column (deadline-retired but still burning iterations)
                B[:, j] = 1.0            # safe nonzero rhs, budget 0
                mitv[j] = 0
                mask[j] = True
                blk.orphans.discard(j)
            elif mask is None:
                B[:, j] = 1.0            # initial fill: inert pad column
                mitv[j] = 0

    @staticmethod
    def _observe_result(res: RequestResult) -> None:
        """One retirement into the metrics registry — the single source
        of truth ``bench_service`` and external scrapes read; every
        value here is host-known (the engine already pulled the flags),
        so recording adds no device read."""
        _metrics.ENGINE_REQUESTS.inc(status=res.status.name)
        t = res.telemetry
        _metrics.REQUEST_QUEUE_WAIT.observe(t.queue_wait_s)
        _metrics.REQUEST_WALL.observe(t.wall_s)
        _metrics.REQUEST_CHUNKS.observe(t.chunks_resident)
        _metrics.SOLVE_ITERATIONS.observe(res.iterations)

    def _service_chunk(self, entry: RegisteredOperator
                       ) -> List[RequestResult]:
        with _span("engine.chunk", operator=entry.name):
            t0 = self._clock()
            out = self._service_chunk_inner(entry)
            _metrics.ENGINE_CHUNK_SECONDS.observe(self._clock() - t0)
        blk = self._blocks[entry.name]
        _metrics.ENGINE_QUEUE_DEPTH.set(
            len(self._queues[entry.name]), operator=entry.name)
        _metrics.ENGINE_SLOT_OCCUPANCY.set(
            0 if blk is None else sum(s is not None for s in blk.slots),
            operator=entry.name)
        return out

    def _service_chunk_inner(self, entry: RegisteredOperator
                             ) -> List[RequestResult]:
        name = entry.name
        q = self._queues[name]
        blk = self._blocks[name]
        m = self.scfg.max_batch
        np_dtype = np.dtype(entry.dtype)

        # 1) admit + step, as ONE compiled program per chunk: either the
        # plain chunk step, or the fused splice-then-step when freed
        # slots are being refilled mid-flight (admission costs no extra
        # dispatch or host round-trip)
        if blk is None:
            if not q:
                return []
            B = np.zeros((entry.n, m), np_dtype)
            tolv = np.full((m,), self.scfg.tol, np.float64)
            mitv = np.zeros((m,), np.int32)
            blk = _Block(state=None, slots=[None] * m)
            self._blocks[name] = blk
            self._fill_vectors(entry, range(m), B, tolv, mitv)
            blk.B = jnp.asarray(B)
            with _span("engine.init_fill", operator=name):
                blk.state = entry.step_fn(
                    entry.init_fn(blk.B, jnp.asarray(tolv),
                                  jnp.asarray(mitv)))
        else:
            free = [j for j in range(m) if blk.slots[j] is None]
            mask = np.zeros((m,), bool)
            if free and (q or blk.orphans):
                B = np.zeros((entry.n, m), np_dtype)
                tolv = np.zeros((m,), np.float64)
                mitv = np.zeros((m,), np.int32)
                self._fill_vectors(entry, free, B, tolv, mitv, mask=mask)
            if mask.any():
                with _span("engine.splice_step", operator=name,
                           refills=int(mask.sum())):
                    mask_d, B_d = jnp.asarray(mask), jnp.asarray(B)
                    blk.state = entry.splice_step_fn(
                        blk.state, mask_d, B_d,
                        jnp.asarray(tolv), jnp.asarray(mitv))
                    blk.B = _merge_columns(mask_d, B_d, blk.B)
            else:
                with _span("engine.step", operator=name):
                    blk.state = entry.step_fn(blk.state)
        for req in blk.slots:
            if req is not None:
                req.chunks_resident += 1

        # 3) retire finished / deadline-blown columns (ONE host transfer
        # for the (m,) flag vectors — plus the typed status vector when
        # the block is guarded and the trace ring when tracing is on:
        # the harvest rides the host read the engine already does)
        st = blk.state
        guarded = "status" in st
        traced = "trace" in st
        flags = [st["converged"], st["breakdown"], st["iterations"],
                 st["relres"], st["col_maxiter"]]
        if guarded:
            flags.append(st["status"])
        if traced:
            flags += [st["trace"], st["i"]]
        with _span("engine.retire", operator=name):
            got = jax.device_get(tuple(flags))
        conv, brk, iters, relres, budget = got[:5]
        k = 5
        status_arr = None
        if guarded:
            status_arr = got[k]
            k += 1
        trace_buf, trace_steps = None, 0
        if traced:
            trace_buf, trace_steps = got[k], int(got[k + 1])

        # a converged column retires only once its true residual meets
        # its tol (one block matvec and one more (m,) host read, in chunks
        # where some request converged); a column the drifting recurrence
        # fooled is residual-replaced on the device and stays resident
        verify = np.array([req is not None and bool(conv[j])
                           for j, req in enumerate(blk.slots)])
        true_rr = None
        if verify.any():
            with _span("engine.verify", operator=name,
                       columns=int(verify.sum())):
                blk.state, true_d = entry.verify_fn(blk.state, blk.B,
                                                    verify)
                st = blk.state
                got = jax.device_get((st["converged"], true_d)
                                     + ((st["status"],) if guarded else ()))
            conv, true_rr = got[0], got[1]
            if guarded:
                status_arr = got[2]
            redo = int((verify & ~conv).sum())
            if redo:
                _metrics.ENGINE_REPLACEMENTS.inc(redo)
        recovery = self.scfg.recovery
        results: List[RequestResult] = []
        x_host = None
        now = self._clock()
        for j, req in enumerate(blk.slots):
            if req is None:
                continue
            finished = bool(conv[j] or brk[j] or iters[j] >= budget[j])
            late = (req.deadline is not None
                    and now - req.t_submit > req.deadline)
            if not (finished or late):
                continue
            # typed retirement status: the guarded block carries the
            # in-reduction per-column code; unguarded blocks get the
            # coarse classification — DEADLINE trumps either
            if guarded and finished \
                    and int(status_arr[j]) != SolveStatus.RUNNING.value:
                sts = SolveStatus(int(status_arr[j]))
            elif conv[j]:
                sts = SolveStatus.CONVERGED
            elif brk[j]:
                sts = SolveStatus.BREAKDOWN
            else:
                sts = SolveStatus.MAXITER
            if late and not finished:
                sts = SolveStatus.DEADLINE
            poisoned = sts == SolveStatus.NONFINITE \
                or not np.isfinite(relres[j])
            blk.slots[j] = None
            if late and not finished:
                blk.orphans.add(j)       # still iterating: freeze later
            if poisoned:
                blk.orphans.add(j)       # scrub before the slot is reused
            # failed requests re-enqueue with capped exponential backoff
            # (stable rid); no result is emitted for this attempt
            if recovery is not None and sts.is_failure \
                    and sts != SolveStatus.DEADLINE \
                    and req.retries < recovery.max_retries and not late:
                req.retries += 1
                back = 0.0
                if recovery.retry_backoff_s:
                    back = min(
                        recovery.retry_backoff_s * 2 ** (req.retries - 1),
                        recovery.retry_backoff_cap_s)
                req.not_before = now + back
                q.append(req)
                _metrics.ENGINE_RETRIES.inc()
                continue
            if x_host is None:
                x_host = np.asarray(st["x"])
            xj = x_host[:, j].copy()
            if not np.isfinite(xj).all():
                # finite-output guarantee: a poisoned column never hands
                # NaN back to the caller (the typed status says why)
                xj = np.where(np.isfinite(xj), xj, 0.0)
            # a converged request reports the true residual it was
            # verified with
            rr_j = float(true_rr[j] if conv[j] else relres[j])
            trace = None
            if traced:
                # per-column slice of the block's shared ring; spliced
                # columns had their pre-admission rows NaN'd, which
                # ConvergenceTrace.per_iteration() drops
                trace = ConvergenceTrace(
                    np.ascontiguousarray(trace_buf[:, :, j]), trace_steps)
            res = RequestResult(
                rid=req.rid, operator=name, x=xj,
                iterations=int(iters[j]),
                relres=rr_j if np.isfinite(rr_j) else float("inf"),
                converged=bool(conv[j]), breakdown=bool(brk[j]),
                telemetry=RequestTelemetry(
                    queue_wait_s=req.t_start - req.t_submit,
                    service_s=now - req.t_start,
                    wall_s=now - req.t_submit,
                    chunks_resident=req.chunks_resident,
                    deadline_exceeded=bool(late and not finished)),
                status=sts, retries=req.retries, trace=trace)
            self._observe_result(res)
            results.append(res)

        # 4) drop a drained block (frozen orphans die with it)
        if not blk.live() and not q:
            self._blocks[name] = None

        return results
