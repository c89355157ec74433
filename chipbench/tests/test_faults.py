"""A run with its timed path broken underneath reads ``correct`` false,
once for each fault a cell can have, and a sound run reads it true.

The faults are planted in the program (the session and engine programs
are built after the plant, so the window drives the broken path):

- a solver loop that returns its state unchanged;
- half of the engine's batch left out (its columns never step);
- the exchange between chips left out (the halo permutes deliver zeros);
- the answer altered where it is produced.
"""
import dataclasses

import jax.numpy as jnp
import pytest

from repro import api
from repro.core import SOLVERS
from repro.core import distributed
from repro.service import engine as engine_mod

from chipbench.tests.small import SERVE, SOLVE, cell_for, run

METHOD = "p-bicgsafe-rr"


@pytest.fixture(autouse=True)
def fresh_sessions():
    """Sessions are cached by content; every run builds its programs
    anew, so that a planted fault reaches them."""
    api.clear_session_cache()
    yield
    api.clear_session_cache()


def unchanged_state(monkeypatch):
    real = SOLVERS[METHOD]

    def frozen(A, b, x0=None, *, config, **kw):
        return real(A, b, x0, config=dataclasses.replace(config, maxiter=0),
                    **kw)
    monkeypatch.setitem(SOLVERS, METHOD, frozen)


def unchanged_engine_state(monkeypatch):
    monkeypatch.setattr(api, "step_chunk",
                        lambda bmv, state, k, **kw: state)


def half_batch(monkeypatch):
    real = api.step_chunk

    def half(bmv, state, k, **kw):
        new = real(bmv, state, k, **kw)
        m = state["x"].shape[-1]
        keep = jnp.arange(m) < m // 2

        def pick(n, o):
            if getattr(n, "ndim", 0) >= 1 and n.shape[-1] == m:
                return jnp.where(keep, n, o)
            return n
        return {f: pick(new[f], state[f]) if f in state else new[f]
                for f in new}
    monkeypatch.setattr(api, "step_chunk", half)


def no_exchange(monkeypatch):
    monkeypatch.setattr(distributed, "ring_shift",
                        lambda x, *a, **k: jnp.zeros_like(x))


def altered_solve(monkeypatch):
    real = SOLVERS[METHOD]

    def altered(*a, **k):
        res = real(*a, **k)
        return res._replace(x=res.x * 1.01)
    monkeypatch.setitem(SOLVERS, METHOD, altered)


def altered_answer(monkeypatch):
    real = engine_mod.RequestResult

    def altered(**kw):
        return real(**{**kw, "x": kw["x"] * 1.01})
    monkeypatch.setattr(engine_mod, "RequestResult", altered)


FAULTS = {
    SOLVE: [unchanged_state, altered_solve],
    SERVE: [unchanged_engine_state, half_batch, altered_answer],
    "mesh": [unchanged_state, no_exchange, altered_solve],
    "mesh2x2": [unchanged_state, no_exchange],
}


@pytest.mark.parametrize("workload", sorted(FAULTS))
def test_sound_run_is_correct(workload):
    out = run(cell_for(workload))
    assert out.correct, out.checks
    assert out.attempted > 0 and out.failed <= out.attempted


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w, fs in sorted(FAULTS.items()) for f in fs],
    ids=lambda v: getattr(v, "__name__", v))
def test_fault_reads_not_correct(workload, fault, monkeypatch):
    fault(monkeypatch)
    out = run(cell_for(workload))
    assert not out.correct, out.checks
