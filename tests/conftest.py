"""Shared pytest fixtures.

NOTE: XLA_FLAGS / device-count overrides are deliberately NOT set here —
smoke tests and benches must see the real single CPU device.  Multi-device
tests spawn subprocesses with their own XLA_FLAGS (tests/test_distributed.py).
"""
import os
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")
if SRC not in sys.path:
    sys.path.insert(0, os.path.abspath(SRC))


def enable_x64(flag: bool = True):
    """x64 context manager (``jax.enable_x64``); test modules import it
    from here."""
    import jax
    return jax.enable_x64(flag)


@pytest.fixture
def x64():
    """Run a test in double precision (solver fidelity, paper protocol)."""
    with enable_x64(True):
        yield
