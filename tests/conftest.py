"""Shared pytest config.

x64 is enabled for the PageRank-solver numerics (the paper pushes xi to
1e-15; float32 saturates near 1e-7 — the paper's own §VI.B(4) observation
about double-precision limits, one tier up).

NOTE: XLA_FLAGS / device-count forcing is deliberately NOT set here — smoke
tests must see the real single CPU device; tests that need a small fake
mesh spawn a subprocess.
"""
import jax

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
