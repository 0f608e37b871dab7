import os
import sys

# Tests run single-device (multi-device tests spawn subprocesses that
# set XLA_FLAGS themselves).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _reset_kernel_fallback_state():
    """The kernel-fallback plane keeps process-global one-shot state
    (warn dedup, dispatch memo, registered sinks); clear it between
    tests so one test's captures never leak into the next."""
    from repro.kernels import ops
    ops.reset_fallback_state()
    yield
    ops.reset_fallback_state()


def make_clustered(n, d=16, k=20, seed=1, scale=5.0):
    r = np.random.default_rng(seed)
    cents = r.normal(size=(k, d)) * scale
    a = r.integers(0, k, n)
    return (cents[a] + r.normal(size=(n, d))).astype(np.float32)
