"""Repo-wide pytest configuration for deterministic CI runs.

* Restricts JAX to the CPU backend (``JAX_PLATFORMS=cpu``, set before jax
  initialises) so the suite behaves identically on dev boxes, CI runners
  and TPU hosts, and a test process never takes a host's TPU.
* Seeds every stdlib/numpy RNG and pins a session PRNG key fixture, so runs
  are reproducible bit-for-bit.
* Prepends ``src/`` to ``sys.path`` so ``pytest`` works from a clean checkout
  even without ``pip install -e .`` (the PYTHONPATH=src hack stays optional).
"""
import os
import random
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")

_SRC = os.path.join(os.path.dirname(__file__), "src")
if os.path.isdir(_SRC) and _SRC not in sys.path:
    sys.path.insert(0, _SRC)

import numpy as np
import pytest

SEED = 20180611  # the paper's arXiv year+month, for want of a better constant


def pytest_configure(config):
    random.seed(SEED)
    np.random.seed(SEED)
    import jax

    # The executor engines dispatch nested segment jits from inside
    # io_callbacks; when the whole train step is jitted (launcher tests),
    # XLA's async CPU dispatch runs the outer program on its nproc-sized
    # execution pool, and on single-core runners the nested dispatch
    # starves — a hard deadlock.  Synchronous CPU dispatch makes the
    # nesting safe everywhere the suite runs.
    jax.config.update("jax_cpu_enable_async_dispatch", False)
    try:  # derandomize property tests when the optional dep is present
        from hypothesis import settings

        settings.register_profile("ci", derandomize=True, deadline=None)
        settings.load_profile("ci")
    except ImportError:
        pass


@pytest.fixture
def prng_key():
    """Session-stable JAX PRNG key."""
    import jax

    return jax.random.PRNGKey(SEED)
