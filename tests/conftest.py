import os
import sys

# NOTE: no XLA_FLAGS here on purpose — smoke tests must see 1 device.
# Multi-device tests spawn subprocesses with their own flags.
# The suite runs on the CPU backend (Pallas kernels in interpret mode) even
# on a machine with a chip: the chip belongs to one process, and is not
# this one.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# repo root, so tests can import the tools.analyze gate package
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng_key():
    import jax

    return jax.random.PRNGKey(0)
