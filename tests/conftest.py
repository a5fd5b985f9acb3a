"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests must see 1 device
(the dry-run sets 512 placeholder devices itself, in a subprocess).

The suite runs on the CPU: JAX_PLATFORMS=cpu, which the orchestrator's
worker processes inherit, is what allows ``--jobs`` > 1."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
