"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests must see 1 CPU device
(only launch/dryrun + launch/roofline request 512 placeholder devices)."""

import jax
import pytest

jax.config.update("jax_default_matmul_precision", "float32")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-device subprocess tests (minutes, not seconds)"
    )


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
