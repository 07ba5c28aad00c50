"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches must
see the real (1-device) CPU; only launch/dryrun.py forces 512 devices."""
import jax
import pytest


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)
