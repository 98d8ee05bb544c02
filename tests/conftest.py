import jax
import numpy as np
import pytest

# Tests run on the real device set (1 CPU device) — the 512-device forcing
# happens ONLY inside launch/dryrun.py (its own process).

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def rng():
    return np.random.RandomState(0)


@pytest.fixture()
def key():
    return jax.random.PRNGKey(0)
