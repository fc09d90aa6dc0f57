# NOTE: no XLA_FLAGS here on purpose — tests run on the 1 real CPU device.
# Only launch/dryrun.py requests 512 placeholder devices, in its own
# process.
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
