import os

# Tests see ONE device (never set the 512-device dry-run flag globally);
# dry-run smoke tests spawn subprocesses with their own XLA_FLAGS.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
