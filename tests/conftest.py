import os
import sys

# the suite runs on the CPU (Pallas kernels in interpret mode) even on a
# machine with a TPU: a test process that took the chip would hold it
# against the program itself. Set before anything imports JAX.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the entry points (serve/train ``main``) place a persistent compile cache;
# tests that call them must not leave one behind
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
# tests must see ONE cpu device (dry-run sets its own 512-device flag in a
# subprocess); make sure nothing leaks in from the environment.
os.environ.pop("XLA_FLAGS", None)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)
