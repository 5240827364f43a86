import os

# Keep tests on the single real CPU device (the 512-device override belongs
# ONLY to launch/dryrun.py).  Cap intra-op threads for stable CI timing.
os.environ.setdefault("XLA_FLAGS", "--xla_cpu_multi_thread_eigen=false")
# Tests run on the CPU: on a machine with a TPU, test workers must never
# contend for the chip (the chip path is chip_smoke.py)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
