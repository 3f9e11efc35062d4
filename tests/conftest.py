import os
import sys

# Tests see the real single CPU device (the dry-run sets its own 512-device
# flag in its own process). Sharded-path tests spawn subprocesses with a
# small forced device count — see tests/test_sharded_paths.py.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def trivial_mesh():
    """1x1 mesh on the single CPU device: exercises every shard_map code path
    (psum over singleton axes) without forcing a device count."""
    from repro.launch.mesh import make_mesh

    return make_mesh((1, 1), ("data", "model"))
