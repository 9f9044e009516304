"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh so multi-chip sharding tests
(tp/pp/dp/sp/ep over jax.sharding.Mesh) run without TPU hardware — the
same setup the driver uses for dryrun_multichip validation.

No machine the tests run on has 8 accelerators (the sandbox has none;
JAX_PLATFORMS=cpu alone gives one CPU device), so the device count is
forced at config level by __graft_entry__._force_cpu_devices before
any test imports jax.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")  # for subprocess children

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from __graft_entry__ import _force_cpu_devices  # noqa: E402

_force_cpu_devices(8)

# The suite rebuilds the same tiny engines hundreds of times, and every
# rebuild re-jits programs XLA has already compiled in this very run
# (a new engine is a new set of jit closures). With the persistent
# compile cache on — the same directory the server children of the
# tests use — a repeat is a load, not a compile: about a fifth off the
# engine-heavy files, starting from an empty cache (ROADMAP C0).
from ome_tpu import device  # noqa: E402

device.enable_compile_cache()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running (chaos soak / multi-node) tests, excluded "
        "from the tier-1 `-m 'not slow'` run")
