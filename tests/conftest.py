"""Test config: force an 8-device virtual CPU platform before jax initializes.

Mirrors how the reference tests distributed behavior fully in-process
(ref: pkg/testkit/mockstore.go CreateMockStore + unistore region splitting):
we get an 8-device mesh on CPU so shard_map/psum/all_to_all paths run
without TPU hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never take the chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")
# persistent compile cache: repeated test runs skip XLA compiles. Where the
# environment names a directory that one is used, else the checkout's own
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".xla_cache"),
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running acceptance tests, excluded from tier-1 (-m 'not slow')"
    )


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True)
def host_states_nest_by_the_rule():
    """A host state opened inside a host state that is neither a bottom
    nor its `exec.*` child breaks the state clock's nesting rule
    (util/tracing.py): whichever test drove the path fails."""
    from tidb_tpu.util import tracing

    yield
    breaches = dict(tracing.nesting_breaches)
    tracing.nesting_breaches.clear()
    assert not breaches, f"host states nested against the rule: {breaches}"
