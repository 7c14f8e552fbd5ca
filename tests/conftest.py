"""Test harness: a virtual 8-device CPU mesh, set BEFORE jax is imported."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs with -m 'not slow' (ROADMAP.md); register the marker so
    # slow-tagged tests deselect cleanly instead of warning
    config.addinivalue_line("markers",
                            "slow: multi-second tests excluded from tier-1")


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)
    import mxnet_tpu as mx

    mx.random.seed(0)
    yield
