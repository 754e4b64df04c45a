"""Test config: run on a virtual 8-device CPU mesh (SURVEY.md §4).

Must set env BEFORE jax initialises its backends.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    import mxnet_tpu as mx
    mx.random.seed(42)
    np.random.seed(42)
    yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-process drills excluded from the tier-1 window "
        "(run with -m slow)")
