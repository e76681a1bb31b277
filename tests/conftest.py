"""Test harness configuration.

Mirrors the reference's multi-process-without-a-cluster strategy
(tests/test_algos/test_algos.py LT_DEVICES fixture + gloo backend): here the
JAX analog is a virtual 8-device CPU platform, so every sharding/collective
path is exercised without TPU hardware. These env vars MUST be set before the
first `import jax` anywhere in the test process.
"""

import os

# JAX reads both variables when it is imported (JAX_PLATFORMS) and when the
# CPU client is first built (XLA_FLAGS), so nothing else is needed — as long
# as this module runs before anything imports jax.
os.environ["JAX_PLATFORMS"] = "cpu"
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (prev + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _no_env_var_leaks():
    """Guard env-var leaks between tests (parity with reference tests/conftest.py:20-60)."""
    guarded = ("SHEEPRL_SEARCH_PATH",)
    before = {k: os.environ.get(k) for k in guarded}
    yield
    for k, v in before.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
