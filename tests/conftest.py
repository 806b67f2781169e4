"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax loads.

Mirrors the reference's test strategy (SURVEY §4): deterministic seeds, CPU
as the reference backend, multi-device tests without real hardware (the
reference tests model parallelism on cpu contexts the same way).
"""

import os

# XLA_FLAGS and JAX_PLATFORMS are read at jax's first backend init, so both
# are set before the import.
_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as _np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: exceeds the tier-1 wall-clock budget or is a "
        "known-flaky long drill (deselected by -m 'not slow'; see the "
        "verify command in ROADMAP.md)")


@pytest.fixture(autouse=True)
def _seed_everything():
    """Deterministic per-test seeding (reference: with_seed decorator;
    MXNET_TEST_SEED overrides, logged seed for repro)."""
    seed = int(os.environ.get("MXNET_TEST_SEED", "0"))
    _np.random.seed(seed)
    import incubator_mxnet_tpu as mx
    mx.random.seed(seed)
    yield
