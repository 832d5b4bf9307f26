"""Test configuration: force an 8-device virtual CPU platform so sharding
and collectives are exercised without TPU hardware (the analogue of the
reference's same-host multi-GPU test runs, src/ops/tests/test_harness.py
``-ll:gpu {1,2,4,8}``)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# The env var is read when jax is first imported; pin the platform via
# jax.config as well in case something imported jax before this file.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _flight_dir_tmp(tmp_path, monkeypatch):
    """Resilience tests die on purpose under active telemetry; route
    their flight-recorder dumps (telemetry/fleet.py, default
    ``artifacts/``) into the test's tmp dir so runs never dirty the
    tree.  Tests that pin a specific dir just setenv over this."""
    monkeypatch.setenv("FF_FLIGHT_DIR", str(tmp_path / "flight"))


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture()
def rng():
    import numpy as np

    return np.random.default_rng(0)
