"""Test harness: 8 virtual CPU devices so the full mesh / shard_map / vote
path runs without TPU hardware (SURVEY §4: distributed tests without a
cluster). Must set env BEFORE jax is imported anywhere."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: left out of the tier-1 run (-m 'not slow')")
