"""Test harness: force an 8-device virtual CPU "slice".

Plays the role the mocked k8s API plays in the reference test suite
(``/root/reference/tests/base/case.py``): multi-chip topology without real
hardware.  Must set env vars before jax is first imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
os.environ.setdefault("JAX_THREEFRY_PARTITIONABLE", "1")

import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Every ``e2e`` test (real subprocess gangs) is implicitly ``slow`` —
    the dev loop (`make test-fast`, -m 'not slow') skips them; the round
    gate (`make gate`) runs everything."""
    slow = pytest.mark.slow
    for item in items:
        if "e2e" in item.keywords:
            item.add_marker(slow)


@pytest.fixture()
def tmp_registry(tmp_path):
    """A fresh sqlite run registry in a temp dir."""
    from polyaxon_tpu.db.registry import RunRegistry

    reg = RunRegistry(tmp_path / "registry.db")
    yield reg
    reg.close()
