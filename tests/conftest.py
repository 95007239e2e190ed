"""Test harness: force an 8-device virtual CPU platform.

Mirrors the reference strategy of faking multi-node on one host
(BLUEFOG_NODES_PER_MACHINE, reference common/mpi_context.cc:320-337): here a
single host exposes 8 XLA CPU devices and meshes/submeshes are built over
them. Set BLUEFOG_TEST_DEVICES to change the count, BLUEFOG_TEST_PLATFORM
to run the suite on another platform than ``cpu``.
"""

import os

_NUM = os.environ.get("BLUEFOG_TEST_DEVICES", "8")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count={_NUM}"
).strip()
os.environ["JAX_PLATFORMS"] = os.environ.get("BLUEFOG_TEST_PLATFORM", "cpu")
# hermetic: no test (nor a subprocess it starts) reads or leaves a
# persistent compile cache (bf.init() would otherwise place one)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    return jax.devices("cpu")
