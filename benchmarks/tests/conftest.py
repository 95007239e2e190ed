"""The benchmark's own tests run on a 4-device virtual CPU mesh (as
tests/conftest.py does for the repo's), with no persistent compile cache.
Run them with ``python -m pytest benchmarks/tests -q``."""

import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: minutes, not seconds (the off-chip v5e compiles)"
    )
