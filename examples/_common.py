# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Shared example bootstrap: the device list an example runs on.

The reference examples run under ``bfrun -np N`` (one MPI process per
worker); here a single controller drives N mesh devices: every chip of
an accelerator backend, or — with ``JAX_PLATFORMS=cpu`` — an N-device
virtual CPU platform, the same trick the test harness uses
(tests/conftest.py).

Import and call :func:`setup_devices` BEFORE importing jax elsewhere.
"""

import os
import sys

# the examples live next to the package; make it importable without install
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def setup_devices(default: int = 8):
    """The default backend's devices: all the chips there are on an
    accelerator, ``BLUEFOG_EXAMPLE_DEVICES`` (default 8) virtual devices
    on CPU. The platform is JAX's choice (``JAX_PLATFORMS``), never
    changed here."""
    n = int(os.environ.get("BLUEFOG_EXAMPLE_DEVICES", default))
    from bluefog_tpu.platforms import ensure_cpu_device_count

    ensure_cpu_device_count(n)  # read by the CPU backend only
    import jax

    devices = jax.devices()
    if devices[0].platform != "cpu":
        return devices
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} CPU devices, have {len(devices)}; the CPU backend "
            "initialized before setup_devices() could set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} — call "
            "setup_devices() before any jax operation"
        )
    return devices[:n]
