"""Test harness config (SURVEY.md §4 prescription).

Tests run on the CPU backend with 8 virtual devices so N-way sharding is
exercised without several chips; the chip itself is covered by
``chip_smoke.py``, which runs the same phases at full size on a TPU.

``jax.config.update('jax_platforms', 'cpu')`` pins the platform whatever the
ambient ``JAX_PLATFORMS`` says (a run on the chip machine must not grab the
chip for unit tests). XLA_FLAGS must be set before the first backend
initialization to get the 8 virtual CPU devices.
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_report_header(config):
    return f"jax backend: {jax.devices()[0].platform}, devices: {len(jax.devices())}"
