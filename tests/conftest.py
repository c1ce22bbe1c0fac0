"""Test configuration.

Pins JAX to the CPU with 8 virtual devices so multi-chip sharding paths can
be exercised without TPU hardware (tests never run on the chip;
chip_smoke.py does), enables float64 so device parity tests match the host
oracle's arithmetic bit-for-bit (the chip path runs float32; see
ops/solver.py and tests/test_tpu_compile.py), and enables panic-on-assert so
resource accounting violations fail tests loudly.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["VOLCANO_TPU_PANIC"] = "1"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_degrade_ladder():
    """The process-default degradation ladder (scheduler/degrade.py) is
    deliberately global — a test that trips its breakers must not leak a
    degraded rung into later tests' solve paths."""
    from volcano_tpu.scheduler import degrade

    degrade.reset()
    yield
    degrade.reset()
