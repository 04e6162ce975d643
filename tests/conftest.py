"""Test harness: an 8-device virtual CPU platform, set up before jax initializes.

Mirrors the reference's fake-device strategy (SURVEY.md §4: custom_cpu plugin — a CPU
masquerading as an accelerator) so multi-chip sharding semantics are testable without a
TPU pod. ``JAX_PLATFORMS`` is only defaulted, never overwritten: ``tools/run_tpu_tests.sh``
exports ``JAX_PLATFORMS=tpu`` to run the ``@pytest.mark.tpu`` tests on the chip.
"""
import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def on_tpu() -> bool:
    """THE gate for ``@pytest.mark.tpu`` (Mosaic-compiled kernels, hardware PRNG)."""
    return jax.devices()[0].platform == "tpu"


def pytest_collection_modifyitems(config, items):
    marked = [it for it in items if it.get_closest_marker("tpu")]
    if marked and not on_tpu():
        skip = pytest.mark.skip(reason="needs a real TPU (Mosaic / hardware "
                                       "PRNG): tools/run_tpu_tests.sh")
        for it in marked:
            it.add_marker(skip)
