"""Test configuration.

Tests run JAX on a virtual 8-device CPU mesh; the chip runs through
chip_smoke.py and benchmark/run.py, which leave the platform alone.
jax.config.update after import is authoritative over JAX_PLATFORMS, so
the pin holds whatever the environment names.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# Test workloads are tiny; without this the adaptive small-workload
# routing would send every driver-level test down the scalar path and
# silently stop exercising the device engine.
from gatekeeper_tpu.engine import jax_driver  # noqa: E402

jax_driver.SMALL_WORKLOAD_EVALS = 0


@pytest.fixture(autouse=True)
def _empty_span_ring(monkeypatch):
    """Each test starts with a fresh process tracer.  The tracer is
    global and its ring keeps the last 4,096 spans, so a reader that
    refuses a full ring (the benchmark's window spans) would otherwise
    pass or fail by which tests ran before it in the same process.
    (Imported here, not at the top: importing the tracer configures the
    package's logging, which must wait for pytest's capture.)"""
    monkeypatch.setattr("gatekeeper_tpu.obs.trace._tracer", None)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running end-to-end captures (bench runs)")
