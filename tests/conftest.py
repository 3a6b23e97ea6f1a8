"""Test configuration.

Runs JAX on a virtual 8-device CPU mesh so multi-chip sharding paths are
exercised without TPU hardware. On the chip, `python chip_smoke.py` is
the end-to-end check and `JEPSEN_TPU_PLATFORM=tpu pytest -m tpu` the
differential tier.
"""

import atexit
import os
import shutil
import tempfile

# Opt-in real-hardware tier: JEPSEN_TPU_PLATFORM set to a non-cpu
# platform (`JEPSEN_TPU_PLATFORM=tpu pytest -m tpu` on a TPU host)
# skips the CPU pin so the `tpu`-marked differential suites run on the
# chip.
ON_HARDWARE = os.environ.get("JEPSEN_TPU_PLATFORM", "") not in ("", "cpu")

if not ON_HARDWARE:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Each test process keeps its serialized executables in a directory of
# its own (tests that exercise the cache point it elsewhere), and JAX's
# persistent compilation cache stays off, as it was before the AOT
# cache followed JAX_COMPILATION_CACHE_DIR.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    _cache = tempfile.mkdtemp(prefix="jepsen-tpu-test-cache-")
    atexit.register(shutil.rmtree, _cache, True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)

import random

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tpu: CPU-vs-device differential tests meant for real hardware "
        "(run with JEPSEN_TPU_PLATFORM=tpu pytest -m tpu)")


def pytest_collection_modifyitems(config, items):
    """`tpu`-marked tests only run when hardware is opted in; everything
    else is excluded under the hardware tier (one chip, no virtual
    mesh — the CPU-pinned assumptions of the main suite don't hold)."""
    if ON_HARDWARE:
        skip = pytest.mark.skip(reason="hardware tier runs -m tpu only")
        for it in items:
            if "tpu" not in it.keywords:
                it.add_marker(skip)
    else:
        skip = pytest.mark.skip(
            reason="needs real hardware: JEPSEN_TPU_PLATFORM=tpu")
        for it in items:
            if "tpu" in it.keywords:
                it.add_marker(skip)


@pytest.fixture(autouse=True)
def _seed():
    random.seed(42)


@pytest.fixture(autouse=True)
def _fresh_tracer():
    """Drop the process-global tracer around every test: tracing stays
    on (the default-on paths are exercised for real), but one test's
    span events never accumulate into the next — a session-long event
    buffer would grow the gen2 GC scan under the deadline-sensitive
    suite e2e tests."""
    from jepsen_tpu import trace
    trace.reset()
    yield
    trace.reset()
