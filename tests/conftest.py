"""Test configuration: run all tests on a virtual 8-device CPU mesh.

Multi-chip hardware is not available to CI; sharding correctness is
validated on 8 virtual CPU devices (the same XLA partitioner runs on
both backends). The flags must be set before jax is imported anywhere.

DCCRG_TEST_TPU=1 leaves the platform to JAX instead (the chip, on a
machine that has one) and runs only the kernel suites with a native
chip path: tests/test_pallas_kernel.py, tests/test_poisson_kernel.py
and tests/test_bulk_executor.py; the rest skip.
"""

import os

_USE_TPU = os.environ.get("DCCRG_TEST_TPU", "") == "1"

if not _USE_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax  # noqa: E402

if not _USE_TPU:
    # the config update holds even if jax was imported before this file
    jax.config.update("jax_platforms", "cpu")
    # the image may pre-set JAX_ENABLE_X64 before the setdefault above
    jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--dccrg-debug", action="store_true", default=False,
        help="set DCCRG_DEBUG=1 for the whole run: invariant verifiers "
             "at every structure rebuild plus transactional post-commit "
             "validation (the reference's -DDEBUG builds). The CI leg "
             "tests/ci_debug_leg.sh runs a tier-1 marker subset with it.",
    )


def pytest_configure(config):
    if config.getoption("--dccrg-debug"):
        os.environ["DCCRG_DEBUG"] = "1"


@pytest.fixture(autouse=True)
def _tpu_mode_scope(request):
    """DCCRG_TEST_TPU=1 exists to run the Pallas kernel tests on the
    real (single) chip; everything else is written for the 8-device
    virtual CPU mesh and skips rather than failing on mesh setup."""
    if _USE_TPU and not any(k in request.node.nodeid for k in (
            "test_pallas_kernel", "test_poisson_kernel",
            "test_bulk_executor")):
        pytest.skip("CPU-mesh test; run without DCCRG_TEST_TPU")
    yield


@pytest.fixture(scope="session")
def devices():
    import jax

    return jax.devices()


@pytest.fixture
def rng():
    return np.random.default_rng(42)
