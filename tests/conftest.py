"""Test configuration: run everything on a virtual 8-device CPU mesh so
multi-chip sharding logic is exercised without TPU hardware, and so the suite
is fast/deterministic.  The chip is reached through ``chip_smoke.py``; what
the chip's compiler accepts is checked here by ``test_chip_compile.py``
against a described (not attached) v5e.  UNICORE_TPU_TEST_ON_TPU=1 leaves
the platform alone, for kernel parity on hardware (not run on this chip yet).

The platform is pinned through the jax config as well as the environment,
before any backend is initialized: conftest import time is early enough
(pytest imports conftest before test modules).
"""

import os

# the suite neither reads nor writes the persistent compilation cache:
# the entry points would otherwise place one in the checkout, shared by
# every xdist worker and every CLI subprocess a test starts
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

if os.environ.get("UNICORE_TPU_TEST_ON_TPU", "") != "1":
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.RandomState(0)


@pytest.fixture(autouse=True)
def _no_mesh_left_by_an_earlier_trainer():
    """A Trainer tells the kernel dispatch which mesh its step runs on;
    a test must not inherit the mesh of a trainer an earlier test
    built."""
    from unicore_tpu.ops import backend

    backend.set_spmd_mesh(None)


@pytest.fixture(autouse=True, scope="module")
def _no_dispatch_record_left_by_an_earlier_file():
    """``ops.backend`` records process-wide which path every kernel shape
    took, and a benchmark cell's run checks the WHOLE record against the
    path its file names: a test file must not inherit the record of a
    file that forced the other path (an xdist worker runs several files,
    in an order that shifts whenever a file is added)."""
    from unicore_tpu.ops import backend

    backend._DISPATCH.clear()


def pytest_configure(config):
    # "slow": excluded from the tier-1 gate (pytest -m 'not slow') but
    # run by the CI workflow's full `pytest tests/` step — for tests
    # whose value is end-to-end coverage, not per-commit latency (e.g.
    # the Pass-3 CLI round-trip, which AOT-compiles the train step in
    # three subprocesses)
    config.addinivalue_line(
        "markers", "slow: heavy end-to-end test, excluded from tier-1"
    )
