"""Test harness config: force CPU platform with 8 virtual devices.

The analog of the reference's subprocess+env distributed-test trick
(test_dist_base.py): XLA's host-platform device-count flag gives us an
8-device mesh on CPU so every sharding/collective path is exercised without
TPU hardware (SURVEY.md §4).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reseed():
    import paddle_tpu

    paddle_tpu.seed(2024)
    yield


# hang watchdog: if any single test runs >8 min, dump every thread's stack and
# abort the process instead of stalling the whole run (converts intermittent
# environment hangs into diagnosable failures).
import faulthandler  # noqa: E402

# under xdist the workers contend for cores, so compile-heavy tests run
# several times slower — scale the hang threshold accordingly
_WATCHDOG_SECS = 900 if os.environ.get("PYTEST_XDIST_WORKER") else 480


@pytest.fixture(autouse=True)
def _hang_watchdog():
    faulthandler.dump_traceback_later(_WATCHDOG_SECS, exit=True)
    yield
    faulthandler.cancel_dump_traceback_later()
