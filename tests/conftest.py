"""Force an 8-virtual-device CPU platform for all tests.

Mirrors the reference's CI strategy of exercising the full training paths on
commodity hardware (`tests/python_package_test`); the virtual device mesh lets
the distributed learners (`lightgbm_tpu/parallel`) run real XLA collectives
on one host (the in-process fake the reference never had — SURVEY §4).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# persistent compilation cache: the suite's wall time is dominated by
# re-compiling the same tree programs run-over-run; warm runs skip XLA
# entirely (delete the directory to force a cold run).  Placed through the
# environment, BEFORE jax is imported, and only when the variable is unset:
# JAX reads it itself, the worker processes the tests spawn inherit it, and
# lightgbm_tpu.use_compile_cache() then sets no directory of its own.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".jax_cache")

import jax  # noqa: E402

# the tests run on the CPU whatever the machine holds (JAX_PLATFORMS may be
# unset or name a chip); config.update re-selects the platform even when a
# plugin initialized jax before this file ran
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)  # for gpu_use_dp parity tests
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
jax.config.update("jax_persistent_cache_enable_xla_caches", "all")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.RandomState(42)


# per-test timeout for serving-, chaos- and analysis-marked tests (threads
# + sockets + injected faults + subprocess gates): a hung accept loop, a
# lost batcher event or an injected network hang must fail ONE test, not
# stall the tier-1 suite.
# SIGALRM fires in the main thread, which is exactly where the test body
# blocks; no external pytest-timeout dependency needed.
import signal  # noqa: E402

_SERVING_TIMEOUT_S = 120


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    marker = item.get_closest_marker("serving") \
        or item.get_closest_marker("chaos") \
        or item.get_closest_marker("analysis") \
        or item.get_closest_marker("lifecycle") \
        or item.get_closest_marker("elastic") \
        or item.get_closest_marker("soak")
    if marker is None or not hasattr(signal, "SIGALRM"):
        yield
        return
    timeout = int(marker.kwargs.get("timeout", _SERVING_TIMEOUT_S))

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"{marker.name} test exceeded its {timeout}s SIGALRM timeout")

    old = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(timeout)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
