"""Test config: force an 8-device virtual CPU mesh before JAX import.

Mirrors the reference CI matrix over MPI rank counts {1,2,4,6,8}
(cmake/testing/pmmg_tests.cmake:30-63) — here rank = virtual CPU device.
JAX_PLATFORMS is force-overridden: unit tests run on the virtual CPU
mesh, never on a chip.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# NO persistent compile cache for the CPU test matrix by default: on
# this image the XLA:CPU AOT cache is unreliable — serialize()
# intermittently SIGABRTs inside put_executable_and_time, and reloading
# entries warns about machine-feature mismatches (+prefer-no-scatter)
# that "could lead to SIGILL" (cpu_aot_loader.cc).  PARMMG_TEST_CACHE=1
# opts back in through the one cache rule (utils/compilecache).
if os.environ.get("PARMMG_TEST_CACHE", "") == "1":
    from parmmg_tpu.utils.compilecache import (default_cache_dir,
                                               enable_persistent_cache)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    enable_persistent_cache(default_cache_dir())

# Free compiled executables between test modules: the XLA:CPU runtime on
# this image becomes unstable after many hundred compilations in one
# process (intermittent segfaults in backend_compile_and_load / aborts in
# executable.serialize, always late in a long run; every test passes in a
# fresh process).  Dropping the executable caches per module keeps the
# process young.  scripts/run_tests.sh (one process per file) is the
# belt-and-braces runner.
import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 (ROADMAP verify) runs `-m 'not slow'` on a small CPU box
    # where XLA compiles dominate: tests whose adapt/SPMD programs take
    # minutes to compile are marked slow and covered by the per-file
    # tier-2 runner (scripts/run_tests.sh) instead
    config.addinivalue_line(
        "markers", "slow: heavy XLA compile; excluded from the tier-1 gate")


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    yield
    jax.clear_caches()
