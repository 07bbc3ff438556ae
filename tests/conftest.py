"""Test harness: force JAX onto 8 virtual CPU devices BEFORE any test runs.

This proves every mesh/collective code path (dp/tp shardings, psum/pmean
over the mesh) without TPU hardware, per SURVEY.md §4 item 4. JAX reads
JAX_PLATFORMS at import and XLA_FLAGS at the first backend init, so both
go into the environment before `import jax`; subprocess tests inherit
them (and strip the device-count flag where they want one device).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

from dotaclient_tpu.runtime.device import use_compile_cache  # noqa: E402

# Persistent compilation cache, placed by the same rule as the binaries
# (JAX_COMPILATION_CACHE_DIR if set, else the fixed directory in the
# checkout): warm runs skip the recompiles a fresh pytest process pays,
# and the binaries subprocess tests start find the same directory.
use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


def clean_subprocess_env(extra=None):
    """Env dict for subprocesses spawned FROM pytest: the parent's, less
    the 8-virtual-device flag (children run the one-device topology a
    deployed binary sees)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = env.get("XLA_FLAGS", "").replace(
        " --xla_force_host_platform_device_count=8", ""
    )
    if extra:
        env.update(extra)
    return env


# --------------------------------------------------------------- lockcheck

import pytest  # noqa: E402


@pytest.fixture
def lockcheck():
    """Instrumented-lock race harness (dotaclient_tpu/analysis/lockcheck):
    patches threading.Lock/RLock for the duration of the test — but only
    locks CREATED by repo code are instrumented; stdlib/JAX internals
    keep native locks. Yields the LockMonitor; assert on
    monitor.inversions / monitor.over_held / monitor.report() in the
    test. Production code never imports the module — this fixture is the
    only enablement path, so shipping binaries stay inert."""
    from dotaclient_tpu.analysis.lockcheck import LockMonitor

    monitor = LockMonitor()
    monitor.install()
    try:
        yield monitor
    finally:
        monitor.uninstall()


@pytest.fixture
def racecheck():
    """Vector-clock happens-before race sanitizer (dotaclient_tpu/
    analysis/racecheck): patches threading.Lock/RLock/Condition/Event/
    Thread and queue.Queue (repo-created objects only) for the duration
    of the test; opt instances into attribute-write tracing with
    monitor.watch(obj). Assert on monitor.races / monitor.report().
    Mutually exclusive with the lockcheck fixture within one test (one
    substrate may own threading at a time — install refuses otherwise).
    Production never imports the module; this fixture is the only
    enablement path."""
    from dotaclient_tpu.analysis.racecheck import RaceMonitor

    monitor = RaceMonitor()
    monitor.install()
    try:
        yield monitor
    finally:
        monitor.uninstall()
