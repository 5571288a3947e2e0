"""Test harness: force an 8-device virtual CPU mesh before JAX initializes.

Multi-chip hardware is unavailable in CI; sharding correctness is validated on a
virtual CPU mesh (the reference has no such fake-cluster mode — multi-node there
means a real mpiexec cluster, SURVEY.md §4)."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_cpu_devices():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {devs}"
    return devs[:8]


@pytest.fixture(autouse=True, scope="module")
def _bound_compiled_program_accumulation():
    """Free compiled executables between test MODULES: a full one-shot
    `pytest tests/` accumulates thousands of distinct XLA:CPU programs in
    one process, and on single-core hosts the compiler segfaults once
    enough executables are live (observed twice at ~76% of the suite,
    crashing inside backend_compile_and_load while compiling yet another
    kernel; the same tests pass when the process starts closer to them).
    Clearing jit caches per module bounds the live-program count; modules
    re-jit lazily at a small cost."""
    yield
    import gc

    import jax

    jax.clear_caches()
    gc.collect()
