"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip hardware is unavailable in CI; shardings are validated on a virtual
CPU mesh (the reference's analogous trick is compile-time-injecting simulated
Storage/MessageBus into real replicas — src/testing/cluster.zig:58).

Tests run on the CPU backend: pinned here in the environment and in
jax.config before any backend initializes, so a suite started on a
machine with a chip never takes it.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _verify_flag_isolated():
    """constants.VERIFY is process-global and the simulator flips it on
    (VOPR doctrine); restore it around every test so a Cluster in one
    test cannot silently enable extra checks (or fire their asserts) in
    unrelated later tests."""
    from tigerbeetle_tpu import constants

    was = constants.VERIFY
    yield
    constants.set_verify(was)


@pytest.fixture(autouse=True, scope="module")
def _bound_live_executables():
    """Full-suite single-process runs accumulate hundreds of compiled
    XLA executables; past a threshold the CPU backend's compiler
    segfaults DETERMINISTICALLY (observed twice at the same test with
    identical stacks — compile of the ring window kernel after ~530
    tests — while the same module passes in isolation). Clearing the
    jit caches at module boundaries bounds live executables; modules
    recompile what they use, trading some wall time for a crash-free
    single-command suite run."""
    yield
    jax.clear_caches()
