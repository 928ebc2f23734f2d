"""Test configuration: force an 8-device virtual CPU platform BEFORE jax
imports, so sharding tests exercise a multi-chip mesh without TPU hardware
(mirrors the reference's strategy of testing multi-device graphs on CPU
places, e.g. broadcast_op_handle_test.cc)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

# Pin the platform through jax.config as well: it wins as long as no
# backend is initialized yet, whatever was imported before this conftest.
import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'` (ROADMAP.md): multi-process soaks and
    # real-size chip-less compiles opt out with `slow`; `chaos` tags the
    # fault-injection resilience suite so it can be run alone
    # (`-m chaos`).  `timeout` is pytest-timeout's marker when that
    # plugin is present; registering it here keeps the suite
    # warning-clean when it isn't.
    config.addinivalue_line(
        "markers",
        "slow: excluded from tier-1 (-m 'not slow'): multi-process soaks, "
        "and real-size chip-less compiles of what a benchmark cell's "
        "`correct` and rate already hold on the chip.  Not a way to make "
        "room: a tier-1 test over 20 s on the driver's run is resized by "
        "the PR that adds it, and a file over 300 s of junit time is split "
        "by subject (ROADMAP D16)")
    config.addinivalue_line(
        "markers", "chaos: fault-injection resilience tests")
    config.addinivalue_line(
        "markers", "timeout(seconds): per-test timeout (pytest-timeout)")


@pytest.fixture
def host_devices():
    """Factory fixture for chip-less SPMD tests: ``host_devices(n)``
    returns `n` virtual CPU devices for a device mesh.

    ``--xla_force_host_platform_device_count`` only takes effect BEFORE
    the jax backend initializes, so this conftest already forces 8
    devices at import time (above).  The fixture configures the flag
    itself in the one window where that is still possible (jax not yet
    imported — e.g. a test subprocess importing this conftest fresh)
    and otherwise validates the initialized platform, SKIPPING when it
    came up with fewer devices than the test needs (a real accelerator
    platform pinned first, or a host that overrode XLA_FLAGS) — a mesh
    test must never hard-fail an environment it cannot reconfigure."""
    import sys

    def _get(n):
        if "jax" not in sys.modules:  # pragma: no cover — conftest
            flags = os.environ.get("XLA_FLAGS", "")  # imports jax above
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags
                    + f" --xla_force_host_platform_device_count={n}")
        import jax as _jax

        devs = _jax.devices()
        if len(devs) < n:
            pytest.skip(
                f"needs {n} devices but the platform already "
                f"initialized with {len(devs)} — "
                "xla_force_host_platform_device_count cannot be "
                "re-applied after backend init")
        return devs[:n]

    return _get


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Give every test a fresh default main/startup program and scope."""
    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core import scope as scope_mod

    prev_main = framework.switch_main_program(fluid.Program())
    prev_startup = framework.switch_startup_program(fluid.Program())
    prev_scope = scope_mod._current_scope
    scope_mod._current_scope = scope_mod.Scope()
    # fresh name counters too: generated names (fc_0.w_0, ...) must not
    # depend on how many layers earlier tests built — string-sorted name
    # lookups go wrong once a counter crosses 10 (fc_10 < fc_9)
    with framework.unique_name_guard():
        yield
    framework.switch_main_program(prev_main)
    framework.switch_startup_program(prev_startup)
    scope_mod._current_scope = prev_scope
    # the AMP policy is process-wide and survives program resets on
    # purpose; a test that set it explicitly (test_executor.py ends on
    # disable_amp()) must not decide what a later file in the same worker
    # lowers for the TPU: test_tpu_lowering.py::TestConvBnChain (9 cases)
    # reads the un-set default, and failed whenever xdist's loadfile gave
    # one worker both files, which the two test files PR 31 adds made it do
    from paddle_tpu.core import amp

    amp.reset_amp()
