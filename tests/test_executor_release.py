"""Where a step lets go of what it consumed (PR 37): run_step drops the
staged state and key at the end of `executor.commit`, once the scope holds
what the step produced, so the arrays the call took by donation are released
while the device computes and not after the wait.  Held here, for each entry
point of tests/test_executor_turnaround_spans.py: when `executor.wait`
begins, nothing but the test's own weak references knows the arrays that
were the scope's state before the call."""

import weakref

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.core.executor import RNG_STATE_VAR

from test_executor_turnaround_spans import ENTRIES, _entry, obs_on  # noqa: F401


def _scope_state():
    """name -> the very object the scope holds, for every array in it."""
    scope = fluid.global_scope()
    return {n: scope.find_var(n) for n in scope.local_var_names()
            if isinstance(scope.find_var(n), jax.Array)}


def _weak_state():
    """Weak references to the scope's arrays, and nothing strong left."""
    return {n: weakref.ref(v) for n, v in _scope_state().items()}


@pytest.fixture
def at_the_wait(monkeypatch):
    """Calls `probe()` at the moment `executor.wait` starts to wait: the
    executors' jax.block_until_ready, patched; `seen` collects its
    results."""
    seen = []
    hook = {"probe": None}
    real = jax.block_until_ready

    def waited(x):
        if hook["probe"] is not None:
            seen.append(hook["probe"]())
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", waited)
    return hook, seen


def _alive(refs):
    return sorted(n for n, r in refs.items() if r() is not None)


@pytest.mark.parametrize("when", ["first", "steady"])
@pytest.mark.parametrize("kind,how", ENTRIES)
def test_the_old_state_is_gone_when_the_wait_begins(kind, how, when,
                                                    at_the_wait):
    """`first`: the call after the startup program, whose state the stage
    may have to place (under a mesh it does: the tuple plan.state_values
    returned and the one stage returned differ); `steady`: a call whose
    state is what the last one returned, in place and donated."""
    hook, seen = at_the_wait
    step = _entry(kind, how)
    if when == "steady":
        step()
    refs = _weak_state()
    assert {RNG_STATE_VAR, f"turn_{kind}_{how}_w"} <= set(refs)
    assert _alive(refs) == sorted(refs)  # the scope's, until the call
    hook["probe"] = lambda: _alive(refs)
    (loss,) = step()
    hook["probe"] = None
    assert seen == [[]]  # one wait, and at its start none was left
    assert isinstance(loss, np.ndarray) and np.isfinite(loss).all()
    # the scope went on to the step's own outputs
    assert set(_scope_state()) >= set(refs)


@pytest.mark.parametrize("kind,how", ENTRIES)
def test_the_old_state_is_gone_at_the_return_of_a_device_fetch(kind, how):
    """return_numpy=False has no wait: the caller pays the same release
    before the return that it paid at the return."""
    step = _entry(kind, how, return_numpy=False)
    step()
    refs = _weak_state()
    (out,) = step()
    assert _alive(refs) == []
    assert isinstance(out, jax.Array)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("return_numpy", [True, False])
def test_a_skipped_step_keeps_the_very_objects_in_the_scope(
        return_numpy, monkeypatch):
    """FLAGS_check_numerics, the sentinel tripped (a fetch poisoned by
    FAULT_NAN_AT_STEP): nothing is written back and donation is off, so the
    frame's letting go frees nothing: the scope holds what it held, and the
    next step trains on it."""
    from paddle_tpu.resilience import faultinject

    fluid.set_flags({"FLAGS_check_numerics": True,
                     "FLAGS_check_numerics_max_consecutive": 5})
    try:
        step = _entry("serial", "run", return_numpy=return_numpy)
        step()
        before = _scope_state()
        faultinject.reset()
        monkeypatch.setenv("FAULT_NAN_AT_STEP", "0")
        (skipped,) = step()
        monkeypatch.delenv("FAULT_NAN_AT_STEP")
        assert np.isnan(np.asarray(skipped)).all()
        after = _scope_state()
        assert set(after) == set(before)
        assert all(after[n] is before[n] for n in before)
        assert not any(v.is_deleted() for v in after.values())
        (ok,) = step()
        assert np.isfinite(np.asarray(ok)).all()
        assert _scope_state()[RNG_STATE_VAR] is not before[RNG_STATE_VAR]
    finally:
        faultinject.reset()
        fluid.set_flags({"FLAGS_check_numerics": False,
                         "FLAGS_check_numerics_max_consecutive": 3})


@pytest.mark.parametrize("when", ["first", "steady"])
def test_costing_is_recorded_and_keeps_no_array(when, obs_on, at_the_wait):
    """FLAGS_observability_cost: the once-a-program cost is still recorded,
    from the arguments' shapes: on the very step that is costed the old
    state is gone when the wait begins, as on any other."""
    hook, seen = at_the_wait
    step = _entry("serial", "run")
    if when == "steady":
        step()  # compiled and run with costing off: the entry is pending
    fluid.set_flags({"FLAGS_observability_cost": "native"})
    try:
        obs.reset()
        refs = _weak_state()
        hook["probe"] = lambda: _alive(refs)
        step()
        hook["probe"] = None
        gauge = obs.default_registry().gauge(
            "paddle_tpu_cost_bytes_per_step", "")
        (series,) = gauge.snapshot()["series"]
        assert series["value"] > 0
        assert series["labels"]["platform"] == "native"
        step()  # the same entry: costed once
        assert len(gauge.snapshot()["series"]) == 1
    finally:
        fluid.set_flags({"FLAGS_observability_cost": "off"})
    assert seen == [[]]
