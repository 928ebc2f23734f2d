"""The one staging rule (core/executor.py: in_place, stage_values): a step
hands its jit call the state that is already in place as the very objects the
scope holds, and jax.device_put touches only what is not.  Counts and
identities only; no timing (tier-1 runs on the CPU)."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core import executor as executor_mod
from paddle_tpu.core.executor import RNG_STATE_VAR, in_place, stage_values
from paddle_tpu.parallel import make_mesh

ENTRIES = ["serial", "spmd"]
KINDS = ["host", "uncommitted", "elsewhere", "in_place"]
W = "staging_w"


class _Step:
    """One fc + SGD step behind each executor's `run`, with a feed that is
    in place, so that what a step moves is state alone."""

    def __init__(self, kind):
        self.kind = kind
        x = layers.data("x", [4], dtype="float32")
        y = layers.fc(x, size=2, param_attr=fluid.ParamAttr(name=W))
        self.loss = layers.reduce_mean(y)
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(self.loss)
        self.exe = fluid.Executor(fluid.CPUPlace())
        self.exe.run(fluid.default_startup_program())
        host = np.random.RandomState(0).randn(4, 4).astype("float32")
        if self.kind == "serial":
            dev = fluid.CPUPlace().jax_device()
            self.want = jax.sharding.SingleDeviceSharding(dev)
            self.elsewhere = jax.devices()[1]
            feed_want = self.want
        else:
            mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
            self.pe = fluid.ParallelExecutor(loss_name=self.loss.name,
                                             mesh=mesh)
            self.want = mesh.replicated()
            # a single-device array under a mesh: the serial->SPMD handoff
            self.elsewhere = jax.devices()[0]
            feed_want = mesh.batch_sharding()
        self.feed = {"x": jax.device_put(host, feed_want)}

    def step(self):
        runner = self.exe if self.kind == "serial" else self.pe
        return runner.run(feed=self.feed, fetch_list=[self.loss])[0]

    def as_kind(self, kind, host):
        if kind == "host":
            return host
        if kind == "uncommitted":
            return jnp.asarray(host)
        return jax.device_put(host, self.elsewhere)


def _spy(monkeypatch):
    """Every stage_values call of a step: (given, staged, moved)."""
    calls = []

    def spy(vals, wants):
        staged, moved = stage_values(vals, wants)
        calls.append((vals, staged, moved))
        return staged, moved

    monkeypatch.setattr(executor_mod, "stage_values", spy)
    return calls


def _count_device_put(monkeypatch):
    puts = []
    real = jax.device_put

    def counting(x, *a, **k):
        puts.append(x)
        return real(x, *a, **k)

    monkeypatch.setattr(jax, "device_put", counting)
    return puts


def _state_snapshot(scope):
    return {n: np.asarray(scope.find_var(n)) for n in scope.local_var_names()}


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("entry", ENTRIES)
def test_stage_places_only_what_is_not_in_place(entry, kind, monkeypatch):
    s = _Step(entry)
    scope = fluid.global_scope()
    s.step()                      # compiles; leaves every value in place
    snap = _state_snapshot(scope)
    given = scope.find_var(W)
    assert in_place(given, s.want)
    if kind != "in_place":
        given = s.as_kind(kind, snap[W])
        assert not in_place(given, s.want)
        scope.set_var(W, given)

    calls = _spy(monkeypatch)
    puts = _count_device_put(monkeypatch)
    loss = s.step()
    new_w = np.asarray(scope.find_var(W))
    monkeypatch.undo()

    (vals, staged, moved), = calls
    (i,) = [j for j, v in enumerate(vals) if v is given]
    if kind == "in_place":
        # a steady step: nothing goes to jax.device_put, and the call gets
        # the very objects the scope holds
        assert moved == 0 and not puts
        assert staged is vals
    else:
        assert moved == 1 and len(puts) == 1
        assert staged[i] is not given
        assert staged[i].committed and staged[i].sharding == s.want
        # every other value went on as the object it came as
        assert all(a is b for j, (a, b) in enumerate(zip(vals, staged))
                   if j != i)

    # the same step from a scope of host values (every value placed, the
    # parent's path for all of them) gives the same bits
    for n, v in snap.items():
        scope.set_var(n, v)
    ref_loss = s.step()
    np.testing.assert_array_equal(loss, ref_loss)
    np.testing.assert_array_equal(new_w, np.asarray(scope.find_var(W)))


def test_stage_values_takes_one_sharding_or_one_each():
    dev0, dev1 = jax.devices()[:2]
    w0 = jax.sharding.SingleDeviceSharding(dev0)
    w1 = jax.sharding.SingleDeviceSharding(dev1)
    a = jax.device_put(np.ones(3, "float32"), dev0)
    b = jax.device_put(np.ones(3, "float32"), dev1)
    staged, moved = stage_values((a, b), w0)
    assert moved == 1 and staged[0] is a and staged[1].sharding == w0
    staged, moved = stage_values((a, b), (w0, w1))
    assert moved == 0 and staged[0] is a and staged[1] is b
    staged, moved = stage_values((a, b), (w1, w0))
    assert moved == 2
    assert [v.sharding for v in staged] == [w1, w0]
    assert all(v.committed for v in staged)
    assert stage_values((), w0) == ((), 0)


def test_lod_feed_is_placed_whole():
    """A LoD feed is a pytree, not a jax.Array: it goes to device_put as
    one value and comes back with its leaves on the device."""
    from paddle_tpu.core.lod import LoDValue

    dev = jax.devices()[0]
    want = jax.sharding.SingleDeviceSharding(dev)
    lod = LoDValue(np.ones((2, 3, 1), "float32"), np.array([3, 2], "int32"))
    (staged,), moved = stage_values((lod,), want)
    assert moved == 1 and isinstance(staged, LoDValue)
    assert in_place(staged.data, want) and in_place(staged.lengths, want)


def test_two_threads_share_in_place_state_by_object(monkeypatch):
    """The Hogwild sharers (AsyncExecutor's threads): donate_states=False
    on one scope.  A value in place is now shared by object among the
    threads; nothing is donated, so every step of every thread runs and
    every value it leaves is finite and in place."""
    x = layers.data("x", [4], dtype="float32")
    y = layers.fc(x, size=2, param_attr=fluid.ParamAttr(name=W))
    loss = layers.reduce_mean(layers.square(y))
    fluid.optimizer.SGDOptimizer(learning_rate=0.01).minimize(loss)
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    exe = fluid.Executor(fluid.CPUPlace(), donate_states=False)
    scope = fluid.global_scope()
    program = fluid.default_main_program()
    want = jax.sharding.SingleDeviceSharding(fluid.CPUPlace().jax_device())
    feed = {"x": jax.device_put(np.ones((2, 4), "float32"),
                                fluid.CPUPlace().jax_device())}
    exe.run(program, feed=feed, fetch_list=[loss], scope=scope)
    calls = _spy(monkeypatch)
    errors, losses = [], []

    def work():
        try:
            for _ in range(20):
                losses.append(float(np.ravel(exe.run(
                    program, feed=feed, fetch_list=[loss], scope=scope)[0])[0]))
        except Exception as e:  # surfaced below, in the test's thread
            errors.append(e)

    threads = [threading.Thread(target=work) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert len(losses) == 40 and np.isfinite(losses).all()
    assert len(calls) == 40 and all(c[2] == 0 for c in calls)
    assert all(c[1] is c[0] for c in calls)
    assert in_place(scope.find_var(W), want)
    assert np.isfinite(np.asarray(scope.find_var(W))).all()


def test_skipped_step_leaves_parameters_accepted_as_in_place(monkeypatch):
    """FLAGS_check_numerics: a skipped step writes nothing back, so the
    scope keeps the previous step's objects (donation is off); the next,
    good step takes them as in place and trains on."""
    import os

    from paddle_tpu.resilience import faultinject

    x = layers.data("x", [4], dtype="float32")
    y = layers.fc(x, size=2, param_attr=fluid.ParamAttr(name=W))
    loss = layers.reduce_mean(y)
    fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    feed = {"x": jax.device_put(np.ones((2, 4), "float32"),
                                fluid.CPUPlace().jax_device())}
    fluid.set_flags({"FLAGS_check_numerics": True})
    try:
        exe.run(feed=feed, fetch_list=[loss])
        held = scope.find_var(W)
        before = np.asarray(held).copy()
        calls = _spy(monkeypatch)
        monkeypatch.setenv("FAULT_NAN_AT_STEP", "0")
        faultinject.reset()
        (bad,) = exe.run(feed=feed, fetch_list=[loss])
        assert np.isnan(np.asarray(bad)).all()
        assert scope.find_var(W) is held           # skipped: same object
        exe.run(feed=feed, fetch_list=[loss])      # the good step
        assert [c[2] for c in calls] == [0, 0]
        assert any(v is held for v in calls[-1][1])
        after = np.asarray(scope.find_var(W))
        assert np.isfinite(after).all() and not np.array_equal(after, before)
        np.testing.assert_array_equal(np.asarray(held), before)
    finally:
        os.environ.pop("FAULT_NAN_AT_STEP", None)
        faultinject.reset()
        fluid.set_flags({"FLAGS_check_numerics": False})


def test_key_and_scope_shared_across_places():
    """A scope shared by executors on two places: each finds the other's
    values not in place and takes them over, exactly counted."""
    x = layers.data("x", [4], dtype="float32")
    y = layers.fc(x, size=2, param_attr=fluid.ParamAttr(name=W))
    loss = layers.reduce_mean(y)
    fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    feed = {"x": np.ones((2, 4), "float32")}
    exe.run(feed=feed, fetch_list=[loss])
    dev0 = fluid.CPUPlace().jax_device()
    assert scope.find_var(RNG_STATE_VAR).devices() == {dev0}
    assert scope.find_var(W).devices() == {dev0}

    class Other(fluid.CPUPlace):
        def jax_device(self):
            return jax.devices()[1]

    other = fluid.Executor(Other())
    fluid.set_flags({"FLAGS_observability": True})
    from paddle_tpu import observability as obs

    obs.reset()
    try:
        other.run(feed=feed, fetch_list=[loss])
        other.run(feed=feed, fetch_list=[loss])
        stage = [s.args for s in obs.default_tracer().spans()
                 if s.name == "executor.stage"]
    finally:
        fluid.set_flags({"FLAGS_observability": False})
        obs.reset()
    # the first step takes everything over, the second only the host feed
    assert [a["moved"] for a in stage] == [stage[0]["n"], 1]
    assert scope.find_var(W).devices() == {jax.devices()[1]}
