"""The span tree of a step as the four entry points emit it since PR 35:
executor.run > executor.step > {plan, stage, dispatch, commit, fetch >
{wait, copy}}, `seq` on the step, one executor.run a step however the call
came in, and no wait and no copy where the fetch stays on the device.  The
marks benchmark/harness/turnaround.py reads are the wait's end, the
dispatch's start and the ends of the step and the run."""

import glob
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observability as obs

PHASES = ["executor.plan", "executor.stage", "executor.dispatch",
          "executor.commit", "executor.fetch"]
UNDER_FETCH = ["executor.wait", "executor.copy"]
ENTRIES = [("serial", "run"), ("spmd", "run"), ("spmd", "compiled")]


@pytest.fixture
def obs_on():
    fluid.set_flags({"FLAGS_observability": True})
    obs.reset()
    yield
    obs.reset()
    fluid.set_flags({"FLAGS_observability": False})


def _entry(kind, how, **kw):
    """A callable that makes one call into the executor of `kind` through
    `how` (`compiled`: Executor.run handed a CompiledProgram), the feed
    staged on the device(s) once."""
    import jax

    x = layers.data("x", [4], dtype="float32")
    y = layers.fc(x, size=2,
                  param_attr=fluid.ParamAttr(name=f"turn_{kind}_{how}_w"))
    loss = layers.reduce_mean(y)
    fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    host = {"x": np.ones((4, 4), "float32")}
    if kind == "serial":
        feed = jax.device_put(host, exe.place.jax_device())
        return lambda: exe.run(feed=feed, fetch_list=[loss], **kw)
    from paddle_tpu.parallel import make_mesh

    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    feed = jax.device_put(host, mesh.batch_sharding())
    if how == "compiled":
        prog = fluid.CompiledProgram(
            fluid.default_main_program()).with_data_parallel(
                loss_name=loss.name, mesh=mesh)
        return lambda: exe.run(prog, feed=feed, fetch_list=[loss], **kw)
    pe = fluid.ParallelExecutor(loss_name=loss.name, mesh=mesh)
    return lambda: pe.run(feed=feed, fetch_list=[loss], **kw)


def _ring():
    return sorted((s for s in obs.default_tracer().spans()
                   if s.name.startswith("executor.")),
                  key=lambda s: (s.t0, -s.t1))


def _within(spans, outer, names):
    return [s for s in spans if s.name in names
            and outer.t0 <= s.t0 and s.t1 <= outer.t1]


@pytest.mark.parametrize("kind,how", ENTRIES)
def test_every_entry_point_emits_the_whole_tree_in_order(kind, how, obs_on):
    step = _entry(kind, how)
    obs.reset()  # the startup program's run
    step()
    step()
    step()
    spans = _ring()
    runs = [s for s in spans if s.name == "executor.run"]
    steps = [s for s in spans if s.name == "executor.step"]
    # one run a step, however the call came in: none nested, none beside
    assert len(runs) == len(steps) == 3
    assert all(r.parent is None and r.args == {} for r in runs)
    assert all(a.t1 <= b.t0 for a, b in zip(runs, runs[1:]))
    seqs = [s.args["seq"] for s in steps]
    assert seqs == list(range(seqs[0], seqs[0] + 3))
    for run, st in zip(runs, steps):
        assert _within(spans, run, ["executor.step"]) == [st]
        assert st.parent == "executor.run" and st.args["kind"] == kind
        phases = _within(spans, st, PHASES)
        assert [s.name for s in phases] == PHASES
        assert all(s.parent == "executor.step" for s in phases)
        assert all(a.t1 <= b.t0 for a, b in zip(phases, phases[1:]))
        fetch = phases[-1]
        wait, copy = _within(spans, fetch, UNDER_FETCH)
        assert [wait.name, copy.name] == UNDER_FETCH
        assert wait.parent == copy.parent == "executor.fetch"
        # the wait first, and nothing of the fetch before it or between the
        # two but the spans' own entries and exits
        assert fetch.t0 <= wait.t0 <= wait.t1 <= copy.t0 <= copy.t1 <= fetch.t1
        # no count of their own: the readers take their ends alone
        assert wait.args == copy.args == {}
        assert fetch.args == {"n": 1}
        # the marks the turnaround's reader takes lie in this order
        dispatch = phases[2]
        assert run.t0 <= st.t0 <= dispatch.t0 < wait.t1 <= st.t1 <= run.t1
    # and nothing of the executors' lies outside a run
    assert all(any(r.t0 <= s.t0 and s.t1 <= r.t1 for r in runs)
               for s in spans)


@pytest.mark.parametrize("kind,how", ENTRIES)
def test_a_fetch_that_stays_on_the_device_has_no_wait_and_no_copy(
        kind, how, obs_on):
    import jax

    step = _entry(kind, how, return_numpy=False)
    obs.reset()
    (out,) = step()
    assert isinstance(out, jax.Array)  # as it was: handed on, not waited for
    names = [s.name for s in _ring()]
    assert names == ["executor.run", "executor.step"] + PHASES
    assert np.isfinite(np.asarray(out)).all()


def test_the_flag_off_leaves_the_ring_empty():
    assert not obs.enabled()
    step = _entry("serial", "run")
    obs.reset()
    (loss,) = step()
    assert isinstance(loss, np.ndarray)
    assert obs.default_tracer().spans() == []


def test_seq_numbers_the_process_steps_across_executors(obs_on):
    """One counter a process: two executors' steps interleaved still number
    upward, so a reader that sees k and k + 1 knows nothing ran between."""
    a = _entry("serial", "run")
    b = _entry("spmd", "run")
    obs.reset()
    a(), b(), a()
    steps = [s for s in _ring() if s.name == "executor.step"]
    seqs = [s.args["seq"] for s in steps]
    assert [s.args["kind"] for s in steps] == ["serial", "spmd", "serial"]
    assert seqs == list(range(seqs[0], seqs[0] + 3))


def test_the_fetched_values_are_what_they_were(obs_on):
    """The wait changes no value: what a step fetches through the wait and
    the copy is what the same step hands on as a device array (a program
    with no optimizer, so every step computes the same)."""
    x = layers.data("x", [4], dtype="float32")
    y = layers.fc(x, size=3, param_attr=fluid.ParamAttr(name="turn_same_w"))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"x": np.arange(8, dtype="float32").reshape(2, 4)}
    (on_host,) = exe.run(feed=feed, fetch_list=[y])
    (on_device,) = exe.run(feed=feed, fetch_list=[y], return_numpy=False)
    assert isinstance(on_host, np.ndarray) and on_host.shape == (2, 3)
    np.testing.assert_array_equal(on_host, np.asarray(on_device))


@pytest.mark.parametrize("kind,how", [("serial", "run"), ("spmd", "run")])
def test_the_tree_lands_in_a_plain_profiler_session(kind, how, tmp_path):
    """Sink A, the flag off: the host plane of the trace holds the same
    tree with the step's `seq`, which is what
    benchmark/harness/turnaround.py reads on the chip."""
    import jax
    from jax.profiler import ProfileData

    assert not obs.enabled()
    step = _entry(kind, how)
    step()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # as benchmark/harness/trace.py starts it
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        step()
        step()
    finally:
        jax.profiler.stop_trace()
    assert obs.default_tracer().spans() == []
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    evs = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            evs += [(e.name.split("#")[0], e.start_ns,
                     e.start_ns + e.duration_ns, dict(e.stats))
                    for line in plane.lines for e in line.events
                    if e.name.startswith("executor.")]
    evs.sort(key=lambda e: (e[1], -e[2]))
    tree = ["executor.run", "executor.step"] + PHASES + UNDER_FETCH
    assert [e[0] for e in evs] == tree * 2
    first, second = evs[:len(tree)], evs[len(tree):]
    assert second[1][3]["seq"] == first[1][3]["seq"] + 1
    for one in (first, second):
        by = {e[0]: e for e in one}
        assert by["executor.step"][3]["kind"] == kind
        for name in ["executor.run"] + UNDER_FETCH:
            assert by[name][3] == {}
        run, st, fetch = (by["executor." + n] for n in ("run", "step",
                                                         "fetch"))
        assert run[1] <= st[1] and st[2] <= run[2]
        for name in UNDER_FETCH:
            assert fetch[1] <= by[name][1] and by[name][2] <= fetch[2]
