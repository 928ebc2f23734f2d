"""The set-up log (paddle_tpu/observability/compiles.py): one record an
executable from jax's own compile events, one first run a program the
executor had not met, the persistent cache's directory beside them."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observability as obs
from paddle_tpu.observability import compiles

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a small program through fluid.Executor, the log's snapshot as the last line
STAGE = r"""
import json, os, sys
sys.path.insert(0, os.environ["STAGE_REPO"])
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import layers

x = layers.data("x", [16], dtype="float32")
h = layers.fc(x, size=32, act="relu")
loss = layers.mean(layers.fc(h, size=4))
fluid.optimizer.Adam(0.01).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(fluid.default_startup_program())
for _ in range(3):
    exe.run(feed={"x": np.ones((8, 16), "float32")}, fetch_list=[loss])
print(json.dumps(fluid.observability.default_compile_log().snapshot()))
"""


def _stage(cache_dir, **env):
    env = {**{k: v for k, v in os.environ.items()
              if not k.startswith("JAX_COMPILATION_CACHE")},
           "STAGE_REPO": REPO, "JAX_PLATFORMS": "cpu",
           "FLAGS_compile_cache_dir": str(cache_dir),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1", **env}
    out = subprocess.run([sys.executable, "-c", STAGE], capture_output=True,
                         text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _programs(snap):
    """(the start-up program's first run, the step program's, the records
    inside each)."""
    startup = next(r for r in snap["runs"]
                   if r["n_feed"] == 0 and r["n_fetch"] == 0)
    step = next(r for r in snap["runs"] if r["n_fetch"] > 0)
    inside = {run["index"]: [r for r in snap["records"]
                             if r["run"] == run["index"]]
              for run in (startup, step)}
    return startup, step, inside


@pytest.fixture(scope="module")
def two_processes(tmp_path_factory):
    """Two fresh processes against one cache directory: the first builds,
    the second loads."""
    cache = tmp_path_factory.mktemp("xla_cache")
    return _stage(cache), _stage(cache), cache


def test_a_cold_process_has_two_first_runs_whose_records_missed(
        two_processes):
    cold, _, cache = two_processes
    startup, step, inside = _programs(cold)
    assert [r["index"] for r in cold["runs"]] == [0, 1]  # 3 steps, 1 miss
    assert (startup["kind"], step["kind"]) == ("serial", "serial")
    assert step["n_feed"] == 1 and step["n_fetch"] == 1
    assert step["n_state"] >= startup["n_state"] > 0
    assert startup["program"] != step["program"]
    assert startup["t1"] <= step["t0"] < step["t1"]
    assert cold["cache_dir"] == str(cache)
    assert cold["imported_at"] < startup["t0"]
    for run in (startup, step):
        recs = inside[run["index"]]
        assert recs and recs[-1]["fun"] == "jit(fn)"
        for r in recs:
            assert r["cache"] == "miss"
            assert r["entry_bytes"] > 0
            assert r["retrieval_s"] is None
            assert run["t0"] <= r["t_end"] <= run["t1"]
        big = recs[-1]
        assert min(big["trace_s"], big["lower_s"], big["backend_s"]) > 0
    written = {n: os.path.getsize(os.path.join(cache, n))
               for n in os.listdir(cache) if n.endswith("-cache")}
    got = [r["entry_bytes"] for r in cold["records"] if r["entry_bytes"]]
    assert sorted(got) == sorted(written.values())
    assert all(r["evicted_bytes"] == 0 for r in cold["records"])


def test_a_warm_process_loads_the_same_records(two_processes):
    cold, warm, _ = two_processes
    assert [r["fun"] for r in warm["records"]] == \
        [r["fun"] for r in cold["records"]]
    assert [r["run"] for r in warm["records"]] == \
        [r["run"] for r in cold["records"]]
    for r in warm["records"]:
        assert r["cache"] == "hit"
        assert r["retrieval_s"] > 0
        # jax's closing event wraps the retrieval
        assert r["backend_s"] >= r["retrieval_s"]
        assert r["evicted_bytes"] is None
    # no cap, so the cache keeps no stamps to size a hit by
    assert all(r["entry_bytes"] is None for r in warm["records"])
    _, _, cold_in = _programs(cold)
    _, _, warm_in = _programs(warm)
    for index in cold_in:
        assert sum(r["backend_s"] for r in warm_in[index]) < \
            sum(r["backend_s"] for r in cold_in[index])


def test_under_a_cap_a_write_evicts(two_processes, tmp_path):
    cold, _, _ = two_processes
    sizes = sorted(r["entry_bytes"] for r in cold["records"])
    cap = str(sizes[-1] + sizes[-2] - 1)  # the two programs do not fit
    cache = tmp_path / "capped"
    first = _stage(cache, JAX_COMPILATION_CACHE_MAX_SIZE=cap)
    assert all(r["cache"] == "miss" for r in first["records"])
    evicting = [r for r in first["records"] if r["evicted_bytes"]]
    assert evicting
    left = sum(os.path.getsize(os.path.join(cache, n))
               for n in os.listdir(cache) if n.endswith("-cache"))
    assert left <= int(cap)
    assert sum(r["entry_bytes"] for r in first["records"]) - \
        sum(r["evicted_bytes"] for r in first["records"]) == left
    # the next process builds again what went, and pushes out what stayed
    second = _stage(cache, JAX_COMPILATION_CACHE_MAX_SIZE=cap)
    assert any(r["cache"] == "miss" for r in second["records"])
    assert sum(r["evicted_bytes"] or 0 for r in second["records"]) > 0


def test_under_a_cap_a_hit_is_sized_by_its_stamp(two_processes):
    """A cap that holds everything: the cache stamps an entry at every
    `get`, and the stamps newer than the log's start size the hits."""
    cold, _, cache = two_processes
    warm = _stage(cache, JAX_COMPILATION_CACHE_MAX_SIZE=str(10 ** 9))
    assert [r["cache"] for r in warm["records"]] == \
        ["hit"] * len(cold["records"])
    assert [r["entry_bytes"] for r in warm["records"]] == \
        [r["entry_bytes"] for r in cold["records"]]
    assert all(r["evicted_bytes"] is None for r in warm["records"])


def test_nested_traces_and_executables_are_not_added_twice(two_processes):
    """Inner `pjit` traces lie inside the step function's, and the constants
    a trace computes eagerly have records of their own: a run's records sum
    to less than the run."""
    for snap in two_processes[:2]:
        startup, step, inside = _programs(snap)
        for run in (startup, step):
            recs = inside[run["index"]]
            wall = run["t1"] - run["t0"]
            assert sum(r["trace_s"] for r in recs) < wall
            assert sum(r["trace_s"] + r["lower_s"] + r["backend_s"]
                       for r in recs) < wall
        # the start-up program's initializers compile inside its trace
        assert len(inside[startup["index"]]) > 1


# -- the log fed by hand ------------------------------------------------------

def _executable(log, fun="f", trace=(0.3,), lower=0.2, backend=1.0,
                cache=None, inner=()):
    """jax's events for one executable, in jax's order."""
    for name, seconds in inner:
        log._on_begin(compiles.TRACE, 0.0, fun_name=name)
        log._on_duration(compiles.TRACE, seconds, fun_name=name)
    for seconds in trace:
        log._on_begin(compiles.TRACE, 0.0, fun_name=fun)
        log._on_duration(compiles.TRACE, seconds, fun_name=fun)
    log._on_begin(compiles.LOWER, 0.0, fun_name=f"jit({fun})")
    log._on_duration(compiles.LOWER, lower, fun_name=f"jit({fun})")
    log._on_begin(compiles.BACKEND, 0.0, fun_name=f"jit({fun})")
    if cache is not None:
        log._on_event(compiles.REQUEST)
    if cache == "hit":
        log._on_event(compiles.HIT)
        log._on_duration(compiles.RETRIEVAL, 0.4)
    log._on_duration(compiles.BACKEND, backend, fun_name=f"jit({fun})")


def test_a_record_is_assembled_from_the_order_of_jaxs_events():
    log = compiles.CompileLog()
    _executable(log, "step", inner=[("sin", 0.01), ("inner", 0.05)])
    _executable(log, "loaded", cache="hit", backend=0.5)
    _executable(log, "asked", cache="miss")
    recs = log.snapshot()["records"]
    assert [r["fun"] for r in recs] == ["jit(step)", "jit(loaded)",
                                        "jit(asked)"]
    assert [r["cache"] for r in recs] == ["off", "hit", "miss"]
    assert recs[0]["trace_s"] == 0.3  # not 0.36: the inner ones lie inside
    assert (recs[0]["lower_s"], recs[0]["backend_s"]) == (0.2, 1.0)
    assert recs[1]["retrieval_s"] == 0.4
    assert recs[2]["retrieval_s"] is None
    assert all(r["run"] is None for r in recs)
    assert recs[0]["t_end"] <= recs[1]["t_end"] <= recs[2]["t_end"]
    assert log.count == 3 and log.dropped == 0


def test_a_trace_that_was_not_lowered_is_no_record_and_no_ones_trace():
    log = compiles.CompileLog()
    log._on_begin(compiles.TRACE, 0.0, fun_name="shape_only")
    log._on_duration(compiles.TRACE, 9.0, fun_name="shape_only")  # eval_shape
    assert log.count == 0
    _executable(log, "other", trace=())  # its trace came from jax's cache
    (rec,) = log.snapshot()["records"]
    assert rec["trace_s"] == 0.0 and rec["lower_s"] == 0.2


def test_an_executable_made_inside_a_trace_is_taken_out_of_it():
    log = compiles.CompileLog()
    log._on_begin(compiles.TRACE, 0.0, fun_name="outer")
    _executable(log, "iota", trace=(0.1,), lower=0.1, backend=0.3)
    log._on_duration(compiles.TRACE, 2.0, fun_name="outer")
    _executable(log, "outer", trace=())
    inner, outer = log.snapshot()["records"]
    assert inner["trace_s"] == 0.1
    assert outer["trace_s"] == pytest.approx(2.0 - 0.5)


def test_first_runs_take_the_records_that_ended_inside_them():
    log = compiles.CompileLog()
    _executable(log, "before")
    log.open_run("aaaaaaaaaaaa")
    _executable(log, "init")
    log.close_run("serial", 0, 0, 4)
    _executable(log, "between")
    log.open_run("forgotten")  # capture_program: built, never run
    log.open_run("bbbbbbbbbbbb")
    _executable(log, "step")
    log.close_run("spmd", 2, 1, 9)
    log.close_run("spmd", 2, 1, 9)  # nothing open: nothing added
    snap = log.snapshot()
    assert [(r["index"], r["kind"], r["program"], r["n_feed"], r["n_fetch"],
             r["n_state"]) for r in snap["runs"]] == [
        (0, "serial", "aaaaaaaaaaaa", 0, 0, 4),
        (2, "spmd", "bbbbbbbbbbbb", 2, 1, 9)]
    assert [r["run"] for r in snap["records"]] == [None, 0, None, 2]
    assert log.since(1) == {
        "executables": 3, "cache_misses": 0,
        "compile_s": pytest.approx(3 * 1.5)}


def test_the_log_is_bounded_and_reset_clears_it():
    log = compiles.CompileLog(capacity=3)
    for i in range(5):
        _executable(log, f"f{i}")
    snap = log.snapshot()
    assert [r["fun"] for r in snap["records"]] == [
        "jit(f2)", "jit(f3)", "jit(f4)"]
    assert (log.count, snap["dropped"]) == (5, 2)
    log.clear()
    assert log.count == 0 and log.snapshot()["records"] == []

    default = obs.default_compile_log()
    _executable(default, "by_hand")
    assert default.count > 0
    obs.reset()
    snap = default.snapshot()
    assert (default.count, snap["records"], snap["runs"]) == (0, [], [])


def test_threads_feed_one_log_without_losing_a_record():
    """Hogwild threads share the log: each assembles its own executables
    (jax fires on the compiling thread) and closes its own first runs."""
    import threading

    log = compiles.CompileLog(capacity=100000)
    n_threads, n_each = 16, 100
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def work(k):
        for i in range(n_each):
            log.open_run(f"t{k}")
            _executable(log, f"f{k}", cache="hit" if i % 2 else "miss")
            log.close_run("serial", k, 1, i)

    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(was)
    snap = log.snapshot()
    assert log.count == len(snap["records"]) == n_threads * n_each
    assert sorted(r["index"] for r in snap["runs"]) == \
        list(range(n_threads * n_each))
    for k in range(n_threads):
        mine = [r for r in snap["records"] if r["fun"] == f"jit(f{k})"]
        assert len(mine) == n_each
        # no thread's events leaked into another's record
        assert all((r["trace_s"], r["lower_s"], r["backend_s"]) ==
                   (0.3, 0.2, 1.0) for r in mine)
        assert sum(r["cache"] == "hit" for r in mine) == n_each // 2
    assert all(r["run"] is not None for r in snap["records"])


# -- in the program ------------------------------------------------------------

def _step_program(name):
    x = layers.data("x", [4], dtype="float32")
    y = layers.fc(x, size=2, param_attr=fluid.ParamAttr(name=name))
    loss = layers.reduce_mean(y)
    fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    return exe, loss


@pytest.fixture
def obs_on():
    fluid.set_flags({"FLAGS_observability": True})
    obs.reset()
    yield
    obs.reset()
    fluid.set_flags({"FLAGS_observability": False})


def test_dispatch_carries_executables_on_the_step_that_compiled_only(
        obs_on):
    """A program whose feed shape changes once: steps 0 and 2 compile."""
    exe, loss = _step_program("clog_shape_w")
    obs.reset()
    for rows in (2, 2, 3, 3):
        exe.run(feed={"x": np.ones((rows, 4), "float32")},
                fetch_list=[loss])
    dispatch = [s for s in obs.default_tracer().spans()
                if s.name == "executor.dispatch"]
    assert len(dispatch) == 4
    for i in (0, 2):
        args = dispatch[i].args
        assert args["executables"] >= 1 and args["cache_misses"] >= 0
        assert 0 < args["compile_s"] < dispatch[i].duration
    for i in (1, 3):
        assert dispatch[i].args == {}
    # the in-memory table missed once: the jit inside it retraced at step 2
    snap = obs.default_compile_log().snapshot()
    assert len(snap["runs"]) == 1
    assert sum(r["fun"] == "jit(fn)" for r in snap["records"]) == 2


def test_an_executor_that_keeps_no_table_opens_no_first_run():
    """`use_program_cache=False`: every step misses, so none is a first run
    and the ring keeps the start-up's; the executables are logged all the
    same, outside any run."""
    exe, loss = _step_program("clog_notable_w")
    log = obs.default_compile_log()
    runs = len(log.snapshot()["runs"])
    count = log.count
    for _ in range(3):
        exe.run(feed={"x": np.ones((2, 4), "float32")}, fetch_list=[loss],
                use_program_cache=False)
    snap = log.snapshot()
    assert len(snap["runs"]) == runs and log.count > count
    made = snap["records"][count - log.count:]
    assert made and all(r["run"] is None for r in made)


def test_under_the_flag_a_record_goes_to_the_ring(obs_on):
    exe, loss = _step_program("clog_ring_w")
    obs.reset()
    exe.run(feed={"x": np.ones((2, 4), "float32")}, fetch_list=[loss])
    spans = obs.default_tracer().spans()
    (step,) = [s for s in spans if s.name == "executor.step"]
    (rec,) = [r for r in obs.default_compile_log().snapshot()["records"]
              if r["fun"] == "jit(fn)"]
    for name, key in (("compile.trace", "trace_s"),
                      ("compile.lower", "lower_s"),
                      ("compile.backend", "backend_s")):
        (s,) = [s for s in spans
                if s.name == name and s.args["fun"] == "jit(fn)"]
        assert s.duration == pytest.approx(rec[key])
        assert s.args["cache"] == rec["cache"]
        assert step.t0 <= s.t0 and s.t1 <= step.t1  # under the step that paid
    # the log is the one copy of the numbers: no registry instrument repeats
    # them (the in-memory table keeps its own, paddle_tpu_compile_cache)
    names = {m["name"] for m in obs.default_registry().snapshot()["metrics"]}
    assert "paddle_tpu_compile_cache" in names
    assert not any("persistent_cache" in n or "compile_seconds" in n
                   for n in names), names


def test_export_run_and_obsdump_show_the_set_up(obs_on, tmp_path):
    exe, loss = _step_program("clog_export_w")
    exe.run(feed={"x": np.ones((2, 4), "float32")}, fetch_list=[loss])
    report = obs.export_run(str(tmp_path))
    setup = json.load(open(tmp_path / "report.json"))["setup"]
    assert setup == json.loads(json.dumps(report["setup"]))
    assert len(setup["runs"]) == 2 and setup["records"]
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obsdump.py"),
         str(tmp_path)], capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-500:]
    assert "== set-up ==" in out.stdout
    assert out.stdout.count("first run") == 2
    assert "jit(fn)" in out.stdout
