"""The five `step_ms_p50` / `stall*.train` readers over
benchmark/harness/step_log.py: what the measured window's steps were made
of, read from the program's own always-on step log
(paddle_tpu/observability/stepstats.py) and cut to the window."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmark.harness import manifest, step_log

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIVE = ["step_ms_p50.train", "stalled_steps.train", "stall_share.train",
        "stall_wait_share.train", "stall_asleep_share.train"]
FIELDS = ["seq", "kind", "fresh", "t_start", "t_dispatch", "t_dispatched",
          "t_fetch", "t_ready", "t_end", "cpu_fetch", "cpu_ready"]
T_START, SETUP_S, WINDOW_S = 1000.0, 10.0, 1.0
CUT = T_START + SETUP_S
OBS = {"kind": "train", "setup_s": SETUP_S, "window_s": WINDOW_S, "steps": 8}


def _reader(name):
    return manifest.load_py(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"))


def _record(seq, start, wait=0.09, cpu=0.002, ready=True):
    """A step as the program logs it: 1 ms each of plan, dispatch, commit
    and copy around a wait of `wait` s with the process on a CPU for `cpu`
    of it."""
    fetch = start + 0.003
    return [seq, 0.0, 0.0, start, start + 0.001, start + 0.002, fetch,
            fetch + wait if ready else None, fetch + wait + 0.001,
            5.0 + seq, 5.0 + seq + cpu]


def _snapshot(stalled=None, dropped=0, **at_3):
    """Two warm steps before the cut, eight in the window (the fourth three
    periods long unless `stalled` says how it waited), three traced ones
    after it."""
    offsets = [0.0, 0.1, 0.2, 0.3, 0.6, 0.7, 0.8, 0.9]
    rows = [_record(0, CUT - 0.25), _record(1, CUT - 0.15)]
    for i, off in enumerate(offsets):
        how = (stalled or {}) if i == 3 else {}
        rows.append(_record(2 + i, CUT + off, **how, **(at_3 if i == 3
                                                        else {})))
    rows += [_record(10 + i, CUT + WINDOW_S + 0.5 + 0.1 * i)
             for i in range(3)]
    return {"fields": FIELDS, "kinds": ["serial", "spmd"],
            "count": len(rows) + dropped, "dropped": dropped, "stalls": [],
            "records": rows}


@pytest.fixture
def in_a_run(monkeypatch):
    """`T_START` on the running `__main__` and a program whose log is the
    snapshot handed to `log(...)`; the detail goes nowhere."""
    state = {}
    monkeypatch.setattr(sys.modules["__main__"], "T_START", T_START,
                        raising=False)
    monkeypatch.setattr(step_log, "snapshot", lambda: state.get("snap"))
    monkeypatch.setattr(step_log, "DETAIL", os.devnull)
    monkeypatch.setattr(step_log, "_last", [None, None])

    def log(snap):
        state["snap"] = snap
        return dict(OBS)  # a fresh obs: a fresh reading
    return log


def test_the_manifest_names_the_five_under_the_rate_in_every_training_cell(
        manifest_holds):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        man = json.load(f)
    (rate,) = [m for m in man["end_to_end"]
               if m["name"] == "train_samples_per_s"]
    # the fourteen training cells this PR found; a later cell appends itself
    manifest_holds("per_layer", FIVE, cells=rate["workloads"][:14],
                   moves="train_samples_per_s", source="program_counter",
                   layer="program to step", better="lower")


# the fourth step waits 0.15 s longer, the process on a CPU for 0.05 s of
# that, and its caller takes the other 0.05 s of its 0.2 s over the median
BY_HAND = {"step_ms_p50.train": 100.0, "stalled_steps.train": 1,
           "stall_share.train": 20.0, "stall_wait_share.train": 15.0,
           "stall_asleep_share.train": 10.0}


@pytest.mark.parametrize("name", FIVE)
def test_reader(name, in_a_run):
    read = _reader(name).read
    assert read({}) is None
    assert read({"kind": "serve", "setup_s": 1.0, "window_s": 1.0,
                 "steps": 3}) is None
    obs = in_a_run(_snapshot(stalled={"wait": 0.24, "cpu": 0.052}))
    assert read(obs) == pytest.approx(BY_HAND[name])
    assert read(obs) == pytest.approx(BY_HAND[name])  # and asked again
    # no step stalled: a time and four zeros, never None
    calm = _snapshot()
    for row, off in zip(calm["records"][5:10], (0.3, 0.4, 0.5, 0.6, 0.7)):
        row[:] = _record(row[0], CUT + off)
    calm["records"][10:] = [_record(20 + i, CUT + 0.8 + 0.1 * i)
                            for i in range(2)] + calm["records"][10:]
    obs = in_a_run(calm)
    obs["steps"] = 10
    assert read(obs) == pytest.approx(
        100.0 if name == "step_ms_p50.train" else 0.0)


@pytest.mark.parametrize("how, wait_share, asleep_share", [
    ({"wait": 0.29, "cpu": 0.002}, 20.0, 20.0),   # the process slept
    ({"wait": 0.29, "cpu": 0.202}, 20.0, 0.0),    # a thread was busy
    ({"wait": 0.29, "cpu": 0.9}, 20.0, 0.0),      # several were
    ({"wait": 0.09, "cpu": 0.002}, 0.0, 0.0),     # the caller took it
    ({"wait": 0.5, "cpu": 0.002}, 20.0, 20.0),    # no more than the excess
])
def test_the_three_shares_are_ordered(how, wait_share, asleep_share,
                                      in_a_run):
    got = step_log.summary(in_a_run(_snapshot(stalled=how)))
    assert got["stalled_steps"] == 1
    assert got["stall_share"] == pytest.approx(20.0)
    assert got["stall_wait_share"] == pytest.approx(wait_share)
    assert got["stall_asleep_share"] == pytest.approx(asleep_share)
    assert got["stall_asleep_share"] <= got["stall_wait_share"] \
        <= got["stall_share"]


def test_the_cut_and_the_count(in_a_run):
    snap = _snapshot()
    found = step_log.detail(snap, CUT, WINDOW_S, 8)
    # the warm steps and the traced ones are outside; the periods add up to
    # the window, the last closed by its end
    assert [s["seq"] for s in found["steps"]] == list(range(2, 10))
    assert sum(s["period_ms"] for s in found["steps"]) == pytest.approx(
        WINDOW_S * 1e3)
    (slow,) = [s for s in found["steps"] if s["stalled"]]
    assert slow["seq"] == 5 and slow["period_ms"] == pytest.approx(300.0)
    assert slow["caller_ms"] == pytest.approx(300.0 - 94.0)
    assert step_log.summary(in_a_run(snap)) == found["summary"]
    # the harness counted another number of steps than the log holds
    for steps in (7, 9):
        obs = in_a_run(snap)
        obs["steps"] = steps
        assert step_log.summary(obs) is None
    # a step of the window did not wait (`return_numpy=False`)
    assert step_log.summary(in_a_run(_snapshot(ready=False))) is None
    # the ring dropped records and its oldest starts inside the window
    late = _snapshot(dropped=3)
    late["records"] = late["records"][2:]
    assert step_log.summary(in_a_run(late)) is None
    # dropped long before the window: nothing of it is missing
    assert step_log.summary(in_a_run(_snapshot(dropped=3))) is not None


def test_no_log_no_start_nothing_to_read(in_a_run, monkeypatch):
    assert step_log.summary(in_a_run(_snapshot())) is not None
    # a parent commit's program keeps no such log
    assert step_log.summary(in_a_run(None)) is None
    # and another command than the benchmark's has no T_START
    monkeypatch.delattr(sys.modules["__main__"], "T_START")
    assert step_log.summary(in_a_run(_snapshot())) is None


def test_it_reads_the_programs_own_log_and_its_report(tmp_path, monkeypatch):
    """The real log of a toy program, as a run leaves it: every step of the
    window is there under the harness's count, and the detail's command
    prints the window from the file a reader left and from an `export_run`
    report."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import layers, observability

    x = layers.data("x", [4], dtype="float32")
    loss = layers.reduce_mean(layers.fc(x, size=2))
    fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"x": np.ones((2, 4), "float32")}
    t_start = time.perf_counter()
    monkeypatch.setattr(sys.modules["__main__"], "T_START", t_start,
                        raising=False)
    monkeypatch.setattr(step_log, "DETAIL", str(tmp_path / "step_log.json"))
    monkeypatch.setattr(step_log, "_last", [None, None])
    observability.reset()
    exe.run(feed=feed, fetch_list=[loss])  # warm-up: before the cut
    t_window = time.perf_counter()
    for _ in range(12):
        exe.run(feed=feed, fetch_list=[loss])
    window_s = time.perf_counter() - t_window
    exe.run(feed=feed, fetch_list=[loss])  # a traced step: after it
    obs = {"kind": "train", "setup_s": t_window - t_start,
           "window_s": window_s, "steps": 12}
    got = step_log.summary(obs)
    assert set(got) == {n[:-len(".train")] for n in FIVE}
    assert 0 < got["step_ms_p50"] < window_s * 1e3
    assert got["stall_asleep_share"] <= got["stall_wait_share"] \
        <= got["stall_share"]
    for name in FIVE:
        assert _reader(name).read(obs) == got[name[:-len(".train")]]
    report = observability.export_run(str(tmp_path / "run"))
    observability.reset()
    whole = step_log.of_report(report)
    assert len(whole["steps"]) == 14 and step_log.of_report({}) is None
    for path, steps in ((step_log.DETAIL, 12),
                        (str(tmp_path / "run" / "report.json"), 14)):
        out = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "benchmark", "harness", "step_log.py"), path],
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr[-500:]
        head, *lines = [json.loads(ln) for ln in out.stdout.splitlines()]
        assert head["from"] == path and len(lines) == steps
        # the periods add up to the window, less the caller's microseconds
        # before the first start
        assert sum(ln["period_ms"] for ln in lines) == pytest.approx(
            head["window_s"] * 1e3, abs=0.5)
