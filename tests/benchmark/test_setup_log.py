"""The ten `setup_*.train` readers over benchmark/harness/setup_log.py: what
`setup_s` is made of, read from the program's own set-up log
(paddle_tpu/observability/compiles.py) and cut at the window's start."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmark.harness import manifest, setup_log

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NO_CACHE = ["setup_import_s.train", "setup_startup_s.train",
            "setup_first_step_s.train", "setup_trace_lower_s.train",
            "setup_compile_s.train", "setup_other_compile_s.train"]
CACHE = ["setup_cache_load_s.train", "setup_cache_misses.train",
         "setup_cache_entries_mb.train", "setup_cache_evicted_mb.train"]
PIECES = ["setup_import_s.train", "setup_startup_s.train",
          "setup_first_step_s.train", "setup_other_compile_s.train"]
CELLS = ["transformer-train", "ouro-train-loop4"]


def _reader(name):
    return manifest.load_py(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"))


def test_the_manifest_names_the_ten_under_setup_s_in_every_cell():
    mine = [m for m in MANIFEST["per_layer"]
            if m["name"] in NO_CACHE + CACHE]
    assert len(mine) == 10
    # the ten training cells this PR found; a later cell appends itself
    cells = {c["name"] for c in MANIFEST["workloads"][:10]}
    for m in mine:
        assert cells <= set(m["workloads"])
        assert (m["moves"], m["source"], m["layer"], m["better"]) == (
            "setup_s", "program_counter", "program to step", "lower")


@pytest.fixture(scope="module")
def rehearsals():
    """{cell: (the traced rehearsal's metrics, its `setup_s`)} through the
    benchmark's own command, so that `__main__.T_START` is there."""
    out = {}
    for cell in CELLS:
        env = {k: v for k, v in os.environ.items()
               if k not in ("XLA_FLAGS", "BENCH_RUN")}
        env.update(JAX_PLATFORMS="cpu")
        run = subprocess.run(
            [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
             "--workload", cell, "--seed", "3000000052", "--seconds", "1",
             "--trace", "1", "--rehearse"],
            capture_output=True, text=True, timeout=600, cwd=REPO, env=env)
        assert run.returncode == 3, run.stdout[-2000:] + run.stderr[-2000:]
        lines = run.stdout.strip().splitlines()
        obs = next(json.loads(ln[len("[bench] "):]) for ln in lines
                   if ln.startswith('[bench] {"kind"'))
        out[cell] = (json.loads(lines[-1])["metrics"], obs["setup_s"])
    return out


@pytest.fixture
def filled(monkeypatch, tmp_path):
    """The program's log filled by hand as a warm run fills it, `T_START`
    on the running `__main__`, and the obs of a window that starts now."""
    from paddle_tpu import observability
    from paddle_tpu.observability import compiles

    log = observability.default_compile_log()
    observability.reset()
    monkeypatch.setattr(compiles, "cache_dir", lambda: str(tmp_path))
    monkeypatch.setattr(sys.modules["__main__"], "T_START",
                        time.perf_counter(), raising=False)

    def executable(fun, trace, lower, backend, hit=True):
        log._on_begin(compiles.TRACE, 0.0, fun_name=fun)
        log._on_duration(compiles.TRACE, trace, fun_name=fun)
        log._on_duration(compiles.LOWER, lower, fun_name=f"jit({fun})")
        log._on_event(compiles.REQUEST)
        if hit:
            log._on_event(compiles.HIT)
            log._on_duration(compiles.RETRIEVAL, backend / 2)
        log._on_duration(compiles.BACKEND, backend, fun_name=f"jit({fun})")

    log.note_imported()
    executable("helper", 0.25, 0.25, 0.5)
    log.open_run("aaaaaaaaaaaa")
    executable("fn", 1.0, 1.0, 2.0)
    log.close_run("serial", 0, 0, 7)
    log.open_run("bbbbbbbbbbbb")
    executable("fn", 4.0, 2.0, 8.0, hit=False)
    log.close_run("serial", 3, 1, 7)
    executable("reference", 0.5, 0.5, 1.0)
    obs = {"kind": "train",
           "setup_s": time.perf_counter() - sys.modules["__main__"].T_START}
    yield obs, log, executable
    observability.reset()


EXPECTED = {"setup_trace_lower_s.train": 8.0, "setup_compile_s.train": 8.0,
            "setup_cache_load_s.train": 1.0, "setup_cache_misses.train": 1,
            "setup_other_compile_s.train": 3.0,
            "setup_cache_entries_mb.train": 0.0,
            "setup_cache_evicted_mb.train": 0.0}


@pytest.mark.parametrize("name", NO_CACHE + CACHE)
def test_reader(name, rehearsals, filled):
    read = _reader(name).read
    assert read({}) is None
    assert read({"kind": "serve", "setup_s": 1.0}) is None
    # a rehearsal's line: the cache is off there
    for cell, (metrics, setup_s) in rehearsals.items():
        if name in CACHE:
            assert name not in metrics, cell
        else:
            assert 0 <= metrics[name]["value"] < setup_s, cell
            assert metrics[name]["unit"] == "s"
    # over the log filled by hand, cache on
    obs, log, executable = filled
    first = read(obs)
    assert first is not None and first >= 0
    if name in EXPECTED:
        assert first == pytest.approx(EXPECTED[name])
    else:
        assert 0 <= first <= obs["setup_s"]
    # what follows the cut is not set-up: the traced window, and the trace
    # events of `loop_bodies_lowered.train`'s second lowering
    from paddle_tpu.observability import compiles

    log._on_begin(compiles.TRACE, 0.0, fun_name="fn")
    log._on_duration(compiles.TRACE, 30.0, fun_name="fn")
    executable("fn", 5.0, 5.0, 5.0, hit=False)
    log.open_run("cccccccccccc")
    executable("fn", 6.0, 6.0, 6.0, hit=False)
    log.close_run("serial", 0, 0, 7)
    assert read(obs) == first


@pytest.mark.parametrize("cell", CELLS)
def test_the_time_pieces_sum_to_less_than_setup_s(cell, rehearsals):
    metrics, setup_s = rehearsals[cell]
    assert set(NO_CACHE) <= set(metrics)
    assert not set(CACHE) & set(metrics)
    pieces = [metrics[n]["value"] for n in PIECES]
    assert all(p > 0 for p in pieces)
    assert sum(pieces) < setup_s
    # the program's executables were built inside its two first runs
    inside = metrics["setup_trace_lower_s.train"]["value"] + \
        metrics["setup_compile_s.train"]["value"]
    assert 0 < inside < metrics["setup_startup_s.train"]["value"] + \
        metrics["setup_first_step_s.train"]["value"]


def test_no_log_no_start_nothing_to_read(filled, monkeypatch):
    obs, log, _ = filled
    assert setup_log.summary(obs) is not None
    # a parent commit's program keeps no log
    monkeypatch.setattr(setup_log, "snapshot", lambda: None)
    assert setup_log.summary(obs) is None
    monkeypatch.undo()
    # and another command than the benchmark's has no T_START
    assert setup_log.summary({"kind": "train", "setup_s": 1.0}) is None
