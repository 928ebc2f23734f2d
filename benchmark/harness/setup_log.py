"""What set-up was made of, from the program's own set-up log
(paddle_tpu/observability/compiles.py: one record an executable from jax's
compile events, one first run a program the executor had not met, both on
`time.perf_counter()`, the clock of `benchmark/run.py::T_START` and of
`obs["setup_s"]`).

The cut: the measured window starts at `T_START + obs["setup_s"]`, with
`T_START` read from the running `__main__` (the benchmark's one command).
Nothing after it is set-up: the traced window, and what the readers
themselves trace and compile (`loop_bodies_lowered.train` lowers the step
once more).  Before the cut the program's executables are those of two
first runs, the start-up program's (the first that takes no feed and
fetches nothing) and the step program's (the first that fetches); the
records inside no first run are the benchmark's own: the plain reference
and the harness's helpers.

Ten readers in layer_metrics/ (`setup_*.train`), one key of `summary` each;
None where the parent's program keeps no such log, where there is no
`T_START`, or, for the four `cache_*` keys, where the persistent cache is
off (a rehearsal turns it off)."""

from __future__ import annotations

import sys

MB = 1e6


def snapshot():
    """The program's set-up log as plain values, None where the program has
    none (a parent commit from before it)."""
    try:
        from paddle_tpu.observability import default_compile_log
    except ImportError:
        return None
    return default_compile_log().snapshot()


def summary(obs, snap=None, t_start=None):
    """{key: reading} of the ten metrics from one snapshot of the log, cut
    at the window's start; None where there is nothing to read."""
    if obs.get("kind") != "train" or obs.get("setup_s") is None:
        return None
    if t_start is None:
        t_start = getattr(sys.modules.get("__main__"), "T_START", None)
    if snap is None:
        snap = snapshot()
    if t_start is None or snap is None:
        return None
    cut = t_start + obs["setup_s"]
    runs = [r for r in snap["runs"] if r["t1"] <= cut]
    startup = next((r for r in runs
                    if r["n_feed"] == 0 and r["n_fetch"] == 0), None)
    step = next((r for r in runs if r["n_fetch"] > 0), None)
    if startup is None or step is None or snap["imported_at"] is None:
        return None
    records = [r for r in snap["records"] if r["t_end"] <= cut]
    mine = [r for r in records
            if r["run"] in (startup["index"], step["index"])]
    other = [r for r in records if r["run"] is None]
    out = {
        "import_s": snap["imported_at"] - t_start,
        "startup_s": startup["t1"] - startup["t0"],
        "first_step_s": step["t1"] - step["t0"],
        "trace_lower_s": sum(r["trace_s"] + r["lower_s"] for r in mine),
        "compile_s": sum(r["backend_s"] for r in mine
                         if r["cache"] != "hit"),
        "other_compile_s": sum(r["trace_s"] + r["lower_s"] + r["backend_s"]
                               for r in other),
    }
    if snap["cache_dir"]:
        out.update(
            cache_load_s=sum(r["retrieval_s"] or 0.0 for r in mine
                             if r["cache"] == "hit"),
            cache_misses=sum(r["cache"] == "miss" for r in mine),
            cache_entries_mb=sum(r["entry_bytes"] or 0
                                 for r in records) / MB,
            cache_evicted_mb=sum(r["evicted_bytes"] or 0
                                 for r in records) / MB)
    return out


def reading(obs, key):
    """One reader's number."""
    s = summary(obs)
    return None if s is None else s.get(key)

