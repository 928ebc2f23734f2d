"""What the measured window's steps were made of, one by one, from the
program's own step log (paddle_tpu/observability/stepstats.py: one record a
step of either executor, flag or no flag, on `time.perf_counter()`, the
clock of `benchmark/run.py::T_START` and of `obs["setup_s"]`; the
process's CPU time, `time.process_time()`, read where the wait begins and
where it ends).  The untraced 20 s that `train_samples_per_s` is made of
print no step's time; this reads them afterwards, in the same process.

The cut, as harness/setup_log.py makes it: the measured window is
[`T_START + obs["setup_s"]`, `+ obs["window_s"]`], with `T_START` read from
the running `__main__` (the benchmark's one command).  The records that
start inside it have to number `obs["steps"]`, each with every mark;
otherwise every reader returns None (the log and the harness disagree, and
no number is better than a wrong one).  None too for `read({})`, for a
parent whose program keeps no such log, and where the log dropped records
that the window held.

A step's PERIOD is the next step's start less its own, the last one closed
by the window's end: the periods add up to the window (less the
microseconds before the first start), and the caller's time between two
calls is in them.  With m the window's median period, a step has STALLED
where its period is over m by more than max(5 ms, 5% of m).  Five readers
in layer_metrics/, one key of `summary` each:

    step_ms_p50        m, ms: the step as the rate would read it had no
                       step stalled (batch / m is the rate a stall cannot
                       move)
    stalled_steps      how many stalled
    stall_share        the stalled steps' periods less m, summed, over
                       window_s, %: what the stalls cost the rate
    stall_wait_share   the part of that excess inside the wait (fetch start
                       to ready: a stalled step's wait less the window's
                       median wait, at most its excess), %.  Equal to
                       stall_share: the host was waiting to hear of the
                       step's end; smaller: the rest was plan, dispatch,
                       commit, the copy or the caller (the detail below
                       says which)
    stall_asleep_share the part of the wait's excess during which the
                       process was on no CPU (wall excess less the excess
                       of process_time over its median, floored at 0), %.
                       Equal to stall_wait_share: the whole process slept,
                       so what it waited for is outside it; well under it:
                       a thread of the process was busy

so stall_asleep_share <= stall_wait_share <= stall_share on every line, and
all five read 0 or a time, never None, on a sound run.

    python3 benchmark/harness/step_log.py [<report.json>]

prints the last run's window (a reader leaves it in bench_out/step_log.json,
which the harness keeps out of git) or, given an `export_run` report, every
record of its `steps` section as one window: a first JSON line with the
summary, then a line a step with its period, its phases and the process's
CPU time inside the wait, ms, stalled steps marked.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DETAIL = os.path.join(ROOT, "bench_out", "step_log.json")
MARKS = ("t_start", "t_dispatch", "t_dispatched", "t_fetch", "t_ready",
         "t_end", "cpu_fetch", "cpu_ready")


def snapshot():
    """The program's step log as plain values, None where the program has
    none (a parent commit from before it)."""
    try:
        from paddle_tpu.observability import step_stats

        return step_stats().snapshot()
    except (ImportError, AttributeError):
        return None


def detail(snap, cut: float, window_s: float, steps: int):
    """The window [cut, cut + window_s] of one snapshot: {"summary": the
    five readings, "steps": a dict a step, ms}; None where the records
    inside it do not number `steps`, lack a mark, or were dropped."""
    names = snap["fields"]
    rows = [dict(zip(names, r)) for r in snap["records"]]
    end = cut + window_s
    if snap["dropped"] and (not rows or rows[0]["t_start"] >= cut):
        return None  # what rotated out may have been the window's
    inside = [r for r in rows if cut <= r["t_start"] <= end]
    if not inside or len(inside) != steps or any(
            r[k] is None for r in inside for k in MARKS):
        return None
    starts = [r["t_start"] for r in inside] + [end]
    periods = [b - a for a, b in zip(starts, starts[1:])]
    waits = [r["t_ready"] - r["t_fetch"] for r in inside]
    cpus = [r["cpu_ready"] - r["cpu_fetch"] for r in inside]
    median = statistics.median(periods)
    limit = median + max(0.005, 0.05 * median)
    wait_median, cpu_median = statistics.median(waits), statistics.median(cpus)
    excess = in_wait = asleep = 0.0
    out = []
    for r, period, wait, cpu in zip(inside, periods, waits, cpus):
        stalled = period > limit
        if stalled:
            over = period - median
            waited = min(max(0.0, wait - wait_median), over)
            excess += over
            in_wait += waited
            asleep += max(0.0, waited - max(0.0, cpu - cpu_median))
        ms = {"plan": r["t_dispatch"] - r["t_start"],
              "dispatch": r["t_dispatched"] - r["t_dispatch"],
              "commit": r["t_fetch"] - r["t_dispatched"],
              "wait": wait, "wait_cpu": cpu,
              "copy": r["t_end"] - r["t_ready"],
              "caller": period - (r["t_end"] - r["t_start"]),
              "period": period}
        out.append({"seq": int(r["seq"]), "stalled": stalled,
                    **{k + "_ms": v * 1e3 for k, v in ms.items()}})
    return {
        "cut": cut, "window_s": window_s,
        "summary": {"step_ms_p50": median * 1e3,
                    "stalled_steps": sum(s["stalled"] for s in out),
                    "stall_share": 100.0 * excess / window_s,
                    "stall_wait_share": 100.0 * in_wait / window_s,
                    "stall_asleep_share": 100.0 * asleep / window_s},
        "steps": out}


# the last window read: five readers ask for one obs, one after the other
_last = [None, None]


def summary(obs, snap=None, t_start=None):
    """{key: reading} of the five metrics from one snapshot of the log, cut
    to the measured window; None where there is nothing to read.  In a run
    (`T_START` on the running `__main__`) the window's detail goes to
    bench_out/step_log.json for this file's `__main__`."""
    if obs.get("kind") != "train" or not obs.get("steps") \
            or obs.get("setup_s") is None or not obs.get("window_s"):
        return None
    if _last[0] is obs and snap is None and t_start is None:
        return _last[1]
    in_a_run = t_start is None and snap is None
    if t_start is None:
        t_start = getattr(sys.modules.get("__main__"), "T_START", None)
    if snap is None:
        snap = snapshot()
    if t_start is None or snap is None:
        return None
    found = detail(snap, t_start + obs["setup_s"], obs["window_s"],
                   obs["steps"])
    out = None if found is None else found["summary"]
    if in_a_run:
        _last[:] = [obs, out]
        if found is not None:
            try:
                os.makedirs(os.path.dirname(DETAIL), exist_ok=True)
                with open(DETAIL, "w") as f:
                    json.dump(found, f)
            except OSError:
                pass  # the detail is a convenience, the readings are not
    return out


def reading(obs, key):
    """One reader's number."""
    s = summary(obs)
    return None if s is None else s.get(key)


def of_report(report):
    """Every complete record of an `export_run` report's `steps` section
    as one window, first start to last end; None without the section."""
    snap = (report or {}).get("steps")
    if not snap or not snap.get("records"):
        return None
    at = {n: i for i, n in enumerate(snap["fields"])}
    whole = [r for r in snap["records"]
             if all(r[at[k]] is not None for k in MARKS)]
    if not whole:
        return None
    cut = whole[0][at["t_start"]]
    return detail({**snap, "records": whole, "dropped": 0}, cut,
                  whole[-1][at["t_end"]] - cut, len(whole))


if __name__ == "__main__":
    path = sys.argv[1] if len(sys.argv) > 1 else DETAIL
    with open(path) as f:
        doc = json.load(f)
    found = doc if "summary" in doc else of_report(doc)
    if found is None:
        sys.exit(f"{path}: no step log to read")
    print(json.dumps({"from": path, "cut": found["cut"],
                      "window_s": found["window_s"], **found["summary"]}))
    for step in found["steps"]:
        print(json.dumps(step))
