"""The step's turnaround, split on clocks that cannot disagree.

On a synchronous step the first device's gap between run k and run k + 1 of
the step's module is G = R' + H + L: R' from the device's last operation to
the host learning of it, H the host's path from there to the next jit call,
L from that call to the device's first operation.  G is read on the device's
clock alone (the end of one run to the start of the next on the first
device's `XLA Modules` line).  H is read on the host's clock alone, between
two marks the program makes: the end of `executor.wait` (every fetched value
is ready) and the start of the next step's `executor.dispatch` (the jit
call).  G - H = R' + L is then the runtime's share, launch and completion
notice, exact as a sum, and H splits into contiguous host intervals at the
ends of `executor.step` and `executor.run`:

    wait end -> step end        copy      the copy to the host, span exits
    step end -> run end         release   the executor's frames let go of the
                                          staged and donated arguments
    run end  -> next run start  caller    the caller's loop, not the program's
    run start -> dispatch start entry     prelude, plan, stage

No host timestamp is ever subtracted from a device timestamp, so no skew
between the two clocks can move any of it (a split that intersects host
spans with device intervals, as the five `gap_*_ms.train` did until PR 42,
is good only to the skew).  The one number here that does compare the clocks
measures just that: the shift the device's timestamps would need for every
run of the module to begin after its dispatch began and end before its wait
returned.

Steps are known by `executor.step`'s `seq`, a boundary is two steps with
consecutive numbers whose `executor.run` spans lie wholly inside
`bench.window` (so the partial gaps at the window's two ends are left out),
and every value is a mean over the boundaries.  A trace of a program without
`executor.wait` or `executor.run` (the parent of the PR that added them; a
step with `return_numpy=False`) gives None everywhere.

    python3 benchmark/harness/turnaround.py [<logdir>]

prints the split of the newest trace under <logdir> (default
bench_out/trace) as one JSON object, with G, the room hi - lo that the
causality bounds leave (how finely any host/device intersection can be
trusted in this trace) and the host durations of plan, stage and dispatch.
"""

from __future__ import annotations

import os

if __package__ in (None, ""):  # run as a file: find the sibling modules
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.harness import step_spans, trace
else:
    from . import step_spans, trace

RUN, STEP = "executor.run", "executor.step"
DISPATCH, WAIT = "executor.dispatch", "executor.wait"
# the host parts of a boundary, in the order the host goes through them
PARTS = ("copy", "release", "caller", "entry")
# the phases whose own host durations the detail prints, a step
PHASES = ("executor.plan", "executor.stage", "executor.dispatch")
MODULES_LINE = "XLA Modules"


def host_steps(spans, t0: float, t1: float) -> list:
    """The steps whose `executor.run` lies wholly inside [t0, t1], by start:
    {seq, run: (s, e), step: (s, e), dispatch: its start, wait: its end,
    phases: {name: duration}}.  A run without a numbered step, a dispatch or
    a wait inside it is left out."""

    def inside(name, s0, e0):
        return [sp for sp in spans
                if sp[0] == name and sp[1] >= s0 and sp[2] <= e0]

    steps = []
    for _, rs, re, _ in inside(RUN, t0, t1):
        inner = inside(STEP, rs, re)
        if not inner:
            continue
        _, ss, se, counts = inner[0]
        dispatch, wait = inside(DISPATCH, ss, se), inside(WAIT, ss, se)
        if not dispatch or not wait or "seq" not in counts:
            continue
        steps.append({
            "seq": counts["seq"], "run": (rs, re), "step": (ss, se),
            "dispatch": dispatch[0][1], "wait": wait[-1][2],
            "phases": {n: sum(e - s for _, s, e, _ in inside(n, ss, se))
                       for n in PHASES}})
    return steps


def module_runs(profile) -> list:
    """[(start, end)] of the runs of the step's module on the first device,
    by start: of the events of its `XLA Modules` line, those of the name
    that took most time."""
    devices = []
    for plane in profile.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            devices.append((int(m.group(1)), [
                (trace.op_name(e.name), float(e.start_ns),
                 float(e.start_ns) + float(e.duration_ns))
                for l in plane.lines if l.name == MODULES_LINE
                for e in l.events]))
    evs = min(devices)[1] if devices else []
    total = {}
    for n, s, e in evs:
        total[n] = total.get(n, 0.0) + (e - s)
    if not total:
        return []
    main = max(total, key=total.get)
    return sorted((s, e) for n, s, e in evs if n == main)


def pair_runs(steps, runs) -> list | None:
    """For each step the index of the module run that overlaps its dispatch
    start to wait end most: a step is a hundred times longer than any skew,
    so the device's clock need not be right for this.  None unless every
    step finds a run of its own, in order."""
    paired = []
    for st in steps:
        best, most = None, 0.0
        for i, (s, e) in enumerate(runs):
            overlap = min(e, st["wait"]) - max(s, st["dispatch"])
            if overlap > most:
                best, most = i, overlap
        if best is None or (paired and best <= paired[-1]):
            return None
        paired.append(best)
    return paired


def _mean(values):
    return sum(values) / len(values)


def reduce(profile) -> dict | None:
    """The split of one trace, ns, or None where it has no `bench.window` or
    no boundary between two numbered steps inside it: boundaries, steps,
    host_ns and its four parts (`copy_ns`, ...), phase_ns {span: its own
    duration a step}; and, where the first device's module runs pair with
    the steps, gap_ns (G), runtime_ns (G - H), lo_ns and hi_ns (the bounds
    causality puts on a shift of the device's timestamps), room_ns (hi -
    lo), shift_ns (0 where 0 lies between them, else the nearer bound,
    signed: positive moves the device later), else None for each."""
    win = step_spans.window(profile)
    if win is None:
        return None
    steps = host_steps(step_spans.executor_spans(profile), *win)
    bounds = [(a, b) for a, b in zip(steps, steps[1:])
              if b["seq"] == a["seq"] + 1]
    if not bounds:
        return None
    parts = {
        "copy": [a["step"][1] - a["wait"] for a, _ in bounds],
        "release": [a["run"][1] - a["step"][1] for a, _ in bounds],
        "caller": [b["run"][0] - a["run"][1] for a, b in bounds],
        "entry": [b["dispatch"] - b["run"][0] for _, b in bounds],
    }
    out = {"boundaries": len(bounds), "steps": len(steps),
           "host_ns": _mean([b["dispatch"] - a["wait"] for a, b in bounds]),
           "phase_ns": {n: _mean([st["phases"][n] for st in steps])
                        for n in PHASES},
           "gap_ns": None, "runtime_ns": None, "lo_ns": None, "hi_ns": None,
           "room_ns": None, "shift_ns": None}
    for name, values in parts.items():
        out[name + "_ns"] = _mean(values)
    runs = module_runs(profile)
    paired = pair_runs(steps, runs)
    if paired is None:
        return out
    for st, i in zip(steps, paired):
        st["module"], st["module_index"] = runs[i], i
    if any(b["module_index"] != a["module_index"] + 1 for a, b in bounds):
        return out  # a run of the module between two steps: not one a step
    out["gap_ns"] = _mean([b["module"][0] - a["module"][1]
                           for a, b in bounds])
    out["runtime_ns"] = out["gap_ns"] - out["host_ns"]
    lo = max(st["dispatch"] - st["module"][0] for st in steps)
    hi = min(st["wait"] - st["module"][1] for st in steps)
    out["lo_ns"], out["hi_ns"], out["room_ns"] = lo, hi, hi - lo
    out["shift_ns"] = 0.0 if lo <= 0.0 <= hi else min(lo, hi, key=abs)
    return out


def newest(root: str | None = None) -> dict | None:
    """The split of the newest trace under bench_out/trace (the harness
    keeps one a cell and has just written this run's)."""
    found = trace.newest_parsed(root)
    if found is None:
        return None
    return found.once("turnaround", lambda p: reduce(p.profile))


def _traced(obs) -> dict | None:
    # no device operation in the trace is a CPU rehearsal: its host times
    # are another backend's, and no reading of the chip's turnaround
    if obs.get("kind") != "train" or not obs.get("trace_steps") \
            or not (obs.get("trace") or {}).get("n_ops"):
        return None
    return newest()


def part_ms(obs, part: str):
    """`host`, `runtime`, or one of PARTS: its mean over the traced window's
    step boundaries, ms.  None without the program's spans, and for
    `runtime` without a device plane."""
    red = _traced(obs)
    if red is None or red[part + "_ns"] is None:
        return None
    return red[part + "_ns"] / 1e6


def clock_skew_us(obs):
    """The size of the shift the device's timestamps need to obey causality
    in the traced window, us: 0 where the trace is consistent as it is,
    which is a floor and not alignment (a shift smaller than the room hi -
    lo leaves reads 0: the detail's lo and hi show it)."""
    red = _traced(obs)
    if red is None or red["shift_ns"] is None:
        return None
    return abs(red["shift_ns"]) / 1e3


if __name__ == "__main__":
    import json
    import sys

    root = sys.argv[1] if len(sys.argv) > 1 else None
    print(json.dumps({"trace": trace.newest_trace(root),
                      "split": newest(root)}))
