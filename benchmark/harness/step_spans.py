"""The idle time of the first device, split by the program's own spans.

The executors wrap each phase of a step in a span on the profiler's clock
(`executor.step` over `executor.plan`, `.stage`, `.dispatch`, `.commit`,
`.fetch`; paddle_tpu/core/executor.py::run_step).  Here each idle interval
of the first device inside `bench.window` (the complement of the union of
its operations, as trace.reduce takes it) is cut at the spans' boundaries,
and each piece goes to the innermost `executor.*` span that covers it, by
intersection of intervals.  A span's counts (`moved`, `n`) are the event's
stats, or a `#k=v,k=v#` suffix of its name where the profiler left them
there.

A trace of a program without these spans (the parent of the PR that added
them) gives None everywhere: the readers then report nothing.

    python3 benchmark/harness/step_spans.py [<logdir>]

prints the split and the clock check of the newest trace under <logdir>
(default bench_out/trace) as one JSON object.
"""

from __future__ import annotations

import bisect
import glob
import os

if __package__ in (None, ""):  # run as a file: find the sibling module
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.harness import trace
else:
    from . import trace

PREFIX = "executor."
STEP = "executor.step"
# the phases a gap metric is named after; the rest is unattributed
PHASES = ("plan", "stage", "dispatch", "fetch")
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "bench_out", "trace")
MODULES_LINE = "XLA Modules"


def span_name(raw: str) -> str:
    """What precedes any `#...#` the profiler appends for a span's counts."""
    return raw.split("#", 1)[0]


def span_counts(event) -> dict:
    """A span's counts: the event's stats, and `k=v` pairs between `#`s in
    its name; whole numbers as int, the rest as they are."""
    out = {}
    raw = event.name
    if "#" in raw:
        for pair in raw.split("#")[1].split(","):
            k, eq, v = pair.partition("=")
            if eq:
                out[k] = v
    for k, v in getattr(event, "stats", None) or ():
        out[k] = v
    for k, v in out.items():
        try:
            out[k] = int(v)
        except (TypeError, ValueError):
            pass
    return out


def executor_spans(profile) -> list:
    """The program's spans on the host planes, sorted by start:
    [(name, start_ns, end_ns, counts)]."""
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    s = float(e.start_ns)
                    spans.append((span_name(e.name), s,
                                  s + float(e.duration_ns), span_counts(e)))
    spans.sort(key=lambda sp: sp[1])
    return spans


def window(profile):
    """(start_ns, end_ns) of the benchmark's `bench.window`, or None."""
    outer = [(s, e) for n, s, e in trace.host_spans(profile)
             if n == "bench.window"]
    if not outer:
        return None
    return min(s for s, _ in outer), max(e for _, e in outer)


def first_device_ops(profile, t0: float, t1: float) -> list:
    """The first device's operations inside the window, clipped to it."""
    ops = {d: evs for d, evs in trace.device_ops(profile).items() if evs}
    return trace._clip(ops[min(ops)], t0, t1) if ops else []


def idle_intervals(ops, t0: float, t1: float) -> list:
    """[(start, end)] of the window in which no operation ran."""
    idle, edge = [], t0
    for s, e in trace.union((s, e) for _, s, e in ops):
        if s > edge:
            idle.append((edge, s))
        edge = max(edge, e)
    if t1 > edge:
        idle.append((edge, t1))
    return idle


def innermost_segments(spans) -> tuple:
    """The timeline cut at every span boundary: (cuts, names), piece i is
    [cuts[i], cuts[i + 1]) and names[i] the innermost (shortest) span that
    covers it, None where none does."""
    cuts = sorted({t for _, s, e, _ in spans for t in (s, e)})
    names = []
    for a, b in zip(cuts, cuts[1:]):
        best = None
        for name, s, e, _ in spans:
            if s <= a and b <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        names.append(best[0] if best else None)
    return cuts, names


def split_idle(idle, spans) -> dict:
    """{span name: idle ns under it as the innermost span} over `idle`."""
    cuts, names = innermost_segments(spans)
    out = {}
    for s, e in idle:
        i = max(bisect.bisect_right(cuts, s) - 1, 0)
        while i < len(names) and cuts[i] < e:
            piece = min(e, cuts[i + 1]) - max(s, cuts[i])
            if piece > 0 and names[i] is not None:
                out[names[i]] = out.get(names[i], 0.0) + piece
            i += 1
    return out


def clock_check(profile, spans, t0: float, t1: float) -> dict:
    """Host and device clocks against causality, over the steps inside the
    window: the k-th `executor.step` against the k-th run of the step's
    module on the first device (its `XLA Modules` line).  A dispatch that
    begins after its module began, or a fetch that ends before its module
    ended, is clock skew, and bounds how finely the split can be trusted."""
    runs = []
    for plane in profile.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        evs = [(trace.op_name(e.name), float(e.start_ns),
                float(e.start_ns) + float(e.duration_ns))
               for l in plane.lines if l.name == MODULES_LINE
               for e in l.events]
        runs.append((int(m.group(1)), evs))
    evs = [ev for ev in (min(runs)[1] if runs else [])
           if ev[1] >= t0 and ev[2] <= t1]
    by_name = {}
    for n, s, e in evs:
        by_name[n] = by_name.get(n, 0.0) + (e - s)
    if not by_name:
        return {"steps_paired": 0}
    main = max(by_name, key=by_name.get)
    modules = sorted((s, e) for n, s, e in evs if n == main)
    steps = [(s, e) for n, s, e, _ in spans
             if n == STEP and s >= t0 and e <= t1]

    def inside(name, s0, e0):
        return [(s, e) for n, s, e, _ in spans
                if n == name and s >= s0 and e <= e0]

    late_dispatch = early_fetch = 0
    lead, lag = [], []
    for (s0, e0), (ms, me) in zip(steps, modules):
        for s, _ in inside("executor.dispatch", s0, e0)[:1]:
            late_dispatch += s > ms
            lead.append(ms - s)
        for _, e in inside("executor.fetch", s0, e0)[-1:]:
            early_fetch += e < me
            lag.append(e - me)
    out = {"steps": len(steps), "module_runs": len(modules),
           "steps_paired": min(len(steps), len(modules)),
           "dispatch_after_first_op": late_dispatch,
           "fetch_before_last_op": early_fetch}
    if lead and lag:
        # how far before its module a dispatch begins, how long after its
        # module's end a fetch returns: the smallest of each is the margin
        out["min_dispatch_lead_us"] = min(lead) / 1e3
        out["min_fetch_lag_us"] = min(lag) / 1e3
    return out


def reduce(profile) -> dict | None:
    """The split of one trace, or None where it has no `bench.window` or no
    `executor.step` inside it:
    idle_ns (None without a device plane), by_span {name: idle ns under it},
    host_ns {name: the spans' own durations, which no clock skew touches},
    moved (sum of `moved` over the window's `executor.stage` spans), steps,
    clock."""
    win = window(profile)
    if win is None:
        return None
    t0, t1 = win
    spans = [sp for sp in executor_spans(profile)
             if sp[2] > t0 and sp[1] < t1]
    if not any(n == STEP for n, _, _, _ in spans):
        return None
    host = {}
    for n, s, e, _ in spans:
        host[n] = host.get(n, 0.0) + (e - s)
    out = {"window_ns": t1 - t0, "idle_ns": None, "by_span": {},
           "host_ns": host,
           "steps": sum(n == STEP for n, _, _, _ in spans),
           "moved": sum(c.get("moved", 0) for n, _, _, c in spans
                        if n == "executor.stage")}
    ops = first_device_ops(profile, t0, t1)
    if ops:
        idle = idle_intervals(ops, t0, t1)
        out["idle_ns"] = sum(e - s for s, e in idle)
        out["by_span"] = split_idle(idle, spans)
        out["clock"] = clock_check(profile, spans, t0, t1)
    return out


def newest_trace(root: str | None = None):
    found = glob.glob(os.path.join(root or TRACE_ROOT, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


_parsed = {}  # {(path, mtime): the split}: one parse a process


def newest(root: str | None = None) -> dict | None:
    """The split of the newest trace under bench_out/trace: the harness
    keeps one a cell and has just written this run's."""
    path = newest_trace(root)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _parsed:
        from jax.profiler import ProfileData

        _parsed.clear()
        _parsed[key] = reduce(ProfileData.from_file(path))
    return _parsed[key]


def _traced(obs) -> dict | None:
    if obs.get("kind") != "train" or not obs.get("trace_steps") \
            or obs.get("trace") is None:
        return None
    return newest()


def gap_ms(obs, phase: str | None):
    """Idle ms of the first device per traced step under `executor.<phase>`;
    for phase None, all its idle time less the named phases'.  None without
    a device plane or without the program's spans."""
    red = _traced(obs)
    if red is None or red["idle_ns"] is None:
        return None
    named = {p: red["by_span"].get(PREFIX + p, 0.0) for p in PHASES}
    ns = red["idle_ns"] - sum(named.values()) if phase is None \
        else named[phase]
    return ns / 1e6 / obs["trace_steps"]


def values_moved(obs):
    """`moved` summed over the window's `executor.stage` spans, per traced
    step: from the host plane alone, so a CPU rehearsal reports it too."""
    red = _traced(obs)
    if red is None:
        return None
    return red["moved"] / obs["trace_steps"]


if __name__ == "__main__":
    import json
    import sys

    root = sys.argv[1] if len(sys.argv) > 1 else None
    print(json.dumps({"trace": newest_trace(root), "split": newest(root)}))
