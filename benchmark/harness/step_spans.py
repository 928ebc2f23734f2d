"""The program's own spans on the host plane, and what they count.

The executors wrap each phase of a step in a span on the profiler's clock
(`executor.run` over `executor.step` over `executor.plan`, `.stage`,
`.dispatch`, `.commit`, `.fetch`, the last over `executor.wait` and
`executor.copy`; paddle_tpu/core/executor.py::run_step).  Here are the spans
of a trace with their counts (a span's counts, `moved`, `n`, `seq`, are the
event's stats, or a `#k=v,k=v#` suffix of its name where the profiler left
them there), the benchmark's window, and what the window's spans sum to: the
spans' own host durations, the steps, and the values a step had to place
(`values_moved_per_step.train`).  Nothing here compares a host timestamp
with a device's: the device's gap between two steps is split in
turnaround.py, on one clock at a time, and trace.reduce names each idle gap
by the innermost span over it.

A trace of a program without these spans gives None: the reader then
reports nothing.

    python3 benchmark/harness/step_spans.py [<logdir>]

prints the spans' sums of the newest trace under <logdir> (default
bench_out/trace) as one JSON object.
"""

from __future__ import annotations

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
span_name = trace.span_name
window = trace.window


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


def reduce(profile) -> dict | None:
    """The sums of one trace, or None where it has no `bench.window` or no
    `executor.step` inside it: window_ns, host_ns {name: the spans' own
    durations, on the host's clock alone}, steps, and moved (sum of `moved`
    over the window's `executor.stage` spans)."""
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
    return {"window_ns": t1 - t0, "host_ns": host,
            "steps": sum(n == STEP for n, _, _, _ in spans),
            "moved": sum(c.get("moved", 0) for n, _, _, c in spans
                         if n == "executor.stage")}


def newest(root: str | None = None) -> dict | None:
    """The sums of the newest trace under bench_out/trace."""
    found = trace.newest_parsed(root)
    if found is None:
        return None
    return found.once("step_spans", lambda p: reduce(p.profile))


def _traced(obs) -> dict | None:
    if obs.get("kind") != "train" or not obs.get("trace_steps") \
            or obs.get("trace") is None:
        return None
    return newest()


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
    print(json.dumps({"trace": trace.newest_trace(root),
                      "spans": newest(root)}))
