"""From a profiler trace (jax.profiler.ProfileData) to numbers: the union of
the intervals in which an operation ran on each device, the idle gaps and
which host span covered them (the benchmark's own `bench.*` and, inside
those, the executors' `executor.*` phases), the operations that took most
time, and the time in all-reduce operations.

Works on anything shaped like ProfileData: `.planes`, each with `.name` and
`.lines`, each with `.name` and `.events`, each with `.name`, `.start_ns`
and `.duration_ns`.

A traced run's `.xplane.pb` is found and parsed here, once a process
(`newest_parsed`); step_spans, turnaround and scope_time read from that."""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# lines of a device plane that repeat the operations at a coarser grain
COARSE_LINES = ("XLA Modules", "Steps", "XLA TraceMe", "Framework Ops",
                "Framework Name Scope", "Source code")
# the host annotations kept: the benchmark's own, and the program's phases of
# a step (paddle_tpu/core/executor.py::run_step), which name an idle gap
SPAN_PREFIXES = ("bench.", "executor.")
WINDOW = "bench.window"
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "bench_out", "trace")
COLLECTIVE = re.compile(r"all-reduce|all_reduce|AllReduce", re.I)


def newest_trace(root: str | None = None) -> str | None:
    """The newest .xplane.pb anywhere under `root` (default bench_out/trace,
    where the harness keeps one a cell and has just written this run's)."""
    found = glob.glob(os.path.join(root or TRACE_ROOT, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


class Parsed:
    """One .xplane.pb: its bytes (scope_time reads the operations' metadata
    from them), the ProfileData built from them, and what each module has
    reduced from either, each made once (`once`)."""

    def __init__(self, raw: bytes, profile):
        self.raw, self.profile = raw, profile
        self._made = {}

    def once(self, what: str, make):
        if what not in self._made:
            self._made[what] = make(self)
        return self._made[what]


_parsed = {}  # {(path, mtime): Parsed}: one read and one parse a process


def newest_parsed(root: str | None = None) -> Parsed | None:
    """The newest trace under `root`, read and parsed once a process and
    file (a file written anew has another mtime); None where none is."""
    path = newest_trace(root)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _parsed:
        from jax.profiler import ProfileData

        with open(path, "rb") as f:
            raw = f.read()
        _parsed.clear()
        _parsed[key] = Parsed(raw, ProfileData.from_serialized_xspace(raw))
    return _parsed[key]


def start(logdir: str) -> None:
    """Start the profiler with its Python tracer off: that tracer records
    every Python call (900 k events in 3 s of a training cell) and slows the
    host it is meant to watch.  TraceAnnotations still land on the host
    plane."""
    import shutil

    import jax

    shutil.rmtree(logdir, ignore_errors=True)  # one trace a cell is kept
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=options)


def op_name(raw: str) -> str:
    """The operation's own name from what the trace calls it: on the v5e an
    event is named by its whole HLO line, `%fusion.12 = (...) fusion(...)`."""
    return raw.split(" = ", 1)[0].lstrip("%")[:80]


def span_name(raw: str) -> str:
    """What precedes any `#...#` the profiler appends for a span's counts."""
    return raw.split("#", 1)[0]


def load(logdir: str):
    """The ProfileData of the newest trace under `logdir`."""
    found = newest_parsed(logdir)
    if found is None:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found.profile


def device_ops(profile) -> dict:
    """{device index: [(name, start_ns, end_ns), ...] sorted by start} from
    the operations line of each device plane (every line that is not a
    coarser repeat, where no line is called "XLA Ops")."""
    out = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = list(plane.lines)
        picked = [l for l in lines if l.name == OPS_LINE] or \
            [l for l in lines if l.name not in COARSE_LINES]
        evs = [(op_name(e.name), float(e.start_ns),
                float(e.start_ns) + float(e.duration_ns))
               for l in picked for e in l.events]
        evs.sort(key=lambda e: e[1])
        out[int(m.group(1))] = evs
    return out


def host_spans(profile) -> list:
    """The TraceAnnotations on the host planes that the benchmark
    (`bench.*`) and the executors (`executor.*`) made, without the counts
    the profiler appends to a name: [(name, start_ns, end_ns)]."""
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIXES):
                    spans.append((span_name(e.name), float(e.start_ns),
                                  float(e.start_ns) + float(e.duration_ns)))
    return spans


def window(profile, spans=None):
    """(start_ns, end_ns) of the benchmark's `bench.window`, or None."""
    outer = [(s, e) for n, s, e in
             (host_spans(profile) if spans is None else spans) if n == WINDOW]
    if not outer:
        return None
    return min(s for s, _ in outer), max(e for _, e in outer)


def first_device_ops(profile, t0: float, t1: float) -> list:
    """The first device's operations inside [t0, t1], clipped to it."""
    ops = {d: evs for d, evs in device_ops(profile).items() if evs}
    return _clip(ops[min(ops)], t0, t1) if ops else []


def _union_named(evs) -> list:
    """Merged intervals of possibly overlapping (name, start, end)s, each
    with the operation that ended it: [((start, end), name)]."""
    merged = []
    for n, s, e in sorted(evs, key=lambda x: x[1]):
        if merged and s <= merged[-1][0][1]:
            if e > merged[-1][0][1]:
                merged[-1] = ((merged[-1][0][0], e), n)
        else:
            merged.append(((s, e), n))
    return merged


def union(intervals) -> list:
    """Merged [(start, end)] of possibly overlapping (start, end)s."""
    return [iv for iv, _ in _union_named((None, s, e) for s, e in intervals)]


def _clip(evs, t0, t1):
    return [(n, max(s, t0), min(e, t1)) for n, s, e in evs
            if e > t0 and s < t1]


def _covering(spans, t: float) -> str:
    """The innermost (shortest) of the kept spans that covers t: inside a
    step the executor's phase (`executor.wait` under `executor.fetch` under
    `executor.step` under `bench.step`)."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside-spans"


def reduce(profile, top: int = 10) -> dict:
    """busy_s and window_s (averaged over the devices that ran anything),
    the `top` operations by device time, the `top` idle-gap groups of the
    first device by the innermost host span over the gap's middle and the
    operation before it (`<span>|after:<op>`), and all-reduce seconds on the
    first device.  The window is the span of the benchmark's outermost
    annotation `bench.window`, else the first to the last device
    operation."""
    ops = {d: evs for d, evs in device_ops(profile).items() if evs}
    if not ops:
        return {"busy_s": 0.0, "window_s": 0.0, "devices": 0,
                "device_ops": [], "idle_gaps": [], "collective_s": 0.0,
                "n_ops": 0}
    spans = host_spans(profile)
    t0, t1 = window(profile, spans) or (
        min(evs[0][1] for evs in ops.values()),
        max(e for evs in ops.values() for _, _, e in evs))
    busy, by_name, n_ops = [], {}, 0
    for d, evs in sorted(ops.items()):
        evs = _clip(evs, t0, t1)
        n_ops += len(evs)
        busy.append(sum(e - s for (s, e), _ in _union_named(evs)))
        for n, s, e in evs:
            by_name[n] = by_name.get(n, 0.0) + (e - s)
    n_dev = len(ops)
    first = min(ops)
    evs0 = _clip(ops[first], t0, t1)
    gaps = {}
    edge = t0
    prev = "window-start"
    for (s, e), name in _union_named(evs0):
        if s > edge:
            key = f"{_covering(spans, (edge + s) / 2)}|after:{prev}"
            gaps[key] = gaps.get(key, 0.0) + (s - edge)
        edge, prev = max(edge, e), name
    if t1 > edge:
        key = f"{_covering(spans, (edge + t1) / 2)}|after:{prev}"
        gaps[key] = gaps.get(key, 0.0) + (t1 - edge)
    coll = sum(e - s for n, s, e in evs0 if COLLECTIVE.search(n))

    def ranked(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": sum(busy) / n_dev / 1e9,
            "window_s": (t1 - t0) / 1e9,
            "devices": n_dev,
            # summed over the devices, then per device
            "device_ops": [[k, v / n_dev] for k, v in ranked(by_name)],
            "idle_gaps": ranked(gaps),
            "collective_s": coll / 1e9,
            "n_ops": n_ops}
