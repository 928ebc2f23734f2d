"""Structured trace spans + the one Chrome/Perfetto trace writer.

`span("step", step=n)` / `span("ckpt.save")` record (name, start, end,
thread, parent, attrs) into a process-wide Tracer.
Spans nest correctly across threads — each thread carries its own span
stack (thread-local), so a checkpoint writer thread's spans never adopt
the training thread's open "step" as parent.

One mechanism, two sinks.  Sink A, the profiler's clock: every span enters
a jax.profiler.TraceAnnotation, which is inert outside a profiler session
and, inside one (however it was started: jax.profiler.start_trace, Fluid's
start_profiler, TensorBoard's capture), lands on the host plane of the same
xplane.pb as the device's operations, counts and all.  Sink B, the ring
below (and through it the chrome trace): only under FLAGS_observability.

The chrome-trace writer here is the single exporter for the repo:
`timeline.export_chrome_trace` (the old 50-line stub) is rebased onto it
and merges profiler.record_event spans with observability spans into one
Perfetto-loadable file per run, with `thread_name` metadata events and
stable per-thread tids (main thread is always tid 0; other threads are
ordered by their first span's start time — insertion-order ints with no
names left Perfetto rows unlabeled).

Flag off: a span is its TraceAnnotation and nothing else: this module
reads no clock, appends nothing, and nothing outlives the with-block.  (A
step's boundaries are kept all the same, by the step log, stepstats.py,
which core/executor.py::run_step writes beside the spans.)
"""

from __future__ import annotations

import json
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from .. import flags as _flags

__all__ = ["Span", "Tracer", "span", "default_tracer",
           "write_chrome_trace", "chrome_trace_doc"]


def _on() -> bool:
    return _flags._VALUES["FLAGS_observability"]


class Span:
    """One finished span."""

    __slots__ = ("name", "t0", "t1", "tid", "thread_name", "parent",
                 "args", "cat")

    def __init__(self, name: str, t0: float, t1: float, tid: int,
                 thread_name: str, parent: Optional[str] = None,
                 args: Optional[dict] = None, cat: str = "obs"):
        self.name = name
        self.t0 = t0
        self.t1 = t1
        self.tid = tid
        self.thread_name = thread_name
        self.parent = parent
        self.args = args or {}
        self.cat = cat

    @property
    def duration(self) -> float:
        return self.t1 - self.t0

    def to_dict(self) -> dict:
        return {"name": self.name, "t0": self.t0, "t1": self.t1,
                "tid": self.tid, "thread_name": self.thread_name,
                "parent": self.parent, "args": dict(self.args),
                "cat": self.cat}


class _SpanCtx:
    __slots__ = ("_tracer", "_name", "_args", "_t0", "_annot", "seconds")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._annot = TraceAnnotation(name, **args)
        self._t0 = None
        self.seconds = None  # the span's duration, under the flag only

    def __enter__(self):
        self._annot.__enter__()
        if _on():
            self._tracer._stack().append(self._name)
            self._t0 = time.perf_counter()
        return self

    def set(self, **counts) -> None:
        """Counts known only once the work is done (`moved`, `cache`):
        onto the open span, in both sinks."""
        self._annot.set_metadata(**counts)
        if self._t0 is not None:
            self._args.update(counts)

    def __exit__(self, *exc):
        self._annot.__exit__(*exc)
        if self._t0 is not None:
            t1 = time.perf_counter()
            self.seconds = t1 - self._t0
            stack = self._tracer._stack()
            if stack and stack[-1] == self._name:
                stack.pop()
            parent = stack[-1] if stack else None
            th = threading.current_thread()
            self._tracer._append(Span(
                self._name, self._t0, t1, threading.get_ident(), th.name,
                parent=parent, args=self._args))
        return False


class Tracer:
    """Thread-safe span store with per-thread nesting stacks.

    Bounded: keeps the newest `capacity` spans (deque ring — a
    long-lived trainer with observability on must not grow host memory
    one Span per step forever; the step log and the profiler trace are
    bounded the same way).  `dropped` counts evictions so an export can
    say the trace is a tail window."""

    def __init__(self, capacity: int = 65536):
        import collections

        self._lock = threading.Lock()
        self._spans = collections.deque(maxlen=int(capacity))
        self._tls = threading.local()
        self.dropped = 0

    def _stack(self) -> List[str]:
        s = getattr(self._tls, "stack", None)
        if s is None:
            s = self._tls.stack = []
        return s

    def _append(self, s: Span) -> None:
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(s)

    def span(self, name: str, **args) -> _SpanCtx:
        return _SpanCtx(self, name, args)

    def record(self, name: str, t0: float, t1: float, **args) -> None:
        """Record an already-timed span (importing timings measured
        elsewhere, e.g. a checkpoint writer's durations)."""
        if not _on():
            return
        th = threading.current_thread()
        self._append(Span(name, t0, t1, threading.get_ident(), th.name,
                          args=args))

    def add(self, span: Span) -> None:
        """Append an already-built Span verbatim — the request tracer
        emits kept cross-thread span trees through here, with each
        span's ORIGINAL thread identity preserved (record() would stamp
        the calling thread's)."""
        if not _on():
            return
        self._append(span)

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0


_default = Tracer()


def default_tracer() -> Tracer:
    return _default


def span(name: str, **args):
    """`with span("step", step=n):` — a TraceAnnotation always, and a
    record in the default tracer when FLAGS_observability is on."""
    return _default.span(name, **args)


# -- chrome trace writing ---------------------------------------------------

def _stable_tids(spans: List[Span]) -> Dict[Tuple[int, str], int]:
    """(ident, thread name) -> stable tid.  Keyed on the PAIR, not the
    bare OS ident: CPython reuses thread idents after join, so a stream
    of short-lived writer threads (ckpt_finalize_<step>) would otherwise
    collapse onto one mislabeled row.  The main thread is pinned to tid
    0; every other row is numbered by its first span's start time
    (deterministic for a given run, and Perfetto sorts rows by tid so
    the hot thread stays on top)."""
    main = threading.main_thread()
    main_key = (main.ident, main.name)
    first_seen: Dict[Tuple[int, str], float] = {}
    for s in spans:
        key = (s.tid, s.thread_name)
        seen = first_seen.get(key)
        if seen is None or s.t0 < seen:
            first_seen[key] = s.t0
    tids: Dict[Tuple[int, str], int] = {}
    nxt = 1
    if main_key in first_seen:
        tids[main_key] = 0
    for key, _ in sorted(first_seen.items(),
                         key=lambda kv: (kv[1], kv[0])):
        if key in tids:
            continue
        tids[key] = nxt
        nxt += 1
    return tids


def chrome_trace_doc(spans: Iterable[Span], pid: int = 0,
                     process_name: str = "paddle_tpu") -> dict:
    """Chrome trace-event JSON document: one 'X' complete event per span
    plus 'M' metadata events naming the process and every thread."""
    spans = sorted(spans, key=lambda s: s.t0)
    tids = _stable_tids(spans)
    events: List[dict] = [{
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "args": {"name": process_name},
    }]
    for (_, name), tid in sorted(tids.items(), key=lambda kv: kv[1]):
        events.append({
            "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": name},
        })
        events.append({
            "name": "thread_sort_index", "ph": "M", "pid": pid, "tid": tid,
            "args": {"sort_index": tid},
        })
    for s in spans:
        ev = {
            "name": s.name,
            "ph": "X",
            "ts": s.t0 * 1e6,                 # microseconds
            "dur": max(0.0, s.t1 - s.t0) * 1e6,
            "pid": pid,
            "tid": tids[(s.tid, s.thread_name)],
            "cat": s.cat,
        }
        if s.args or s.parent:
            ev["args"] = dict(s.args)
            if s.parent:
                ev["args"]["parent"] = s.parent
        events.append(ev)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def write_chrome_trace(path: str, spans: Iterable[Span],
                       pid: int = 0) -> int:
    """Write the Perfetto-loadable JSON; returns the number of span ('X')
    events written (metadata events excluded — the count callers assert
    on is "how many spans landed")."""
    spans = list(spans)
    doc = chrome_trace_doc(spans, pid=pid)
    with open(path, "w") as f:
        json.dump(doc, f)
    return len(spans)
