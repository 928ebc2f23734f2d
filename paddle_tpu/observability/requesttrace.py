"""Request-scoped tracing: trace ids, cross-thread span trees, and
tail-based sampling.

PR-3 spans nest per-THREAD — right for a training loop, useless for a
serving request whose life crosses threads (minted on the caller's
thread, batched on the dispatcher's, completed back on the caller's).
This module traces the REQUEST: ``Engine.submit()`` mints a ``trace_id``
and starts a :class:`RequestTrace`; every stage appends a child span
*with the thread it actually ran on*; completion hands the trace to the
tracer's ``finish()``, which decides whether the span tree survives into
the merged Perfetto trace.

**Span tree shape.**  The root span (default name ``"request"``) covers
submit -> completion on the submitting thread; children
(``request.queued``, ``request.dispatch``, ...) carry
``parent=<root name>`` and ride on whichever thread recorded them, so
the Chrome-trace export shows one request as correlated slices across
thread rows.  Every span's ``args`` carries the ``trace_id`` — the join
key against metric exemplars and flight-recorder events.

**Tail-based sampling.**  Tracing every request would blow the span
ring on any real workload, and the interesting requests are precisely
the ones you cannot pick in advance: the slow and the broken.  So the
decision is made at the END of each request (tail-based): keep full
span detail iff the outcome is not "ok" (errored / shed / timed out /
quarantined / rejected) or the latency is at or above the rolling p99
of recent successful requests — all under ``FLAGS_request_trace_budget``,
a HARD per-run cap (once spent, even keep-worthy requests drop).  The
decision lands on ``paddle_tpu_request_traces{decision=}``
(kept / sampled_out / budget_dropped), so an export can say how much of
the tail survived.

Callers gate on FLAGS_observability — with the flag off nothing here is
ever reached (the serving zero-allocation contract covers
``Engine.submit()``).
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from typing import List, Optional, Tuple

from .. import flags as _flags
from .metrics import default_registry
from .stepstats import StepStats
from .tracing import Span, default_tracer

__all__ = ["RequestTrace", "RequestTracer", "default_request_tracer",
           "mint_trace_id"]

# process nonce + monotonic counter: unique within a process, collisions
# across processes only if pid AND startup-millisecond both coincide
_NONCE = f"{os.getpid() & 0xFFFF:04x}{int(time.time() * 1e3) & 0xFFFFFF:06x}"
_COUNTER = itertools.count(1)


def mint_trace_id() -> str:
    """A fresh request trace id (``<process-nonce>-<seq>``)."""
    return f"{_NONCE}-{next(_COUNTER):06x}"


class RequestTrace:
    """One in-flight request's span tree, appendable from any thread.

    ``event()`` defaults to the calling thread; pass ``tid``/
    ``thread_name`` to backfill a span onto the thread it conceptually
    belongs to (e.g. the queue-wait span onto the submitting thread,
    recorded by the dispatcher)."""

    __slots__ = ("trace_id", "name", "t0", "tid", "thread_name",
                 "attrs", "_spans", "_lock")

    def __init__(self, trace_id: str, name: str = "request",
                 t0: Optional[float] = None):
        th = threading.current_thread()
        self.trace_id = trace_id
        self.name = name
        self.t0 = time.perf_counter() if t0 is None else t0
        self.tid = threading.get_ident()
        self.thread_name = th.name
        self.attrs: dict = {}
        self._spans: List[Span] = []
        self._lock = threading.Lock()

    def event(self, name: str, t0: float, t1: float,
              tid: Optional[int] = None,
              thread_name: Optional[str] = None, **args) -> None:
        """Append one child span (parented under the root)."""
        if tid is None:
            tid = threading.get_ident()
            thread_name = threading.current_thread().name
        args["trace_id"] = self.trace_id
        span = Span(name, t0, t1, tid, thread_name or f"thread-{tid}",
                    parent=self.name, args=args, cat="request")
        with self._lock:
            self._spans.append(span)

    def annotate(self, **kv) -> None:
        """Attach attributes to the root span (bucket, rows, tokens...)."""
        self.attrs.update(kv)

    def _close(self, t_end: float, outcome: str,
               latency: float) -> List[Span]:
        """Root + children, ready for the tracer (internal)."""
        args = dict(self.attrs)
        args["trace_id"] = self.trace_id
        args["outcome"] = outcome
        args["latency_s"] = latency
        root = Span(self.name, self.t0, t_end, self.tid, self.thread_name,
                    args=args, cat="request")
        with self._lock:
            return [root] + list(self._spans)


class RequestTracer:
    """Tail-sampling sink for finished RequestTraces.

    Keeps a rolling latency ring of SUCCESSFUL requests (errored ones
    would drag the p99 toward the failures we already force-keep) and
    emits kept span trees into the default Tracer, where they merge
    into the one Perfetto trace per run."""

    def __init__(self, latency_window: int = 512):
        self._lock = threading.Lock()
        self._latency = StepStats(capacity=int(latency_window))
        # p99 cache keyed on the ring's monotonic count: finish() runs
        # once per request; re-sorting the window only when it changed
        self._p99: Tuple[int, Optional[float]] = (0, None)
        self.kept = 0
        self.sampled_out = 0
        self.budget_dropped = 0

    def start(self, name: str = "request",
              trace_id: Optional[str] = None,
              t0: Optional[float] = None) -> RequestTrace:
        return RequestTrace(trace_id or mint_trace_id(), name=name, t0=t0)

    def rolling_p99(self) -> Optional[float]:
        with self._lock:
            return self._p99_locked()

    def _p99_locked(self) -> Optional[float]:
        count = self._latency.count
        cached_at, p99 = self._p99
        if count != cached_at:
            p99 = self._latency.percentile(99)
            self._p99 = (count, p99)
        return p99

    def finish(self, rt: RequestTrace, outcome: str = "ok",
               t_end: Optional[float] = None) -> bool:
        """Close a trace and decide its fate; returns True when its
        spans were kept (emitted into the merged trace).  The p99
        comparison uses the evidence BEFORE this request's own sample
        lands — a request is slow relative to its predecessors."""
        if t_end is None:
            t_end = time.perf_counter()
        latency = t_end - rt.t0
        forced = outcome != "ok"
        with self._lock:
            p99 = self._p99_locked()
            keep = forced or p99 is None or latency >= p99
            if not forced:
                self._latency.add(rt.t0, t_end)
            budget = int(_flags._VALUES["FLAGS_request_trace_budget"])
            if keep and self.kept >= budget:
                keep = False
                self.budget_dropped += 1
                decision = "budget_dropped"
            elif keep:
                self.kept += 1
                decision = "kept"
            else:
                self.sampled_out += 1
                decision = "sampled_out"
        default_registry().counter(
            "paddle_tpu_request_traces",
            "finished request traces by tail-sampling decision",
        ).inc(decision=decision)
        if keep:
            tracer = default_tracer()
            for span in rt._close(t_end, outcome, latency):
                tracer.add(span)
        return keep

    def stats(self) -> dict:
        with self._lock:
            return {
                "kept": self.kept,
                "sampled_out": self.sampled_out,
                "budget_dropped": self.budget_dropped,
                "rolling_p99_s": self._p99_locked(),
            }

    def reset(self) -> None:
        with self._lock:
            self._latency.reset()
            self._p99 = (0, None)
            self.kept = 0
            self.sampled_out = 0
            self.budget_dropped = 0


_default = RequestTracer()


def default_request_tracer() -> RequestTracer:
    """The process-wide tracer Engine.submit() mints into."""
    return _default
