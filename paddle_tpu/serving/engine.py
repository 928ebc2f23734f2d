"""Batching inference engine: thread-safe submit(feed) -> Future.

One Engine wraps one loaded model — an AOT StableHLO artifact
(inference/aot.py) or an Executor-compiled Program — behind a bounded
request queue and a single dispatcher thread:

- **submit() is thread-safe and non-blocking**: callers get a
  concurrent.futures.Future; the dispatcher coalesces queued requests
  into micro-batches padded to the bucket ladder (batching.py), runs the
  backend once per batch, and slices per-request rows back out.
- **Backpressure** is a bounded queue: submit raises QueueFullError once
  `queue_depth` requests are pending — callers shed load explicitly
  instead of the engine buffering unboundedly.
- **Deadlines**: submit(feed, timeout=...) arms an absolute deadline; a
  request still queued when it expires fails with RequestTimeoutError
  (requests already inside a dispatched batch always complete — an XLA
  dispatch cannot be recalled).
- **Drain** mirrors resilience.PreemptionDrain semantics: begin_drain()
  stops admissions (submit raises EngineClosedError), the dispatcher
  finishes the in-flight batch and every queued request that still has
  deadline headroom, then parks.  attach_drain(PreemptionDrain) wires
  SIGTERM straight to begin_drain via the drain's listener hook.
- **Compile discipline**: every dispatch is padded to a ladder bucket, so
  the backend sees at most len(buckets) distinct batch shapes for the
  life of the engine.  The engine counts first-seen shapes
  (`compile_counters()`) — the serving analogue of the executor's
  compile-cache hit/miss counters — and tests assert the ladder bound.

Fault isolation (the serving half of the resilience pillar):

- **Batch-level blast radius**: a backend raise inside one dispatch
  fails ONLY that batch's futures — each gets a typed
  EngineInternalError naming the cause — and the dispatcher moves on to
  the next batch.
- **Dispatcher supervision**: an exception that escapes the dispatch
  cycle anyway (a bug outside the protected region) kills the thread;
  the supervisor hook restarts it with the queue preserved, so queued
  futures never strand behind a dead thread.
- **Circuit breaker**: `breaker_threshold` CONSECUTIVE internal errors
  open the breaker — submit() fails fast with EngineUnhealthyError for
  `breaker_cooldown_s`, then half-opens (requests probe the backend);
  one successful dispatch closes it.  Callers shed to a replica instead
  of queueing onto a backend that fails every batch.
- **Overload shedding**: a request whose deadline is already unmeetable
  at submit time — queue depth x the observed per-batch latency p50
  (an engine-local StepStats ring) says it cannot dispatch before it
  expires — is rejected immediately with RequestTimeoutError instead of
  rotting in the queue and timing out after burning its wait.
- **health()**: one snapshot — SERVING/DEGRADED/DRAINING/BROKEN, queue
  depth, breaker state, last-dispatch age, dispatcher liveness, shed
  and restart counts, optional attached KV-pool utilization — exported
  through observability gauges when the flag is on.

Observability (queue depth, batch occupancy, latency histograms,
admission/reject/timeout counters) gates on FLAGS_observability with the
established zero-work disabled path: one dict lookup, no allocation —
tier-1 extends the tracemalloc assertion to submit().  With the flag ON
every request is traced end to end (ISSUE 8): submit() mints a
`trace_id` (on the returned Future and on every typed error), the
request's life is recorded as a cross-thread span tree
(submit -> queued -> dispatch, each span on the thread that ran it) and
tail-sampled into the merged Perfetto trace
(observability/requesttrace.py), latency histograms carry OpenMetrics
exemplars linking their p99 bucket to the trace behind it, and every
lifecycle event lands in the flight recorder (observability/flight.py)
— which auto-dumps a JSONL black box when the breaker trips or health()
enters BROKEN.
"""

from __future__ import annotations

import logging
import threading
import time
import weakref
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import flags as _flags
from ..observability import flight as _flight
from ..observability import requesttrace as _rtrace
from ..observability.stepstats import StepStats
from ..resilience import faultinject as _finject
from . import metrics as _smetrics
from .batching import (
    BucketLadder,
    Request,
    coalesce,
    parse_buckets,
    request_rows,
    scatter,
)

__all__ = [
    "Engine",
    "EngineConfig",
    "EngineClosedError",
    "EngineInternalError",
    "EngineUnhealthyError",
    "QueueFullError",
    "RequestTimeoutError",
    "AotBackend",
    "ExecutorBackend",
]

_log = logging.getLogger("paddle_tpu.serving")


class RequestTimeoutError(TimeoutError):
    """A request's deadline expired before its batch was dispatched —
    either in the queue, or at submit() when deadline-aware admission
    predicts the queue cannot dispatch it in time (shed)."""


class QueueFullError(RuntimeError):
    """The engine's bounded request queue is at queue_depth (backpressure:
    the caller must shed or retry, the engine will not buffer more)."""


class EngineClosedError(RuntimeError):
    """submit() after begin_drain()/close(): the engine no longer admits
    new requests (in-flight and queued work still completes)."""


class EngineInternalError(RuntimeError):
    """A micro-batch's dispatch failed inside the engine (backend raise,
    scatter bug): every future in THAT batch gets this error — naming
    the underlying cause — and the dispatcher survives to serve the next
    batch.  The original exception rides on `cause` / `__cause__`."""

    def __init__(self, cause: BaseException):
        self.cause = cause
        super().__init__(
            f"batch dispatch failed: {type(cause).__name__}: {cause}")
        self.__cause__ = cause


class EngineUnhealthyError(RuntimeError):
    """The circuit breaker is open: `breaker_threshold` consecutive
    batches failed, so submit() fails fast for `breaker_cooldown_s`
    instead of queueing onto a backend that fails everything.  After the
    cool-down the breaker half-opens and requests probe the backend;
    one successful dispatch closes it."""


class EngineConfig:
    """Knobs for the dynamic batcher.

    buckets: batch-size ladder (default: FLAGS_serving_buckets).  An
        EMPTY ladder selects pass-through mode: no concat/pad/split —
        each request dispatches alone with its feed forwarded verbatim
        (the Inferencer path; also the only mode that can carry ragged
        LoD feeds).
    max_batch: admission cap on rows per request (default: the largest
        bucket).
    max_wait_s: how long the oldest queued request may wait for the
        batch to fill before dispatching anyway.
    queue_depth: bounded-queue capacity in requests (backpressure).
    default_timeout_s: deadline applied when submit() passes none.
    breaker_threshold: consecutive internal (batch-dispatch) errors that
        open the circuit breaker (default FLAGS_serving_breaker_threshold).
    breaker_cooldown_s: how long an open breaker fails submit() fast
        before half-opening a probe (default
        FLAGS_serving_breaker_cooldown_s).
    shed_deadlines: deadline-aware admission — reject a request at
        submit() when queue depth x observed per-batch latency p50 says
        it cannot dispatch before its deadline (default True; requests
        without a deadline are never shed).
    """

    def __init__(self, buckets: Optional[Sequence[int]] = None,
                 max_batch: Optional[int] = None,
                 max_wait_s: float = 0.002,
                 queue_depth: int = 256,
                 default_timeout_s: Optional[float] = None,
                 breaker_threshold: Optional[int] = None,
                 breaker_cooldown_s: Optional[float] = None,
                 shed_deadlines: bool = True):
        self.buckets = (parse_buckets() if buckets is None
                        else parse_buckets(buckets))
        self.max_batch = (int(max_batch) if max_batch is not None
                          else (self.buckets[-1] if self.buckets else 0))
        self.max_wait_s = float(max_wait_s)
        self.queue_depth = int(queue_depth)
        self.default_timeout_s = default_timeout_s
        self.breaker_threshold = int(
            breaker_threshold if breaker_threshold is not None
            else _flags.flag("serving_breaker_threshold"))
        self.breaker_cooldown_s = float(
            breaker_cooldown_s if breaker_cooldown_s is not None
            else _flags.flag("serving_breaker_cooldown_s"))
        self.shed_deadlines = bool(shed_deadlines)


class AotBackend:
    """Adapter over the predict callable load_compiled_inference_model
    returns (or an artifact directory)."""

    def __init__(self, predict_or_dir):
        if isinstance(predict_or_dir, str):
            from ..inference import load_compiled_inference_model

            predict_or_dir = load_compiled_inference_model(predict_or_dir)
        self.predict = predict_or_dir
        self.feed_names = list(self.predict.feed_names)
        self.fetch_names = list(getattr(self.predict, "fetch_names", []))
        self.meta = dict(getattr(self.predict, "meta", {}) or {})

    def __call__(self, feed: Dict[str, Any]) -> List[np.ndarray]:
        return self.predict(feed)


class ExecutorBackend:
    """Adapter over a live Executor + Program (+ Scope): every dispatch
    goes through the executor's compiled-program cache, so the engine and
    any direct exe.run callers share one compile per program signature."""

    def __init__(self, executor, program, fetch_list,
                 scope=None, feed_names: Optional[Sequence[str]] = None):
        self.executor = executor
        self.program = program
        self.fetch_list = list(fetch_list)
        self.scope = scope
        # feed_names=None skips engine-side feed validation (the executor
        # keys its cache on whatever names arrive)
        self.feed_names = list(feed_names) if feed_names is not None else None
        from ..core.framework import Variable

        self.fetch_names = [
            v.name if isinstance(v, Variable) else str(v)
            for v in self.fetch_list
        ]
        self.meta: Dict[str, Any] = {}

    def __call__(self, feed: Dict[str, Any], return_numpy: bool = True):
        from ..core.scope import scope_guard

        if self.scope is not None:
            with scope_guard(self.scope):
                return self.executor.run(
                    self.program, feed=feed, fetch_list=self.fetch_list,
                    return_numpy=return_numpy)
        return self.executor.run(
            self.program, feed=feed, fetch_list=self.fetch_list,
            return_numpy=return_numpy)


def _plan_buckets(backend, requested: Tuple[int, ...]) -> Tuple[Tuple[int, ...], Optional[str]]:
    """The bucket planner: a static-batch artifact (shape polymorphism
    failed at export — meta['symbolic_error'] records why) can only run
    its one exported batch size, so the ladder collapses to it and the
    reason rides on the engine for debuggability."""
    meta = getattr(backend, "meta", None) or {}
    if meta.get("batch") == "static" and requested:
        shapes = meta.get("exported_shapes") or []
        static_b = int(shapes[0][0]) if shapes and shapes[0] else 1
        reason = (
            f"artifact exported with a STATIC batch of {static_b} "
            f"(symbolic batch unavailable: {meta.get('symbolic_error')}); "
            f"ladder {requested} collapsed to ({static_b},)")
        return (static_b,), reason
    return requested, None


class Engine:
    """Thread-safe batching front end over one loaded model."""

    def __init__(self, backend, config: Optional[EngineConfig] = None,
                 name: str = "engine"):
        self.backend = backend
        self.config = config or EngineConfig()
        self.name = name
        # replica label (set by distributed.Router.add_replica): rides
        # on every flight-recorder event, request trace, and health
        # gauge this engine emits, so per-replica telemetry stays
        # attributable after aggregate_dir() merges process dumps
        self.replica: Optional[str] = None
        buckets, self.bucket_reason = _plan_buckets(
            backend, self.config.buckets)
        self.ladder = BucketLadder(buckets)
        if self.ladder.buckets:
            self.max_batch = min(self.config.max_batch or
                                 self.ladder.max_bucket,
                                 self.ladder.max_bucket)
        else:
            self.max_batch = 0  # pass-through mode

        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queue: List[Request] = []
        self._closed = False      # no new admissions
        self._stopped = False     # dispatcher exited
        self._inflight = 0        # requests inside the current dispatch
        # first-seen dispatch shapes — the serving compile counters: a
        # "miss" is a batch shape the backend has never seen (a fresh
        # XLA specialization for a symbolic artifact / a fresh jit trace
        # for an executor program), a "hit" reuses one
        self._shapes_seen: set = set()
        self._shape_hits = 0
        self._shape_misses = 0
        self._dispatched_batches = 0
        self._dispatched_rows = 0
        self._occupancy_sum = 0.0

        # fault isolation / supervision state (all under self._lock)
        self._internal_errors = 0         # total failed dispatches
        self._consecutive_errors = 0      # streak feeding the breaker
        self._last_error: Optional[str] = None
        self._breaker_open_until = 0.0    # 0.0: closed; <=now: half-open
        self._breaker_trips = 0
        self._dispatcher_restarts = 0
        self._last_dispatch_ok: Optional[float] = None
        self._shed = 0                    # deadline-aware rejections
        self._close_timed_out = False
        # chaos (FAULT_SERVE_REPLICA_KILL): a killed replica's
        # dispatcher dies WITHOUT restart — models a dead process
        self._replica_killed = False
        # observed per-batch dispatch latency — the shedding estimator's
        # input (engine-local ring: admission control is functional, not
        # telemetry, so it runs regardless of FLAGS_observability)
        self._batch_lat = StepStats(capacity=128)
        # percentile caches keyed by the ring's monotonic count: the
        # submit fast path (p50, deadline shedding) and continuously
        # polled health() (p99) must not re-sort the 128-sample window
        # when nothing new landed
        self._batch_lat_p50: Tuple[int, Optional[float]] = (0, None)
        self._batch_lat_p99: Tuple[int, Optional[float]] = (0, None)
        self._pool = None                 # optional attach_pool target
        # last health() verdict — the flight recorder logs state EDGES
        # (SERVING->BROKEN), not every poll
        self._last_health_state: Optional[str] = None

        # trailing feed shapes (everything past the batch dim) each
        # request must match — seeded from the AOT meta when available,
        # learned from the first request otherwise.  Validating at
        # submit() keeps one client's mis-shaped request from failing
        # the innocent requests coalesced into the same micro-batch.
        self._trailing: Dict[str, Tuple[int, ...]] = {}
        for fm in (getattr(backend, "meta", None) or {}).get("feeds", []):
            self._trailing[fm["name"]] = tuple(int(d) for d in fm["shape"][1:])

        # The dispatcher holds only a WEAKREF to the engine between
        # cycles (and parks in bounded waits), so an Engine that is
        # dropped without close() is garbage-collected and its thread
        # exits within ~_IDLE_PARK_S instead of leaking both forever.
        with self._lock:
            self._spawn_dispatcher_locked()

    def _flight_record(self, kind: str, **fields) -> None:
        """One engine lifecycle event into the flight recorder, labeled
        with the replica name when this engine serves behind a Router."""
        if self.replica is not None:
            fields.setdefault("replica", self.replica)
        _flight.default_flight().record(kind, engine=self.name, **fields)

    def _spawn_dispatcher_locked(self) -> None:
        # under the lock, as everyone who asks `self._thread.is_alive()`
        # is: between the assignment and start() the new thread reads as
        # dead, and the thread itself may die (and look) at once
        self._thread = threading.Thread(
            target=_dispatch_entry, args=(weakref.ref(self),),
            name=f"serving-{self.name}", daemon=True)
        self._thread.start()

    def _restart_dead_dispatcher_locked(self) -> bool:
        """The ONE place a dead dispatcher is found dead, counted and
        replaced, under the lock: by the supervisor (which runs on the
        dying thread itself) or by a submit() that finds the thread gone.
        Whoever comes second finds the new thread alive and does
        nothing.  -> whether this caller restarted it."""
        thread = self._thread
        if thread.is_alive() and thread is not threading.current_thread():
            return False
        self._dispatcher_restarts += 1
        self._spawn_dispatcher_locked()
        return True

    # -- submission ----------------------------------------------------

    @classmethod
    def from_artifact(cls, dirname_or_predict,
                      config: Optional[EngineConfig] = None,
                      name: str = "engine") -> "Engine":
        return cls(AotBackend(dirname_or_predict), config=config, name=name)

    @classmethod
    def from_program(cls, executor, program, fetch_list, scope=None,
                     feed_names: Optional[Sequence[str]] = None,
                     config: Optional[EngineConfig] = None,
                     name: str = "engine") -> "Engine":
        return cls(
            ExecutorBackend(executor, program, fetch_list, scope=scope,
                            feed_names=feed_names),
            config=config, name=name)

    def submit(self, feed: Dict[str, Any],
               timeout: Optional[float] = None,
               call_kwargs: Optional[Dict[str, Any]] = None,
               sampling=None, adapter_id: Optional[str] = None) -> Future:
        """Enqueue one request; returns a Future resolving to the list of
        per-fetch numpy arrays (this request's rows only).

        timeout: seconds until the request's deadline; None uses
        config.default_timeout_s.  call_kwargs forwards extra backend
        keyword args and is only legal in pass-through mode (a padded
        batch serves many requests — per-request backend options cannot
        apply).  sampling: a serving.SamplingParams threaded to the
        backend the same way (pass-through only — it is a PER-REQUEST
        contract; a decode-style backend receives it as the `sampling`
        call kwarg and hands it to DecodeRequest.sampling).
        adapter_id: the model variant to serve this request under
        (ISSUE 19) — same pass-through-only threading; a decode-style
        backend hands it to ``DecodeRequest.adapter_id`` and the
        loop's AdapterPool resolves or typed-rejects it.

        With FLAGS_observability on, the returned Future carries a
        fresh `trace_id` (also attached to every typed error this
        request can fail with) and the request's life is traced
        submit -> dispatch -> completion as a cross-thread span tree —
        kept in the merged Perfetto trace when tail sampling elects it
        (slow / errored / shed / timed out, under
        FLAGS_request_trace_budget).  Off, `fut.trace_id` is None and
        nothing from the observability package runs or allocates."""
        obs_on = _flags._VALUES["FLAGS_observability"]
        if sampling is not None:
            from .sampling import SamplingParams

            if not isinstance(sampling, SamplingParams):
                raise TypeError(
                    f"sampling must be a serving.SamplingParams, got "
                    f"{type(sampling).__name__}")
            call_kwargs = dict(call_kwargs or {}, sampling=sampling)
        if adapter_id is not None:
            if not isinstance(adapter_id, str):
                raise TypeError(
                    f"adapter_id must be a str, got "
                    f"{type(adapter_id).__name__}")
            call_kwargs = dict(call_kwargs or {}, adapter_id=adapter_id)
        fut: Future = Future()
        fut.trace_id = None
        feed_names = self.backend.feed_names
        if feed_names is not None:
            missing = [n for n in feed_names if n not in feed]
            if missing:
                raise KeyError(f"feed is missing {missing}")
            unknown = [n for n in sorted(feed) if n not in set(feed_names)]
            if unknown:
                raise KeyError(
                    f"feed has unknown keys {unknown}; this engine serves "
                    f"feeds {feed_names}")
        if self.ladder.buckets:
            if call_kwargs:
                raise ValueError(
                    "call_kwargs requires pass-through mode (empty bucket "
                    "ladder): a padded batch cannot carry per-request "
                    "backend options")
            rows = request_rows(feed, feed_names or sorted(feed))
            if rows < 1:
                raise ValueError("request must carry at least one row")
            if rows > self.max_batch:
                raise ValueError(
                    f"request has {rows} rows but max_batch={self.max_batch} "
                    f"(ladder {self.ladder.buckets}); split it client-side")
            self._check_trailing(feed, feed_names or sorted(feed))
        else:
            rows = 0  # pass-through: never split
        if timeout is None:
            timeout = self.config.default_timeout_s
        rt = None
        if obs_on:
            rt = _rtrace.default_request_tracer().start()
            fut.trace_id = rt.trace_id
            if self.replica is not None:
                # the replica attribute is the join key a merged
                # (aggregate_dir) view filters kept traces by
                rt.annotate(replica=self.replica)
        now = time.perf_counter()
        req = Request(
            feed=feed, future=fut, rows=rows, enqueued_at=now,
            deadline=(now + timeout) if timeout is not None else None,
            call_kwargs=dict(call_kwargs) if call_kwargs else None,
            trace_id=fut.trace_id, trace=rt,
        )
        with self._cond:
            if self._closed:
                self._reject(rt, EngineClosedError(
                    f"engine '{self.name}' is draining/closed"),
                    "closed", obs_on)
            if self._replica_killed:
                # a chaos-killed replica has no dispatcher and never
                # will — admitting would strand the request in a queue
                # nothing drains; reject typed so the router's raced
                # health cache falls over to a survivor instead
                self._reject(rt, EngineClosedError(
                    f"engine '{self.name}': replica was killed"),
                    "closed", obs_on)
            if self._breaker_open_until > now:
                self._reject(rt, EngineUnhealthyError(
                    f"engine '{self.name}' circuit breaker is open "
                    f"({self._consecutive_errors} consecutive dispatch "
                    f"failures, last: {self._last_error}); retry in "
                    f"{self._breaker_open_until - now:.2f}s"),
                    "breaker_open", obs_on)
            if len(self._queue) >= self.config.queue_depth:
                self._reject(rt, QueueFullError(
                    f"engine '{self.name}' queue is at "
                    f"{self.config.queue_depth} requests"),
                    "queue_full", obs_on)
            if req.deadline is not None and self.config.shed_deadlines:
                est = self._estimate_dispatch_wait_locked()
                if est is not None and now + est >= req.deadline:
                    self._shed += 1
                    self._reject(rt, RequestTimeoutError(
                        f"shed: ~{est:.3f}s of queued work ahead "
                        f"(observed batch p50 x queue depth) already "
                        f"violates this request's {timeout:.3f}s "
                        f"deadline — rejecting at submit instead of "
                        f"expiring in queue"),
                        "deadline_shed", obs_on)
            # a dispatcher that died without its supervisor running
            # (never under normal faults) must not strand the queue
            if not self._stopped:
                self._restart_dead_dispatcher_locked()
            self._queue.append(req)
            depth = len(self._queue)
            if obs_on:
                # still under the cond: the dispatcher cannot take the
                # batch (it needs this lock) until the submit span and
                # flight event are recorded — otherwise a fast dispatch
                # could finish() the trace before its submit span lands
                rt.event("request.submit", rt.t0, time.perf_counter())
                self._flight_record(
                    "submit", trace_id=fut.trace_id,
                    depth=depth)
            self._cond.notify_all()
        if obs_on:
            _smetrics.record_submit(depth)
        return fut

    def _reject(self, rt, exc: Exception, reason: str,
                obs_on: bool) -> None:
        """Account one rejected submission and raise `exc` (with the
        request's trace_id attached).  Rejections are forced-keep in
        tail sampling — a shed or fast-failed request is exactly the
        kind an operator wants the span tree for."""
        if obs_on:
            _smetrics.record_reject(reason)
            self._flight_record(
                "reject", reason=reason,
                trace_id=rt.trace_id)
            exc.trace_id = rt.trace_id
            _rtrace.default_request_tracer().finish(
                rt, outcome=("shed" if reason == "deadline_shed"
                             else f"rejected_{reason}"))
        raise exc

    def _estimate_dispatch_wait_locked(self) -> Optional[float]:
        """Earliest-possible-dispatch estimate for a NEW request, from
        the work already ahead of it: whole batches the queue holds
        (plus the in-flight one) x the observed per-batch latency p50.
        None when there is nothing ahead or no latency observed yet —
        shedding needs evidence, never a guess."""
        if not self._queue and not self._inflight:
            return None
        p50 = self._batch_lat_p50_cached()
        if p50 is None:
            return None
        if self.ladder.buckets:
            rows_ahead = sum(r.rows for r in self._queue)
            batches_ahead = -(-rows_ahead // self.ladder.max_bucket)
        else:
            batches_ahead = len(self._queue)
        if self._inflight:
            batches_ahead += 1
        return batches_ahead * p50

    def _batch_lat_p50_cached(self) -> Optional[float]:
        """Observed batch-latency p50, re-sorted only when the ring has
        new samples — the steady-state submit path pays one int compare,
        not an O(K log K) window sort under self._cond."""
        count = self._batch_lat.count
        cached_at, p50 = self._batch_lat_p50
        if count != cached_at:
            p50 = self._batch_lat.percentile(50)
            self._batch_lat_p50 = (count, p50)
        return p50

    def _batch_lat_p99_cached(self) -> Optional[float]:
        """Same one-sort-per-change scheme for the p99 health() polls."""
        count = self._batch_lat.count
        cached_at, p99 = self._batch_lat_p99
        if count != cached_at:
            p99 = self._batch_lat.percentile(99)
            self._batch_lat_p99 = (count, p99)
        return p99

    def _check_trailing(self, feed: Dict[str, Any],
                        feed_names: Sequence[str]) -> None:
        """Reject a request whose trailing dims disagree with the model
        (AOT meta) or with previously admitted traffic — BEFORE it can
        be coalesced with (and fail) innocent batch-mates."""
        for n in feed_names:
            shape = tuple(int(d) for d in getattr(feed[n], "shape", ())[1:])
            with self._lock:
                want = self._trailing.get(n)
                if want is None:
                    self._trailing[n] = shape
                    continue
            if shape != want:
                raise ValueError(
                    f"feed '{n}' has trailing shape {list(shape)} but this "
                    f"engine serves {list(want)} (batches coalesce "
                    "row-wise; trailing dims must match)")

    def infer(self, feed: Dict[str, Any], timeout: Optional[float] = None,
              call_kwargs: Optional[Dict[str, Any]] = None):
        """Blocking submit: returns the fetch list directly."""
        return self.submit(feed, timeout=timeout,
                           call_kwargs=call_kwargs).result()

    # -- drain / close -------------------------------------------------

    def begin_drain(self) -> None:
        """Stop admissions; the dispatcher finishes in-flight + queued
        work then parks.  SIGNAL-SAFE — it is the PreemptionDrain
        listener, and the handler runs on the main thread, possibly
        while that very thread holds the engine lock inside submit():
        the flag write is a plain GIL-atomic store and the wake-up is a
        best-effort NON-BLOCKING acquire (skipping it only costs the
        dispatcher's bounded park, <= _IDLE_PARK_S, before it sees the
        flag)."""
        self._closed = True
        if self._cond.acquire(blocking=False):
            try:
                self._cond.notify_all()
            finally:
                self._cond.release()

    def drain(self, timeout: Optional[float] = None) -> bool:
        """begin_drain() then wait for the queue and in-flight batch to
        finish.  Returns True when fully drained (timeout=0 polls)."""
        self.begin_drain()
        deadline = (time.perf_counter() + timeout
                    if timeout is not None else None)
        with self._cond:
            while self._queue or self._inflight:
                wait = None
                if deadline is not None:
                    wait = deadline - time.perf_counter()
                    if wait <= 0:
                        return False
                self._cond.wait(wait)
        return True

    # how long close() waits for the dispatcher thread to exit; a join
    # that outlasts this surfaces as stats()["close_timed_out"]
    _JOIN_TIMEOUT_S = 5.0

    def close(self, timeout: Optional[float] = None) -> None:
        """Drain, stop the dispatcher thread, and join it.  If the
        drain timed out, whatever is still queued fails with
        EngineClosedError — a stopped dispatcher must never leave a
        future unresolved (callers block in .result()).  A dispatcher
        that outlives the join (a backend call that never returns) is
        logged and surfaced as stats()['close_timed_out'] instead of
        close() returning as if the shutdown completed cleanly."""
        self.drain(timeout)
        with self._cond:
            self._stopped = True
            leftovers, self._queue = self._queue, []
            self._cond.notify_all()
        for r in leftovers:  # outside the lock: done-callbacks may reenter
            self._fail(r, EngineClosedError(
                f"engine '{self.name}' closed before this request was "
                "dispatched (drain timed out)"))
        self._thread.join(timeout=self._JOIN_TIMEOUT_S)
        if self._thread.is_alive():
            with self._lock:
                self._close_timed_out = True
            _log.warning(
                "engine '%s': dispatcher thread still alive %.1fs after "
                "close() — a backend dispatch is stuck; its batch's "
                "futures remain pending", self.name, self._JOIN_TIMEOUT_S)

    def attach_drain(self, drain) -> "Engine":
        """Wire a resilience.PreemptionDrain: its SIGTERM/SIGINT notice
        triggers begin_drain(), so a preemption stops admissions while
        queued and in-flight batches complete."""
        drain.on_request(self.begin_drain)
        return self

    def __enter__(self) -> "Engine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    @property
    def draining(self) -> bool:
        return self._closed

    # -- introspection -------------------------------------------------

    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    def _counters_locked(self) -> Dict[str, int]:
        return {
            "distinct_shapes": len(self._shapes_seen),
            "miss": self._shape_misses,
            "hit": self._shape_hits,
        }

    def compile_counters(self) -> Dict[str, int]:
        """Serving-side compile accounting: distinct batch shapes ever
        dispatched ('miss' = first sight), bounded by len(buckets) for a
        bucketed engine no matter the request mix."""
        with self._lock:
            return self._counters_locked()

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            batches = self._dispatched_batches
            return {
                "batches": batches,
                "rows": self._dispatched_rows,
                "mean_occupancy": (self._occupancy_sum / batches
                                   if batches else 0.0),
                "queue_depth": len(self._queue),
                "buckets": self.ladder.buckets,
                "bucket_reason": self.bucket_reason,
                "internal_errors": self._internal_errors,
                "breaker_trips": self._breaker_trips,
                "dispatcher_restarts": self._dispatcher_restarts,
                "shed": self._shed,
                "close_timed_out": self._close_timed_out,
                "replica_killed": self._replica_killed,
                **self._counters_locked(),
            }

    # -- dispatcher ----------------------------------------------------

    # longest a truly idle dispatcher parks before re-checking the
    # engine weakref in _dispatch_entry — bounds both abandoned-engine
    # thread lifetime and how long close() can lag an empty engine
    _IDLE_PARK_S = 0.5

    def _take_batch(self) -> Tuple[Optional[List[Request]], List[Request]]:
        """Called under the lock.  Pop the next dispatchable batch (or
        None to keep waiting) and the expired requests removed from the
        queue.  Expired futures are completed by the CALLER outside the
        lock: Future.set_exception runs done-callbacks synchronously,
        and a callback touching the engine from under its own lock
        would deadlock the dispatcher."""
        now = time.perf_counter()
        expired = [r for r in self._queue if r.expired(now)]
        if expired:
            self._queue = [r for r in self._queue if not r.expired(now)]
        if not self._queue:
            return None, expired
        if not self.ladder.buckets:
            return [self._queue.pop(0)], expired  # pass-through: 1 at a time
        # greedy FIFO pack up to the largest bucket
        batch: List[Request] = []
        rows = 0
        for r in self._queue:
            if rows + r.rows > self.ladder.max_bucket:
                break
            batch.append(r)
            rows += r.rows
        full = rows >= self.ladder.max_bucket or len(batch) < len(self._queue)
        oldest_wait = now - batch[0].enqueued_at
        if full or oldest_wait >= self.config.max_wait_s or self._closed:
            del self._queue[:len(batch)]
            return batch, expired
        return None, expired

    def _wait_time(self) -> Optional[float]:
        """Called under the lock: how long the dispatcher may sleep —
        until the oldest request's batch-fill window or the earliest
        deadline, whichever is sooner."""
        if not self._queue:
            return None  # idle: park (bounded by _IDLE_PARK_S)
        now = time.perf_counter()
        oldest = self._queue[0].enqueued_at
        wait = max(0.0, self.config.max_wait_s - (now - oldest))
        for r in self._queue:
            if r.deadline is not None:
                wait = min(wait, max(0.0, r.deadline - now))
        return wait

    def _finish_trace(self, req: Request, outcome: str, t_end: float,
                      dispatch: Optional[Tuple[float, float, dict]] = None,
                      ) -> bool:
        """Close one request's span tree: the queue-wait span on the
        SUBMITTING thread, an optional (t0, t1, args) dispatch span on
        the calling thread, then the tail-sampling decision.  Returns
        whether the trace was kept — the one shape every completion
        path (success, batch failure, timeout, close) shares."""
        rt = req.trace
        if rt is None:
            return False
        q_end = dispatch[0] if dispatch is not None else t_end
        rt.event("request.queued", req.enqueued_at, q_end,
                 tid=rt.tid, thread_name=rt.thread_name)
        if dispatch is not None:
            rt.event("request.dispatch", dispatch[0], dispatch[1],
                     **dispatch[2])
        return _rtrace.default_request_tracer().finish(
            rt, outcome=outcome, t_end=t_end)

    def _fail(self, req: Request, exc: Exception) -> None:
        """Complete a future exceptionally; never call under the lock."""
        if req.trace is not None and _flags._VALUES["FLAGS_observability"]:
            exc.trace_id = req.trace_id
            outcome = ("timeout" if isinstance(exc, RequestTimeoutError)
                       else "closed")
            self._flight_record(
                "request_fail", outcome=outcome,
                trace_id=req.trace_id, error=type(exc).__name__)
            self._finish_trace(req, outcome, time.perf_counter())
        if req.future.set_running_or_notify_cancel():
            req.future.set_exception(exc)
        if _flags._VALUES["FLAGS_observability"] and isinstance(
                exc, RequestTimeoutError):
            _smetrics.record_timeout()

    def _dispatch_cycle(self) -> bool:
        """One dispatcher iteration: take (or wait for) a batch, fail
        whatever expired, run the batch.  Returns False once stopped."""
        # chaos: a raise HERE is outside every protected region — the
        # dispatcher thread dies and the supervisor must restart it
        _finject.serve_dispatch_raise("thread")
        # chaos: replica kill — the dispatcher dies and the supervisor
        # must NOT restart it (a dead process has no supervisor); fires
        # between batches so no in-flight work is lost, only queued
        # requests fail over
        if _finject.serve_replica_kill(self.replica or self.name):
            with self._lock:
                self._replica_killed = True
            raise RuntimeError(
                f"faultinject: replica {self.replica or self.name} "
                "killed")
        with self._cond:
            if self._stopped:
                self._cond.notify_all()
                return False
            batch, expired = self._take_batch()
            if batch is None:
                if self._closed and not self._queue:
                    self._cond.notify_all()  # wake drain() waiters
                if not expired:
                    wait = self._wait_time()
                    self._cond.wait(self._IDLE_PARK_S if wait is None
                                    else min(wait, self._IDLE_PARK_S))
            else:
                self._inflight = len(batch)
        now = time.perf_counter()
        for r in expired:
            self._fail(r, RequestTimeoutError(
                f"request expired after {now - r.enqueued_at:.3f}s in "
                f"queue (deadline {r.deadline - r.enqueued_at:.3f}s)"))
        if batch is None:
            return True
        try:
            self._dispatch(batch)
        finally:
            with self._cond:
                self._inflight = 0
                self._cond.notify_all()
        return True

    def _dispatch(self, batch: List[Request]) -> None:
        obs_on = _flags._VALUES["FLAGS_observability"]
        # t0 always: the batch-latency ring feeds deadline shedding
        t0 = time.perf_counter()
        if obs_on:
            self._flight_record(
                "dispatch", n_requests=len(batch),
                trace_ids=[r.trace_id for r in batch])
        try:
            _finject.serve_slow_step()
            _finject.serve_dispatch_raise("batch")
            if not self.ladder.buckets:
                req = batch[0]
                outs = self.backend(req.feed, **(req.call_kwargs or {}))
                # real feed shapes, not a constant: an executor backend
                # re-traces per shape, and compile_counters must say so
                self._note_shape(tuple(sorted(
                    (n, tuple(getattr(v, "shape", ()) or ()))
                    for n, v in req.feed.items())))
                if req.future.set_running_or_notify_cancel():
                    req.future.set_result(outs)
                rows = bucket = 1
            else:
                rows = sum(r.rows for r in batch)
                bucket = self.ladder.bucket_for(rows)
                feed_names = self.backend.feed_names or sorted(batch[0].feed)
                feed = coalesce(batch, feed_names, bucket)
                self._note_shape(
                    tuple((n,) + tuple(feed[n].shape) for n in feed_names))
                outs = self.backend(feed)
                scatter(batch, outs)
        except Exception as e:  # noqa: BLE001 — backend failure fails the batch
            # pass-through mode forwards ONE request's own feed/kwargs
            # verbatim, so a raise there is that request's error: the
            # future gets the ORIGINAL exception and the breaker is not
            # advanced — one bad client must not open the breaker on
            # everyone (the request-level blast radius).  A bucketed
            # dispatch serves many requests: the failure is the
            # engine's, wrapped as EngineInternalError and counted
            # toward the breaker.
            batched = bool(self.ladder.buckets)
            err = EngineInternalError(e) if batched else e
            if obs_on:
                # typed errors carry the trace ids they failed:
                # EngineInternalError serves a whole batch, so it gets
                # the list (and the first id on .trace_id for the
                # common single-request case); a pass-through error is
                # one request's own and gets its id directly
                try:
                    err.trace_ids = [r.trace_id for r in batch]
                    err.trace_id = batch[0].trace_id
                except AttributeError:
                    pass  # a __slots__ exception from a backend:
                    # losing the annotation must not kill the dispatcher
                self._flight_record(
                    "batch_fail",
                    error=f"{type(e).__name__}: {e}",
                    trace_ids=[r.trace_id for r in batch])
            # count BEFORE resolving futures: a caller that catches the
            # batch error and immediately checks health()/submits must
            # see the breaker already advanced
            self._note_internal_error(e, trip=batched)
            # failed dispatches are service-time evidence too: without
            # them a slow-failing outage would leave the shed estimator
            # trusting a stale fast-success p50
            now = time.perf_counter()
            self._batch_lat.add(t0, now)
            if obs_on:
                for r in batch:
                    if r.trace is None:
                        continue
                    # scatter() may have resolved the first futures
                    # before the raise: those requests SUCCEEDED from
                    # their callers' view and must not be error-labeled
                    # (or force-kept) in the trace
                    ok = False
                    if r.future.done():
                        try:
                            ok = r.future.exception() is None
                        except Exception:  # cancelled
                            ok = False
                    kept = self._finish_trace(
                        r, "ok" if ok else "error", now,
                        dispatch=(t0, now, {} if ok else
                                  {"error": type(e).__name__}))
                    if ok:
                        _smetrics.record_request_latency(
                            now - r.enqueued_at,
                            trace_id=r.trace_id if kept else None)
            for r in batch:
                if r.future.done():
                    continue  # scatter resolved it before the raise
                try:
                    r.future.set_exception(err)
                except Exception:  # cancelled between check and set
                    pass
            if obs_on:
                _smetrics.record_batch_error()
            return
        now = time.perf_counter()
        with self._lock:
            self._dispatched_batches += 1
            self._dispatched_rows += rows
            self._occupancy_sum += rows / float(bucket)
            # a successful dispatch is the breaker's close/probe signal
            breaker_was_open = self._breaker_open_until != 0.0
            self._consecutive_errors = 0
            self._breaker_open_until = 0.0
            self._last_dispatch_ok = now
        self._batch_lat.add(t0, now)
        if obs_on:
            if breaker_was_open:
                self._flight_record("breaker_close")
            _smetrics.record_batch(
                bucket=bucket, rows=rows, latency_s=now - t0)
            for r in batch:
                if r.trace is not None:
                    r.trace.annotate(rows=r.rows, bucket=bucket)
                kept = self._finish_trace(
                    r, "ok", now, dispatch=(t0, now, {"bucket": bucket}))
                # exemplars only reference KEPT traces — a link into
                # the merged trace must resolve
                _smetrics.record_request_latency(
                    now - r.enqueued_at,
                    trace_id=r.trace_id if kept else None)

    def _note_shape(self, key: Tuple) -> None:
        with self._lock:
            if key in self._shapes_seen:
                self._shape_hits += 1
            else:
                self._shapes_seen.add(key)
                self._shape_misses += 1

    # -- supervision / breaker -----------------------------------------

    def _note_internal_error(self, exc: BaseException,
                             trip: bool = True) -> None:
        """Count one failed dispatch; trip the breaker after
        breaker_threshold consecutive failures.  trip=False (the
        pass-through request-error path) counts the total but leaves the
        breaker streak alone — a per-request failure is not an engine
        health signal."""
        now = time.perf_counter()
        with self._lock:
            self._internal_errors += 1
            self._last_error = f"{type(exc).__name__}: {exc}"
            if not trip:
                return
            self._consecutive_errors += 1
            if (self._consecutive_errors >= self.config.breaker_threshold
                    and self._breaker_open_until <= now):
                # closed/half-open -> open (a re-failed probe re-trips)
                self._breaker_open_until = (
                    now + self.config.breaker_cooldown_s)
                self._breaker_trips += 1
                tripped = True
            else:
                tripped = False
        if tripped:
            _log.warning(
                "engine '%s': circuit breaker OPEN after %d consecutive "
                "dispatch failures (last: %s); fast-failing submits for "
                "%.2fs", self.name, self.config.breaker_threshold,
                self._last_error, self.config.breaker_cooldown_s)
            if _flags._VALUES["FLAGS_observability"]:
                _smetrics.record_breaker_trip()
                # the black box: a breaker trip IS the incident — dump
                # the last N lifecycle events as a JSONL artifact
                fl = _flight.default_flight()
                fl.record("breaker_open", engine=self.name,
                          consecutive_errors=self.config.breaker_threshold,
                          last_error=self._last_error,
                          cooldown_s=self.config.breaker_cooldown_s)
                try:
                    fl.dump("breaker_trip")
                except OSError as e:  # an unwritable dir must not
                    _log.warning(     # poison the dispatch path
                        "flight-recorder dump failed: %s", e)

    def _on_dispatcher_death(self, exc: BaseException) -> None:
        """Supervisor: the dispatcher thread died outside every
        protected region.  Restart it with the queue preserved (the
        queue lives on the engine, not the thread) unless the engine is
        already stopped — or chaos-killed (FAULT_SERVE_REPLICA_KILL):
        a killed replica process has no supervisor, so the engine goes
        BROKEN and its queued requests fail typed for callers (the
        router, serve_bench --chaos --replicas) to fail over."""
        self._note_internal_error(exc)
        with self._cond:
            if self._replica_killed:
                self._stopped = True
                leftovers, self._queue = self._queue, []
                self._cond.notify_all()
            elif self._stopped:
                self._cond.notify_all()
                return
            else:
                leftovers = None
                queued = len(self._queue)
                restarted = self._restart_dead_dispatcher_locked()
        if leftovers is not None:
            _log.warning(
                "engine '%s': replica killed by chaos; failing %d "
                "queued requests over to survivors", self.name,
                len(leftovers))
            if _flags._VALUES["FLAGS_observability"]:
                self._flight_record(
                    "replica_kill", queued=len(leftovers),
                    error=f"{type(exc).__name__}: {exc}")
            for r in leftovers:  # outside the lock: done-callbacks
                self._fail(r, EngineInternalError(exc))
            return
        if not restarted:  # another dispatcher is alive already
            return
        _log.warning(
            "engine '%s': dispatcher thread died (%s: %s); restarted "
            "with %d queued requests preserved", self.name,
            type(exc).__name__, exc, queued)
        if _flags._VALUES["FLAGS_observability"]:
            _smetrics.record_dispatcher_restart()
            self._flight_record(
                "dispatcher_restart",
                error=f"{type(exc).__name__}: {exc}", queued=queued)

    # -- health ---------------------------------------------------------

    def attach_pool(self, pool) -> "Engine":
        """Report a KVCachePool's utilization in health() — for engines
        fronting a decode loop."""
        self._pool = pool
        return self

    def health(self) -> Dict[str, Any]:
        """One operator-facing snapshot of engine liveness:

        - state: SERVING (healthy), DEGRADED (failing dispatches or a
          near-full queue, still admitting), DRAINING (no admissions,
          finishing queued work), BROKEN (breaker open, or the
          dispatcher is dead)
        - queue/breaker/dispatcher/shed/last-dispatch detail backing it

        Exported through observability gauges when FLAGS_observability
        is on."""
        now = time.perf_counter()
        with self._lock:
            depth = len(self._queue)
            cap = self.config.queue_depth
            breaker_open = self._breaker_open_until > now
            half_open = (self._breaker_open_until != 0.0
                         and not breaker_open)
            alive = self._thread.is_alive()
            last_ok = self._last_dispatch_ok
            snap = {
                "queue_depth": depth,
                "queue_capacity": cap,
                "inflight": self._inflight,
                "breaker": {
                    "state": ("open" if breaker_open
                              else "half_open" if half_open else "closed"),
                    "consecutive_errors": self._consecutive_errors,
                    "threshold": self.config.breaker_threshold,
                    "trips": self._breaker_trips,
                    "cooldown_remaining_s": max(
                        0.0, self._breaker_open_until - now),
                    "last_error": self._last_error,
                },
                "internal_errors": self._internal_errors,
                "last_dispatch_age_s": (
                    now - last_ok if last_ok is not None else None),
                "dispatcher_alive": alive,
                "dispatcher_restarts": self._dispatcher_restarts,
                "shed": self._shed,
                "close_timed_out": self._close_timed_out,
                # the admission latency ring the shed estimator reads —
                # operators see the same numbers shedding decides from
                "batch_latency_p50_s": self._batch_lat_p50_cached(),
                "batch_latency_p99_s": self._batch_lat_p99_cached(),
                "batch_latency_window": min(self._batch_lat.count,
                                            self._batch_lat.capacity),
            }
            draining = self._closed
            degraded = (self._consecutive_errors > 0
                        or depth >= 0.8 * cap)
            stopped = self._stopped
            killed = self._replica_killed
        if breaker_open or killed or (not alive and not stopped):
            state = "BROKEN"
        elif draining:
            state = "DRAINING"
        elif degraded:
            state = "DEGRADED"
        else:
            state = "SERVING"
        snap["state"] = state
        # atomic read-and-swap: concurrent health() pollers must see
        # each state edge exactly once (one BROKEN transition = one
        # flight dump, not one per poller)
        with self._lock:
            prev = self._last_health_state
            self._last_health_state = state
        if _flags._VALUES["FLAGS_observability"] and state != prev:
            fl = _flight.default_flight()
            fl.record("health", engine=self.name,
                      state=state, previous=prev)
            if state == "BROKEN":
                # entering BROKEN is the other dump trigger (a dead
                # dispatcher reaches here without a breaker trip)
                try:
                    fl.dump("health_broken")
                except OSError as e:
                    _log.warning(
                        "flight-recorder dump failed: %s", e)
        if self._pool is not None:
            st = self._pool.stats()
            snap["pool"] = {
                "used_pages": st["used_pages"],
                "num_pages": st["num_pages"],
                "utilization": st["used_pages"] / float(st["num_pages"]),
            }
        else:
            snap["pool"] = None
        if _flags._VALUES["FLAGS_observability"]:
            _smetrics.record_health(
                state, depth,
                breaker_open=breaker_open,
                pool_utilization=(snap["pool"] or {}).get("utilization"),
                pool=getattr(self._pool, "name", "kv"),
                replica=self.replica)
        return snap


def _dispatch_entry(ref: "weakref.ref") -> None:
    """Dispatcher thread body.  Holds the engine STRONGLY only while
    running one cycle; between cycles only the weakref survives, so an
    engine dropped without close() becomes collectable and this thread
    exits on the next _IDLE_PARK_S heartbeat instead of pinning the
    engine (and its backend/executor/scope) forever.

    A raise escaping the cycle (batch failures never do — _dispatch
    contains them) hands off to the engine's supervisor hook, which
    restarts the dispatcher with the queue preserved."""
    while True:
        eng = ref()
        if eng is None:
            return
        try:
            alive = eng._dispatch_cycle()
        except BaseException as e:  # noqa: BLE001 — supervisor restarts
            eng._on_dispatcher_death(e)
            return
        if not alive:
            return
        del eng
