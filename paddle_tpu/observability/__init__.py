"""Unified telemetry spine: metrics registry, trace spans, step stats,
and perf-regression gates — one place every layer reports into.

Before this subsystem each layer reported on itself ad hoc: bench.py
hand-rolled timing dicts, resilience/ counted retries and sentinel trips
in private state, core/aot_tpu.py printed cost tables, and timeline.py
was a chrome-trace stub with no hot-path consumers.  Now:

- **Metrics** (`metrics.py`): Counter / Gauge / Histogram with labels in
  a process-wide registry; JSON snapshots, Prometheus text exposition,
  atomic per-process dumps with cross-process merge (`aggregate_dir`).
- **Spans** (`tracing.py`): `span("step", step=n)` / `span("ckpt.save")`
  nest per-thread, land in any running jax.profiler session (flag or no
  flag), and export one merged Chrome/Perfetto trace per run with named
  threads and stable tids (timeline.py is rebased onto this writer).  Both
  executors wrap a step's phases in them (`executor.step` over `.plan`,
  `.stage`, `.dispatch`, `.commit`, `.fetch`; core/executor.py::run_step).
- **The set-up log** (`compiles.py`): one record an executable from jax's
  own compile events (trace, lowering, build or load from the persistent
  cache, the cache entry's bytes and what its write evicted) and one first
  run a program the executor had not met.  Always on: set-up is over
  before a reader runs.
- **The step log** (`stepstats.py`): one record a step of either executor,
  all numbers, at the boundaries the `executor.*` spans mark (step start,
  dispatch start and end, fetch start, ready, step end) plus the process's
  CPU time around the wait, under the span's `seq`.  Always on too, and for
  the same reason: a stalled step is over before a reader can ask, and the
  runs it hits are the untraced ones.  Rolling p50/p99 of the step's
  duration come from it; a stalled step is logged as it happens.  Beside
  it the BENCH_BASELINE regression gate bench.py uses to emit pass/fail
  deltas.
- **Request traces** (`requesttrace.py`): per-request trace ids minted
  at Engine.submit(), cross-thread span trees (submit thread ->
  dispatcher -> completion) folded into the same merged trace, kept by
  TAIL-based sampling — slow (>= rolling p99), errored, shed, timed-out
  and quarantined requests keep full detail under
  FLAGS_request_trace_budget.  Latency/TTFT histograms carry
  OpenMetrics exemplars referencing kept trace ids.
- **Flight recorder** (`flight.py`): bounded ring of structured serving
  lifecycle events that auto-dumps JSONL (FLAGS_flight_dir) when the
  circuit breaker trips or engine health enters BROKEN — the black box
  every chaos failure leaves behind.

Two instruments are always on, the set-up log and the step log; a span's
place in a profiler session needs no flag either.  Everything else is
gated on **FLAGS_observability** (env `FLAGS_observability=1` or
`fluid.set_flags({"FLAGS_observability": True})`).  Disabled, every other
instrument returns after one dict lookup and a span is an inert
jax.profiler.TraceAnnotation — no locks, no clock reads, no registry call,
nothing appended, nothing that outlives the with-block; a steady step adds
one record to the step log, eight clock reads in `stepstats.py` and no
allocation, and nothing to the set-up log (tier-1 asserts all of this of
the executor's disabled path).  `FLAGS_observability_cost=native|tpu`
additionally records each compiled program's bytes/step from XLA's cost
model (the `tpu` mode prices the CHIP program via the chip-less AOT
tier, core/aot_tpu.py — a bytes/step measurement loop with no chip).

Artifacts: `export_run(dirname)` writes `metrics.prom`, `metrics.json`,
`trace.json` (Perfetto-loadable) and `report.json` (step-time summary, the
step log and its stalls, the set-up log, regression verdicts);
`tools/obsdump.py` renders a run directory into a human-readable report.
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import List, Optional

from .. import flags as _flags
from .compiles import CompileLog, default_compile_log  # noqa: F401
from .flight import (  # noqa: F401
    FlightRecorder,
    default_flight,
    flight_dir,
)
from .metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
)
from .requesttrace import (  # noqa: F401
    RequestTrace,
    RequestTracer,
    default_request_tracer,
    mint_trace_id,
)
from .stepstats import (  # noqa: F401
    MAX_LINES,
    StepStats,
    gate_results,
    load_baseline_metrics,
    regression_verdict,
)
from .tracing import (  # noqa: F401
    Span,
    Tracer,
    default_tracer,
    span,
    write_chrome_trace,
)

__all__ = [
    "CompileLog",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "RequestTrace",
    "RequestTracer",
    "default_compile_log",
    "default_flight",
    "default_registry",
    "default_request_tracer",
    "flight_dir",
    "mint_trace_id",
    "StepStats",
    "Span",
    "Tracer",
    "default_tracer",
    "span",
    "write_chrome_trace",
    "enabled",
    "enable",
    "disable",
    "step_stats",
    "record_executor_step",
    "record_cost",
    "record_device_memory",
    "export_run",
    "regression_verdict",
    "load_baseline_metrics",
    "gate_results",
    "reset",
]


def enabled() -> bool:
    """Whether FLAGS_observability is on (the one gate every instrument
    checks first)."""
    return _flags._VALUES["FLAGS_observability"]


def enable() -> None:
    _flags.set_flags({"FLAGS_observability": True})


def disable() -> None:
    _flags.set_flags({"FLAGS_observability": False})


_step_stats = StepStats()
default_compile_log().listen()


def step_stats() -> StepStats:
    """The process-wide step log core/executor.py::run_step writes."""
    return _step_stats


def reset() -> None:
    """Clear the default registry, tracer, request tracer, flight
    recorder, set-up log and step log (fresh run in the same process;
    tests)."""
    default_registry().reset()
    default_tracer().clear()
    default_request_tracer().reset()
    default_flight().reset()
    default_compile_log().clear()
    _step_stats.reset()


# -- executor instruments ---------------------------------------------------
# Called from the executors' hot paths ONLY when FLAGS_observability is on
# (core/executor.py performs the flag check so the disabled path never
# enters these); each emits into the default registry.

def record_executor_step(seconds: float, donated: bool,
                         skipped: bool = False) -> None:
    """One step of either executor into the registry: the `executor.step`
    span's duration (plan to the fetched values on the host; with
    return_numpy=False the fetch does not wait and the device's time shows
    up at the caller's sync points), donation status, and whether the
    sentinel skipped the write-back.  (The step log has the step already,
    flag or no flag.)"""
    reg = default_registry()
    reg.histogram(
        "paddle_tpu_executor_step_seconds",
        "wall time of a step (the executor.step span)",
    ).observe(seconds)
    reg.counter(
        "paddle_tpu_executor_steps",
        "executor steps by state-donation status",
    ).inc(donated="1" if donated else "0")
    if skipped:
        reg.counter(
            "paddle_tpu_executor_skipped_steps",
            "steps skipped by the FLAGS_check_numerics sentinel",
        ).inc()


def record_compile_cache(hit: bool) -> None:
    reg = default_registry()
    reg.counter(
        "paddle_tpu_compile_cache",
        "Executor compiled-program cache lookups",
    ).inc(result="hit" if hit else "miss")


def record_cost(cost: dict, program: str,
                platform: str = "native") -> None:
    """XLA cost-model attribution for one compiled program: bytes/step
    and flops/step, labeled by program fingerprint so a flag flip that
    recompiles lands on a separate series: a chip-free A/B loop."""
    reg = default_registry()
    labels = {"program": program, "platform": platform}
    b = cost.get("bytes accessed")
    if b is not None:
        reg.gauge(
            "paddle_tpu_cost_bytes_per_step",
            "XLA cost model: HBM bytes accessed per step",
        ).set(float(b), **labels)
    fl = cost.get("flops")
    if fl is not None:
        reg.gauge(
            "paddle_tpu_cost_flops_per_step",
            "XLA cost model: flops per step",
        ).set(float(fl), **labels)


def record_device_memory(device) -> None:
    """Device-memory watermarks, sampled per step from the device's PJRT
    allocator stats: current bytes in use plus the high-water mark.
    Backends that expose `peak_bytes_in_use` (TPU) report the
    allocator's own watermark; otherwise the gauge keeps a monotonic max
    of the sampled `bytes_in_use`.  Backends without memory_stats (or
    returning nothing — CPU jax) are silently skipped."""
    try:
        stats = device.memory_stats()
    except Exception:
        return
    if not stats:
        return
    reg = default_registry()
    dev = str(getattr(device, "id", device))
    in_use = stats.get("bytes_in_use")
    if in_use is not None:
        reg.gauge(
            "paddle_tpu_device_bytes_in_use",
            "device allocator bytes currently in use",
        ).set(float(in_use), device=dev)
    peak_gauge = reg.gauge(
        "paddle_tpu_device_peak_bytes_in_use",
        "device-memory high-water mark (allocator peak, or the running "
        "max of sampled bytes_in_use when the backend reports no peak)",
    )
    peak = stats.get("peak_bytes_in_use")
    if peak is not None:
        peak_gauge.set(float(peak), device=dev)
    elif in_use is not None:
        # no allocator peak: monotonic max under the metric lock
        # (hogwild threads racing a read-then-set could move the
        # watermark backwards)
        peak_gauge.set_max(float(in_use), device=dev)


# -- run artifacts ----------------------------------------------------------

def merged_spans(include_tracer: bool = True) -> List[Span]:
    """Profiler.record_event spans (+ the observability tracer's spans
    unless include_tracer=False), one list — the single source for the
    'one merged trace per run' export (timeline.export_chrome_trace
    draws from here too, so the _trace tuple-shape knowledge lives in
    exactly one place)."""
    spans = default_tracer().spans() if include_tracer else []
    try:
        from .. import profiler as _profiler

        for rec in _profiler._trace:
            # (name, t0, t1, ident[, thread_name]) — older 4-tuples from
            # in-flight processes still export, just unnamed
            name, t0, t1, ident = rec[0], rec[1], rec[2], rec[3]
            tname = rec[4] if len(rec) > 4 else f"thread-{ident}"
            spans.append(Span(name, t0, t1, ident, tname, cat="host"))
    except Exception:
        pass
    return spans


def export_run(dirname: str, results: Optional[List[dict]] = None,
               baseline_path: Optional[str] = None,
               tolerance: float = 0.05) -> dict:
    """Write the run's telemetry artifacts into `dirname`:

    - metrics.prom  — Prometheus text exposition of the default registry
    - metrics.json  — the same registry as a merge-able JSON snapshot
    - trace.json    — merged Chrome/Perfetto trace (spans + profiler
      events, named threads, stable tids)
    - report.json   — step-time summary (p50/p99), the step log (`steps`:
      stepstats.py's snapshot, every retained record and the stalled
      ones), the set-up log (`setup`: compiles.py's snapshot), optional
      bench results, and regression verdicts vs `baseline_path`

    Where more steps stalled than the step log's WARNING lines told of,
    one more line says how many.

    On multi-process runs EVERY artifact is namespaced `*_<pid>.*` for
    process index > 0 (a shared run dir must never have two processes
    racing non-atomic writes to one file); aggregate the metrics
    snapshots with MetricsRegistry.aggregate_dir.

    Returns the report dict."""
    os.makedirs(dirname, exist_ok=True)
    reg = default_registry()
    pid = 0
    try:
        import jax

        pid = int(jax.process_index())
    except Exception:
        pass
    sfx = "" if pid == 0 else f"_{pid}"
    with open(os.path.join(dirname, f"metrics{sfx}.prom"), "w") as f:
        # OpenMetrics flavor: classic sample lines plus histogram
        # exemplars, so the p99 bucket links to its trace_id
        f.write(reg.to_openmetrics())
    reg.dump(os.path.join(dirname, f"metrics{sfx}.json"))
    n_spans = write_chrome_trace(
        os.path.join(dirname, f"trace{sfx}.json"), merged_spans(), pid=pid)
    report = {
        "version": 1,
        "wall_time": time.time(),
        "step_time": _step_stats.summary(),
        "steps": _step_stats.snapshot(),
        "span_count": n_spans,
        "request_traces": default_request_tracer().stats(),
        "flight_dumps": list(default_flight().dump_paths),
        "setup": default_compile_log().snapshot(),
    }
    if results:
        report["results"] = results
    if baseline_path:
        try:
            report["regression"] = gate_results(
                results or [], baseline_path, tolerance=tolerance)
            report["baseline_path"] = baseline_path
        except (OSError, ValueError, json.JSONDecodeError) as e:
            report["regression_error"] = f"{type(e).__name__}: {e}"
    tmp = os.path.join(dirname, f".report{sfx}.json.tmp")
    with open(tmp, "w") as f:
        json.dump(report, f, indent=2)
    os.replace(tmp, os.path.join(dirname, f"report{sfx}.json"))
    unsaid = _step_stats.stalls_seen - MAX_LINES
    if unsaid > 0:
        logging.getLogger("paddle_tpu").warning(
            "%d more steps stalled than were logged: report%s.json's "
            "`steps.stalls` has those the step log still holds", unsaid, sfx)
    return report
