"""The step log + the perf-regression gate.

`StepStats` is the step log, the second instrument that is ALWAYS on (the
set-up log, compiles.py, is the first, and for the same reason: what it
records is over before a reader can ask; the runs a stalled step hits are
the untraced ones).  One record a call of core/executor.py::run_step, all
numbers, on `time.perf_counter()` (the clock of the set-up log and of
`benchmark/run.py::T_START`), at the boundaries the `executor.*` spans
already mark:

    seq           `executor.step`'s `seq` (the process's steps by number:
                  the same step on the host plane of any profiler session)
    kind          an index into KINDS (-1: not an executor's step, `add`)
    fresh         1 where the step missed the executor's table or the
                  set-up log grew under it (it compiled or loaded)
    t_start       step start = plan start
    t_dispatch    dispatch start (plan and stage lie before it)
    t_dispatched  dispatch end = commit start
    t_fetch       fetch start = commit end
    t_ready       the end of `executor.wait` (jax.block_until_ready)
    t_end         step end: the end of `executor.copy`
    cpu_fetch, cpu_ready   `time.process_time()` at t_fetch and t_ready:
                  how long the PROCESS (every thread of it, the runtime's
                  too) was on a CPU while the step waited

With `return_numpy=False` there is no wait: t_ready and the two cpu fields
are NaN.  A step that raised stays without its later marks.

Written in place into one `array('d')` of capacity x WIDTH allocated once,
the counters in arrays too, so a steady step replaces no Python object and
allocates nothing that outlives it: six reads of one clock, two of the
other, one lock (in `begin`; a stall takes it once more), no registry
call.  Bounded: the newest
`capacity` records, `dropped` in the snapshot; `observability.reset()`
clears it.  A step that outlasts `capacity` later ones (another thread's)
writes its last marks into a record that is no longer its own.

A **stall** is a record whose own time, t_end - t_start, is over the median
own time of the BLOCK records before its block of BLOCK by more than max(5
ms, 5%): the reference is refreshed every BLOCK steps, so a step's check is
one comparison (in `end`).  The caller's time between two calls is in no
step's own time: a training loop that evaluates or checkpoints every so
often has not stalled (the benchmark's readers, whose loop does nothing
between two steps, count it: they work on periods, a start to the next).  A
fresh record and every record before the first whole block have no
reference and are never stalls.  A stall is logged as it ends, once, through
`logging.getLogger("paddle_tpu")` at WARNING, at most MAX_LINES lines until
`reset`; `snapshot()["stalls"]` has every one the ring still holds.

Readers: `summary()` (p50 / p90 / p99 of t_end - t_start: `export_run`'s
`step_time`, tools/obsdump.py, bench.py), `snapshot()` (`export_run`'s
`steps`; the benchmark's five `step_ms_p50` / `stall*.train` metrics
through benchmark/harness/step_log.py, which reads every field), the
WARNING line.  The serving engine and the request tracer keep windows of
their own latencies in the same class through `add(t0, t1)`.

The regression gate compares a current measurement against a banked
baseline (BENCH_BASELINE: a previous bench.py artifact, or any
{metric: value} JSON) and emits a machine-readable pass/fail verdict with
the delta — ROADMAP chip A/B items get banked as artifacts a later run
can be gated on, instead of eyeballed JSON diffs.
"""

from __future__ import annotations

import json
import logging
import math
import threading
import time
from array import array
from typing import Dict, List, Optional

__all__ = ["StepStats", "stall_line", "regression_verdict",
           "load_baseline_metrics", "gate_results"]

FIELDS = ("seq", "kind", "fresh", "t_start", "t_dispatch", "t_dispatched",
          "t_fetch", "t_ready", "t_end", "cpu_fetch", "cpu_ready")
(SEQ, KIND, FRESH, START, DISPATCH, DISPATCHED, FETCH, READY, END,
 CPU_FETCH, CPU_READY) = range(len(FIELDS))
WIDTH = len(FIELDS)
_CPU = CPU_FETCH - FETCH    # from a wall mark to its process_time twin
assert CPU_READY - READY == _CPU
KINDS = ("serial", "spmd")
_KIND = {name: float(i) for i, name in enumerate(KINDS)}
BLOCK = 64                  # steps a reference median is taken over and holds
MAX_LINES = 8               # WARNING lines before the log goes quiet
_NAN = float("nan")
_BLANK = array("d", [_NAN] * WIDTH)
# the two clocks, bound once: a step reads them eight times
_wall = time.perf_counter
_cpu = time.process_time


def limit_over(times: List[float]) -> tuple:
    """(median, the time past which a step has stalled) of one block's
    steps; no number among them, no reference."""
    s = sorted(t for t in times if t == t)
    if not s:
        return _NAN, math.inf
    mid = len(s) // 2
    median = s[mid] if len(s) % 2 else (s[mid - 1] + s[mid]) / 2.0
    return median, median + max(0.005, 0.05 * median)


def _stall(row, median: float) -> dict:
    """One stalled record as plain values, seconds; None where the record
    has no such mark (no wait with `return_numpy=False`)."""
    def span(a, b):
        d = row[b] - row[a]
        return None if d != d else d

    kind = int(row[KIND])
    return {
        "seq": None if row[SEQ] != row[SEQ] else int(row[SEQ]),
        "kind": KINDS[kind] if 0 <= kind < len(KINDS) else None,
        "step_s": span(START, END), "median_s": median,
        "plan_s": span(START, DISPATCH),
        "dispatch_s": span(DISPATCH, DISPATCHED),
        "commit_s": span(DISPATCHED, FETCH),
        "wait_s": span(FETCH, READY),
        "wait_cpu_s": span(CPU_FETCH, CPU_READY),
        "copy_s": span(READY, END),
    }


def stall_line(stall: dict) -> str:
    """The one line a stall is told in: the WARNING as it happens and
    tools/obsdump.py's "== step time ==" print the same."""
    def ms(key):
        v = stall.get(key)
        return "-" if v is None else f"{v * 1e3:.2f}"

    return (f"step {stall['seq']} stalled: {ms('step_s')} ms against a "
            f"median of {ms('median_s')}; plan {ms('plan_s')}, dispatch "
            f"{ms('dispatch_s')}, commit {ms('commit_s')}, wait "
            f"{ms('wait_s')} (the process on a CPU for "
            f"{ms('wait_cpu_s')} of it), copy {ms('copy_s')} ms")


class StepStats:
    """The step log: a fixed-capacity ring of records (module docstring)."""

    def __init__(self, capacity: int = 4096):
        if capacity < 1:
            raise ValueError("StepStats capacity must be >= 1")
        self.capacity = int(capacity)
        self._buf = array("d", [_NAN] * (self.capacity * WIDTH))
        # [records begun, stalls seen, lines logged] and `end`'s
        # reference, in arrays so that a step stores numbers and replaces
        # no object
        self._n = array("q", [0, 0, 0])
        self._ref = array("d", [math.inf, _NAN])
        self._lock = threading.Lock()

    # -- the step's path ----------------------------------------------------

    def begin(self, seq: int, kind: str) -> int:
        """Open a record at step start; the handle `mark`, `mark_cpu` and
        `end` take (the record's offset in the ring)."""
        buf, count = self._buf, self._n
        now = _wall()
        with self._lock:
            n = count[0]
            count[0] = n + 1
            if not n % BLOCK and n and self.capacity > BLOCK:
                self._ref[1], self._ref[0] = limit_over(
                    self._own_times(n - BLOCK, n))
            at = (n % self.capacity) * WIDTH
            buf[at:at + WIDTH] = _BLANK
            buf[at + SEQ] = seq
            buf[at + KIND] = _KIND.get(kind, -1.0)
            buf[at + START] = now
        return at

    def mark(self, at: int) -> None:
        """`at` = handle + field: that boundary is now."""
        self._buf[at] = _wall()

    def mark_cpu(self, at: int) -> None:
        """`mark`, and the process's CPU time beside it (FETCH, READY)."""
        buf = self._buf
        buf[at] = _wall()
        buf[at + _CPU] = _cpu()

    def end(self, rec: int, fresh: bool) -> None:
        """Step end; `fresh`: the step missed the executor's table or the
        set-up log grew under it, so it is no stall and no one's
        reference."""
        buf = self._buf
        now = _wall()
        buf[rec + END] = now
        buf[rec + FRESH] = fresh
        # _ref: [the time past which a step has stalled (inf while there
        # is no reference), the median it came from]
        if now - buf[rec + START] > self._ref[0] and not fresh:
            self._stalled(rec)

    def _own_times(self, lo: int, hi: int) -> List[float]:
        """t_end - t_start of records lo .. hi - 1 by number (NaN where one
        has not ended).  Under the lock."""
        buf, cap = self._buf, self.capacity
        return [buf[at + END] - buf[at + START]
                for at in ((i % cap) * WIDTH for i in range(lo, hi))]

    def _stalled(self, rec: int) -> None:
        """The record at `rec` ran past the reference: count it, and log it
        while lines are left."""
        with self._lock:
            self._n[1] += 1
            if self._n[2] >= MAX_LINES:
                return
            self._n[2] += 1
            stall = _stall(self._buf[rec:rec + WIDTH], self._ref[1])
        logging.getLogger("paddle_tpu").warning(stall_line(stall))

    # -- a window of latencies that are not steps ---------------------------

    def add(self, t0: float, t1: float) -> None:
        """A record of a start and an end alone (the serving engine's batch
        latencies, the request tracer's)."""
        with self._lock:
            n = self._n[0]
            self._n[0] = n + 1
            at = (n % self.capacity) * WIDTH
            self._buf[at:at + WIDTH] = _BLANK
            self._buf[at + KIND] = -1.0
            self._buf[at + START] = t0
            self._buf[at + END] = t1

    # -- readers ------------------------------------------------------------

    @property
    def count(self) -> int:
        """Total records begun (including ones rotated out of the
        window)."""
        with self._lock:
            return self._n[0]

    @property
    def stalls_seen(self) -> int:
        """Stalls met as they happened since the last `reset` (the first
        MAX_LINES of them were logged)."""
        with self._lock:
            return self._n[1]

    def _rows(self) -> tuple:
        """(records begun, the retained records oldest -> newest as
        lists), one copy under the lock."""
        with self._lock:
            n = self._n[0]
            if n <= self.capacity:
                flat = self._buf[:n * WIDTH]
            else:
                cut = (n % self.capacity) * WIDTH
                flat = self._buf[cut:] + self._buf[:cut]
        return n, [list(flat[i:i + WIDTH])
                   for i in range(0, len(flat), WIDTH)]

    def _durations(self) -> tuple:
        """(records begun, the retained records' durations, t_end -
        t_start, oldest -> newest): a record that never ended has none."""
        n, rows = self._rows()
        return n, [d for d in (r[END] - r[START] for r in rows) if d == d]

    def window(self) -> List[float]:
        """The retained records' durations, oldest -> newest."""
        return self._durations()[1]

    @staticmethod
    def _rank(sorted_w: List[float], q: float) -> float:
        """Nearest-rank percentile of an already-sorted non-empty list."""
        n = len(sorted_w)
        return sorted_w[max(0, min(n - 1, math.ceil(q / 100.0 * n) - 1))]

    def percentile(self, q: float) -> Optional[float]:
        """Nearest-rank percentile over the window (q in [0, 100])."""
        w = sorted(self.window())
        return self._rank(w, q) if w else None

    def p50(self) -> Optional[float]:
        return self.percentile(50)

    def p90(self) -> Optional[float]:
        return self.percentile(90)

    def p99(self) -> Optional[float]:
        return self.percentile(99)

    def summary(self) -> dict:
        # one copy for the whole summary (count + window taken together so
        # a concurrent step can't skew them apart), one sort serving
        # min/max and every percentile
        n, w = self._durations()
        if not w:
            return {"count": n, "window": 0}
        last = w[-1]
        w.sort()
        return {
            "count": n,
            "window": len(w),
            "mean_s": sum(w) / len(w),
            "min_s": w[0],
            "max_s": w[-1],
            "last_s": last,
            "p50_s": self._rank(w, 50),
            "p90_s": self._rank(w, 90),
            "p99_s": self._rank(w, 99),
        }

    def snapshot(self) -> dict:
        """The whole log as plain values (`export_run`'s `steps` section,
        the benchmark's harness/step_log.py): `fields` names the columns of
        `records` (oldest first, None for a mark that was not made),
        `kinds` the values of `kind`, `dropped` the records that rotated
        out, `stalls` the stalled ones among those retained (`_stall`'s
        keys), by the rule `end` applies as they happen."""
        n, rows = self._rows()
        first = n - len(rows)   # the number of rows[0] among all begun
        stalls = []
        own = [r[END] - r[START] for r in rows]
        for lo in range(-first % BLOCK, len(rows), BLOCK):
            if lo < BLOCK:
                continue  # no whole block before this one is retained
            median, limit = limit_over(own[lo - BLOCK:lo])
            stalls += [_stall(r, median)
                       for r, t in zip(rows[lo:lo + BLOCK],
                                       own[lo:lo + BLOCK])
                       if t > limit and not r[FRESH]]
        return {
            "fields": list(FIELDS), "kinds": list(KINDS), "count": n,
            "dropped": first, "stalls": stalls,
            "records": [[None if v != v else v for v in r] for r in rows],
        }

    def reset(self) -> None:
        with self._lock:
            self._n[0] = self._n[1] = self._n[2] = 0
            self._ref[0], self._ref[1] = math.inf, _NAN


def regression_verdict(metric: str, baseline: float, current: float,
                       tolerance: float = 0.05,
                       higher_is_better: bool = True) -> dict:
    """Pass/fail comparison of one number against its baseline.

    delta is relative: (current - baseline) / baseline.  With
    higher_is_better (throughput), fail when current < baseline *
    (1 - tolerance); for lower-is-better series (step time), fail when
    current > baseline * (1 + tolerance)."""
    if baseline is None or baseline == 0:
        return {"metric": metric, "verdict": "no_baseline",
                "baseline": baseline, "current": current}
    delta = (current - baseline) / abs(baseline)
    if higher_is_better:
        ok = current >= baseline * (1.0 - tolerance)
    else:
        ok = current <= baseline * (1.0 + tolerance)
    return {
        "metric": metric,
        "baseline": baseline,
        "current": current,
        "delta_pct": round(delta * 100.0, 3),
        "tolerance_pct": round(tolerance * 100.0, 3),
        "higher_is_better": higher_is_better,
        "verdict": "pass" if ok else "fail",
    }


def load_baseline_metrics(path: str) -> Dict[str, float]:
    """{metric: value} from a baseline file.  Accepts (a) a bench.py
    artifact line — primary record + "extra_metrics" list — or (b) a
    plain {metric: value} mapping, or (c) an obsdump report.json (its
    "results" list)."""
    with open(path) as f:
        doc = json.load(f)
    out: Dict[str, float] = {}

    def _take(rec) -> None:
        m, v = rec.get("metric"), rec.get("value")
        if isinstance(m, str) and isinstance(v, (int, float)):
            out[m] = float(v)

    if isinstance(doc, dict) and "metric" in doc:
        _take(doc)
        for rec in doc.get("extra_metrics", []) or []:
            if isinstance(rec, dict):
                _take(rec)
    elif isinstance(doc, dict) and "results" in doc:
        for rec in doc.get("results", []) or []:
            if isinstance(rec, dict):
                _take(rec)
    elif isinstance(doc, dict):
        for m, v in doc.items():
            if isinstance(v, (int, float)):
                out[m] = float(v)
    return out


# metric-name shapes where SMALLER is better — bytes/step cost tables
# (BENCH_COST_ONLY), durations, step times.  Throughputs (the default)
# are higher-is-better.
_LOWER_IS_BETTER_SUFFIXES = ("_bytes_per_step", "_seconds", "_s",
                             "_bytes", "_time")


def metric_higher_is_better(metric: str) -> bool:
    return not str(metric).endswith(_LOWER_IS_BETTER_SUFFIXES)


def gate_results(results: List[dict], baseline_path: str,
                 tolerance: float = 0.05) -> List[dict]:
    """Verdicts for every result whose metric the baseline also has.
    Direction follows the metric's name: throughputs gate on falling
    below baseline, bytes/durations on rising above it."""
    base = load_baseline_metrics(baseline_path)
    verdicts = []
    for rec in results:
        m = rec.get("metric")
        if m in base and isinstance(rec.get("value"), (int, float)):
            verdicts.append(regression_verdict(
                m, base[m], float(rec["value"]), tolerance=tolerance,
                higher_is_better=metric_higher_is_better(m)))
    return verdicts
