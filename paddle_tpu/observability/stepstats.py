"""StepStats ring buffer + the perf-regression gate.

`StepStats` keeps the last K step wall times (either executor records
every `executor.step` span's duration when FLAGS_observability is on) and
answers
rolling p50/p90/p99 — the numbers obsdump renders and bench.py reports.

The regression gate compares a current measurement against a banked
baseline (BENCH_BASELINE: a previous bench.py artifact, or any
{metric: value} JSON) and emits a machine-readable pass/fail verdict with
the delta — ROADMAP chip A/B items get banked as artifacts a later run
can be gated on, instead of eyeballed JSON diffs.
"""

from __future__ import annotations

import json
import math
import threading
from typing import Dict, List, Optional

__all__ = ["StepStats", "regression_verdict", "load_baseline_metrics",
           "gate_results"]


class StepStats:
    """Fixed-capacity ring buffer of step durations (seconds)."""

    def __init__(self, capacity: int = 2048):
        if capacity < 1:
            raise ValueError("StepStats capacity must be >= 1")
        self.capacity = int(capacity)
        self._buf: List[float] = [0.0] * self.capacity
        self._n = 0          # total recorded (monotonic)
        self._lock = threading.Lock()

    def record(self, seconds: float) -> None:
        with self._lock:
            self._buf[self._n % self.capacity] = float(seconds)
            self._n += 1

    @property
    def count(self) -> int:
        """Total steps recorded (including ones rotated out of the
        window)."""
        with self._lock:
            return self._n

    def window(self) -> List[float]:
        """The retained samples, oldest -> newest."""
        with self._lock:
            n = self._n
            if n <= self.capacity:
                return self._buf[:n]
            start = n % self.capacity
            return self._buf[start:] + self._buf[:start]

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
        # one lock/copy for the whole summary (count + window taken
        # together so concurrent record()s can't skew them apart), one
        # sort serving min/max and every percentile
        with self._lock:
            n = self._n
            if n <= self.capacity:
                w = self._buf[:n]
            else:
                start = n % self.capacity
                w = self._buf[start:] + self._buf[:start]
        if not w:
            return {"count": 0, "window": 0}
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

    def reset(self) -> None:
        with self._lock:
            self._n = 0


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
