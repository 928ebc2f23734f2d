"""Metrics registry: Counter / Gauge / Histogram with labels.

One spine for every number the system reports about itself — executor step
times, compile-cache hits, checkpoint durations, sentinel trips, RPC
retries — replacing the per-subsystem private counters (bench.py timing
dicts, resilience attempt counts, aot_tpu printed tables).

Design constraints, in order:

- **Near-zero overhead when disabled.**  Every instrument method
  (`inc`/`set`/`observe`) starts with one plain dict lookup of
  `FLAGS_observability` and returns; no locks, no allocation, no time
  syscalls are reached on the disabled path.  Tier-1 asserts this
  (tests/test_observability.py).
- **Thread-safe when enabled.**  Hogwild AsyncExecutor threads, async
  checkpoint writers and the elastic trainer all emit concurrently; each
  metric serializes on its own lock.
- **Process-safe aggregation.**  Multi-host runs have one registry per
  process; `dump()` writes a snapshot atomically (write-then-rename) and
  `merge()`/`aggregate_dir()` combine snapshots with well-defined
  semantics (counters/histograms add, gauges last-write-wins by dump
  time) — the multi-host tests merge per-process dumps instead of
  sharing memory.
- **Two export formats.**  `snapshot()` (JSON-able dict, the obsdump/
  report format) and `to_prometheus()` (Prometheus text exposition
  format, scrape-ready).
"""

from __future__ import annotations

import bisect
import json
import math
import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

from .. import flags as _flags

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "default_registry",
    "DEFAULT_BUCKETS",
]

# step-time-shaped default buckets (seconds): sub-ms host dispatch up to
# multi-second compiles, +Inf implicit
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 120.0,
)


def _on() -> bool:
    # direct dict access, no string concat (flags.flag canonicalizes per
    # call) — this is the hot-path gate
    return _flags._VALUES["FLAGS_observability"]


def _label_key(labels: dict) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


def _escape_value(v: str) -> str:
    """Escape a label VALUE per the Prometheus text-format spec:
    backslash, double-quote, and newline.  Trace-id and error-class
    labels flow through here — an unescaped quote in an error message
    would corrupt every sample after it on a scrape."""
    return (v.replace("\\", "\\\\")
             .replace('"', '\\"')
             .replace("\n", "\\n"))


def _fmt_labels(key: Tuple[Tuple[str, str], ...], extra: str = "") -> str:
    parts = [f'{k}="{_escape_value(v)}"' for k, v in key]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


class _Metric:
    """Shared shell: name, help text, per-label-key series under a lock."""

    kind = "untyped"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._lock = threading.Lock()
        self._series: Dict[Tuple, object] = {}

    def _snapshot_series(self) -> List[dict]:
        raise NotImplementedError

    def snapshot(self) -> dict:
        with self._lock:
            series = self._snapshot_series()
        return {"name": self.name, "type": self.kind, "help": self.help,
                "series": series}


class Counter(_Metric):
    """Monotonically increasing float per label set."""

    kind = "counter"

    def inc(self, amount: float = 1.0, **labels) -> None:
        if not _on():
            return
        key = _label_key(labels)
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + float(amount)

    def value(self, **labels) -> float:
        with self._lock:
            return float(self._series.get(_label_key(labels), 0.0))

    def _snapshot_series(self) -> List[dict]:
        return [{"labels": dict(k), "value": v}
                for k, v in sorted(self._series.items())]

    def _merge_series(self, series: List[dict]) -> None:
        with self._lock:
            for s in series:
                key = _label_key(s.get("labels", {}))
                self._series[key] = (
                    self._series.get(key, 0.0) + float(s["value"]))

    def _prom(self, out: List[str]) -> None:
        with self._lock:
            items = sorted(self._series.items())
        for key, v in items:
            out.append(f"{self.name}_total{_fmt_labels(key)} {_num(v)}")

    def _prom_name(self) -> str:
        return self.name + "_total"


class Gauge(_Metric):
    """Last-written value per label set (plus its write wall time, so a
    cross-process merge can keep the newest writer's value)."""

    kind = "gauge"

    def set(self, value: float, **labels) -> None:
        if not _on():
            return
        key = _label_key(labels)
        with self._lock:
            self._series[key] = (float(value), time.time())

    def inc(self, amount: float = 1.0, **labels) -> None:
        if not _on():
            return
        key = _label_key(labels)
        with self._lock:
            cur = self._series.get(key, (0.0, 0.0))[0]
            self._series[key] = (cur + float(amount), time.time())

    def dec(self, amount: float = 1.0, **labels) -> None:
        self.inc(-amount, **labels)

    def set_max(self, value: float, **labels) -> None:
        """Monotonic high-water mark: keep max(current, value), decided
        under the metric lock (a read-then-set from racing threads could
        move a watermark backwards)."""
        if not _on():
            return
        value = float(value)
        key = _label_key(labels)
        with self._lock:
            cur = self._series.get(key)
            if cur is None or cur[0] < value:
                self._series[key] = (value, time.time())

    def value(self, **labels) -> Optional[float]:
        with self._lock:
            got = self._series.get(_label_key(labels))
        return None if got is None else float(got[0])

    def _snapshot_series(self) -> List[dict]:
        return [{"labels": dict(k), "value": v, "written_at": t}
                for k, (v, t) in sorted(self._series.items())]

    def _merge_series(self, series: List[dict]) -> None:
        with self._lock:
            for s in series:
                key = _label_key(s.get("labels", {}))
                t = float(s.get("written_at", 0.0))
                if key not in self._series or self._series[key][1] <= t:
                    self._series[key] = (float(s["value"]), t)

    def _prom(self, out: List[str]) -> None:
        with self._lock:
            items = sorted(self._series.items())
        for key, (v, _) in items:
            out.append(f"{self.name}{_fmt_labels(key)} {_num(v)}")

    def _prom_name(self) -> str:
        return self.name


class _HistSeries:
    __slots__ = ("counts", "sum", "count", "min", "max", "exemplars")

    def __init__(self, n_buckets: int):
        self.counts = [0] * n_buckets  # per-bucket (non-cumulative)
        self.sum = 0.0
        self.count = 0
        self.min = math.inf
        self.max = -math.inf
        # per-bucket OpenMetrics exemplars, allocated lazily on first
        # exemplar-carrying observation: [{labels, value, ts} | None]
        self.exemplars = None


class Histogram(_Metric):
    """Bucketed distribution per label set; also tracks min/max/sum."""

    kind = "histogram"

    def __init__(self, name: str, help: str = "",
                 buckets: Optional[Sequence[float]] = None):
        super().__init__(name, help)
        bs = tuple(sorted(buckets)) if buckets else DEFAULT_BUCKETS
        self.buckets: Tuple[float, ...] = bs
        self._n = len(bs) + 1  # +Inf bucket

    def observe(self, value: float, exemplar: Optional[dict] = None,
                **labels) -> None:
        """Record one observation.  `exemplar` optionally attaches
        OpenMetrics exemplar labels (e.g. {"trace_id": ...}) to the
        bucket this value lands in — last writer wins per bucket, so
        the p99 bucket always links to a RECENT trace that put a sample
        there (`to_openmetrics()` renders them; the classic
        `to_prometheus()` exposition ignores them)."""
        if not _on():
            return
        value = float(value)
        key = _label_key(labels)
        idx = bisect.bisect_left(self.buckets, value)
        with self._lock:
            s = self._series.get(key)
            if s is None:
                s = self._series[key] = _HistSeries(self._n)
            s.counts[idx] += 1
            s.sum += value
            s.count += 1
            if value < s.min:
                s.min = value
            if value > s.max:
                s.max = value
            if exemplar:
                if s.exemplars is None:
                    s.exemplars = [None] * self._n
                s.exemplars[idx] = {
                    "labels": {k: str(v) for k, v in exemplar.items()},
                    "value": value,
                    "ts": time.time(),
                }

    def series_summary(self, **labels) -> Optional[dict]:
        with self._lock:
            s = self._series.get(_label_key(labels))
            if s is None:
                return None
            return self._summarize(s)

    def _summarize(self, s: _HistSeries) -> dict:
        out = {
            "count": s.count, "sum": s.sum,
            "min": None if s.count == 0 else s.min,
            "max": None if s.count == 0 else s.max,
            "buckets": [[le, c] for le, c in
                        zip(list(self.buckets) + ["+Inf"], s.counts)],
        }
        if s.exemplars is not None:
            # process-local debugging aid: merge()/aggregate_dir ignore
            # them (a cross-process "last exemplar" has no meaning)
            out["exemplars"] = [
                None if e is None else dict(e) for e in s.exemplars]
        return out

    def _snapshot_series(self) -> List[dict]:
        return [dict(labels=dict(k), **self._summarize(s))
                for k, s in sorted(self._series.items())]

    def _merge_series(self, series: List[dict]) -> None:
        with self._lock:
            for rec in series:
                key = _label_key(rec.get("labels", {}))
                s = self._series.get(key)
                if s is None:
                    s = self._series[key] = _HistSeries(self._n)
                incoming = [c for _, c in rec["buckets"]]
                incoming_les = [le for le, _ in rec["buckets"]]
                want_les = list(self.buckets) + ["+Inf"]
                if incoming_les != want_les:
                    # equal-length but different boundaries would add
                    # counts positionally into the wrong distribution
                    raise ValueError(
                        f"histogram {self.name}: merging snapshot with "
                        f"buckets {incoming_les} into {want_les}")
                s.counts = [a + b for a, b in zip(s.counts, incoming)]
                s.sum += float(rec["sum"])
                s.count += int(rec["count"])
                if rec.get("min") is not None:
                    s.min = min(s.min, float(rec["min"]))
                if rec.get("max") is not None:
                    s.max = max(s.max, float(rec["max"]))

    def _prom(self, out: List[str], exemplars: bool = False) -> None:
        with self._lock:
            items = [(k, self._summarize(s))
                     for k, s in sorted(self._series.items())]
        for key, s in items:
            cum = 0
            ex = s.get("exemplars") if exemplars else None
            for i, (le, c) in enumerate(s["buckets"]):
                cum += c
                le_s = "+Inf" if le == "+Inf" else _num(le)
                extra = 'le="%s"' % le_s
                line = f"{self.name}_bucket{_fmt_labels(key, extra)} {cum}"
                e = ex[i] if ex else None
                if e is not None:
                    # OpenMetrics exemplar: `# {labels} value timestamp`
                    elab = ",".join(
                        f'{k}="{_escape_value(v)}"'
                        for k, v in sorted(e["labels"].items()))
                    line += (f" # {{{elab}}} {_num(e['value'])} "
                             f"{e['ts']:.3f}")
                out.append(line)
            out.append(f"{self.name}_sum{_fmt_labels(key)} {_num(s['sum'])}")
            out.append(f"{self.name}_count{_fmt_labels(key)} {s['count']}")

    def _prom_name(self) -> str:
        return self.name


def _num(v: float) -> str:
    f = float(v)
    return str(int(f)) if f == int(f) and abs(f) < 1e15 else repr(f)


class MetricsRegistry:
    """Get-or-create home for metrics; snapshot / Prometheus / merge."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[str, _Metric] = {}

    def _get_or_create(self, cls, name: str, help: str, **kwargs):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, help, **kwargs)
            elif not isinstance(m, cls):
                raise TypeError(
                    f"metric {name!r} already registered as {m.kind}, "
                    f"requested {cls.kind}")
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get_or_create(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get_or_create(Gauge, name, help)

    def histogram(self, name: str, help: str = "",
                  buckets: Optional[Sequence[float]] = None) -> Histogram:
        h = self._get_or_create(Histogram, name, help, buckets=buckets)
        if buckets is not None and tuple(sorted(buckets)) != h.buckets:
            # silently binning into someone else's layout would corrupt
            # the distribution with no error (the kind-mismatch and
            # merge paths already raise — be consistent)
            raise ValueError(
                f"histogram {name!r} already registered with buckets "
                f"{h.buckets}, requested {tuple(sorted(buckets))}")
        return h

    def metrics(self) -> List[_Metric]:
        with self._lock:
            return [self._metrics[n] for n in sorted(self._metrics)]

    def reset(self) -> None:
        """Drop every metric (tests; fresh runs sharing one process)."""
        with self._lock:
            self._metrics.clear()

    # -- export --------------------------------------------------------
    def snapshot(self) -> dict:
        return {
            "version": 1,
            "wall_time": time.time(),
            "process_index": _process_index(),
            "metrics": [m.snapshot() for m in self.metrics()],
        }

    def to_prometheus(self) -> str:
        out: List[str] = []
        for m in self.metrics():
            out.append(f"# HELP {m._prom_name()} {m.help}")
            out.append(f"# TYPE {m._prom_name()} {m.kind}")
            m._prom(out)
        return "\n".join(out) + ("\n" if out else "")

    def to_openmetrics(self) -> str:
        """OpenMetrics text exposition: same sample lines as
        `to_prometheus()` but with metric-family names on the TYPE/HELP
        lines (`steps` not `steps_total`), histogram-bucket exemplars
        (`... # {trace_id="..."} value ts` — the p99 bucket links to
        the trace that landed there), and the mandatory `# EOF`
        terminator.  `export_run` writes this flavor as metrics.prom."""
        out: List[str] = []
        for m in self.metrics():
            out.append(f"# TYPE {m.name} {m.kind}")
            if m.help:
                out.append(f"# HELP {m.name} {m.help}")
            if isinstance(m, Histogram):
                m._prom(out, exemplars=True)
            else:
                m._prom(out)
        out.append("# EOF")
        return "\n".join(out) + "\n"

    # -- cross-process aggregation ------------------------------------
    def dump(self, path: str) -> str:
        """Write snapshot() atomically (write-then-rename: a reader or a
        concurrent aggregate never sees a torn file)."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.snapshot(), f)
        os.replace(tmp, path)
        return path

    def merge(self, snapshot: dict) -> None:
        """Fold one snapshot() dict in: counters and histograms ADD,
        gauges keep the newest write (by the snapshot's write times)."""
        cls_by_kind = {"counter": Counter, "gauge": Gauge,
                       "histogram": Histogram}
        for rec in snapshot.get("metrics", []):
            cls = cls_by_kind.get(rec.get("type"))
            if cls is None:
                continue
            kwargs = {}
            if cls is Histogram:
                # adopt the incoming bucket layout on first sight
                b = rec.get("series") or []
                if b:
                    kwargs["buckets"] = [
                        le for le, _ in b[0]["buckets"] if le != "+Inf"]
            m = self._get_or_create(cls, rec["name"],
                                    rec.get("help", ""), **kwargs)
            m._merge_series(rec.get("series", []))

    @classmethod
    def aggregate_dir(cls, dirname: str,
                      pattern: str = ".json") -> "MetricsRegistry":
        """Merge every `*<pattern>` snapshot file under `dirname` into a
        fresh registry — the multi-host story: each process dump()s
        `metrics_<pid>.json`, any host aggregates."""
        reg = cls()
        for fn in sorted(os.listdir(dirname)):
            if not fn.endswith(pattern):
                continue
            with open(os.path.join(dirname, fn)) as f:
                reg.merge(json.load(f))
        return reg


def _process_index() -> int:
    try:
        import jax

        return int(jax.process_index())
    except Exception:
        return 0


_default = MetricsRegistry()


def default_registry() -> MetricsRegistry:
    """The process-wide registry every built-in instrument emits into."""
    return _default
