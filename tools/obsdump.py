#!/usr/bin/env python
"""Render an observability run directory into a human-readable report.

A run directory is what `observability.export_run(dir)` (or a
FLAGS_observability=1 bench.py run with BENCH_OBS_DIR, or a serve_bench
--obs-dir run) leaves behind:

    metrics.prom     OpenMetrics text exposition (scrape-ready; histogram
                     buckets carry trace-id exemplars)
    metrics.json     registry snapshot (metrics_<pid>.json per process on
                     multi-host runs; this CLI aggregates them all)
    trace.json       merged Chrome/Perfetto trace (load in ui.perfetto.dev)
    report.json      step-time summary + the step log (`steps`: a record
                     a step with its phases' boundaries, and the stalled
                     ones) + the set-up log (every first run
                     of a program with the executables it made: trace,
                     lowering, build or load from the persistent cache)
                     + regression verdicts + request trace sampling stats
    flight_*.jsonl   flight-recorder dumps (breaker trips / BROKEN health)

Besides metrics and step times this renders a PER-REQUEST timeline for
every request trace that survived tail sampling (slowest first; each
span with its thread and offset from the request's start) and the tail
of every flight-recorder dump — the post-incident reading order is
"which request was slow" then "what was the engine doing when it broke".

Usage:
    python tools/obsdump.py <run_dir> [--baseline BENCH.json] [--tol 0.05]
           [--gate] [--requests N] [--flight DUMP.jsonl]

--baseline re-gates the run's results against a banked bench artifact (a
previous bench.py JSON line or a plain {metric: value} mapping), printing
pass/fail deltas.  Exit codes follow the shared CI-gate contract with
tools/lint_programs.py and tools/serve_bench.py (README "CI gates"):
0 clean · 2 usage/environment error · 3 when --gate finds a regression.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _fmt_s(v) -> str:
    if v is None:
        return "-"
    if v < 1e-3:
        return f"{v * 1e6:.0f}us"
    if v < 1.0:
        return f"{v * 1e3:.2f}ms"
    return f"{v:.3f}s"


def _load_report(run_dir: str) -> dict:
    path = os.path.join(run_dir, "report.json")
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {}


def _aggregate_metrics(run_dir: str):
    from paddle_tpu.observability import MetricsRegistry

    has_snap = any(
        fn.startswith("metrics") and fn.endswith(".json")
        for fn in os.listdir(run_dir))
    if not has_snap:
        return None
    reg = MetricsRegistry()
    for fn in sorted(os.listdir(run_dir)):
        if fn.startswith("metrics") and fn.endswith(".json"):
            with open(os.path.join(run_dir, fn)) as f:
                reg.merge(json.load(f))
    return reg


def _print_step_time(report: dict, out) -> None:
    st = report.get("step_time") or {}
    out.write("== step time ==\n")
    if not st.get("count"):
        out.write("  (no steps recorded)\n")
        return
    out.write(f"  steps recorded : {st['count']} "
              f"(window {st['window']})\n")
    for k, label in (("p50_s", "p50"), ("p90_s", "p90"), ("p99_s", "p99"),
                     ("mean_s", "mean"), ("min_s", "min"),
                     ("max_s", "max")):
        out.write(f"  {label:<5}: {_fmt_s(st.get(k))}\n")
    # the step log (observability/stepstats.py): a line a stalled step;
    # a report from before it has no `steps` and ends here
    steps = report.get("steps") or {}
    stalls = steps.get("stalls") or []
    if not stalls:
        return
    from paddle_tpu.observability.stepstats import stall_line

    out.write(f"  stalled: {len(stalls)} of the {len(steps['records'])} "
              f"steps the log holds ({steps['dropped']} dropped)\n")
    for stall in stalls:
        out.write(f"    {stall_line(stall)}\n")


def _print_setup(report: dict, out) -> None:
    """The set-up log (observability/compiles.py): a line a first run, a
    line an executable under the run that paid for it."""
    setup = report.get("setup") or {}
    records = setup.get("records") or []
    out.write("== set-up ==\n")
    if not records and not setup.get("runs"):
        out.write("  (nothing compiled or loaded)\n")
        return
    out.write(f"  persistent cache: {setup.get('cache_dir') or 'off'}; "
              f"{sum(r['cache'] == 'hit' for r in records)} hit, "
              f"{sum(r['cache'] == 'miss' for r in records)} miss, "
              f"{sum(r['cache'] == 'off' for r in records)} not asked; "
              f"{setup.get('dropped', 0)} records dropped\n")

    def line(r):
        size = r.get("entry_bytes")
        evicted = r.get("evicted_bytes")
        return (f"    {r['fun']:<36} trace {_fmt_s(r['trace_s'])} lower "
                f"{_fmt_s(r['lower_s'])} backend {_fmt_s(r['backend_s'])} "
                f"{r['cache']}"
                + (f" load {_fmt_s(r['retrieval_s'])}"
                   if r.get("retrieval_s") is not None else "")
                + (f" entry {size / 1e6:.2f}MB" if size else "")
                + (f" evicted {evicted / 1e6:.2f}MB" if evicted else "")
                + "\n")

    for run in setup.get("runs") or []:
        out.write(f"  first run {run['index']} [{run['kind']}] program "
                  f"{run['program']}: {_fmt_s(run['t1'] - run['t0'])} "
                  f"(feed {run['n_feed']}, fetch {run['n_fetch']}, state "
                  f"{run['n_state']})\n")
        for r in records:
            if r["run"] == run["index"]:
                out.write(line(r))
    rest = [r for r in records if r["run"] is None]
    if rest:
        out.write("  outside any first run:\n")
        for r in rest:
            out.write(line(r))


def _print_metrics(reg, out) -> None:
    out.write("== metrics ==\n")
    snap = reg.snapshot()
    for m in snap["metrics"]:
        if m["type"] == "histogram":
            for s in m["series"]:
                lbl = _labels(s)
                out.write(
                    f"  {m['name']}{lbl}: count={s['count']} "
                    f"mean={_fmt_s(s['sum'] / s['count']) if s['count'] else '-'} "
                    f"min={_fmt_s(s.get('min'))} max={_fmt_s(s.get('max'))}\n")
        else:
            for s in m["series"]:
                out.write(f"  {m['name']}{_labels(s)} = {s['value']:g}\n")


def _labels(series: dict) -> str:
    lab = series.get("labels") or {}
    if not lab:
        return ""
    return "{" + ",".join(f"{k}={v}" for k, v in sorted(lab.items())) + "}"


def _print_requests(run_dir: str, report: dict, out, limit: int) -> None:
    """Per-request timelines from the merged trace: spans grouped by
    their args.trace_id (cat == "request"), slowest root first."""
    path = os.path.join(run_dir, "trace.json")
    if not os.path.exists(path):
        return
    with open(path) as f:
        doc = json.load(f)
    evs = doc.get("traceEvents", [])
    tid_names = {e["tid"]: e["args"]["name"] for e in evs
                 if e.get("ph") == "M" and e.get("name") == "thread_name"}
    by_trace = {}
    for e in evs:
        if e.get("ph") != "X" or e.get("cat") != "request":
            continue
        trace_id = (e.get("args") or {}).get("trace_id")
        if trace_id:
            by_trace.setdefault(trace_id, []).append(e)
    stats = report.get("request_traces") or {}
    if not by_trace and not stats:
        return
    out.write("== requests ==\n")
    if stats:
        out.write(
            f"  tail sampling: {stats.get('kept', 0)} kept, "
            f"{stats.get('sampled_out', 0)} sampled out, "
            f"{stats.get('budget_dropped', 0)} over budget "
            f"(rolling p99 {_fmt_s(stats.get('rolling_p99_s'))})\n")

    def root_of(spans):
        # the root carries the outcome; children carry a parent
        for e in spans:
            if "outcome" in (e.get("args") or {}):
                return e
        return spans[0]

    groups = sorted(by_trace.items(),
                    key=lambda kv: -root_of(kv[1]).get("dur", 0.0))
    for trace_id, spans in groups[:limit]:
        root = root_of(spans)
        args = root.get("args") or {}
        out.write(f"  {trace_id} [{args.get('outcome', '?')}] "
                  f"{_fmt_s(root.get('dur', 0.0) / 1e6)} "
                  f"({len(spans)} spans)\n")
        t0 = min(e["ts"] for e in spans)
        for e in sorted(spans, key=lambda e: (e["ts"], e["name"])):
            th = tid_names.get(e["tid"], f"tid {e['tid']}")
            out.write(
                f"    +{(e['ts'] - t0) / 1e3:7.2f}ms "
                f"{_fmt_s(e.get('dur', 0.0) / 1e6):>9}  "
                f"{e['name']:<20} @{th}\n")
    if len(groups) > limit:
        out.write(f"  ... {len(groups) - limit} more "
                  f"(--requests {len(groups)} to see all)\n")


def _print_flight(run_dir: str, report: dict, out, extra: str = None,
                  tail: int = 8) -> None:
    """Render the tail of every flight-recorder dump in the run dir
    (plus any paths report.json recorded and an explicit --flight
    path): the black box of what the engine was doing when the breaker
    tripped / health went BROKEN."""
    paths = sorted(
        os.path.join(run_dir, fn) for fn in os.listdir(run_dir)
        if fn.startswith("flight") and fn.endswith(".jsonl"))
    seen = {os.path.abspath(p) for p in paths}
    for p in list(report.get("flight_dumps") or []) + (
            [extra] if extra else []):
        ap = os.path.abspath(p)
        if ap not in seen and os.path.exists(p):
            seen.add(ap)
            paths.append(p)
    if not paths:
        return
    out.write("== flight recorder ==\n")
    for p in paths:
        try:
            with open(p) as f:
                lines = [json.loads(ln) for ln in f if ln.strip()]
        except (OSError, json.JSONDecodeError) as e:
            out.write(f"  {p}: unreadable ({e})\n")
            continue
        if not lines:
            out.write(f"  {p}: empty\n")
            continue
        header, events = lines[0], lines[1:]
        out.write(f"  {p}\n    reason={header.get('reason')} "
                  f"events={header.get('events')} "
                  f"dropped={header.get('dropped')} "
                  f"(last {min(tail, len(events))}):\n")
        for evt in events[-tail:]:
            detail = {k: v for k, v in evt.items()
                      if k not in ("seq", "t", "mono", "thread", "kind")}
            out.write(f"    #{str(evt.get('seq', '?')):<4} "
                      f"[{evt.get('thread')}] {evt.get('kind')}: "
                      f"{json.dumps(detail, sort_keys=True)}\n")


def _print_regression(verdicts, out) -> bool:
    """Returns True when any verdict failed."""
    out.write("== regression gate ==\n")
    if not verdicts:
        out.write("  (no baseline)\n")
        return False
    failed = False
    for v in verdicts:
        verdict = v.get("verdict", "?")
        failed = failed or verdict == "fail"
        if "delta_pct" in v:
            sign = "+" if v["delta_pct"] >= 0 else ""
            out.write(
                f"  [{verdict.upper():4}] {v.get('metric')}: "
                f"{v.get('current')} vs baseline {v.get('baseline')} "
                f"({sign}{v['delta_pct']:.2f}%, tol "
                f"{v.get('tolerance_pct')}%)\n")
        else:
            out.write(f"  [{verdict.upper():4}] {v.get('metric', '?')}\n")
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("run_dir")
    ap.add_argument("--baseline", default=None,
                    help="bench artifact / {metric: value} JSON to re-gate "
                         "against (defaults to the verdicts banked in "
                         "report.json)")
    ap.add_argument("--tol", type=float, default=0.05,
                    help="relative tolerance for --baseline (default 0.05)")
    ap.add_argument("--gate", action="store_true",
                    help="exit 3 when a regression verdict fails")
    ap.add_argument("--requests", type=int, default=5,
                    help="max per-request timelines to render "
                         "(slowest first; default 5)")
    ap.add_argument("--flight", default=None,
                    help="render this flight-recorder dump too (dumps "
                         "inside the run dir are picked up "
                         "automatically)")
    args = ap.parse_args(argv)
    out = sys.stdout

    if not os.path.isdir(args.run_dir):
        sys.stderr.write(f"obsdump: {args.run_dir} is not a directory\n")
        return 2
    if args.flight and not os.path.exists(args.flight):
        sys.stderr.write(f"obsdump: flight dump {args.flight} missing\n")
        return 2
    report = _load_report(args.run_dir)
    out.write(f"observability run: {os.path.abspath(args.run_dir)}\n")
    _print_step_time(report, out)
    _print_setup(report, out)

    reg = _aggregate_metrics(args.run_dir)
    if reg is not None:
        _print_metrics(reg, out)
    _print_requests(args.run_dir, report, out, limit=max(0, args.requests))
    _print_flight(args.run_dir, report, out, extra=args.flight)

    verdicts = report.get("regression") or []
    if args.baseline and not os.path.exists(args.baseline):
        sys.stderr.write(f"obsdump: baseline {args.baseline} missing\n")
        return 2
    if args.baseline:
        from paddle_tpu.observability import gate_results

        verdicts = gate_results(
            report.get("results") or [], args.baseline, tolerance=args.tol)
    failed = _print_regression(verdicts, out)

    trace = os.path.join(args.run_dir, "trace.json")
    if os.path.exists(trace):
        with open(trace) as f:
            n = sum(1 for e in json.load(f).get("traceEvents", [])
                    if e.get("ph") == "X")
        out.write(f"== trace ==\n  {trace}: {n} spans "
                  "(load in ui.perfetto.dev)\n")
    return 3 if (args.gate and failed) else 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:  # e.g. piped into head
        os._exit(0)
