#!/usr/bin/env python
"""Chip-less program linter + model-zoo CI gate (paddle_tpu.analysis).

Statically analyzes the chip programs of the model zoo — jaxpr,
TPU-lowered StableHLO, and the AOT-compiled v5e executable
(core/aot_tpu.py; no TPU attached) — and reports typed findings:
relayout copy-pairs around custom calls, broadcast-materialized
custom-call operands, missed buffer donation, recompile hazards, silent
dtype promotions, scan/while carry widenings, host-sync points, SPMD
collective placement, and (the kernel-interior tier, analysis/pallas.py)
pallas_call VMEM working sets priced against the v5e budget.
Per-program AOT bytes/step and finding counts are banked in
AOT_COST_ZOO.json (the successor table to
AOT_COST_PAGED.json) and gated per PR.  Findings are ordered
severity-then-bytes (and vmem-overflow findings carry per-finding
vmem_bytes/budget in --json) so gate diffs are stable.

Usage:
    python tools/lint_programs.py                       # lint the zoo
    python tools/lint_programs.py --programs paged_decode
    python tools/lint_programs.py --bank                # rewrite baselines
    python tools/lint_programs.py --gate                # CI: exit 3 on any
                                                        # new finding or
                                                        # bytes regression
    python tools/lint_programs.py --inject broadcast_lse --gate
                                                        # prove the gate
                                                        # trips (exit 3)
    python tools/lint_programs.py --list                # zoo + corpus names

Exit codes (shared CI-gate contract with obsdump.py and serve_bench.py —
see README "CI gates"):  0 clean · 2 usage/environment error · 3 gate
regression.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--programs", default=None,
                    help="comma-separated zoo subset (default: all)")
    ap.add_argument("--inject", default=None,
                    help="comma-separated known-bad corpus programs to "
                         "splice into the run (each must trip the gate)")
    ap.add_argument("--detectors", default=None,
                    help="comma-separated detector subset (default: all)")
    ap.add_argument("--baseline", default=None,
                    help="baseline JSON (default: repo AOT_COST_ZOO.json)")
    ap.add_argument("--tol", type=float, default=None,
                    help="bytes/step tolerance (default: the baseline "
                         "file's own, else 0.02)")
    ap.add_argument("--bank", action="store_true",
                    help="rewrite the baseline from this run (refuses "
                         "when --programs/--inject filtered the zoo)")
    ap.add_argument("--json", default=None, help="write results here")
    ap.add_argument("--gate", action="store_true",
                    help="exit 3 when the baseline comparison fails")
    ap.add_argument("--list", action="store_true",
                    help="print zoo + corpus program names and exit")
    args = ap.parse_args(argv)
    out = sys.stdout

    from paddle_tpu import analysis
    from paddle_tpu.analysis.corpus import CORPUS

    if args.list:
        out.write("zoo programs:    " + " ".join(sorted(analysis.ZOO))
                  + "\n")
        out.write("corpus programs: " + " ".join(sorted(CORPUS)) + "\n")
        out.write("detectors:       " + " ".join(analysis.DETECTORS)
                  + "\n")
        return 0

    try:
        from paddle_tpu.core.aot_tpu import tpu_topology

        tpu_topology()
    except Exception as e:
        sys.stderr.write(
            f"lint_programs: no chip-less TPU topology available: {e}\n")
        return 2

    programs = args.programs.split(",") if args.programs else None
    inject = args.inject.split(",") if args.inject else ()
    detectors = args.detectors.split(",") if args.detectors else None
    if args.gate and detectors is not None:
        # a detector subset produces no counts for the other detectors,
        # so their regressions would gate GREEN — same hole --bank refuses
        sys.stderr.write(
            "lint_programs: --gate with --detectors would silently skip "
            "the other detectors' baselines — run the full set\n")
        return 2
    if args.bank and (programs is not None or inject
                      or detectors is not None):
        # before the zoo is compiled: a refusal costs no compile
        sys.stderr.write(
            "lint_programs: refusing to --bank a filtered/injected "
            "run — baselines must cover the whole zoo with every "
            "detector\n")
        return 2
    try:
        results = analysis.run_zoo(
            programs, inject=inject, detectors=detectors,
            progress=lambda m: out.write(f"  .. {m}\n"))
    except KeyError as e:
        sys.stderr.write(f"lint_programs: {e.args[0]}\n")
        return 2

    out.write("== programs ==\n")
    for r in results:
        err = (f" COMPILE-ERROR: {r.artifacts.compile_error[:80]}"
               if r.artifacts.compile_error else "")
        out.write(
            f"  {r.name:24} bytes/step={r.bytes_per_step:.4g} "
            f"flops/step={r.flops_per_step:.4g} "
            f"findings={sum(r.finding_counts().values())} "
            f"fp={r.artifacts.fingerprint}{err}\n")
    out.write("== findings ==\n")
    any_findings = False
    for r in results:
        for f in r.findings:
            any_findings = True
            out.write("  " + f.format() + "\n")
    if not any_findings:
        out.write("  (none)\n")

    def write_json(verdicts):
        if not args.json:
            return
        with open(args.json, "w") as f:
            json.dump({
                "programs": {
                    r.name: {
                        "bytes_per_step": r.bytes_per_step,
                        "flops_per_step": r.flops_per_step,
                        "findings": [x.as_dict() for x in r.findings],
                        "finding_counts": r.finding_counts(),
                        "config": r.config,
                        "fingerprint": r.artifacts.fingerprint,
                        "compile_error": r.artifacts.compile_error,
                    } for r in results
                },
                "verdicts": verdicts,
            }, f, indent=1, sort_keys=True)
            f.write("\n")

    baseline = args.baseline or analysis.default_baseline_path()
    if args.bank:
        try:
            doc = (analysis.bank(results, baseline, tolerance=args.tol)
                   if args.tol is not None
                   else analysis.bank(results, baseline))
        except ValueError as e:  # a program's AOT compile failed
            sys.stderr.write(f"lint_programs: {e}\n")
            return 2
        out.write(f"banked {len(doc['programs'])} programs -> "
                  f"{baseline}\n")
        write_json([])
        return 0

    failed = False
    verdicts = []
    if os.path.exists(baseline):
        # an unfiltered run must also notice banked programs that
        # VANISHED from the zoo (coverage loss fails, not passes)
        verdicts, failed = analysis.gate(
            results, baseline, args.tol,
            require_all=programs is None and not inject)
        out.write("== gate vs " + os.path.basename(baseline) + " ==\n")
        for v in verdicts:
            line = f"  [{v['verdict'].upper():4}] {v['metric']}"
            if "current" in v and "baseline" in v:
                line += f": {v['current']} vs baseline {v['baseline']}"
            if "delta_pct" in v:
                line += (f" ({'+' if v['delta_pct'] >= 0 else ''}"
                         f"{v['delta_pct']:.2f}%, tol "
                         f"{v.get('tolerance_pct')}%)")
            if "reason" in v:
                line += f" — {v['reason']}"
            out.write(line + "\n")
    elif args.gate:
        sys.stderr.write(
            f"lint_programs: --gate needs a baseline ({baseline} "
            "missing; run --bank first)\n")
        return 2

    write_json(verdicts)
    return 3 if (args.gate and failed) else 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:  # piped into head
        os._exit(0)
