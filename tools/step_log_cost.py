#!/usr/bin/env python
"""What the step log costs a step, on this machine's CPU (ISSUE 68: under
3 us): the calls core/executor.py::run_step makes into
observability/stepstats.py, in its order, on a store of their own, and the
two clocks alone beside them.

    python tools/step_log_cost.py [--steps 200000] [--repeats 5]

One JSON object: `step_ns` (the best repeat: the others hold the machine's
noise), `wall_ns` and `cpu_ns` (one read of `time.perf_counter` /
`time.process_time`), `clocks_ns` (six of the first, two of the second: what
no code can take off).  No jax, no device: a host number, whatever the
host."""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _stepstats():
    # the module by its file: `import paddle_tpu` would bring jax with it
    spec = importlib.util.spec_from_file_location(
        "stepstats", os.path.join(REPO, "paddle_tpu", "observability",
                                  "stepstats.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _best_ns(fn, steps: int, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(steps)
        best = min(best, (time.perf_counter() - t0) / steps * 1e9)
    return best


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=200000)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()
    ss = _stepstats()
    log = ss.StepStats()
    dispatch, dispatched, fetch, ready = (
        ss.DISPATCH, ss.DISPATCHED, ss.FETCH, ss.READY)

    def a_step(steps):
        for seq in range(steps):
            rec = log.begin(seq, "serial")
            log.mark(rec + dispatch)
            log.mark(rec + dispatched)
            log.mark_cpu(rec + fetch)
            log.mark_cpu(rec + ready)
            log.end(rec, False)

    def empty(steps):
        for _ in range(steps):
            pass

    def wall(steps):
        for _ in range(steps):
            time.perf_counter()

    def cpu(steps):
        for _ in range(steps):
            time.process_time()

    loop = _best_ns(empty, args.steps, args.repeats)
    wall_ns = _best_ns(wall, args.steps, args.repeats) - loop
    cpu_ns = _best_ns(cpu, args.steps, args.repeats) - loop
    print(json.dumps({
        "step_ns": _best_ns(a_step, args.steps, args.repeats) - loop,
        "wall_ns": wall_ns, "cpu_ns": cpu_ns,
        "clocks_ns": 6 * wall_ns + 2 * cpu_ns,
        "steps": args.steps, "repeats": args.repeats}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
