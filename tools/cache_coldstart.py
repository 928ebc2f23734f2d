"""Compile-cache cold-start drill.

Proves — or disproves, with the error documented — that a persisted XLA
executable can be REUSED by a fresh process without recompiling, so that a
second run of a bench skips the minutes the first one compiled for.
(Reference analogue in spirit: the build/run split of
paddle/scripts/paddle_build.sh:59 — compile once, execute many.)

Two stages, each a clean subprocess sharing one cache directory.  The
parent never imports jax, and the stages run one after the other, so on a
chip host each stage has the chip to itself.  The drill measures a COLD
start, so unlike the entry points (core/compiler.py default_compile_cache)
it uses a directory of its own — a temporary one unless --cache-dir — and
keeps JAX_COMPILATION_CACHE_DIR out of the stages' environment:

  warm  — compile + run a small conv+BN+fc training program with
          FLAGS_compile_cache_dir set; record losses, wall time, and from
          the program's set-up log (observability/compiles.py) the
          persistent cache's hit/miss counts with the stage's trace_s,
          lower_s, backend_s and retrieval_s.
  cold  — a FRESH process, same program, same cache dir; done =
          cache_hits > 0, bit-identical losses, and a compile wall that
          dropped.

Usage:
  python tools/cache_coldstart.py [--cache-dir DIR] [--keep]

Prints one JSON line per stage plus a final verdict line
{"coldstart_ok": bool, ...} (exit 0 iff ok).  The cache directory is
left in place with --keep (or a non-tmp --cache-dir).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STAGE_SRC = r"""
import json, os, sys, time
sys.path.insert(0, os.environ["COLDSTART_REPO"])
import jax
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import layers

fluid.default_main_program().random_seed = 7
fluid.default_startup_program().random_seed = 7
x = layers.data("x", [4, 8, 8], dtype="float32")
y = layers.data("y", [1], dtype="int64")
conv = layers.conv2d(x, num_filters=8, filter_size=3, padding=1)
h = layers.batch_norm(conv, act="relu")
pool = layers.pool2d(h, pool_size=8, pool_type="avg")
pred = layers.fc(pool, size=3, act="softmax")
loss = layers.mean(layers.cross_entropy(pred, y))
fluid.optimizer.MomentumOptimizer(0.1, 0.9).minimize(loss)

exe = fluid.Executor(fluid.CPUPlace() if jax.default_backend() == "cpu"
                     else fluid.TPUPlace())
t0 = time.perf_counter()
exe.run(fluid.default_startup_program())
rng = np.random.RandomState(3)
xv = rng.randn(8, 4, 8, 8).astype("float32")
yv = rng.randint(0, 3, size=(8, 1)).astype("int64")
losses = [float(np.ravel(np.asarray(
    exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])[0]))[0])
    for _ in range(3)]
# the program's own set-up log (observability/compiles.py): one record an
# executable, from jax's monitoring events
records = fluid.observability.default_compile_log().snapshot()["records"]
print(json.dumps({
    "stage": os.environ["COLDSTART_STAGE"],
    "wall_s": round(time.perf_counter() - t0, 3),
    "losses": losses,
    "cache_hits": sum(r["cache"] == "hit" for r in records),
    "cache_misses": sum(r["cache"] == "miss" for r in records),
    "trace_s": round(sum(r["trace_s"] for r in records), 4),
    "lower_s": round(sum(r["lower_s"] for r in records), 4),
    "backend_s": round(sum(r["backend_s"] for r in records), 4),
    "retrieval_s": round(sum(r["retrieval_s"] or 0.0 for r in records), 4),
    "backend": jax.default_backend(),
}), flush=True)
"""


def run_stage(name: str, cache_dir: str, timeout_s: float) -> dict:
    env = dict(
        os.environ,
        COLDSTART_REPO=REPO,
        COLDSTART_STAGE=name,
        FLAGS_compile_cache_dir=cache_dir,
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
    )
    # the variable would win over the flag: the drill owns its directory
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    try:
        out = subprocess.run([sys.executable, "-c", STAGE_SRC],
                             capture_output=True, text=True,
                             timeout=timeout_s, env=env)
    except subprocess.TimeoutExpired:
        return {"stage": name, "error": f"timeout after {timeout_s:.0f}s"}
    rec = {"stage": name, "rc": out.returncode}
    for ln in out.stdout.splitlines():
        ln = ln.strip()
        if ln.startswith("{"):
            try:
                rec.update(json.loads(ln))
            except ValueError:
                pass
    if out.returncode != 0:
        rec["stderr_tail"] = out.stderr.strip()[-1200:]
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cache-dir", default=None)
    ap.add_argument("--keep", action="store_true")
    ap.add_argument("--timeout-s", type=float, default=900.0)
    args = ap.parse_args()

    cache_dir = args.cache_dir or tempfile.mkdtemp(prefix="xla_cache_drill_")
    cleanup = args.cache_dir is None and not args.keep
    os.makedirs(cache_dir, exist_ok=True)

    warm = run_stage("warm", cache_dir, args.timeout_s)
    print(json.dumps(warm), flush=True)
    n_entries = len(glob.glob(os.path.join(cache_dir, "*")))
    cold = run_stage("cold", cache_dir, args.timeout_s)
    print(json.dumps(cold), flush=True)

    ok = (
        warm.get("rc") == 0 and cold.get("rc") == 0
        and n_entries > 0
        and cold.get("cache_hits", 0) > 0
        and cold.get("losses") == warm.get("losses")
    )
    verdict = {
        "coldstart_ok": bool(ok),
        "cache_dir": cache_dir,
        "cache_entries_after_warm": n_entries,
        "warm_wall_s": warm.get("wall_s"),
        "cold_wall_s": cold.get("wall_s"),
        "cold_cache_hits": cold.get("cache_hits"),
        "cold_cache_misses": cold.get("cache_misses"),
    }
    print(json.dumps(verdict), flush=True)
    if cleanup:
        shutil.rmtree(cache_dir, ignore_errors=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
