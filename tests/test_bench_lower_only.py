"""End-to-end coverage for bench.py's chip-less gates: the
BENCH_LOWER_ONLY per-model TPU lowering check must run on a CPU host
without starting a reader thread or staging anything on a device, and
its rows must say they are chip-less."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(extra_env, timeout=560):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_TUNE": "0",
        "BENCH_DEADLINE_S": "0",
        "BENCH_COMPILE_CACHE": "0",
        "PYTHONPATH": REPO,
    })
    env.update(extra_env)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    line = next((ln for ln in out.stdout.splitlines()
                 if ln.strip().startswith("{")), None)
    assert line, f"no JSON line from bench.py:\n{out.stdout}\n{out.stderr}"
    return json.loads(line), out


def test_lower_only_gate_covers_flagship_models():
    """BENCH_LOWER_ONLY=1 over the north-star models: each returns a
    `<model>_tpu_lowering` ok record with a nonzero exported module.
    BENCH_DATA=pyreader is set deliberately: the hoisted early-return
    (bench.py regression) must come back BEFORE the reader thread or any
    device staging would start — pre-hoist, this returned with the
    worker still running."""
    rec, out = _run_bench({
        "BENCH_LOWER_ONLY": "1",
        "BENCH_MODELS": "resnet50,transformer",
        # small shapes: the gate's value is the lowering path, not scale
        "BENCH_BS": "4",
        "BENCH_TRANSFORMER_BS": "2",
        "BENCH_DATA": "pyreader",
    })
    results = [rec] + rec.get("extra_metrics", [])
    assert rec.get("model_errors") is None, rec.get("model_errors")
    by_metric = {r["metric"]: r for r in results}
    for model in ("resnet50", "transformer"):
        r = by_metric[f"{model}_tpu_lowering"]
        assert r["value"] == 1 and r["unit"] == "ok"
        assert r["module_bytes"] > 0
        # a declared chip-less row on a declared CPU run
        assert r["chipless"] is True and r["platform"] == "cpu"
    # clean exit == no stray reader thread kept the process alive
    assert out.returncode == 0, out.stderr[-2000:]
