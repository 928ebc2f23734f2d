"""Staged compile-viability probe for the pallas conv+BN-epilogue kernels
(VERDICT r5 item 4 — attack the MFU-0.20 ceiling the round-4 analysis
pinned on BN's extra passes over conv outputs; reference counterpart
conv_fusion_op.cu.cc).

Never learn whether a kernel compiles from a full-model compile.  Three
stages, cheapest first, each a clean subprocess with its own deadline.
The parent never imports jax and runs the stages one after the other, so
on a chip host each stage has the chip to itself — keep it so: a parent
that touched jax would hold the chip and every stage would fail or hang.

  1. tiny block     N=2 16x16x32 -> 32, K=3  (compile + run + parity)
  2. resnet shape   N=8 56x56x64 -> 64, K=3  (the stage-2 block shape)
  3. timed A/B      stage-2 shape, fused pallas pair vs the XLA
                    conv+BN+relu chain, 30 steady-state iters each —
                    ms/iter and the implied activation GB/s for both

On a CPU backend the kernels run in interpret mode — the pipeline is
validated but stage 3's timings are meaningless off-chip and are
labeled backend=cpu.  Prints one JSON line per stage
{"stage": n, "ok": bool, ...}; exit 0 iff every attempted stage passed.
Stops at the first failed stage.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STAGE_SRC = r"""
import json, os, sys, time
sys.path.insert(0, os.environ["PROBE_REPO"])
import jax
# raw jax.jit/pallas_call, never a CompiledBlock: to keep these compiles
# across runs, set JAX_COMPILATION_CACHE_DIR — jax honours it by itself
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels.conv_epilogue import (
    conv_bn_act, conv_bn_act_reference)

stage = int(os.environ["PROBE_STAGE"])
backend = jax.default_backend()
interpret = backend == "cpu"

if stage == 1:
    N, H, C, F, K, iters = 2, 16, 32, 32, 3, 0
elif stage == 2:
    N, H, C, F, K, iters = 8, 56, 64, 64, 3, 0
else:
    N, H, C, F, K, iters = 8, 56, 64, 64, 3, 30

r = np.random.RandomState(0)
x = jnp.asarray(r.randn(N, H, H, C).astype("float32"))
w = jnp.asarray((r.randn(K, K, C, F) * 0.1).astype("float32"))
g = jnp.asarray((r.rand(F) + 0.5).astype("float32"))
b = jnp.asarray((r.randn(F) * 0.1).astype("float32"))
z = jnp.asarray(r.randn(N, H, H, F).astype("float32"))

t0 = time.perf_counter()
y, m, v = conv_bn_act(x, w, g, b, z, interpret=interpret)
jax.block_until_ready(y)
compile_s = time.perf_counter() - t0

yr, mr, vr = conv_bn_act_reference(x, w, g, b, z)
np.testing.assert_allclose(np.asarray(m), np.asarray(mr), rtol=2e-4,
                           atol=2e-4)
np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-3,
                           atol=2e-3)

rec = {"stage": stage, "ok": True, "backend": backend,
       "interpret": interpret, "shape": [N, H, H, C, F, K],
       "compile_s": round(compile_s, 2)}

if iters:
    ref = jax.jit(lambda *a: conv_bn_act_reference(*a))
    fus = lambda *a: conv_bn_act(*a, interpret=interpret)
    for name, fn in (("xla_chain", ref), ("pallas_fused", fus)):
        out = fn(x, w, g, b, z)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(x, w, g, b, z)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / iters * 1e3
        act_bytes = N * H * H * F * 4
        rec[name + "_ms"] = round(ms, 3)
        # conv-out write + epilogue read + y write = 3 activation passes
        rec[name + "_implied_gbps"] = round(3 * act_bytes / (ms / 1e3) / 1e9, 1)

print(json.dumps(rec), flush=True)
"""


def run_stage(stage: int, timeout_s: float) -> dict:
    env = dict(os.environ, PROBE_REPO=REPO, PROBE_STAGE=str(stage))
    t0 = time.perf_counter()
    try:
        out = subprocess.run([sys.executable, "-c", STAGE_SRC],
                             capture_output=True, text=True,
                             timeout=timeout_s, env=env)
    except subprocess.TimeoutExpired:
        return {"stage": stage, "ok": False,
                "error": f"timeout after {timeout_s:.0f}s"}
    rec = {"stage": stage, "ok": False,
           "wall_s": round(time.perf_counter() - t0, 1)}
    for ln in out.stdout.splitlines():
        ln = ln.strip()
        if ln.startswith("{"):
            try:
                rec.update(json.loads(ln))
            except ValueError:
                pass
    if out.returncode != 0:
        rec["ok"] = False
        rec["stderr_tail"] = out.stderr.strip()[-1200:]
    return rec


def main() -> None:
    deadlines = {1: 600.0, 2: 900.0, 3: 900.0}
    all_ok = True
    for stage in (1, 2, 3):
        rec = run_stage(stage, deadlines[stage])
        print(json.dumps(rec), flush=True)
        if not rec.get("ok"):
            all_ok = False
            break
    sys.exit(0 if all_ok else 1)


if __name__ == "__main__":
    main()
