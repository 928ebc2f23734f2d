"""Incremental on-chip proof for the pallas flash-attention backward:
staged, each stage with its own hard deadline, so a kernel that cannot
compile is diagnosed by the CHEAP stage instead of a full-model compile.

  stage 1  standalone backward, one block   dq+dkv pallas_calls, S=128
  stage 2  multi-block backward             S=512, 4x4 grid per kernel
  stage 3  flash fwd+bwd under jax.grad     the real custom-vjp path, jit
  stage 4  jax-shipped kernel pair          FLAGS_flash_bwd=jaxlib route
           (independent implementation: if stages 1-3 fail but 4 passes,
           bench with jaxlib instead of the in-repo pallas backward)

Run:  python tools/flash_bwd_probe.py [stage] [timeout_s]
Each stage runs in a clean subprocess, one after the other.  The parent
never imports jax, so each stage has the chip to itself — keep it so: a
parent that touched jax would hold the chip and every stage would fail or
hang.  Output is one JSON line per stage:
{"stage": N, "ok": bool, "wall_s": ..., "detail": ...}.  Stop at the
first failure — that IS the finding.  Only after all three pass is
FLAGS_flash_bwd=pallas worth trying on a full bench model.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

STAGE_SRC = {
    1: r"""
import time, jax, jax.numpy as jnp, numpy as np
import importlib
fa = importlib.import_module('paddle_tpu.kernels.flash_attention')
B, H, S, D = 1, 1, 128, 64
rng = np.random.RandomState(0)
q = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
klen = jnp.full((B,), S, jnp.int32)
out, lse = fa._pallas_flash(q, q, q, klen, causal=True, scale=0.125)
g = jnp.ones_like(out)
t0 = time.perf_counter()
dq, dk, dv = fa._pallas_flash_bwd(q, q, q, klen, out, lse, g,
                                  causal=True, scale=0.125)
jax.block_until_ready((dq, dk, dv))
print(f"STAGE_OK compile+run {time.perf_counter()-t0:.1f}s", flush=True)
""",
    2: r"""
import time, jax, jax.numpy as jnp, numpy as np
import importlib
fa = importlib.import_module('paddle_tpu.kernels.flash_attention')
B, H, S, D = 2, 4, 512, 64
rng = np.random.RandomState(0)
q = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
klen = jnp.full((B,), S, jnp.int32)
out, lse = fa._pallas_flash(q, q, q, klen, causal=True, scale=0.125)
g = jnp.ones_like(out)
t0 = time.perf_counter()
dq, dk, dv = fa._pallas_flash_bwd(q, q, q, klen, out, lse, g,
                                  causal=True, scale=0.125)
jax.block_until_ready((dq, dk, dv))
print(f"STAGE_OK compile+run {time.perf_counter()-t0:.1f}s", flush=True)
""",
    3: r"""
import time, jax, jax.numpy as jnp, numpy as np
import paddle_tpu as fluid
from paddle_tpu.kernels.flash_attention import flash_attention
fluid.set_flags({"FLAGS_flash_bwd": "pallas"})
B, H, S, D = 2, 8, 512, 64
rng = np.random.RandomState(0)
q = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)

def loss(q):
    return flash_attention(q, q, q, causal=True).sum()

t0 = time.perf_counter()
g = jax.jit(jax.grad(loss))(q)
jax.block_until_ready(g)
print(f"STAGE_OK compile+run {time.perf_counter()-t0:.1f}s", flush=True)
""",
}


STAGE_SRC[4] = r"""
import time, jax, jax.numpy as jnp, numpy as np
import paddle_tpu as fluid
from paddle_tpu.kernels.flash_attention import flash_attention
fluid.set_flags({"FLAGS_flash_bwd": "jaxlib"})
B, H, S, D = 2, 8, 512, 64
rng = np.random.RandomState(0)
q = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)

def loss(q):
    return flash_attention(q, q, q, causal=True).sum()

t0 = time.perf_counter()
g = jax.jit(jax.grad(loss))(q)
jax.block_until_ready(g)
print(f"STAGE_OK compile+run {time.perf_counter()-t0:.1f}s", flush=True)
"""


def run_stage(stage: int, timeout_s: float) -> dict:
    t0 = time.perf_counter()
    try:
        out = subprocess.run(
            [sys.executable, "-c", STAGE_SRC[stage]],
            capture_output=True, text=True, timeout=timeout_s,
            env=dict(os.environ),
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        ok = out.returncode == 0 and "STAGE_OK" in out.stdout
        tail = (out.stdout + out.stderr).strip().splitlines()
        detail = tail[-1][:300] if tail else ""
    except subprocess.TimeoutExpired:
        ok, detail = False, f"timeout after {timeout_s:.0f}s"
    return {"stage": stage, "ok": ok,
            "wall_s": round(time.perf_counter() - t0, 1), "detail": detail}


def main() -> None:
    stages = ([int(sys.argv[1])] if len(sys.argv) > 1 else [1, 2, 3, 4])
    timeout_s = float(sys.argv[2]) if len(sys.argv) > 2 else 900.0
    ok_all = True
    for s in stages:
        r = run_stage(s, timeout_s)
        print(json.dumps(r), flush=True)
        if not r["ok"]:
            ok_all = False
            if s != 4:
                # stages 1-3 build on each other; stage 4 is independent
                # and still worth probing after a 1-3 failure
                if 4 in stages:
                    r4 = run_stage(4, timeout_s)
                    print(json.dumps(r4), flush=True)
                break
    sys.exit(0 if ok_all else 1)


if __name__ == "__main__":
    main()
