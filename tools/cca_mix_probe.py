"""`cca.mix` alone, timed on the chip at `zaya-train-cca16k`'s shape.

  zaya   q~ [B, 16384, 1024], k~ and v~ [B, 16384, 256] bf16: 8 query heads
         on 2 key/value heads of 128, two taps a convolution, rotary 64;
         B 8 sequences a call where the cell has 1, so that a call is
         milliseconds of device time and not the host's 0.4 ms of dispatch
         (a forward kernel is 0.2 ms a sequence); the times are A SEQUENCE

The op compressed_conv_qkv's arithmetic in its two engines: `xla`
(ops/attention_ops.py::compressed_conv_mix, jax.numpy) and `pallas`
(kernels/cca_mix.py, the kernel pair) at the tiles `plan` gives the shape;
`--sweep` also pins every tile of rows in --tiles.  For each: the forward,
and the gradient of a loss that weighs the outputs under jax.checkpoint
(the loss is linear in the outputs, so the compiler drops both forwards of
the kernel pair, whose backward reads the inputs alone: that column is the
BACKWARD kernel; of the jax.numpy engine it is the recomputed forward and
the backward), ms a sequence, the share of the HBM rate that the pass's
part of kernels/cca_mix.py::moved_bytes is of it, and how far the outputs
and the eight gradients lie from the jax.numpy engine's (the largest
difference over the largest value).  Rows go to
chiprun_out/cca_mix_probe.json.

A tool, run by no benchmark cell:
    chiprun --chips 1 -- python3 tools/cca_mix_probe.py --seed 7 [--sweep]
    JAX_PLATFORMS=cpu python3 tools/cca_mix_probe.py --rehearse
`--rehearse` runs a tiny shape through the Pallas interpreter in fp32 and
exits 3: its times are not the chip's.  One process holds the chip; it
starts no child.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from flash_fwd_probe import _time_ms  # noqa: E402

# name: (B, S, H, G, D, k0, k1, rotary_dim, rope_base)
SHAPES = {"zaya": (8, 16384, 8, 2, 128, 2, 2, 64, 5e6)}
REHEARSAL_SHAPES = {"zaya": (1, 384, 4, 2, 128, 2, 2, 64, 5e6)}
HBM_GB_S = 819.0  # one v5e (Google Cloud documentation, "TPU v5e")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--tiles", default="128,256,512,1024")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core import amp
    from paddle_tpu.kernels import cca_mix
    from paddle_tpu.ops.attention_ops import _inv_freq, compressed_conv_mix

    dev = jax.devices()[0]
    if not a.rehearse and dev.platform != "tpu":
        print("cca_mix_probe: no TPU here (use --rehearse on the CPU)",
              file=sys.stderr)
        return 2
    half = jnp.float32 if a.rehearse else jnp.bfloat16
    if not a.rehearse:   # the cell's tier: bf16 on the MXU, bf16 kept
        amp.enable_amp("bfloat16", keep_output=True)
    rows = []
    for name, shape in (REHEARSAL_SHAPES if a.rehearse else SHAPES).items():
        B, S, H, G, D, k0, k1, rotary_dim, base = shape
        n = H + G
        rng = np.random.RandomState(a.seed % (2 ** 32))

        def normal(*s, scale=1.0, dtype=jnp.float32):
            return jnp.asarray(rng.randn(*s) * scale, dtype)

        args = (normal(B, S, H * D, dtype=half),
                normal(B, S, G * D, dtype=half),
                normal(B, S, G * D, dtype=half),
                normal(k0, n * D, scale=0.5), normal(n * D, scale=0.3),
                normal(k1, n, D, D, scale=(k1 * D) ** -0.5),
                normal(n * D, scale=0.1), 1.0 + 0.1 * normal(G))
        cots = tuple(normal(B, m, S, D, dtype=half) for m in (H, G, G))
        plan = cca_mix.plan(S, H, G, D, k0, k1, rotary_dim, half)

        def engine(force, tile=None):
            def fwd(*xs):
                if force == "jax":
                    return compressed_conv_mix(*xs, H, G, rotary_dim, base)
                geo = cca_mix.plan(S, H, G, D, k0, k1, rotary_dim, half, tile)
                return cca_mix.cca_mix(
                    *xs, geo, tuple(_inv_freq(rotary_dim, base)),
                    force == "interpret")

            def loss(*xs):
                outs = jax.checkpoint(fwd)(*xs)
                return sum(jnp.sum(o.astype(jnp.float32) * c)
                           for o, c in zip(outs, cots))
            return jax.jit(fwd), jax.jit(jax.grad(loss, tuple(range(8))))

        kernel = "interpret" if a.rehearse else "pallas"
        variants = [("xla", "jax", 0, 0), ("pallas-plan", kernel, None,
                                           (plan.fwd_tile, plan.bwd_tile))]
        if a.sweep:
            variants += [(f"pallas-{t}", kernel, t, (t, t))
                         for t in map(int, a.tiles.split(",")) if S % t == 0]
        want = None
        for label, force, tile, tiles in variants:
            row = {"shape": name, "variant": label, "tiles": tiles,
                   "seed": a.seed}
            try:
                fwd, grad = engine(force, tile)
                got = [np.asarray(t, np.float32)
                       for t in tuple(fwd(*args)) + tuple(grad(*args))]
                if want is None:
                    want = got
                names = ("q", "k", "v", "dq", "dk", "dv", "daw", "dab", "dbw",
                         "dbb", "dtau")
                row["rel_err"] = {
                    key: float(np.max(np.abs(g - w)) / np.max(np.abs(w)))
                    for key, g, w in zip(names, got, want)}
                if not a.rehearse:  # an interpreter's time is no one's
                    f_ms = _time_ms(fwd, args, a.calls) / B
                    g_ms = _time_ms(grad, args, a.calls) / B
                    once, twice = (cca_mix.moved_bytes(*args[:3], again) / B
                                   for again in (False, True))
                    forward = twice - once
                    backward = once - forward
                    moved = (forward, backward + forward * (force == "jax"))
                    row.update(
                        fwd_ms=round(f_ms, 4), grad_ms=round(g_ms, 4),
                        fwd_hbm_share=round(
                            moved[0] / f_ms / 1e6 / HBM_GB_S, 4),
                        grad_hbm_share=round(
                            moved[1] / g_ms / 1e6 / HBM_GB_S, 4))
            except Exception as e:  # a tile Mosaic refuses is a row
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            rows.append(row)
            print(json.dumps(row), flush=True)
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "rehearsal": bool(a.rehearse), "date": time.strftime(
               "%Y-%m-%d %H:%M UTC", time.gmtime()), "rows": rows}
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/cca_mix_probe.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in ("device", "rehearsal", "date")}))
    return 3 if a.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
