"""`ssd.scan` alone, timed and checked on the chip at `granite-train-ssd8k`'s
shape.

  granite  x [1, 8192, 32, 64], dt [1, 8192, 32], B, C [1, 8192, 1, 128]:
           one Mamba-2 layer of the cell (32 held heads), bf16 streams

The op ssd_scan's arithmetic in its two engines (kernels/ssd_scan.py):
`pallas` (the kernel pair at the tiles `tiles` gives the shape; `--chunks`
pins each chunk length in turn; `--fp32` runs fp32 streams too) and, with
`--xla`, the jax.numpy engine.  For each: the forward and the backward ALONE
(the pullback of jax.vjp, jitted over its residuals), ms a layer.

`--check` holds both engines on fp32 streams to the recurrence one token at
a time at [1, 1024, 8, 64] x 128 states on three inputs: a random one, a
state that decays to nothing inside a token and one that does not decay: the
largest error of y and of each gradient over the largest value.  Rows go to
chiprun_out/ssd_scan_probe.json.

A one-off of PR 63 (ROADMAP D24), run by no benchmark cell:
    chiprun --chips 1 -- python3 tools/ssd_scan_probe.py --seed 7 \
        [--check] [--xla] [--fp32] [--chunks 128,256]
    JAX_PLATFORMS=cpu python3 tools/ssd_scan_probe.py --rehearse --check
`--rehearse` runs a tiny shape through the Pallas interpreter and exits 3:
its times are not the chip's.  One process holds the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tools")]

from ssm_scan_probe import _far, _passes  # noqa: E402

# (B, S, H, P, N, G)
SHAPE, REHEARSAL_SHAPE = (1, 8192, 32, 64, 128, 1), (1, 256, 4, 64, 128, 1)
CHECK_SHAPE, REHEARSAL_CHECK_SHAPE = (1, 1024, 8, 64, 128, 2), \
    (1, 128, 4, 64, 128, 2)
# name: (dt's scale, A's scale)
HARD = {"random": (0.1, 1.0), "decays_to_nothing": (5.0, 8.0),
        "does_not_decay": (1e-4, 1.0)}


def inputs(shape, seed, dt_scale=0.1, a_scale=1.0, dtype="float32"):
    """(x, dt, A, B, C, D), the streams in `dtype`, and a cotangent for
    y."""
    import jax.numpy as jnp
    import numpy as np

    B, S, H, P, N, G = shape
    r = np.random.RandomState(seed % (2 ** 32))
    x = r.randn(B, S, H, P)
    dt = np.log1p(np.exp(r.randn(B, S, H))) * dt_scale
    a = -np.exp(r.rand(H) * 2.5) * a_scale
    b, c = r.randn(B, S, G, N) * 0.3, r.randn(B, S, G, N) * 0.3
    wide = [jnp.asarray(t, dtype) for t in (x, b, c, r.randn(B, S, H, P))]
    return ((wide[0], jnp.asarray(dt, jnp.float32),
             jnp.asarray(a, jnp.float32), wide[1], wide[2],
             jnp.asarray(r.randn(H), jnp.float32)), wide[3])


def token_recurrence(x, dt, a, b, c, d):
    """The recurrence one token at a time."""
    import jax
    import jax.numpy as jnp

    B, _, H, P = x.shape
    G, N = b.shape[2:]

    def token(s, one):
        x, dt, b, c = one
        b, c = (jnp.repeat(t, H // G, axis=1) for t in (b, c))
        s = jnp.exp(dt * a)[..., None, None] * s \
            + (dt[..., None] * x)[..., None] * b[:, :, None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c) + d[:, None] * x

    y = jax.lax.scan(token, jnp.zeros((B, H, P, N), x.dtype), tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))[1]
    return jnp.moveaxis(y, 0, 1)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--xla", action="store_true")
    ap.add_argument("--fp32", action="store_true")
    ap.add_argument("--chunks", default="")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()

    import jax

    from paddle_tpu.kernels import ssd_scan as ssd

    shape = REHEARSAL_SHAPE if a.rehearse else SHAPE
    B, S, H, P, N, G = shape
    rows = []
    for dtype in ["bfloat16"] + ["float32"] * a.fp32:
        size = 2 if dtype == "bfloat16" else 4
        ops, weight = inputs(shape, a.seed, dtype=dtype)
        engines = [("pallas", ssd.tiles(S, H, P, N, G, itemsize=size))]
        engines += [(f"pallas-chunk-{c}",
                     ssd.tiles(S, H, P, N, G, int(c), size))
                    for c in a.chunks.split(",") if c]
        engines += [("xla", None)] * a.xla
        for name, tiles in engines:
            if tiles is None and name != "xla":
                rows.append({"engine": name, "tiles": None})
                continue
            fwd_ms, bwd_ms, _ = _passes(
                lambda *o: ssd.ssd_scan(*o, tiles_=tiles,
                                        interpret=a.rehearse),
                ops, weight, 1 if a.rehearse else a.calls)
            rows.append({"engine": name, "dtype": dtype,
                         "shape": list(shape),
                         "tiles": tiles and tiles._asdict(),
                         "fwd_ms": fwd_ms, "bwd_ms": bwd_ms})
            print(json.dumps(rows[-1]), flush=True)
    if a.check:
        cshape = REHEARSAL_CHECK_SHAPE if a.rehearse else CHECK_SHAPE
        B, S, H, P, N, G = cshape
        for hard, (dt_scale, a_scale) in HARD.items():
            ops, weight = inputs(cshape, a.seed + 1, dt_scale, a_scale)
            want = _passes(token_recurrence, ops, weight, 1)[2]
            for name, tiles in (("pallas", ssd.tiles(S, H, P, N, G)),
                                ("xla", None)):
                got = _passes(
                    lambda *o: ssd.ssd_scan(*o, tiles_=tiles,
                                            interpret=a.rehearse),
                    ops, weight, 1)[2]
                rows.append({"check": hard, "engine": name,
                             "far": _far(got, want)})
                print(json.dumps(rows[-1]), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "ssd_scan_probe.json"), "w") as f:
        json.dump({"device": str(jax.devices()[0]), "rows": rows}, f,
                  indent=1)
    return 3 if a.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
