"""`ssm.scan` alone, timed and checked on the chip at
`phi4flash-train-sambay`'s shape.

  sambay   x, dt [1, 8192, 5120] fp32, A [5120, 16], B, C [1, 8192, 16]:
           one Mamba layer of the cell, chunks of 64

The op selective_scan's arithmetic in its two engines
(kernels/selective_scan.py): `pallas` (the kernel pair at the tiles `tiles`
gives the shape; `--chunks` also pins each chunk length in turn) and, with
`--xla`, the jax.numpy engine.  For each: the forward and the backward
ALONE (the pullback of jax.vjp, jitted over its residuals), ms a layer and
the share of the HBM rate that the pass's part of `moved_bytes` is of it.

`--check` holds both engines to the recurrence one token at a time at [1,
1024, 1024, 16] on three inputs: a random one, a state that decays to
nothing inside a token (dt A ~ -40) and one that does not decay (dt ~ 0):
the largest error of y and of each gradient over the largest value.  What
the CPU interpreter cannot show is there: Mosaic's exp and its rolls.  Rows
go to chiprun_out/ssm_scan_probe.json.

A tool, run by no benchmark cell:
    chiprun --chips 1 -- python3 tools/ssm_scan_probe.py --seed 7 \
        [--check] [--xla] [--chunks 32,64]
    JAX_PLATFORMS=cpu python3 tools/ssm_scan_probe.py --rehearse --check
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

from flash_fwd_probe import _time_ms  # noqa: E402

SHAPE, REHEARSAL_SHAPE = (1, 8192, 5120, 16), (1, 64, 1024, 16)
CHECK_SHAPE, REHEARSAL_CHECK_SHAPE = (1, 1024, 1024, 16), (1, 48, 1024, 16)
HBM_GB_S = 819.0  # one v5e (Google Cloud documentation, "TPU v5e")
NAMES = ("y", "dx", "ddt", "dA", "dB", "dC", "dD")
# name: (dt's scale, A's scale)
HARD = {"random": (0.3, 1.0), "decays_to_nothing": (5.0, 8.0),
        "does_not_decay": (1e-4, 1.0)}


def inputs(shape, seed, dt_scale=0.3, a_scale=1.0):
    """(x, dt, A, B, C, D) fp32 and a cotangent for y."""
    import jax.numpy as jnp
    import numpy as np

    B, S, E, N = shape
    r = np.random.RandomState(seed % (2 ** 32))
    x = r.randn(B, S, E)
    dt = np.log1p(np.exp(r.randn(B, S, E))) * dt_scale
    a = -np.exp(r.randn(E, N) * 0.5) * a_scale
    ops = (x, dt, a, r.randn(B, S, N), r.randn(B, S, N), r.randn(E))
    return (tuple(jnp.asarray(t, jnp.float32) for t in ops),
            jnp.asarray(r.randn(B, S, E), jnp.float32))


def token_recurrence(x, dt, a, b, c, d):
    """The recurrence one token at a time."""
    import jax
    import jax.numpy as jnp

    def row(x, dt, b, c):
        def token(s, one):
            x, dt, b, c = one
            s = jnp.exp(dt[:, None] * a) * s + (dt * x)[:, None] * b[None]
            return s, s @ c + d * x

        return jax.lax.scan(token, jnp.zeros(a.shape), (x, dt, b, c))[1]

    return jax.vmap(row)(x, dt, b, c)


def _passes(fn, ops, weight, calls):
    """(ms forward, ms backward alone, (y, the six gradients))."""
    import jax

    fwd = jax.jit(fn)
    y, pull = jax.vjp(fn, *ops)
    # the pullback an ARGUMENT (a pytree of its residuals): jitted as a
    # closure its residuals are constants of a 1 GB executable
    back = jax.jit(lambda pull, w: pull(w))
    return (_time_ms(fwd, ops, calls),
            _time_ms(back, (pull, weight), calls),
            (y,) + tuple(back(pull, weight)))


def _far(got, want):
    import jax.numpy as jnp

    return {n: float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
            for n, g, w in zip(NAMES, got, want)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--xla", action="store_true")
    ap.add_argument("--chunks", default="")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()

    import jax

    from paddle_tpu.kernels import selective_scan as ss

    shape = REHEARSAL_SHAPE if a.rehearse else SHAPE
    B, S, E, N = shape
    rows = []
    ops, weight = inputs(shape, a.seed)
    moved = ss.moved_bytes(B, S, E, N)
    fwd_share = (3 * B * S * E * 4 + 8 * B * S * N) / moved
    engines = [("pallas", ss.tiles(S, E, N))]
    engines += [(f"pallas-chunk-{c}", ss.tiles(S, E, N, int(c)))
                for c in a.chunks.split(",") if c]
    engines += [("xla", None)] * a.xla
    for name, tiles in engines:
        if tiles is None and name != "xla":
            rows.append({"engine": name, "tiles": None})
            continue
        fwd_ms, bwd_ms, _ = _passes(
            lambda *o: ss.selective_scan(*o, tiles_=tiles,
                                         interpret=a.rehearse),
            ops, weight, 1 if a.rehearse else a.calls)
        rows.append({
            "engine": name, "shape": list(shape),
            "tiles": tiles and tiles._asdict(),
            "fwd_ms": fwd_ms, "bwd_ms": bwd_ms,
            "fwd_hbm_share": moved * fwd_share / (fwd_ms * 1e-3)
            / (HBM_GB_S * 1e9),
            "bwd_hbm_share": moved * (1 - fwd_share) / (bwd_ms * 1e-3)
            / (HBM_GB_S * 1e9)})
        print(json.dumps(rows[-1]), flush=True)
    if a.check:
        cshape = REHEARSAL_CHECK_SHAPE if a.rehearse else CHECK_SHAPE
        for hard, (dt_scale, a_scale) in HARD.items():
            ops, weight = inputs(cshape, a.seed + 1, dt_scale, a_scale)
            want = _passes(token_recurrence, ops, weight, 1)[2]
            for name, tiles in (("pallas", ss.tiles(*cshape[1:])),
                                ("xla", None)):
                got = _passes(
                    lambda *o: ss.selective_scan(*o, tiles_=tiles,
                                                 interpret=a.rehearse),
                    ops, weight, 1)[2]
                rows.append({"check": hard, "engine": name,
                             "far": _far(got, want)})
                print(json.dumps(rows[-1]), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "ssm_scan_probe.json"), "w") as f:
        json.dump({"device": str(jax.devices()[0]), "rows": rows}, f,
                  indent=1)
    return 3 if a.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
