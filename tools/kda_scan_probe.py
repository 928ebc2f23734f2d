"""`kda.scan` / `gdn.scan` alone, timed and checked on the chip at
`kimi-train-kda8k`'s and `qwen3next-train-gdn8k`'s shapes (--shapes).

  kimi       q, k, v [1, 4096, 32 x 128] bf16, g fp32, beta [1, 4096, 32]:
             one KDA layer of the cell, chunks of 64, a decay a key channel
  qwen3next  q, k [1, 8192, 16 x 128], v [1, 8192, 32 x 128] bf16, g and
             beta [1, 8192, 32] fp32: one Gated DeltaNet layer of the cell,
             ONE decay a head, two value heads a key head

The op gated_delta_attention's arithmetic in its two engines: `xla`
(kernels/gated_delta.py::_scan_by_groups, jax.numpy scans over regrouped
copies) and `pallas` (the kernel pair of the same file) at the rows a grid
step `kernel_tiles` gives the shape; `--sweep` also pins every group of
rows in --rows and every count of tiles a loop body in --unroll.  For each:
the forward and the backward ALONE (the pullback of jax.vjp, jitted over
its residuals: the backward kernel, or the jax.numpy backward's scans), ms
a layer, the share of the HBM rate that the pass's part of `moved_bytes` is
of it, and how far the output and the five gradients lie from the jax.numpy
engine's (the largest difference over the largest value).

`--check` runs three inputs at [1, 1024, 4 x 128] (the token recurrence's
backward keeps a state a token): a random one, a decay of e^-1500 a chunk,
and keys alike at beta ~ 1 (tests/test_gated_delta_attention.py's), in the
form of every shape named (the head-decay form: 2 key heads for the 4
value heads, and a fourth input whose four heads decay from e^-0.001 to
e^-21 a token), at fp32 and at bf16 operands: kernel pair and jax.numpy engine against the
recurrence one token at a time in fp32, the largest error of the output
and of each gradient over the largest value.  What the CPU interpreter
cannot show is there: the precision Mosaic gives an fp32 product.  Rows go
to chiprun_out/kda_scan_probe.json.

A tool, run by no benchmark cell:
    chiprun --chips 1 -- python3 tools/kda_scan_probe.py --seed 7 \
        [--shapes kimi,qwen3next] [--sweep] [--check]
    JAX_PLATFORMS=cpu python3 tools/kda_scan_probe.py --rehearse --check
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

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]

from flash_fwd_probe import _time_ms  # noqa: E402

# name: (B, S, H, D, chunk[, key heads: ONE decay a head])
SHAPES = {"kimi": (1, 4096, 32, 128, 64),
          "qwen3next": (1, 8192, 32, 128, 64, 16)}
REHEARSAL_SHAPES = {"kimi": (1, 256, 2, 128, 64),
                    "qwen3next": (1, 256, 2, 128, 64, 1)}
CHECK_SHAPE, REHEARSAL_CHECK_SHAPE = (1, 1024, 4, 128, 64), (1, 256, 2, 128, 64)
HBM_GB_S = 819.0  # one v5e (Google Cloud documentation, "TPU v5e")
NAMES = ("out", "dq", "dk", "dv", "dg", "dbeta")
# name: (rate, shift, alike) of `inputs`
HARD = {"random": (1.0, -2.0, 0.0), "decay_e-1500_a_chunk": (16.0, 1.0, 0.0),
        "keys_alike_beta_near_1": (1.0, -2.0, 1.0)}


def inputs(shape, seed, dtype, rate=1.0, shift=-2.0, alike=0.0):
    """tests/test_gated_delta_attention.py::_inputs' q, k, v (in `dtype`),
    g, beta, and a cotangent for the output."""
    import jax.numpy as jnp
    import numpy as np
    from test_gated_delta_attention import _inputs

    B, S, H, D = shape[:4]
    q, k, v, g, beta = _inputs(B, S, H, D, seed % (2 ** 32), rate, shift,
                               alike, key_heads=(shape[5:] or (None,))[0])
    weight = np.random.RandomState(seed % (2 ** 32)).randn(B, S, H * D)
    return ((q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta),
            jnp.asarray(weight, dtype))


def token_recurrence(q, k, v, g, beta, heads):
    """The recurrence one token at a time, fp32 whatever the operands."""
    import jax.numpy as jnp
    from test_gated_delta_attention import token_recurrence as plain

    return plain(*(t.astype(jnp.float32) for t in (q, k, v)), g, beta, heads)


def _both_passes(fn, args, weight):
    """[out, dq, dk, dv, dg, dbeta] as fp32 numpy, and (forward, pullback
    over its residuals, the pullback's argument) for the clock."""
    import jax
    import numpy as np

    fwd = jax.jit(fn)
    out, pull = jax.vjp(fwd, *args)
    back = jax.jit(lambda p, d: p(d))
    grads = back(pull, weight.astype(out.dtype))
    return ([np.asarray(t, np.float32) for t in (out,) + tuple(grads)],
            (fwd, args), (back, (pull, weight.astype(out.dtype))))


def _rel(got, want):
    import numpy as np

    return {n: float(np.max(np.abs(g - w)) / max(np.max(np.abs(w)), 1e-30))
            for n, g, w in zip(NAMES, got, want)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--rows", default="128,256,512,1024")
    ap.add_argument("--unroll", default="1,2")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--shapes", default="kimi")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import gated_delta as kda

    dev = jax.devices()[0]
    if not a.rehearse and dev.platform != "tpu":
        print("kda_scan_probe: no TPU here (use --rehearse on the CPU)",
              file=sys.stderr)
        return 2
    kernel = "interpret" if a.rehearse else "pallas"
    half = jnp.float32 if a.rehearse else jnp.bfloat16
    rows = []

    def engine(shape, force, group=None, unroll=None):
        H, chunk = shape[2], shape[4]
        return lambda *xs: kda.gated_delta_attention(
            *xs, heads=H, chunk=chunk, force=force, rows=group,
            unroll=unroll)

    shapes = REHEARSAL_SHAPES if a.rehearse else SHAPES
    for name in a.shapes.split(","):
        shape = shapes[name]
        B, S, H, D, chunk = shape[:5]
        form = (shape[5], True) if shape[5:] else (None, False)
        args, weight = inputs(shape, a.seed, half)
        plan, why = kda.kernel_tiles(B, S, H, D, chunk, half)
        assert plan is not None, why
        variants = [("xla", "jax", None, None),
                    ("pallas-plan", kernel, None, None)]
        if a.sweep:
            variants += [(f"pallas-{r}x{u}", kernel, r, u)
                         for r in map(int, a.rows.split(","))
                         for u in map(int, a.unroll.split(","))
                         if S % r == 0 and u <= r // 128]
        size = jnp.dtype(half).itemsize
        wide, gate, small = size * S * H * D, 4 * S * H * D, 4 * S * H
        keys = size * S * (form[0] or H) * D
        moved_fwd = B * (2 * keys + 2 * wide
                         + (small if form[1] else gate) + small)
        moved = (moved_fwd,
                 kda.moved_bytes(B, S, H, D, size, *form) - moved_fwd)
        want = None
        for label, force, group, unroll in variants:
            row = {"shape": name, "variant": label, "seed": a.seed,
                   "rows": group or plan.rows, "unroll": unroll or plan.unroll}
            try:
                got, fwd, back = _both_passes(
                    engine(shape, force, group, unroll), args, weight)
                want = want or got
                row["rel_err"] = _rel(got, want)
                if not a.rehearse:     # an interpreter's time is no one's
                    f_ms = _time_ms(*fwd, a.calls)
                    b_ms = _time_ms(*back, a.calls)
                    row.update(
                        fwd_ms=round(f_ms, 4), bwd_ms=round(b_ms, 4),
                        fwd_hbm_share=round(
                            moved[0] / f_ms / 1e6 / HBM_GB_S, 4),
                        bwd_hbm_share=round(
                            moved[1] / b_ms / 1e6 / HBM_GB_S, 4))
            except Exception as e:  # a tile Mosaic refuses is a row
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            rows.append(row)
            print(json.dumps(row), flush=True)

    for name in a.shapes.split(",") if a.check else ():
        from test_gated_delta_attention import from_weak_to_strong

        shape = REHEARSAL_CHECK_SHAPE if a.rehearse else CHECK_SHAPE
        H, hard = shape[2], dict(HARD)
        if shapes[name][5:]:                  # ONE decay a head
            shape = shape + (H // 2,)
            hard["heads_from_e-0.001_to_e-21_a_token"] = (
                from_weak_to_strong(H), 3.0, 0.0)
        dtypes = (jnp.float32,) if a.rehearse else (jnp.float32, jnp.bfloat16)
        for case, (rate, shift, alike) in hard.items():
            for dtype in dtypes:
                args, weight = inputs(shape, a.seed, dtype, rate, shift, alike)
                want, _, _ = _both_passes(
                    lambda *xs: token_recurrence(*xs, heads=H), args,
                    weight.astype(jnp.float32))
                row = {"check": case, "form": name,
                       "operands": jnp.dtype(dtype).name,
                       "shape": list(shape), "seed": a.seed}
                for label, force in (("xla", "jax"), ("pallas", kernel)):
                    got, _, _ = _both_passes(engine(shape, force), args,
                                             weight)
                    row[label] = _rel(got, want)
                row["pallas_no_further"] = all(
                    row["pallas"][n] <= max(2 * row["xla"][n], 3e-6)
                    for n in NAMES)
                rows.append(row)
                print(json.dumps(row), flush=True)

    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "rehearsal": bool(a.rehearse), "date": time.strftime(
               "%Y-%m-%d %H:%M UTC", time.gmtime()), "rows": rows}
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kda_scan_probe.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in ("device", "rehearsal", "date")}))
    return 3 if a.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
