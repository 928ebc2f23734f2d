"""kernels/kda_mix.py's kernel pairs alone, timed and checked on the chip
at the shapes of the cells that run them (`--shapes`, a layer of each).

  kimi       q~, k~, v~, f, o, gate [B, 4096, 32 x 128] bf16, four taps:
             kda_conv_decay and kda_gated_norm (sigmoid, a bias) of
             `kimi-train-kda8k`; B 4 sequences a call where the cell has 1,
             so that a call is milliseconds of device time and not the
             host's dispatch; the times are A SEQUENCE (a layer of the cell)
  qwen3next  q | k | v [B, 8192, 8192] bf16, four taps, silu, no bias:
             short_conv1d; o, gate [B, 8192, 32 x 128]: kda_gated_norm
             under silu with no bias (`qwen3next-train-gdn8k`; B 2)
  granite    x | B | C [B, 8192, 2304] bf16, four taps, silu, a bias:
             short_conv1d (`granite-train-ssd8k`; B 4)
  phi4flash  x [B, 8192, 5120] bf16, four taps, silu, a bias: short_conv1d
             (`phi4flash-train-sambay`; B 4)

The ops' arithmetic in their two engines: `xla`
(ops/linear_attention_ops.py::conv_decay / ::short_conv / ::gated_norm,
jax.numpy) and `pallas` (kernels/kda_mix.py, the kernel pairs) at the
tiles `conv_tiles` / `short_conv_tiles` / `norm_tiles` give the shape;
`--sweep` also pins every tile of --rows x --channels, `--pairs` keeps
some of the pairs.  For each pair: the forward and the backward ALONE (the
pullback of jax.vjp, jitted over its residuals: the backward kernel, or the
jax.numpy backward's passes), ms a sequence, the share of the HBM rate that
the pass's part of `conv_moved_bytes` / `short_conv_moved_bytes` /
`norm_moved_bytes` is of it, and how far the outputs and the gradients lie
from the jax.numpy engine's (the largest difference over the largest
value).

`--check` runs the first shape's pairs at [2, 1024, 4 x 128] against the
jax.numpy engine ON FP32 COPIES of the inputs, at fp32 and at bf16 streams:
the kernel pairs and, at the same streams, the jax.numpy engine.  What the CPU
interpreter cannot show is there: Mosaic's exp, log1p and rsqrt.  Rows go
to chiprun_out/kda_mix_probe.json.

A tool, run by no benchmark cell:
    chiprun --chips 1 -- python3 tools/kda_mix_probe.py --seed 7 \
        [--shapes kimi,qwen3next,granite,phi4flash] [--sweep] [--check]
    JAX_PLATFORMS=cpu python3 tools/kda_mix_probe.py --rehearse --check
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
sys.path.insert(0, ROOT)

from flash_fwd_probe import _time_ms  # noqa: E402

# name: (B, S, H, D, taps, what runs there): the convolution's pair
# (`conv_decay`: four streams of H D; `short_conv`: one of `width`
# channels, with a bias or none) and the norm's rule over H D (None: the
# cell has no such norm)
SHAPES = {
    "kimi": (4, 4096, 32, 128, 4, dict(conv="conv_decay",
                                       gate=("sigmoid", True))),
    "qwen3next": (2, 8192, 32, 128, 4, dict(conv="short_conv", width=8192,
                                            bias=False,
                                            gate=("silu", False))),
    "granite": (4, 8192, 0, 128, 4, dict(conv="short_conv", width=2304,
                                         bias=True, gate=None)),
    "phi4flash": (4, 8192, 0, 128, 4, dict(conv="short_conv", width=5120,
                                           bias=True, gate=None)),
}
REHEARSAL_SHAPES = {
    name: (2, 256, min(H, 2), D, taps,
           dict(what, **({"width": 256} if "width" in what else {})))
    for name, (_, _, H, D, taps, what) in SHAPES.items()}
CHECK_SIZE, REHEARSAL_CHECK_SIZE = (2, 1024, 4), (1, 256, 2)
EPS = 1e-5
HBM_GB_S = 819.0  # one v5e (Google Cloud documentation, "TPU v5e")


def names(pair, what):
    """The outputs and gradients `_both_passes` returns of a pair."""
    if pair == "conv_decay":
        return ("q", "k", "v", "g", "dq~", "dk~", "dv~", "df", "dwq", "dwk",
                "dwv", "ddt_bias", "da_log")
    if pair == "short_conv":
        return ("y", "dx", "dw") + (("dbias",) if what["bias"] else ())
    return (("out", "do", "dgate")
            + (("dgate_bias",) if what["gate"][1] else ()) + ("dscale",))


def inputs(shape, seed, dtype):
    """{pair: (arguments, cotangents)}: the streams in `dtype`, the
    parameters fp32 and off the values they start at; a bias the site has
    not is None."""
    import jax.numpy as jnp
    import numpy as np

    B, S, H, D, taps, what = shape
    C, rng = H * D, np.random.RandomState(seed % (2 ** 32))

    def normal(*s, scale=1.0, dtype=jnp.float32):
        return jnp.asarray(rng.randn(*s) * scale, dtype)

    def wide(n, C=C):
        return tuple(normal(B, S, C, dtype=dtype) for _ in range(n))

    both = {}
    if what["conv"] == "conv_decay":
        both["conv_decay"] = (
            wide(4) + tuple(normal(taps, C, scale=0.5) for _ in range(3))
            + (normal(C) - 2.0, 1.0 + 0.5 * normal(H)),
            wide(3) + (normal(B, S, C),))
    else:
        W = what["width"]
        both["short_conv"] = (
            wide(1, W) + (normal(taps, W, scale=0.5),
                          normal(W) if what["bias"] else None), wide(1, W))
    if what["gate"]:
        both["gated_norm"] = (
            wide(2) + (0.3 * normal(C) if what["gate"][1] else None,
                       1.0 + 0.3 * normal(D)), wide(1))
    return both


def engines(shape, force, rows=None, channels=None):
    """({pair: function of its arguments}, {pair: the tiles it ran under,
    None for the op's jax.numpy form}), chosen as the ops choose
    (kernels/engine.py) with the door and the tile in the caller's hand"""
    from paddle_tpu.kernels import engine, kda_mix
    from paddle_tpu.ops import linear_attention_ops as ops

    H, what, taken = shape[2], shape[5], {}
    rule = what["gate"] and what["gate"][0]

    def site(pair, plan, form, kernels):
        tiles = taken[pair] = engine.tiles_or_none(force, None, plan)
        return (form() if tiles is None
                else kernels(tiles, force == "interpret"))

    def conv_decay(q, k, v, f, wq, *rest):
        xs = (q, k, v, f, wq, *rest, H)
        return site("conv_decay", lambda: kda_mix.conv_tiles(
            q.shape[1], q.shape[2], wq.shape[0], q.dtype, rows, channels),
            lambda: ops.conv_decay(*xs),
            lambda *tiles: kda_mix.conv_decay(*xs, *tiles))

    def short_conv(x, w, bias):
        return (site("short_conv", lambda: kda_mix.short_conv_tiles(
            x.shape[1], x.shape[2], w.shape[0], x.dtype, rows, channels),
            lambda: ops.short_conv(x, w, bias, "silu"),
            lambda *tiles: kda_mix.short_conv(x, w, bias, "silu", *tiles)),)

    def gated_norm(o, *rest):
        xs = (o, *rest, H, EPS)
        return (site("gated_norm", lambda: kda_mix.norm_tiles(
            o.shape[1], o.shape[2], o.shape[2] // H, o.dtype, rows, channels),
            lambda: ops.gated_norm(*xs, rule),
            lambda *tiles: kda_mix.gated_norm(*xs, *tiles, rule)),)

    return {"conv_decay": conv_decay, "short_conv": short_conv,
            "gated_norm": gated_norm}, taken


def _both_passes(fn, args, cots):
    """[outputs, gradients] as fp32 numpy, and (forward, pullback over its
    residuals) with their arguments for the clock."""
    import jax
    import numpy as np

    fwd = jax.jit(fn)
    outs, pull = jax.vjp(fwd, *args)
    cots = tuple(c.astype(o.dtype) for c, o in zip(cots, outs))
    back = jax.jit(lambda p, d: p(d))
    grads = tuple(g for g in back(pull, cots) if g is not None)
    return ([np.asarray(t, np.float32) for t in tuple(outs) + grads],
            (fwd, args), (back, (pull, cots)))


def _rel(named, got, want):
    import numpy as np

    return {n: float(np.max(np.abs(g - w)) / max(np.max(np.abs(w)), 1e-30))
            for n, g, w in zip(named, got, want, strict=True)}


def _moved(pair, args):
    """(forward, backward) bytes of one call."""
    from paddle_tpu.kernels import kda_mix

    count, streams = {
        "conv_decay": (kda_mix.conv_moved_bytes, args[0:4:3]),
        "short_conv": (kda_mix.short_conv_moved_bytes, args[:1]),
        "gated_norm": (kda_mix.norm_moved_bytes, args[:2])}[pair]
    forward = count(*streams, True) - count(*streams, False)
    return forward, count(*streams, False) - forward


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--rows", default="128,256,512")
    ap.add_argument("--channels", default="128,256,512,1024,2048,4096")
    ap.add_argument("--shapes", default="kimi",
                    help=" | ".join(SHAPES) + ", comma-separated")
    ap.add_argument("--pairs", default="conv_decay,short_conv,gated_norm")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if not a.rehearse and dev.platform != "tpu":
        print("kda_mix_probe: no TPU here (use --rehearse on the CPU)",
              file=sys.stderr)
        return 2
    kernel = "interpret" if a.rehearse else "pallas"
    half = jnp.float32 if a.rehearse else jnp.bfloat16
    rows = []

    table = REHEARSAL_SHAPES if a.rehearse else SHAPES
    chosen = {name: table[name] for name in a.shapes.split(",")}
    for name, shape in chosen.items():
        B, S = shape[:2]
        both = {pair: xs for pair, xs in inputs(shape, a.seed, half).items()
                if pair in a.pairs.split(",")}
        variants = [("xla", "jax", None, None),
                    ("pallas-plan", kernel, None, None)]
        if a.sweep:
            variants += [(f"pallas-{r}x{c}", kernel, r, c)
                         for r in map(int, a.rows.split(","))
                         for c in map(int, a.channels.split(","))
                         if S % r == 0]
        want = {}
        for label, force, r, c in variants:
            fns, taken = engines(shape, force, r, c)
            for pair, (args, cots) in both.items():
                row = {"shape": name, "pair": pair, "variant": label,
                       "seed": a.seed}
                try:
                    got, fwd, back = _both_passes(fns[pair], args, cots)
                    tiles = taken[pair]
                    if (tiles is None) != (force == "jax"):
                        raise ValueError("the shape does not tile so, or "
                                         "the working set does not fit")
                    if tiles is not None:
                        row.update(tiles._asdict())
                    want.setdefault(pair, got)
                    row["rel_err"] = _rel(names(pair, shape[5]), got,
                                          want[pair])
                    moved = [m // B for m in _moved(pair, args)]
                    row["moved_bytes"] = moved
                    if not a.rehearse:  # an interpreter's time is no one's
                        f_ms = _time_ms(*fwd, a.calls) / B
                        b_ms = _time_ms(*back, a.calls) / B
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

    if a.check:
        first = next(iter(chosen.values()))
        small = dict(first[5], **({"width": 512} if "width" in first[5]
                                  else {}))
        shape = ((REHEARSAL_CHECK_SIZE if a.rehearse else CHECK_SIZE)
                 + first[3:5] + (small,))
        dtypes = (jnp.float32,) if a.rehearse else (jnp.float32, jnp.bfloat16)
        for dtype in dtypes:
            both = inputs(shape, a.seed, dtype)
            for pair, (args, cots) in both.items():
                exact = tuple(t if t is None else t.astype(jnp.float32)
                              for t in args)
                want, _, _ = _both_passes(
                    engines(shape, "jax")[0][pair], exact,
                    tuple(c.astype(dtype) for c in cots))
                row = {"check": pair, "streams": jnp.dtype(dtype).name,
                       "shape": list(shape[:5]), "seed": a.seed}
                named = names(pair, small)
                for label, force in (("xla", "jax"), ("pallas", kernel)):
                    got, _, _ = _both_passes(engines(shape, force)[0][pair],
                                             args, cots)
                    row[label] = _rel(named, got, want)
                row["pallas_no_further"] = all(
                    row["pallas"][n] <= max(2 * row["xla"][n], 3e-6)
                    for n in named)
                rows.append(row)
                print(json.dumps(row), flush=True)

    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "rehearsal": bool(a.rehearse), "date": time.strftime(
               "%Y-%m-%d %H:%M UTC", time.gmtime()), "rows": rows}
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kda_mix_probe.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in ("device", "rehearsal", "date")}))
    return 3 if a.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
