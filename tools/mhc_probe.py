"""`mhc.maps` and `mhc.mix` alone, timed and checked on the chip at
`xing-train-mhc4`'s shape.

  xing   X [B, 4096, 4, 3584] bf16, Phi [14336, 24] fp32, 20 Sinkhorn
         iterations: one hyper-connected sublayer of the cell; B 2
         sequences a call where the cell has 1, so that a call is
         milliseconds of device time and not the host's dispatch; the
         times are A SEQUENCE (a sublayer of the cell).  The streams are
         handed over stream-major, [B, n, S, C], and turned to [B, S, n, C]
         inside the jitted function (X' turned back), so that the turn is
         the compiler's to lay out, as it is inside a step: a [B, S, n, C]
         argument in the default layout costs a copy of the streams each
         way in every call, 0.3-0.45 ms a sequence (PERF.md, PR 51)

The ops mhc_maps', mhc_maps_read's, mhc_read's and mhc_write's arithmetic
in their two engines: `xla` (ops/hyper_connection_ops.py::maps /
::maps_read / ::read / ::write, jax.numpy under jax.checkpoint) and
`pallas` (kernels/mhc.py, the three kernel pairs; `read` alone has none
since PR 62, its rows are the jax.numpy form's) at the tiles `maps_tiles` /
`maps_read_tiles` / `mix_tiles` give the shape.  The fused pair `maps_read`
has a row `pallas-plan` (the planner's choice: its `resident` says whether
the forward holds the tile) and a row `pallas-streamed` (the maps' kernel,
then `read`'s forward kernel, at the tile the planner gives that form);
`--sweep` also pins every tile of --rows x --channels that tiles, the fused
pair's in both forms.  For each pair: the forward and the backward ALONE (the pullback of jax.vjp, jitted
over its residuals), ms a sequence, the share of the HBM rate that the
pass's least traffic is of it (`MOVED`, in passes over the streams: what
ISSUE 51's table counts), and how far the outputs and the gradients lie
from the jax.numpy engine's (the largest difference over the largest
value).

`--check` runs the pairs at [2, 1024, 4, 512], at fp32 and at bf16
streams, against an engine that rounds less: `maps` and `maps_read` in
FLOAT64 on the host's CPU (ops/hyper_connection_ops.py::maps takes the
dtype; the gradients through 40 normalisations are sums that cancel, and
two fp32 engines stand ~1e-3 apart where each is that far from the float64
one), `read` and `write` the jax.numpy engine on fp32 copies of the inputs;
the fused pair in both of its forms (`pallas`, `pallas_streamed`).  Both
engines at the same streams are held to it; `pallas_no_further` says that
the kernels lie no further from it than twice the jax.numpy engine does.
What the CPU interpreter cannot show is there: Mosaic's exp, sigmoid, rsqrt
and divide, and the MXU's sums of the parts of Phi.  Rows go to
chiprun_out/mhc_probe.json.

A tool, run by no benchmark cell:
    chiprun --chips 1 -- python3 tools/mhc_probe.py --seed 7 \
        [--sweep] [--check]
    JAX_PLATFORMS=cpu python3 tools/mhc_probe.py --rehearse --check
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

# name: (B, S, n, C)
SHAPES = {"xing": (2, 4096, 4, 3584)}
REHEARSAL_SHAPES = {"xing": (1, 256, 4, 256)}
CHECK_SHAPE, REHEARSAL_CHECK_SHAPE = (2, 1024, 4, 512), (1, 256, 4, 128)
MAPS = dict(epsilon=1e-6, hc_eps=1e-6, iters=20, clamp=(-30.0, 30.0))
HBM_GB_S = 819.0  # one v5e (Google Cloud documentation, "TPU v5e")
_MAPS_GRADS = ("dx", "dphi", "da_pre", "da_post", "da_res", "db_pre",
               "db_post", "db_res")
NAMES = {"maps": ("h",) + _MAPS_GRADS,
         "maps_read": ("h", "x_in") + _MAPS_GRADS,
         "read": ("x_in", "dx", "dh"),
         "write": ("x_out", "dx", "dh", "dy")}
# passes over the streams a pass of a pair has to make (forward, backward),
# a [T, C] value a quarter of one at four streams
MOVED = {"maps": (1.0, 2.0), "maps_read": (1.25, 2.25), "read": (1.25, 2.25),
         "write": (2.25, 3.5)}


def inputs(shape, seed, dtype):
    """{pair: (arguments, cotangents)}: the streams in `dtype` and apart
    from one another, the parameters fp32 and large enough that every map
    moves with the data, H_res near a permutation's mix."""
    import jax.numpy as jnp
    import numpy as np

    B, S, n, C = shape
    N, rng = 2 * n + n * n, np.random.RandomState(seed % (2 ** 32))

    def normal(*s, scale=1.0, dtype=jnp.float32):
        return jnp.asarray(rng.randn(*s) * scale, dtype)

    x = jnp.asarray(rng.randn(B, n, S, C)
                    * (1.0 + np.arange(n)[:, None, None]), dtype)
    h = jnp.asarray(rng.rand(B, N, S), jnp.float32)
    maps = (x, normal(n * C, N, scale=(n * C) ** -0.5),
            jnp.asarray([1.5], jnp.float32), jnp.asarray([-1.5], jnp.float32),
            jnp.asarray([1.2], jnp.float32), normal(n, scale=0.5),
            normal(n, scale=0.5), 2.0 * jnp.eye(n) + normal(n, n, scale=0.5))
    dh = normal(B, N, S)
    return {
        "maps": (maps, (dh,)),
        "maps_read": (maps, (dh, normal(B, S, C, dtype=dtype))),
        "read": ((x, h), (normal(B, S, C, dtype=dtype),)),
        "write": ((x, h, normal(B, S, C, dtype=dtype)),
                  (normal(B, n, S, C, dtype=dtype),))}


def engines(force, rows=None, channels=None, resident=None):
    """({pair: function of its arguments}, {pair: the tiles it ran under,
    None for the op's jax.numpy form}), chosen as the ops choose
    (kernels/engine.py) with the door, the tile and the fused forward's
    form in the caller's hand"""
    import functools

    import jax

    from paddle_tpu.kernels import engine, mhc
    from paddle_tpu.ops import hyper_connection_ops as hc

    taken = {}

    def plan(pair, S, n, C, dtype):
        if pair == "maps":
            return mhc.maps_tiles(S, n, C, MAPS["iters"], dtype, rows,
                                  channels)
        if pair == "maps_read":
            return mhc.maps_read_tiles(S, n, C, MAPS["iters"], dtype, rows,
                                       channels, resident)
        if pair == "write":
            return mhc.mix_tiles(S, n, C, dtype, pair, rows, channels)
        return None   # `read` alone has no kernels

    def site(pair, x, *rest, **cfg):
        x = _turned(x)
        B, S, n, C = x.shape
        tiles = taken[pair] = engine.tiles_or_none(
            force, None, lambda: plan(pair, S, n, C, x.dtype))
        if tiles is None:
            return jax.checkpoint(functools.partial(
                getattr(hc, pair), **cfg))(x, *rest)
        return getattr(mhc, pair)(x, *rest, tiles, force == "interpret",
                                  **cfg)

    return {"maps": lambda *xs: (site("maps", *xs, **MAPS),),
            "maps_read": lambda *xs: site("maps_read", *xs, **MAPS),
            "read": lambda *xs: (site("read", *xs),),
            "write": lambda *xs: (_turned(site("write", *xs)),)}, taken


def _turned(x):
    """[B, n, S, C] <-> [B, S, n, C]"""
    return x.swapaxes(1, 2)


def _both_passes(fn, args, cots):
    """[outputs, gradients] as fp32 numpy, and (forward, pullback over its
    residuals) with their arguments for the clock."""
    import jax
    import numpy as np

    fwd = jax.jit(fn)
    outs, pull = jax.vjp(fwd, *args)
    cots = tuple(c.astype(o.dtype) for c, o in zip(cots, outs))
    back = jax.jit(lambda p, d: p(d))
    grads = back(pull, cots)
    return ([np.asarray(t, np.float32) for t in tuple(outs) + tuple(grads)],
            (fwd, args), (back, (pull, cots)))


def _exact_maps(args, cots):
    """[H, (x_in with a second cotangent,) the gradients] of `maps` (and
    the read under it) in float64 on the host's CPU, as float64 numpy."""
    import jax
    import numpy as np
    from paddle_tpu.ops import hyper_connection_ops as ops

    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        wide = [jax.numpy.asarray(np.asarray(t, np.float64))
                for t in tuple(args) + tuple(cots)]
        wide, cots = wide[:len(args)], tuple(wide[len(args):])

        def fn(x, *small):
            x = _turned(x)
            h = ops.maps(x, *small, **MAPS, dtype=jax.numpy.float64)
            if len(cots) == 1:
                return (h,)
            n = x.shape[2]
            return h, sum(h[:, j][..., None] * x[:, :, j] for j in range(n))

        outs, pull = jax.vjp(fn, *wide)
        return [np.asarray(t) for t in tuple(outs) + tuple(pull(cots))]


def _rel(pair, got, want):
    import numpy as np

    return {n: float(np.max(np.abs(g - w)) / max(np.max(np.abs(w)), 1e-30))
            for n, g, w in zip(NAMES[pair], got, want)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--rows", default="128,256,512,1024")
    ap.add_argument("--channels", default="512,896,1792,3584")
    ap.add_argument("--pairs", default="maps,maps_read,read,write")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if not a.rehearse and dev.platform != "tpu":
        print("mhc_probe: no TPU here (use --rehearse on the CPU)",
              file=sys.stderr)
        return 2
    kernel = "interpret" if a.rehearse else "pallas"
    half = jnp.float32 if a.rehearse else jnp.bfloat16
    pairs, rows = a.pairs.split(","), []

    for name, shape in (REHEARSAL_SHAPES if a.rehearse else SHAPES).items():
        B, S, n, C = shape
        both = inputs(shape, a.seed, half)
        unit = S * n * C * jnp.dtype(half).itemsize
        kernels = [p for p in pairs if p != "read"]   # `read` has none
        fused = [p for p in pairs if p == "maps_read"]
        variants = [("xla", "jax", None, None, None, pairs),
                    ("pallas-plan", kernel, None, None, None, kernels),
                    ("pallas-streamed", kernel, None, None, 0, fused)]
        if a.sweep:
            for r in map(int, a.rows.split(",")):
                for c in map(int, a.channels.split(",")):
                    if S % r:
                        continue
                    variants += [
                        (f"pallas-{r}x{c}", kernel, r, c, None,
                         [p for p in kernels if p != "maps_read"]),
                        (f"pallas-{r}x{c}-streamed", kernel, r, c, 0, fused),
                        (f"pallas-{r}x{c}-resident", kernel, r, c, 1, fused)]
        want = {}
        for label, force, r, c, held, these in variants:
            fns, taken = engines(force, r, c, held)
            for pair in these:
                args, cots = both[pair]
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
                    row["rel_err"] = _rel(pair, got, want[pair])
                    if not a.rehearse:  # an interpreter's time is no one's
                        f_ms = _time_ms(*fwd, a.calls) / B
                        b_ms = _time_ms(*back, a.calls) / B
                        row.update(
                            fwd_ms=round(f_ms, 4), bwd_ms=round(b_ms, 4),
                            fwd_hbm_share=round(MOVED[pair][0] * unit / f_ms
                                                / 1e6 / HBM_GB_S, 4),
                            bwd_hbm_share=round(MOVED[pair][1] * unit / b_ms
                                                / 1e6 / HBM_GB_S, 4))
                except Exception as e:  # a tile Mosaic refuses is a row
                    row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
                rows.append(row)
                print(json.dumps(row), flush=True)

    if a.check:
        shape = REHEARSAL_CHECK_SHAPE if a.rehearse else CHECK_SHAPE
        dtypes = (jnp.float32,) if a.rehearse else (jnp.float32, jnp.bfloat16)
        for dtype in dtypes:
            both = inputs(shape, a.seed, dtype)
            for pair in pairs:
                args, cots = both[pair]
                if pair in ("maps", "maps_read"):
                    want = _exact_maps(args, cots)
                else:
                    want, _, _ = _both_passes(
                        engines("jax")[0][pair],
                        tuple(t.astype(jnp.float32) for t in args), cots)
                row = {"check": pair, "streams": jnp.dtype(dtype).name,
                       "shape": list(shape), "seed": a.seed}
                held = {"xla": ("jax", None)}
                if pair != "read":
                    held["pallas"] = (kernel, None)
                if pair == "maps_read":
                    held["pallas_streamed"] = (kernel, 0)
                for label, (force, form) in held.items():
                    fns, taken = engines(force, resident=form)
                    got, _, _ = _both_passes(fns[pair], args, cots)
                    row[label] = _rel(pair, got, want)
                    if pair == "maps_read" and force != "jax":
                        row[label + "_resident"] = taken[pair].resident
                row["pallas_no_further"] = all(
                    row[label][n] <= max(2 * row["xla"][n], 3e-6)
                    for n in NAMES[pair] for label in held if label != "xla")
                rows.append(row)
                print(json.dumps(row), flush=True)

    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "rehearsal": bool(a.rehearse), "date": time.strftime(
               "%Y-%m-%d %H:%M UTC", time.gmtime()), "rows": rows}
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/mhc_probe.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in ("device", "rehearsal", "date")}))
    return 3 if a.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
