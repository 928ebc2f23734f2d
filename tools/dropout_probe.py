"""How a dropout site draws and keeps its mask, timed on the chip.

  hidden   bf16[96, 256, 2048]   the FFN's hidden       transformer-train
  output   bf16[96, 256, 512]    a sublayer's output    (12 + 30 + 2 sites)
  heads    bf16[96, 8, 256, 64]  attention's output     (18 sites)

Five forms of `keep`, each behind the same `where(keep, x, 0)`:

  tree           `jax.random.bernoulli`, the vjp's residual the mask: what
                 ops/nn_ops.py::_dropout lowered to before PR 55 (XLA clones
                 the generator into every consumer's fusion)
  threefry-kept  threefry's bits against the threshold, ONCE, behind
                 `optimization_barrier`: kernels/dropout_mask.py's `xla`
  rbg-kept       XLA's RngBitGenerator (`jax.lax.rng_bit_generator`), an
                 opaque operation, against the threshold
  pallas-mask    the core's generator in a Pallas kernel that writes the
                 bytes: kernels/dropout_mask.py's `pallas`
  pallas-fused   the same kernel reading x and writing `out` beside the
                 bytes in one pass (the select no longer XLA's to fuse)

and three measurements: `draw` (the mask alone, ms a site, at the three
shapes), `ffn` (x [96, 256, 512] through relu(x W1 + b1), dropout, W2 + b2,
dropout, + x: the forward, the input's gradient and both weights' and
biases' behind the matmuls as the model has them, one jit, ms a call; the
`none` row is the same with no dropout at all, the floor), and `--check`
(the statistics of the chip's own generator over 50 M elements a p: the
keep rate, the worst column's and the worst row's, the agreement of
neighbours along both axes and of one grid step's tile with the next, two
keys, one key twice).  `--sweep` pins the kernel's tile of rows.  `--mesh
4` instead runs ONE site of a Fluid program under ParallelExecutor, the
batch sharded over four chips and the key replicated, and says from the
fetched `Mask` which engine the site was given, the keep rate, and how
often two chips' shards agree (independent masks: keep^2 + p^2; the same
mask: 1).  Rows go to chiprun_out/dropout_probe.json.

A tool, run by no benchmark cell:
    chiprun --chips 1 -- python3 tools/dropout_probe.py --seed 7 [--check]
    chiprun --chips 4 -- python3 tools/dropout_probe.py --seed 7 --mesh 4
    JAX_PLATFORMS=cpu python3 tools/dropout_probe.py --rehearse
`--rehearse` runs tiny shapes on the CPU without the kernels (the
interpreter has no generator) and exits 3: its times are not the chip's.
One process holds the chip; it starts no child.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from flash_fwd_probe import _time_ms  # noqa: E402

SHAPES = {"hidden": (96, 256, 2048), "output": (96, 256, 512),
          "heads": (96, 8, 256, 64)}
REHEARSAL_SHAPES = {"hidden": (4, 32, 256), "output": (4, 32, 128),
                    "heads": (4, 2, 32, 64)}
P = 0.1  # transformer-base's


def _fused_kernel(seeds_ref, x_ref, out_ref, mask_ref, *, below):
    import jax.experimental.pallas as pl
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    pltpu.prng_seed(seeds_ref[2 * i], seeds_ref[2 * i + 1])
    keep = pltpu.prng_random_bits(mask_ref.shape) >= below - 2 ** 31
    mask_ref[...] = keep.astype(mask_ref.dtype)
    out_ref[...] = jnp.where(keep, x_ref[...], 0).astype(out_ref.dtype)


def forms(rehearse: bool, block_rows=None):
    """name -> drop(key, x) -> (out, mask uint8), differentiable in x."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels import dropout_mask

    below = dropout_mask.threshold(P)

    def select(mask_of):
        def drop(key, x):
            mask = mask_of(key, x.shape)
            return jnp.where(mask != 0, x, jnp.zeros((), x.dtype)), mask
        return drop

    def tree(key, x):
        keep = jax.random.bernoulli(key, 1.0 - P, x.shape)
        return jnp.where(keep, x, 0.0).astype(x.dtype), keep.astype(jnp.uint8)

    def rbg(key, shape):
        _, bits = jax.lax.rng_bit_generator(
            jnp.concatenate([key, key]).astype(jnp.uint32), shape,
            dtype=jnp.uint32)
        return (bits >= np.uint32(below)).astype(jnp.uint8)

    def tiled(shape):
        t = dropout_mask.tiles(shape)
        if block_rows and t is not None and t[0] % block_rows == 0:
            t = (t[0], t[1], block_rows)
        return t

    def pallas_mask(key, shape):
        return dropout_mask._pallas(key, shape, below, tiled(shape))

    def fused(key, x):
        import jax.experimental.pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        rows, cols, step = tiled(x.shape)
        tile = pl.BlockSpec((step, cols), lambda i: (i, 0))
        call = pl.pallas_call(
            functools.partial(_fused_kernel, below=below),
            grid=(rows // step,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), tile],
            out_specs=[tile, tile],
            out_shape=[jax.ShapeDtypeStruct((rows, cols), x.dtype),
                       jax.ShapeDtypeStruct((rows, cols), jnp.int8)],
            name="dropout_fused")

        @jax.custom_vjp
        def drop(x):
            out, mask = call(dropout_mask._seeds(key, rows // step),
                             x.reshape(rows, cols))
            return out.reshape(x.shape), mask.reshape(x.shape)

        def fwd(x):
            out, mask = drop(x)
            return (out, mask), mask

        def bwd(mask, cots):
            return (jnp.where(mask != 0, cots[0],
                              jnp.zeros((), cots[0].dtype)),)

        drop.defvjp(fwd, bwd)
        out, mask = drop(x)
        return out, mask.astype(jnp.uint8)

    table = {
        "tree": tree,
        "threefry-kept": select(lambda key, shape: dropout_mask.draw(
            key, shape, P, force="jax")[0]),
        "rbg-kept": select(rbg),
    }
    if not rehearse:
        table["pallas-mask"] = select(pallas_mask)
        table["pallas-fused"] = fused
    return table


def ffn_step(drop, shapes):
    """jit of (key, x, w1, b1, w2, b2, cot) -> the FFN's output and the
    five gradients of sum(out * cot), dropout at its two sites by `drop`
    (None: no dropout)."""
    import jax
    import jax.numpy as jnp

    def forward(x, w1, b1, w2, b2, key):
        k1, k2 = jax.random.split(key)
        lead = x.shape[:-1]
        h = jnp.dot(x, w1, preferred_element_type=jnp.float32) + b1
        h = jax.nn.relu(h).astype(x.dtype).reshape(shapes["hidden"])
        if drop is not None:
            h = drop(k1, h)[0]
        y = jnp.dot(h.reshape(lead + (-1,)), w2,
                    preferred_element_type=jnp.float32) + b2
        y = y.astype(x.dtype).reshape(shapes["output"])
        if drop is not None:
            y = drop(k2, y)[0]
        return x + y.reshape(x.shape)

    def loss(x, w1, b1, w2, b2, key, cot):
        out = forward(x, w1, b1, w2, b2, key)
        return jnp.sum(out.astype(jnp.float32) * cot), out

    grad = jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)
    return jax.jit(lambda key, x, w1, b1, w2, b2, cot: grad(
        x, w1, b1, w2, b2, key, cot))


def check(mask_of, key, shape, p, step_rows):
    """The statistics of one mask [rows, columns] as z-scores (a fair
    generator reads |z| under ~4; the worst of 2048 columns or 24576 rows
    under ~5)."""
    import jax
    import jax.numpy as jnp

    keep = 1.0 - p

    @jax.jit
    def stats(key):
        m = mask_of(key, shape).reshape(-1, shape[-1]).astype(jnp.float32)
        rows, cols = m.shape
        n = rows * cols

        def z(mean, count, q=keep):
            return (mean - q) / jnp.sqrt(q * (1 - q) / count)

        agree = keep * keep + p * p
        out = {
            "keep_rate": jnp.mean(m),
            "z_rate": z(jnp.mean(m), n),
            "z_worst_column": jnp.max(jnp.abs(z(jnp.mean(m, 0), rows))),
            "z_worst_row": jnp.max(jnp.abs(z(jnp.mean(m, 1), cols))),
            "z_lane_neighbours": z(
                jnp.mean(m[:, 1:] == m[:, :-1]), rows * (cols - 1), agree),
            "z_row_neighbours": z(
                jnp.mean(m[1:] == m[:-1]), (rows - 1) * cols, agree),
        }
        if step_rows and rows > step_rows:
            out["z_next_tile"] = z(
                jnp.mean(m[step_rows:] == m[:-step_rows]),
                (rows - step_rows) * cols, agree)
        return out

    other = jax.random.fold_in(key, 1)
    same = jax.jit(lambda a, b: jnp.mean(
        (mask_of(a, shape) == mask_of(b, shape)).astype(jnp.float32)))
    row = {k: float(v) for k, v in stats(key).items()}
    agree = keep * keep + p * p
    row["z_two_keys"] = float((same(key, other) - agree) / (
        agree * (1 - agree) / math.prod(shape)) ** 0.5)
    row["one_key_twice_agree"] = float(same(key, key))
    return {k: round(v, 5) for k, v in row.items()}


def mesh_row(chips, shape, p, seed):
    """One dropout site over ones [chips x shape] through ParallelExecutor
    on `chips` devices: the engine it was given and its shards' masks."""
    import jax
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import layers, observability
    from paddle_tpu.parallel import ParallelExecutor, make_mesh

    fluid.reset_default_env()
    for program in (fluid.default_startup_program(),
                    fluid.default_main_program()):
        program.random_seed = seed % (2 ** 31)
    x = layers.data("x", list(shape[1:]), dtype="float32")
    w = layers.create_parameter([1], "float32", name="probe_w")
    out = layers.dropout(layers.elementwise_mul(x, w), dropout_prob=p)
    loss = layers.mean(out)
    fluid.optimizer.SGD(learning_rate=0.0).minimize(loss)
    place = (fluid.TPUPlace() if jax.devices()[0].platform == "tpu"
             else fluid.CPUPlace())
    fluid.Executor(place).run(fluid.default_startup_program())
    pe = ParallelExecutor(loss_name=loss.name, mesh=make_mesh(
        {"dp": chips}, devices=jax.devices()[:chips]))
    observability.reset()
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        feed = {"x": np.ones((chips * shape[0],) + tuple(shape[1:]),
                             np.float32)}
        mask_name, = [op.outputs["Mask"][0] for op in fluid.
                      default_main_program().desc.block(0).ops
                      if op.type == "dropout"]
        got, mask = (np.asarray(v) for v in pe.run(
            fetch_list=[out, mask_name], feed=feed))
        span, = [dict(s.args) for s in observability.default_tracer().spans()
                 if s.name == "dropout.lower"]
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()
    shards = (mask != 0).reshape(chips, -1)
    return {"what": "mesh", "chips": chips, "p": p, "span": span,
            "mask_dtype": str(mask.dtype),
            "out_is_zero_where_mask_is": bool(
                np.array_equal(got != 0, mask != 0)),
            "elements_a_chip": int(shards.shape[1]),
            "keep_rate_a_chip": [round(float(s.mean()), 5) for s in shards],
            "independent_agree": round((1 - p) ** 2 + p ** 2, 5),
            "agree": {f"{i}-{j}": round(float(
                (shards[i] == shards[j]).mean()), 5)
                for i in range(chips) for j in range(i + 1, chips)}}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--block-rows", default="64,128,256,512,1024")
    ap.add_argument("--mesh", type=int, default=0, metavar="CHIPS")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels import dropout_mask

    dev = jax.devices()[0]
    if not a.rehearse and dev.platform != "tpu":
        print("dropout_probe: no TPU here (use --rehearse on the CPU)",
              file=sys.stderr)
        return 2
    shapes = REHEARSAL_SHAPES if a.rehearse else SHAPES
    half = jnp.float32 if a.rehearse else jnp.bfloat16
    key = jax.random.PRNGKey(a.seed % (2 ** 31))
    rng = np.random.RandomState(a.seed % (2 ** 32))
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    def timed(row, fn, args):
        try:
            jax.block_until_ready(fn(*args))
            if not a.rehearse:  # a CPU's time is no one's
                row["ms"] = round(_time_ms(fn, args, a.calls), 4)
        except Exception as e:  # a form the compiler refuses is a row
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        emit(row)

    if a.mesh:
        emit(mesh_row(a.mesh, shapes["output"], P, a.seed))
    else:
        one_chip(a, shapes, half, key, rng, emit, timed)

    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "rehearsal": bool(a.rehearse), "date": time.strftime(
               "%Y-%m-%d %H:%M UTC", time.gmtime()), "rows": rows}
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/dropout_probe.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in ("device", "rehearsal", "date")}))
    return 3 if a.rehearse else 0


def one_chip(a, shapes, half, key, rng, emit, timed):
    """The rows of one chip: the draws, the FFN, `--check`."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import dropout_mask

    table = forms(a.rehearse)
    # the mask alone
    for name, shape in shapes.items():
        x = jnp.ones(shape, half)
        for form, drop in table.items():
            if form == "pallas-fused":
                continue
            timed({"what": "draw", "shape": name, "form": form,
                   "tiles": dropout_mask.tiles(shape)},
                  jax.jit(lambda k, drop=drop, x=x: drop(k, x)[1]), (key,))
    if a.sweep and not a.rehearse:
        for name, shape in shapes.items():
            for step in map(int, a.block_rows.split(",")):
                drop = forms(False, step)["pallas-mask"]
                x = jnp.ones(shape, half)
                timed({"what": "draw", "shape": name,
                       "form": f"pallas-mask-rows-{step}"},
                      jax.jit(lambda k, drop=drop, x=x: drop(k, x)[1]),
                      (key,))

    # the FFN around its two sites
    lead, d_model = shapes["output"][:-1], shapes["output"][-1]
    d_inner = shapes["hidden"][-1]

    def normal(*s, scale=1.0, dtype=jnp.float32):
        return jnp.asarray(rng.randn(*s) * scale, dtype)

    args = (normal(*lead, d_model, dtype=half),
            normal(d_model, d_inner, scale=d_model ** -0.5, dtype=half),
            normal(d_inner, scale=0.1),
            normal(d_inner, d_model, scale=d_inner ** -0.5, dtype=half),
            normal(d_model, scale=0.1), normal(*lead, d_model))
    for form, drop in [("none", None)] + list(table.items()):
        row = {"what": "ffn", "form": form}
        step = ffn_step(drop, shapes)
        timed(row, step, (key,) + args)

    if a.check:
        shape = (shapes["hidden"][0] * shapes["hidden"][1],
                 shapes["hidden"][2])
        tiled = dropout_mask.tiles(shape)
        for p in (0.1, 0.3, 0.5):
            for engine in ("xla",) if a.rehearse else ("pallas", "xla"):
                def mask_of(k, s, p=p, engine=engine):
                    return dropout_mask.draw(
                        k, s, p,
                        force="jax" if engine == "xla" else engine)[0]
                emit({"what": "check", "engine": engine, "p": p,
                      "elements": shape[0] * shape[1], **check(
                          mask_of, key, shape, p,
                          tiled[2] if engine == "pallas" else 0)})


if __name__ == "__main__":
    sys.exit(main())
