"""kernels/kda_mix.py (the ops kda_conv_decay's and kda_gated_norm's
arithmetic as two Pallas kernel pairs over tiles of rows x blocks of
channels) in the Pallas interpreter on the CPU: every output and every
gradient against ops/linear_attention_ops.py::conv_decay / ::gated_norm,
the jax.numpy forms; the first tile's zeros before position 0 and a tile
boundary inside the taps' reach; what `conv_tiles` / `norm_tiles` say of the
cell's shape and of shapes that do not tile; the bytes the spans count.
The same for the pair of the op short_conv1d (`short_conv`: one stream, a
bias or none, silu | identity) and for the pair after the scan under the
other rules of its gate (silu, no bias: Gated DeltaNet's), against
ops/linear_attention_ops.py::short_conv / ::gated_norm over three tiles of
rows: zeros before the first, a halo on both sides of the second, nothing
after the last."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import paddle_tpu as fluid
from paddle_tpu.kernels import engine, kda_mix
from paddle_tpu.ops import linear_attention_ops as ops

D, EPS = 128, 1e-5
CONV = ("q", "k", "v", "g", "dq~", "dk~", "dv~", "df", "dwq", "dwk", "dwv",
        "ddt_bias", "da_log")
NORM = ("out", "do", "dgate", "dgate_bias", "dscale")
# S of two tiles of 128 rows; more than one channel block but in `b2`
CASES = {
    "two_channel_blocks": dict(),
    "b2": dict(B=2, channels=256),
    "two_taps_three_heads": dict(H=3, taps=2),
    "bf16": dict(dtype=jnp.bfloat16),
}


def _inputs(seed=0, B=1, S=256, H=2, taps=4, dtype=jnp.float32, **_):
    """((kda_conv_decay's arguments, cotangents), (kda_gated_norm's)): the
    streams in `dtype`, the parameters fp32 and off where they start."""
    rng, C = np.random.RandomState(seed), H * D

    def normal(*shape, scale=1.0, dtype=jnp.float32):
        return jnp.asarray(rng.randn(*shape) * scale, dtype)

    def wide(n, dtype=dtype):
        return tuple(normal(B, S, C, dtype=dtype) for _ in range(n))

    return ((wide(4) + tuple(normal(taps, C, scale=0.5) for _ in range(3))
             + (normal(C) - 1.0, 0.5 * normal(H)), wide(4, jnp.float32)),
            (wide(2) + (0.3 * normal(C), 1.0 + 0.3 * normal(D)),
             wide(1, jnp.float32)))


# short_conv1d's pair and the gate's other rules, three tiles of 128 rows
# x two blocks of channels; `bias`: the convolution's; `gate`: (rule, a
# bias row or none) of the norm beside it
SHORT_CASES = {
    "silu_4taps": dict(gate=("silu", False)),
    "silu_4taps_bf16": dict(dtype=jnp.bfloat16, gate=("silu", False)),
    "silu_4taps_bias": dict(bias=True, gate=("silu", True)),
    "silu_4taps_bias_bf16": dict(bias=True, dtype=jnp.bfloat16,
                                 gate=("silu", False)),
    "identity_2taps": dict(taps=2, act="identity", gate=("sigmoid", False)),
    "identity_2taps_bias_bf16": dict(taps=2, act="identity", bias=True,
                                     dtype=jnp.bfloat16,
                                     gate=("silu", True)),
}


def _short_inputs(seed=0, S=384, C=256, taps=4, bias=False,
                  dtype=jnp.float32, gate=("silu", False), **_):
    """((short_conv's arguments, cotangent), (gated_norm's)), a bias being
    None where the case has none."""
    rng = np.random.RandomState(seed)

    def normal(*shape, scale=1.0, dtype=jnp.float32):
        return jnp.asarray(rng.randn(*shape) * scale, dtype)

    return (((normal(1, S, C, dtype=dtype), normal(taps, C, scale=0.5),
              normal(C) if bias else None), (normal(1, S, C),)),
            ((normal(1, S, C, dtype=dtype), normal(1, S, C, dtype=dtype),
              0.3 * normal(C) if gate[1] else None, 1.0 + 0.3 * normal(D)),
             (normal(1, S, C),)))


def _names(kw):
    """The tensors a short case compares: no gradient for a bias it has
    not."""
    return (("y", "dx", "dw") + (("dbias",) if kw.get("bias") else ())
            + ("out", "do", "dgate")
            + (("dgate_bias",) if kw["gate"][1] else ()) + ("dscale",))


def _passes(fn, args, cots):
    """Outputs and gradients of `fn` (which returns (outputs, tiles)) under
    the loss that weighs the outputs by `cots`, as fp32 numpy; the tiles."""
    seen = []

    def loss(*xs):
        outs, tiles = fn(*xs)
        seen.append(tiles)
        outs = outs if isinstance(outs, tuple) else (outs,)
        return sum(jnp.sum(o.astype(jnp.float32) * c)
                   for o, c in zip(outs, cots)), outs

    (_, outs), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(i for i, a in enumerate(args) if a is not None),
        has_aux=True))(*args)
    return ([np.asarray(t, np.float32) for t in tuple(outs) + tuple(grads)],
            seen[0])


def _site(force, plan, pair, form, *args):
    """(outputs, tiles) of one site by the engine `force` names: as the op
    chooses it (kernels/engine.py::site), without the span."""
    tiles = engine.tiles_or_none(force, None, plan)
    if tiles is None:
        return form(*args), None
    return pair(*args, tiles, force == "interpret"), tiles


def _conv(force, H=2, rows=128, channels=128, **_):
    def site(q, k, v, f, wq, *rest):
        return _site(force, lambda: kda_mix.conv_tiles(
            q.shape[1], q.shape[2], wq.shape[0], q.dtype, rows, channels)
            if engine.one_dtype(q, k, v, f) else None,
            kda_mix.conv_decay, ops.conv_decay, q, k, v, f, wq, *rest, H)
    return site


def _norm(force, H=2, rows=128, channels=128, gate=("sigmoid", True), **_):
    def site(o, gate_, *rest):
        tiles = engine.tiles_or_none(force, None, lambda: kda_mix.norm_tiles(
            o.shape[1], o.shape[2], o.shape[2] // H, o.dtype, rows, channels)
            if engine.one_dtype(o, gate_) else None)
        if tiles is None:
            return ops.gated_norm(o, gate_, *rest, H, EPS, gate[0]), None
        return kda_mix.gated_norm(o, gate_, *rest, H, EPS, tiles,
                                  force == "interpret", gate[0]), tiles
    return site


def _short(force, act="silu", rows=128, channels=128, **_):
    def site(x, w, bias):
        tiles = engine.tiles_or_none(
            force, None, lambda: kda_mix.short_conv_tiles(
                x.shape[1], x.shape[2], w.shape[0], x.dtype, rows, channels))
        if tiles is None:
            return ops.short_conv(x, w, bias, act), None
        return kda_mix.short_conv(x, w, bias, act, tiles,
                                  force == "interpret"), tiles
    return site


@pytest.fixture(scope="module")
def both_engines():
    """{case: {tensor: (kernel pair's, jax.numpy form's)}}, each case's two
    pairs run once in the interpreter."""
    memo = {}

    def of(case):
        if case not in memo:
            kw = CASES[case]
            (conv_args, conv_cots), (norm_args, norm_cots) = _inputs(**kw)
            got, tiles = _passes(_conv("interpret", **kw), conv_args,
                                 conv_cots)
            want, none = _passes(_conv("jax", **kw), conv_args, conv_cots)
            assert tiles is not None and none is None
            assert tiles.halo == (16 if case == "bf16" else 8)
            memo[case] = dict(zip(CONV, zip(got, want)))
            got, tiles = _passes(_norm("interpret", **kw), norm_args,
                                 norm_cots)
            want, none = _passes(_norm("jax", **kw), norm_args, norm_cots)
            assert tiles is not None and none is None and tiles.halo == 0
            memo[case].update(zip(NORM, zip(got, want)))
        return memo[case]

    def of_short(case):
        if case not in memo:
            kw, both = SHORT_CASES[case], []
            for site, (args, cots), halo in zip(
                    (_short, _norm), _short_inputs(**kw),
                    (16 if "bf16" in case else 8, 0)):
                got, tiles = _passes(site("interpret", **kw), args, cots)
                want, none = _passes(site("jax", **kw), args, cots)
                assert tiles is not None and none is None
                assert (tiles.rows, tiles.halo) == (128, halo)
                both += zip(got, want)
            memo[case] = dict(zip(_names(kw), both, strict=True))
        return memo[case]

    of.short = of_short
    return of


@pytest.mark.parametrize("tensor", CONV + NORM)
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_match_the_jnp_engine(both_engines, case, tensor):
    """fp32 streams to ~1e-6 of the largest value (other orders of the same
    fp32 sums); bf16 streams at their rounding: the outputs and the
    cotangents that leave in bf16 to an ulp, the parameters' gradients
    (fp32 sums of the same rounded values) to 1e-5."""
    got, want = both_engines(case)[tensor]
    assert got.shape == want.shape and np.abs(want).max() > 0
    half = case == "bf16" and tensor in (
        "q", "k", "v", "dq~", "dk~", "dv~", "df", "out", "do", "dgate")
    tol = 8e-3 if half else (1e-5 if case == "bf16" else 3e-6)
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


@pytest.mark.parametrize("case, tensor", [
    (case, tensor) for case in sorted(SHORT_CASES)
    for tensor in _names(SHORT_CASES[case])])
def test_the_short_conv_and_the_gates_rules_match_the_jnp_engine(
        both_engines, case, tensor):
    """The same limits for short_conv1d's pair and the norm under each rule
    of its gate, over three tiles of rows."""
    got, want = both_engines.short(case)[tensor]
    assert got.shape == want.shape and np.abs(want).max() > 0
    half = "bf16" in case and tensor in ("y", "dx", "out", "do", "dgate")
    tol = 8e-3 if half else (1e-5 if "bf16" in case else 3e-6)
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


def test_nothing_lies_before_position_zero_and_a_tile_reads_the_one_before():
    """Position 0 sees its own tap alone (zeros before it, whatever the
    halo block holds); an impulse in the last row of the first tile shows
    in the first taps - 1 rows of the second; a cotangent in the first row
    of the second tile comes back to the last rows of the first."""
    (args, _), _ = _inputs(seed=3)
    q, w = np.asarray(args[0]), np.asarray(args[4])
    outs, _ = _conv("interpret")(*args)
    y0 = q[0, 0] * w[3]
    np.testing.assert_allclose(np.asarray(outs[0])[0, 0],
                               y0 / (1 + np.exp(-y0)), rtol=1e-5, atol=1e-6)

    impulse = jnp.zeros_like(args[0]).at[0, 127].set(1.0)
    at = (impulse,) * 3 + args[3:]
    outs, _ = _conv("interpret")(*at)
    got = np.asarray(outs[0])[0]
    for step in range(4):                      # rows 127 .. 130: taps 3 .. 0
        y = w[3 - step]
        np.testing.assert_allclose(got[127 + step], y / (1 + np.exp(-y)),
                                   rtol=1e-5, atol=1e-6)
    assert not got[:127].any() and not got[131:].any()

    def first_of_second_tile(fn):
        return jax.grad(lambda x: fn(x, *args[1:])[0][0][0, 128].sum())(q)

    back = np.asarray(first_of_second_tile(_conv("interpret")))
    want = np.asarray(first_of_second_tile(_conv("jax")))
    assert np.abs(want[0, 125:128]).min() > 0 and not back[0, :125].any()
    np.testing.assert_allclose(back, want, rtol=1e-5, atol=1e-6)


def test_the_cells_shape_tiles_within_the_budget():
    """[1, 4096, 32 x 128] bf16, four taps: both pairs on the kernels, the
    working sets under the planner's budget; the spans' bytes are the
    issue's 384 + 576 MB a layer, and 384 more where the layer is
    recomputed."""
    S, C = 4096, 32 * D
    conv = kda_mix.conv_tiles(S, C, 4, jnp.bfloat16)
    norm = kda_mix.norm_tiles(S, C, D, jnp.bfloat16)
    for tiles in (conv, norm):
        assert S % tiles.rows == 0 and C % tiles.channels == 0
        assert tiles.channels % D == 0 and tiles.rows >= 128
        assert 0 < tiles.fwd_vmem_bytes <= tiles.bwd_vmem_bytes <= \
            engine.PLAN_VMEM_BUDGET
    assert (conv.halo, norm.halo) == (16, 0)
    wide = jax.ShapeDtypeStruct((1, S, C), jnp.bfloat16)
    MB = 2 ** 20
    once = (kda_mix.conv_moved_bytes(wide, wide, False)
            + kda_mix.norm_moved_bytes(wide, wide, False))
    again = (kda_mix.conv_moved_bytes(wide, wide, True)
             + kda_mix.norm_moved_bytes(wide, wide, True))
    assert (once, again - once) == ((384 + 576) * MB, 384 * MB)


@pytest.mark.parametrize("cell, C, dtype, channels", [
    ("qwen3next-train-gdn8k: the widest block", 8192, jnp.bfloat16, 2048),
    ("qwen3next-train-gdn8k, the readers' fp32 step", 8192, jnp.float32,
     2048),
    ("granite-train-ssd8k: 2304 = 2 x 1152 (9 lane vectors)", 2304,
     jnp.bfloat16, 1152),
    ("granite-train-ssd8k, fp32", 2304, jnp.float32, 1152),
    ("phi4flash-train-sambay: 5120 = 4 x 1280", 5120, jnp.bfloat16, 1280),
    ("phi4flash-train-sambay, fp32", 5120, jnp.float32, 1280),
    ("a prime number of lane vectors: whole or one", 13 * 128, jnp.float32,
     13 * 128),
    ("2000 channels are no whole lanes", 2000, jnp.bfloat16, None),
    ("int8 streams have no kernel", 8192, jnp.int8, None),
])
def test_the_short_conv_plan_is_read_from_the_shape(cell, C, dtype, channels):
    """[1, 8192, C] at four taps: the widest block of whole lane vectors
    that divides C (2048 channels at most) and whose backward fits the
    budget, in bf16 and in the fp32 the benchmark's readers lower (tiles
    that fit or None, never an exception); the gated norm beside it at [1,
    8192, 32 x 128]."""
    S = 8192
    tiles = (kda_mix.short_conv_tiles(S, C, 4, dtype)
             if engine.one_dtype(jax.ShapeDtypeStruct((), dtype)) else None)
    if channels is None:
        assert tiles is None, cell
        return
    assert tiles.channels == channels and S % tiles.rows == 0, cell
    assert tiles.halo == engine.halo_rows(dtype)
    assert 0 < tiles.fwd_vmem_bytes <= tiles.bwd_vmem_bytes <= \
        engine.PLAN_VMEM_BUDGET
    norm = kda_mix.norm_tiles(S, 32 * D, D, dtype)
    assert norm is not None and norm.bwd_vmem_bytes <= engine.PLAN_VMEM_BUDGET
    wide = jax.ShapeDtypeStruct((1, S, C), dtype)
    size = S * C * jnp.dtype(dtype).itemsize
    assert kda_mix.short_conv_moved_bytes(wide, False) == 5 * size
    assert kda_mix.short_conv_moved_bytes(wide, True) == 7 * size


def test_the_spans_of_the_new_sites_say_the_engine_and_the_tiles():
    """short_conv1d and kda_gated_norm under silu lowered (abstractly:
    nothing compiles) at `qwen3next-train-gdn8k`'s shapes, fp32 as the
    benchmark's readers lower them: `short_conv.lower` and `kda.mix.lower`
    say `engine` pallas, the planner's tiles and the bytes the passes move
    where the program is for the TPU, xla and zeros on the CPU; another
    activation than silu | identity is the jax.numpy form's everywhere."""
    from paddle_tpu import layers, observability

    S, C, H, taps = 8192, 8192, 32, 4
    shapes = dict(x=[1, S, C], w=[taps, C], o=[1, S, H * D],
                  z=[1, S, H * D], scale=[D])
    fluid.reset_default_env()
    ins = {n: layers.data(n, s, append_batch_size=False, dtype="float32")
           for n, s in shapes.items()}
    outs = [layers.short_conv1d(ins["x"], ins["w"], "silu"),
            layers.short_conv1d(ins["x"], ins["w"], "tanh"),
            layers.kda_gated_norm(ins["o"], ins["z"], None, ins["scale"],
                                  heads=H, gate_activation="silu")]
    feed = {n: np.zeros(s, np.float32) for n, s in shapes.items()}

    def lowered(for_the_tpu):
        observability.reset()
        with fluid.flags.tpu_trace_scope(for_the_tpu):
            compiled, *rest = fluid.Executor(
                fluid.CPUPlace()).capture_program(feed=feed, fetch_list=outs)
            jax.eval_shape(compiled.raw_fn, *rest)
        return [dict(s.args, span=s.name)
                for s in observability.default_tracer().spans()
                if s.name in ("short_conv.lower", "kda.mix.lower")]

    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        on_tpu, on_cpu = lowered(True), lowered(False)
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()
        fluid.reset_default_env()
    conv = kda_mix.short_conv_tiles(S, C, taps, jnp.float32)
    norm = kda_mix.norm_tiles(S, H * D, D, jnp.float32)
    nothing = dict.fromkeys(kda_mix.Tiles._fields, 0)
    want = [dict(span="short_conv.lower", what="short_conv",
                 moved_bytes=5 * S * C * 4, engine="pallas",
                 **conv._asdict()),
            dict(span="short_conv.lower", what="short_conv",
                 moved_bytes=5 * S * C * 4, engine="xla", **nothing),
            dict(span="kda.mix.lower", what="gated_norm",
                 moved_bytes=8 * S * H * D * 4, engine="pallas",
                 **norm._asdict())]
    assert on_tpu == want
    assert on_cpu == [dict(site, engine="xla", **nothing) for site in want]


@pytest.mark.parametrize("why, conv, norm", [
    ("S 100 is no whole tile", dict(S=100), dict(S=100)),
    ("heads of 64", None, dict(head_dim=64)),
    ("96 channels", dict(C=96), dict(C=96, head_dim=96)),
    ("twelve taps reach past the halo", dict(taps=12), None),
    ("pinned rows that do not divide S", dict(rows=96), dict(rows=96)),
])
def test_a_shape_that_does_not_tile_gets_no_tiles(why, conv, norm):
    def conv_tiles(S=256, C=256, taps=4, rows=None):
        return kda_mix.conv_tiles(S, C, taps, jnp.float32, rows)

    def norm_tiles(S=256, C=256, head_dim=128, rows=None):
        return kda_mix.norm_tiles(S, C, head_dim, jnp.float32, rows)

    assert conv_tiles() is not None and norm_tiles() is not None
    assert conv is None or conv_tiles(**conv) is None, why
    assert norm is None or norm_tiles(**norm) is None, why


def test_the_engine_is_read_from_the_shape_and_the_platform():
    """No flag, no environment variable: on the CPU the jax.numpy forms;
    where the program is traced for the TPU, the kernels if the shape
    tiles and the streams share one dtype."""
    (conv_args, _), (norm_args, _) = _inputs(S=128)
    odd = tuple(t[:, :100] for t in conv_args[:4]) + conv_args[4:]
    mixed = (conv_args[0].astype(jnp.bfloat16),) + conv_args[1:]

    def tiles(fn, args, **kw):
        seen = []
        jax.eval_shape(lambda *xs: seen.append(fn(*xs, **kw)[1]), *args)
        return seen[0]

    def conv(*xs, force="auto"):
        return _conv(force, rows=None, channels=None)(*xs)

    def norm(*xs, force="auto"):
        return _norm(force, rows=None, channels=None)(*xs)

    assert tiles(conv, conv_args) is None and tiles(norm, norm_args) is None
    with fluid.flags.tpu_trace_scope(True):
        assert tiles(conv, conv_args).rows == 128
        assert tiles(norm, norm_args).rows == 128
        assert tiles(conv, conv_args, force="jax") is None
        assert tiles(conv, odd) is None and tiles(conv, mixed) is None
        assert tiles(norm, (norm_args[0][:, :100], norm_args[1][:, :100])
                     + norm_args[2:]) is None
