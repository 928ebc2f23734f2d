"""kernels/kda_mix.py (the ops kda_conv_decay's and kda_gated_norm's
arithmetic as two Pallas kernel pairs over tiles of rows x blocks of
channels) in the Pallas interpreter on the CPU: every output and every
gradient against ops/linear_attention_ops.py::conv_decay / ::gated_norm,
the jax.numpy forms; the first tile's zeros before position 0 and a tile
boundary inside the taps' reach; what `conv_tiles` / `norm_tiles` say of the
cell's shape and of shapes that do not tile; the bytes the spans count."""

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
        loss, argnums=tuple(range(len(args))), has_aux=True))(*args)
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


def _norm(force, H=2, rows=128, channels=128, **_):
    def site(o, gate, *rest):
        return _site(force, lambda: kda_mix.norm_tiles(
            o.shape[1], o.shape[2], o.shape[2] // H, o.dtype, rows, channels)
            if engine.one_dtype(o, gate) else None,
            kda_mix.gated_norm, ops.gated_norm, o, gate, *rest, H, EPS)
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
