"""kernels/mhc.py (the ops mhc_maps', mhc_read's and mhc_write's arithmetic
as three Pallas kernel pairs over tiles of rows x blocks of channels) in the
Pallas interpreter on the CPU: every output and every gradient against
ops/hyper_connection_ops.py::maps / ::read / ::write, the jax.numpy forms,
on fp32 copies of the inputs, with the published 20 Sinkhorn iterations;
H_res through the kernel is doubly stochastic; what `maps_tiles` /
`mix_tiles` say of the cell's shape and of shapes that do not tile; the
engine each site is given and the span `mhc.kernel.lower` that says so."""

import functools
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import paddle_tpu as fluid
from paddle_tpu import layers, observability
from paddle_tpu.kernels import engine, mhc
from paddle_tpu.ops import hyper_connection_ops as hc

N_STREAMS, N = 4, 24
CFG = dict(epsilon=1e-6, hc_eps=1e-6, iters=20, clamp=(-30.0, 30.0))
TENSORS = {"maps": ("h", "dx", "dphi", "da_pre", "da_post", "da_res",
                    "db_pre", "db_post", "db_res"),
           "read": ("x_in", "dx", "dh"),
           "write": ("x_out", "dx", "dh", "dy")}
# what leaves a kernel in the streams' dtype
IN_THE_STREAMS_DTYPE = {"maps": {"dx"}, "read": {"x_in", "dx"},
                        "write": {"x_out", "dx", "dy"}}
# one tile: S of one tile of rows, a stream's C one block; several: two
# sequences of two tiles of rows x two blocks of channels a stream
CASES = {
    "one_tile": dict(B=1, S=128, C=128, tile=(128, 128)),
    "several": dict(B=2, S=256, C=256, tile=(128, 128)),
    "several_bf16": dict(B=2, S=256, C=256, tile=(128, 128),
                         dtype=jnp.bfloat16),
}


def _inputs(pair, B, S, C, dtype=jnp.float32, seed=0, a=1.5, **_):
    """(the pair's arguments, its outputs' cotangents): streams that differ
    from one another, parameters large enough that every map moves with
    the data (tests/test_hyper_connection_ops.py::_values' reasons), and
    cotangents a bf16 holds, so that both engines are handed the same."""
    n, r = N_STREAMS, np.random.RandomState(seed)

    def normal(*shape, scale=1.0, dtype=jnp.float32):
        return jnp.asarray(jnp.asarray(r.randn(*shape) * scale,
                                       jnp.bfloat16), dtype)

    x = jnp.asarray(r.randn(B, S, n, C) * (1.0 + np.arange(n)[:, None]),
                    dtype)
    if pair == "maps":
        return ((x, normal(n * C, N, scale=(n * C) ** -0.5),
                 jnp.asarray([a], jnp.float32), jnp.asarray([-a], jnp.float32),
                 jnp.asarray([0.8 * a], jnp.float32), normal(n, scale=0.5),
                 normal(n, scale=0.5),
                 2.0 * jnp.eye(n) + normal(n, n, scale=0.5)),
                (normal(B, N, S),))
    h = jnp.asarray(r.rand(B, N, S), jnp.float32)
    if pair == "read":
        return (x, h), (normal(B, S, C),)
    return (x, h, normal(B, S, C, dtype=dtype)), (normal(B, S, n, C),)


def _engine(pair, force, tile=(None, None)):
    """(output, tiles) of one site by the engine `force` names: as the op
    chooses it (ops/hyper_connection_ops.py::_site), without the span."""
    cfg = CFG if pair == "maps" else {}

    def site(x, *rest):
        B, S, n, C = x.shape

        def plan():
            if not engine.one_dtype(x, *rest[1:] if pair == "write" else ()):
                return None
            if pair == "maps":
                return mhc.maps_tiles(S, n, C, CFG["iters"], x.dtype, *tile)
            return mhc.mix_tiles(S, n, C, x.dtype, pair, *tile)

        tiles = engine.tiles_or_none(force, None, plan)
        if tiles is None:
            return jax.checkpoint(functools.partial(
                getattr(hc, pair), **cfg))(x, *rest), None
        return getattr(mhc, pair)(x, *rest, tiles, force == "interpret",
                                  **cfg), tiles
    return site


def _passes(fn, args, cots):
    """The output and the gradients of `fn` (which returns (output, tiles))
    under the loss that weighs the output by `cots`, as fp32 numpy; the
    tiles."""
    seen = []

    def loss(*xs):
        out, tiles = fn(*xs)
        seen.append(tiles)
        return jnp.sum(out.astype(jnp.float32) * cots[0]), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(len(args))), has_aux=True))(*args)
    return ([np.asarray(t, np.float32) for t in (out,) + tuple(grads)],
            seen[0])


@pytest.fixture(scope="module")
def both_engines():
    """{(pair, case): {tensor: (kernel pair's, jax.numpy form's on fp32
    copies)}}, each run once in the interpreter."""
    memo = {}

    def of(pair, case):
        if (pair, case) not in memo:
            kw = CASES[case]
            args, cots = _inputs(pair, **kw)
            got, tiles = _passes(_engine(pair, "interpret", kw["tile"]),
                                 args, cots)
            want, none = _passes(
                _engine(pair, "jax"),
                tuple(t.astype(jnp.float32) for t in args), cots)
            assert tiles is not None and none is None
            memo[pair, case] = dict(zip(TENSORS[pair], zip(got, want)))
        return memo[pair, case]

    return of


def _held(both_engines, pair, case, tensor):
    """fp32 streams to ~3e-6 of the largest value (other orders of the same
    fp32 sums); bf16 streams: what leaves in bf16 to its rounding, every
    fp32 output (H, dH and the parameters' gradients: fp32 sums of the
    same bf16 values) as at fp32 streams."""
    got, want = both_engines(pair, case)[tensor]
    assert got.shape == want.shape and np.abs(want).max() > 0
    half = case.endswith("bf16") and tensor in IN_THE_STREAMS_DTYPE[pair]
    assert np.abs(got - want).max() <= (8e-3 if half else 3e-6) \
        * np.abs(want).max()


@pytest.mark.parametrize("tensor", TENSORS["maps"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_maps_pair_matches_the_jnp_engine(both_engines, case, tensor):
    _held(both_engines, "maps", case, tensor)


@pytest.mark.parametrize("tensor", TENSORS["read"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_read_pair_matches_the_jnp_engine(both_engines, case, tensor):
    _held(both_engines, "read", case, tensor)


@pytest.mark.parametrize("tensor", TENSORS["write"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_write_pair_matches_the_jnp_engine(both_engines, case, tensor):
    _held(both_engines, "write", case, tensor)


def test_h_res_through_the_kernel_is_doubly_stochastic():
    """Rows and columns add up to 1 to 1e-5 after the 20 iterations, every
    value positive, H_pre in (0, 1) and H_post in (0, 2)."""
    args, _ = _inputs("maps", B=1, S=256, C=128, seed=1, a=0.3)
    h, tiles = _engine("maps", "interpret", (128, 128))(*args)
    assert tiles is not None
    h, n = np.asarray(h), N_STREAMS
    res = np.moveaxis(h[:, 2 * n:], 1, 2).reshape(-1, n, n)
    assert np.all(res > 0)
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-5)
    assert 0 < h[:, :n].min() and h[:, :n].max() < 1
    assert 0 < h[:, n:2 * n].min() and h[:, n:2 * n].max() < 2


def test_three_parts_hold_every_bit_of_an_fp32_value_under_jit():
    """`_split` by masks: each part a bf16 value, the three adding up to
    the fp32 value bit for bit, under jit too (as a pair of casts XLA's
    TPU compiler folded the round trip away and left the first part
    alone: found on the chip by tools/mhc_probe.py --check, PR 51); a
    bf16 value is its own one part."""
    v = jnp.asarray(np.random.RandomState(0).randn(64, 128) * 3.0, jnp.float32)
    parts = jax.jit(lambda t: mhc._split(t, 3))(v)
    assert [p.dtype for p in parts] == [jnp.bfloat16] * 3
    total = sum(np.asarray(p, np.float64) for p in parts)
    np.testing.assert_array_equal(total, np.asarray(v, np.float64))
    assert np.abs(np.asarray(parts[1], np.float32)).max() > 0
    half = v.astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(mhc._split(half, 1)[0]),
                                  np.asarray(half))


def test_the_cells_shape_tiles_within_the_budget():
    """[1, 4096, 4, 3584] bf16, 20 iterations: all three pairs on the
    kernels, whole blocks of 128-lane vectors, the working sets under the
    planner's budget."""
    S, n, C = 4096, 4, 3584
    found = [mhc.maps_tiles(S, n, C, 20, jnp.bfloat16),
             mhc.mix_tiles(S, n, C, jnp.bfloat16, "read"),
             mhc.mix_tiles(S, n, C, jnp.bfloat16, "write")]
    for tiles in found:
        assert S % tiles.rows == 0 and C % tiles.channels == 0
        assert tiles.channels % 128 == 0 and tiles.rows >= 128
        assert 0 < max(tiles.fwd_vmem_bytes, tiles.bwd_vmem_bytes) <= \
            engine.PLAN_VMEM_BUDGET


@pytest.mark.parametrize("why, maps, mix", [
    ("S 100 is no whole tile", dict(S=100), dict(S=100)),
    ("96 channels", dict(C=96), dict(C=96)),
    ("two streams: the map's rows are no whole sublane tiles", dict(n=2),
     None),
    ("eight streams: 5 x 80 columns pass one 128-lane vector", dict(n=8),
     None),
    ("pinned rows that do not divide S", dict(rows=96), dict(rows=96)),
    ("a tile of 64 rows puts no whole vector of tokens on the lanes",
     dict(rows=64), None),
])
def test_a_shape_that_does_not_tile_gets_no_tiles(why, maps, mix):
    def maps_tiles(S=256, n=4, C=256, rows=None):
        return mhc.maps_tiles(S, n, C, 20, jnp.float32, rows)

    def mix_tiles(S=256, n=4, C=256, rows=None):
        return [mhc.mix_tiles(S, n, C, jnp.float32, what, rows)
                for what in ("read", "write")]

    assert maps_tiles() is not None and None not in mix_tiles()
    assert maps is None or maps_tiles(**maps) is None, why
    assert mix is None or mix_tiles(**mix) == [None, None], why


def test_the_engine_is_read_from_the_shape_and_the_platform():
    """No flag, no environment variable: on the CPU the jax.numpy forms;
    where the program is traced for the TPU, the kernels if the shape
    tiles and the streams (and y) share one dtype."""
    args = {pair: _inputs(pair, B=1, S=128, C=128)[0] for pair in TENSORS}
    # S 100 is no whole tile
    odd = {"maps": (args["maps"][0][:, :100],) + args["maps"][1:],
           "read": (args["read"][0][:, :100], args["read"][1][..., :100]),
           "write": (args["write"][0][:, :100], args["write"][1][..., :100],
                     args["write"][2][:, :100])}

    def tiles(pair, xs, **kw):
        seen = []
        fn = _engine(pair, kw.pop("force", "auto"))
        jax.eval_shape(lambda *a: seen.append(fn(*a)[1]), *xs)
        return seen[0]

    for pair, xs in args.items():
        assert tiles(pair, xs) is None
    with fluid.flags.tpu_trace_scope(True):
        for pair, xs in args.items():
            assert tiles(pair, xs).rows == 128
            assert tiles(pair, xs, force="jax") is None
            assert tiles(pair, odd[pair]) is None
        x, h, y = args["write"]
        assert tiles("write", (x.astype(jnp.bfloat16), h, y)) is None
        assert tiles("maps", (args["maps"][0].astype(jnp.float16),)
                     + args["maps"][1:]) is None


def _lowered_spans(for_the_tpu, S=4096, C=3584, iters=20):
    """`mhc.kernel.lower` and `mhc.lower` of one hyper-connected sublayer
    lowered abstractly (nothing compiles, nothing runs)."""
    n = N_STREAMS
    shapes = dict(x=[1, S, n, C], phi=[n * C, N], a_pre=[1], a_post=[1],
                  a_res=[1], b_pre=[n], b_post=[n], b_res=[n, n],
                  y=[1, S, C])
    fluid.reset_default_env()
    ins = {k: layers.data(k, s, append_batch_size=False, dtype="float32")
           for k, s in shapes.items()}
    h = layers.mhc_maps(ins["x"], *(ins[k] for k in (
        "phi", "a_pre", "a_post", "a_res", "b_pre", "b_post", "b_res")),
        sinkhorn_iters=iters)
    x_in = layers.mhc_read(ins["x"], h)
    out = layers.mhc_write(ins["x"], h, ins["y"])
    feed = {k: np.zeros(s, np.float32) for k, s in shapes.items()}
    observability.reset()
    with fluid.flags.tpu_trace_scope(for_the_tpu):
        compiled, *rest = fluid.Executor(fluid.CPUPlace()).capture_program(
            feed=feed, fetch_list=[x_in, out])
        jax.eval_shape(compiled.raw_fn, *rest)
    return {name: [dict(s.args) for s in
                   observability.default_tracer().spans() if s.name == name]
            for name in ("mhc.kernel.lower", "mhc.lower")}


def test_mhc_kernel_lower_says_pallas_at_the_cells_shape():
    """The three ops lowered at [1, 4096, 4, 3584] for the TPU: three
    `mhc.kernel.lower` a sublayer, `engine` pallas with the tiles and the
    working sets `maps_tiles` / `mix_tiles` give the shape; the same
    program on the CPU says xla; `mhc.lower`'s args are what they were,
    on both."""
    S, n, C = 4096, N_STREAMS, 3584
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        on_tpu, on_cpu = _lowered_spans(True), _lowered_spans(False)
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()
        fluid.reset_default_env()
    tiles = {"maps": mhc.maps_tiles(S, n, C, 20, jnp.float32),
             "read": mhc.mix_tiles(S, n, C, jnp.float32, "read"),
             "write": mhc.mix_tiles(S, n, C, jnp.float32, "write")}
    assert [s["what"] for s in on_tpu["mhc.kernel.lower"]] == \
        ["maps", "read", "write"]
    for site in on_tpu["mhc.kernel.lower"]:
        t = tiles[site["what"]]
        assert site == dict(what=site["what"], engine="pallas", rows=t.rows,
                            channels=t.channels,
                            fwd_vmem_bytes=t.fwd_vmem_bytes,
                            bwd_vmem_bytes=t.bwd_vmem_bytes)
    assert on_cpu["mhc.kernel.lower"] == [dict(
        what=what, engine="xla", rows=0, channels=0, fwd_vmem_bytes=0,
        bwd_vmem_bytes=0) for what in ("maps", "read", "write")]
    was = [{"streams": n, "sinkhorn_iters": 20, "sublayers": 1,
            "moved_bytes": hc.moved_bytes(S, n, C, 4, n * C * N * 4)}]
    assert on_tpu["mhc.lower"] == on_cpu["mhc.lower"] == was


def test_a_mesh_of_several_devices_takes_the_jnp_form():
    """XLA cannot partition a Mosaic kernel: for a TPU, at a shape that
    tiles, a site on a mesh of several devices says xla and one device (or
    no mesh) pallas."""
    x, h = _inputs("read", B=1, S=128, C=128)[0]
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        observability.reset()
        with fluid.flags.tpu_trace_scope(True):
            for devices in (4, 1, None):
                mesh = devices and types.SimpleNamespace(num_devices=devices)
                ctx = types.SimpleNamespace(mesh=mesh, kept=0)
                jax.eval_shape(lambda x, h: hc._mhc_read(
                    ctx, {"X": [x], "H": [h]}, {})["Out"][0], x, h)
        engines = [s.args["engine"] for s in
                   observability.default_tracer().spans()
                   if s.name == "mhc.kernel.lower"]
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()
    assert engines == ["xla", "pallas", "pallas"]
