"""kernels/mhc.py (the ops mhc_maps', mhc_maps_read's and mhc_write's
arithmetic as three Pallas kernel pairs over tiles of rows x blocks of
channels) in the Pallas interpreter on the CPU: every output and every
gradient against ops/hyper_connection_ops.py::maps / ::maps_read (`maps`,
then `read`) / ::write, the jax.numpy forms, on fp32 copies of the inputs,
with the published 20 Sinkhorn iterations, the fused pair in both forms of
its forward and with a cotangent on both of its outputs and on each alone;
H_res through the kernel is doubly stochastic; what `maps_tiles` /
`maps_read_tiles` / `mix_tiles` say of the cell's shape and of shapes that
do not tile; the engine each site is given and the span `mhc.kernel.lower`
that says so."""

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
_MAPS_GRADS = ("dx", "dphi", "da_pre", "da_post", "da_res", "db_pre",
               "db_post", "db_res")
TENSORS = {"maps": ("h",) + _MAPS_GRADS,
           "maps_read": ("h", "x_in") + _MAPS_GRADS,
           "write": ("x_out", "dx", "dh", "dy")}
# what leaves a kernel in the streams' dtype
IN_THE_STREAMS_DTYPE = {"maps": {"dx"}, "maps_read": {"x_in", "dx"},
                        "write": {"x_out", "dx", "dy"}}
# one tile: S of one tile of rows, a stream's C one block; several: two
# sequences of two tiles of rows x two blocks of channels a stream
CASES = {
    "one_tile": dict(B=1, S=128, C=128, tile=(128, 128)),
    "several": dict(B=2, S=256, C=256, tile=(128, 128)),
    "several_bf16": dict(B=2, S=256, C=256, tile=(128, 128),
                         dtype=jnp.bfloat16),
}
# the fused pair's: its forward holding the tile (`resident` 1) and as the
# maps' kernel then `read`'s (0), and which of its two outputs the loss
# weighs (`only`; both where it is absent)
FUSED_CASES = {
    "one_tile": dict(CASES["one_tile"], tile=(128, 128, 1)),
    "several": dict(CASES["several"], tile=(128, 128, 1)),
    "several_bf16": dict(CASES["several_bf16"], tile=(128, 128, 1)),
    "several_bf16_streamed": dict(CASES["several_bf16"], tile=(128, 128, 0)),
    "several_streamed": dict(CASES["several"], tile=(128, 128, 0)),
    "several_h_alone": dict(CASES["several"], tile=(128, 128, 1), only=0),
    "several_x_in_alone": dict(CASES["several"], tile=(128, 128, 1), only=1),
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
    if pair in ("maps", "maps_read"):
        return ((x, normal(n * C, N, scale=(n * C) ** -0.5),
                 jnp.asarray([a], jnp.float32), jnp.asarray([-a], jnp.float32),
                 jnp.asarray([0.8 * a], jnp.float32), normal(n, scale=0.5),
                 normal(n, scale=0.5),
                 2.0 * jnp.eye(n) + normal(n, n, scale=0.5)),
                (normal(B, N, S),) + ((normal(B, S, C),)
                                      if pair == "maps_read" else ()))
    h = jnp.asarray(r.rand(B, N, S), jnp.float32)
    if pair == "read":
        return (x, h), (normal(B, S, C),)
    return (x, h, normal(B, S, C, dtype=dtype)), (normal(B, S, n, C),)


def _engine(pair, force, tile=(None, None)):
    """(output, tiles) of one site by the engine `force` names: as the op
    chooses it (ops/hyper_connection_ops.py::_site), without the span."""
    cfg = CFG if pair in ("maps", "maps_read") else {}

    def site(x, *rest):
        B, S, n, C = x.shape

        def plan():
            if not engine.one_dtype(x, *rest[1:] if pair == "write" else ()):
                return None
            if pair == "maps":
                return mhc.maps_tiles(S, n, C, CFG["iters"], x.dtype, *tile)
            if pair == "maps_read":
                return mhc.maps_read_tiles(S, n, C, CFG["iters"], x.dtype,
                                           *tile)
            if pair == "write":
                return mhc.mix_tiles(S, n, C, x.dtype, pair, *tile)
            return None   # `read` alone has no kernels

        tiles = engine.tiles_or_none(force, None, plan)
        if tiles is None:
            return jax.checkpoint(functools.partial(
                getattr(hc, pair), **cfg))(x, *rest), None
        return getattr(mhc, pair)(x, *rest, tiles, force == "interpret",
                                  **cfg), tiles
    return site


def _passes(fn, args, cots, only=None):
    """The outputs and the gradients of `fn` (which returns (output or
    outputs, tiles)) under the loss that weighs the outputs by `cots` (the
    output `only` alone where it is given), as fp32 numpy; the tiles."""
    seen = []

    def loss(*xs):
        out, tiles = fn(*xs)
        seen.append(tiles)
        outs = out if isinstance(out, tuple) else (out,)
        return sum(jnp.sum(o.astype(jnp.float32) * c)
                   for i, (o, c) in enumerate(zip(outs, cots))
                   if only in (None, i)), outs

    (_, outs), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(len(args))), has_aux=True))(*args)
    return ([np.asarray(t, np.float32) for t in tuple(outs) + tuple(grads)],
            seen[0])


@pytest.fixture(scope="module")
def both_engines():
    """{(pair, case): {tensor: (kernel pair's, jax.numpy form's on fp32
    copies)}}, each run once in the interpreter."""
    memo = {}

    def of(pair, case):
        if (pair, case) not in memo:
            kw = (FUSED_CASES if pair == "maps_read" else CASES)[case]
            args, cots = _inputs(pair, **kw)
            got, tiles = _passes(_engine(pair, "interpret", kw["tile"]),
                                 args, cots, kw.get("only"))
            want, none = _passes(
                _engine(pair, "jax"),
                tuple(t.astype(jnp.float32) for t in args), cots,
                kw.get("only"))
            assert tiles is not None and none is None
            assert pair != "maps_read" or tiles.resident == kw["tile"][2]
            memo[pair, case] = dict(zip(TENSORS[pair], zip(got, want)))
        return memo[pair, case]

    return of


def _held(both_engines, pair, case, tensor):
    """fp32 streams to ~3e-6 of the largest value (other orders of the same
    fp32 sums); bf16 streams: what leaves in bf16 to its rounding, every
    fp32 output (H, dH and the parameters' gradients: fp32 sums of the
    same bf16 values) as at fp32 streams."""
    got, want = both_engines(pair, case)[tensor]
    assert got.shape == want.shape
    if case == "several_x_in_alone" and tensor in (
            "da_post", "da_res", "db_post", "db_res"):
        # x_in hangs on H_pre alone: no gradient, from either engine
        assert not np.abs(want).max() and not np.abs(got).max()
        return
    assert np.abs(want).max() > 0
    half = "bf16" in case and tensor in IN_THE_STREAMS_DTYPE[pair]
    assert np.abs(got - want).max() <= (8e-3 if half else 3e-6) \
        * np.abs(want).max()


@pytest.mark.parametrize("tensor", TENSORS["maps"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_maps_pair_matches_the_jnp_engine(both_engines, case, tensor):
    _held(both_engines, "maps", case, tensor)


@pytest.mark.parametrize("tensor", TENSORS["maps_read"])
@pytest.mark.parametrize("case", sorted(FUSED_CASES))
def test_the_fused_pair_matches_maps_then_read_in_jnp(both_engines, case,
                                                      tensor):
    """`mhc_maps_read`'s kernels against `maps` followed by `read` in
    jax.numpy: H, x_in and the gradient of every input."""
    _held(both_engines, "maps_read", case, tensor)


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
    planner's budget; the fused pair's forward holds the tile (128 rows of
    all 4 x 3584 channels are 3.67 MB) at the block of channels its
    streamed form takes, and the second kernel of a maps backward finds a
    block of all four streams at least as wide as the first's one."""
    S, n, C = 4096, 4, 3584
    fused = mhc.maps_read_tiles(S, n, C, 20, jnp.bfloat16)
    streamed = mhc.maps_read_tiles(S, n, C, 20, jnp.bfloat16, resident=0)
    found = [mhc.maps_tiles(S, n, C, 20, jnp.bfloat16), fused, streamed,
             mhc.mix_tiles(S, n, C, jnp.bfloat16, "read"),
             mhc.mix_tiles(S, n, C, jnp.bfloat16, "write")]
    for tiles in found:
        assert S % tiles.rows == 0 and C % tiles.channels == 0
        assert tiles.channels % 128 == 0 and tiles.rows >= 128
        assert 0 < max(tiles.fwd_vmem_bytes, tiles.bwd_vmem_bytes) <= \
            engine.PLAN_VMEM_BUDGET
    assert (fused.resident, streamed.resident) == (1, 0)
    assert fused.channels == streamed.channels == C
    assert fused.rows == 128 < streamed.rows
    assert fused.fwd_vmem_bytes > (n + 2) * fused.rows * C * 2
    for tiles, is_fused in ((found[0], False), (fused, True)):
        block = mhc.stream_channels(tiles.rows, tiles.channels, n, C, 2,
                                    is_fused)
        assert C % block == 0 and n * block >= tiles.channels
    # fp32 streams: a tile of 128 rows is 7.3 MB, the forward streams
    assert mhc.maps_read_tiles(S, n, C, 20, jnp.float32).resident == 0
    # `read` alone has a forward kernel (the streamed form's) and no other
    assert found[3].bwd_vmem_bytes == 0


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
        return [mhc.maps_tiles(S, n, C, 20, jnp.float32, rows)] + [
            mhc.maps_read_tiles(S, n, C, 20, jnp.float32, rows,
                                resident=held) for held in (None, 0, 1)]

    def mix_tiles(S=256, n=4, C=256, rows=None):
        return [mhc.mix_tiles(S, n, C, jnp.float32, what, rows)
                for what in ("read", "write")]

    assert None not in maps_tiles() and None not in mix_tiles()
    assert maps is None or maps_tiles(**maps) == [None] * 4, why
    assert mix is None or mix_tiles(**mix) == [None, None], why


def test_the_engine_is_read_from_the_shape_and_the_platform():
    """No flag, no environment variable: on the CPU the jax.numpy forms;
    where the program is traced for the TPU, the kernels if the shape
    tiles and the streams (and y) share one dtype; `read` alone the
    jax.numpy form everywhere."""
    args = {pair: _inputs(pair, B=1, S=128, C=128)[0]
            for pair in (*TENSORS, "read")}
    # S 100 is no whole tile
    odd = {"maps": (args["maps"][0][:, :100],) + args["maps"][1:],
           "write": (args["write"][0][:, :100], args["write"][1][..., :100],
                     args["write"][2][:, :100])}
    odd["maps_read"] = odd["maps"]

    def tiles(pair, xs, **kw):
        seen = []
        fn = _engine(pair, kw.pop("force", "auto"))
        jax.eval_shape(lambda *a: seen.append(fn(*a)[1]), *xs)
        return seen[0]

    for pair, xs in args.items():
        assert tiles(pair, xs) is None
    with fluid.flags.tpu_trace_scope(True):
        assert tiles("read", args["read"]) is None
        for pair in TENSORS:
            xs = args[pair]
            assert tiles(pair, xs).rows == 128
            assert tiles(pair, xs, force="jax") is None
            assert tiles(pair, odd[pair]) is None
        assert tiles("maps_read", args["maps_read"]).resident == 1
        x, h, y = args["write"]
        assert tiles("write", (x.astype(jnp.bfloat16), h, y)) is None
        for pair in ("maps", "maps_read"):
            assert tiles(pair, (args[pair][0].astype(jnp.float16),)
                         + args[pair][1:]) is None


def _lowered_spans(for_the_tpu, S=4096, C=3584, iters=20):
    """`mhc.kernel.lower` and `mhc.lower` of one hyper-connected sublayer
    (and of a `mhc_maps` and a `mhc_read` beside it) lowered abstractly
    (nothing compiles, nothing runs)."""
    n = N_STREAMS
    shapes = dict(x=[1, S, n, C], phi=[n * C, N], a_pre=[1], a_post=[1],
                  a_res=[1], b_pre=[n], b_post=[n], b_res=[n, n],
                  y=[1, S, C])
    fluid.reset_default_env()
    ins = {k: layers.data(k, s, append_batch_size=False, dtype="float32")
           for k, s in shapes.items()}
    small = [ins[k] for k in ("phi", "a_pre", "a_post", "a_res", "b_pre",
                              "b_post", "b_res")]
    h, x_in = layers.mhc_maps_read(ins["x"], *small, sinkhorn_iters=iters)
    out = layers.mhc_write(ins["x"], h, ins["y"])
    alone = layers.mhc_read(ins["x"], layers.mhc_maps(
        ins["x"], *small, sinkhorn_iters=iters))
    feed = {k: np.zeros(s, np.float32) for k, s in shapes.items()}
    observability.reset()
    with fluid.flags.tpu_trace_scope(for_the_tpu):
        compiled, *rest = fluid.Executor(fluid.CPUPlace()).capture_program(
            feed=feed, fetch_list=[x_in, out, alone])
        jax.eval_shape(compiled.raw_fn, *rest)
    return {name: [dict(s.args) for s in
                   observability.default_tracer().spans() if s.name == name]
            for name in ("mhc.kernel.lower", "mhc.lower")}


def test_mhc_kernel_lower_says_pallas_at_the_cells_shape():
    """A sublayer's two ops lowered at [1, 4096, 4, 3584] for the TPU, and
    `mhc_maps` and `mhc_read` after them: one `mhc.kernel.lower` a site
    that has kernels (`mhc_read` alone has none and no span), `engine`
    pallas with the tiles and the working sets `maps_read_tiles` /
    `mix_tiles` / `maps_tiles` give the shape, `resident` on the fused
    site alone (fp32 streams here: 0; the cell's bf16 streams 1, by
    test_the_cells_shape_tiles_within_the_budget); the same program on the
    CPU says xla; `mhc.lower`'s args are what they were, on both, one a
    `mhc_maps_read` or `mhc_maps` op."""
    S, n, C = 4096, N_STREAMS, 3584
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        on_tpu, on_cpu = _lowered_spans(True), _lowered_spans(False)
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()
        fluid.reset_default_env()
    tiles = {"maps_read": mhc.maps_read_tiles(S, n, C, 20, jnp.float32),
             "write": mhc.mix_tiles(S, n, C, jnp.float32, "write"),
             "maps": mhc.maps_tiles(S, n, C, 20, jnp.float32)}
    assert [s["what"] for s in on_tpu["mhc.kernel.lower"]] == list(tiles)
    for site in on_tpu["mhc.kernel.lower"]:
        t = tiles[site["what"]]
        assert site == dict(what=site["what"], engine="pallas",
                            **t._asdict())
    assert "resident" in tiles["maps_read"]._fields
    assert on_cpu["mhc.kernel.lower"] == [dict(
        what=what, engine="xla", **{f: 0 for f in t._fields})
        for what, t in tiles.items()]
    was = [{"streams": n, "sinkhorn_iters": 20, "sublayers": 1,
            "moved_bytes": hc.moved_bytes(S, n, C, 4, n * C * N * 4)}] * 2
    assert on_tpu["mhc.lower"] == on_cpu["mhc.lower"] == was


def test_a_mesh_of_several_devices_takes_the_jnp_form():
    """XLA cannot partition a Mosaic kernel: for a TPU, at a shape that
    tiles, a site on a mesh of several devices says xla and one device (or
    no mesh) pallas."""
    args = _inputs("maps_read", B=1, S=128, C=128)[0]
    slots = ("X", "Phi", "APre", "APost", "ARes", "BPre", "BPost", "BRes")
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        observability.reset()
        with fluid.flags.tpu_trace_scope(True):
            for devices in (4, 1, None):
                mesh = devices and types.SimpleNamespace(num_devices=devices)
                ctx = types.SimpleNamespace(mesh=mesh, kept=0)
                jax.eval_shape(lambda *a: hc._mhc_maps_read(
                    ctx, {s: [t] for s, t in zip(slots, a)},
                    {"sinkhorn_iters": 20})["Out"][0], *args)
        engines = [s.args["engine"] for s in
                   observability.default_tracer().spans()
                   if s.name == "mhc.kernel.lower"]
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()
    assert engines == ["xla", "pallas", "pallas"]
