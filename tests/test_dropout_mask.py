"""A dropout site draws its mask once and keeps it (ops/nn_ops.py::_dropout,
kernels/dropout_mask.py): the keep rate, the forward and the backward on one
mask, which masks differ and which are the same, the shards of a mesh, and
the span `dropout.lower`.  The Pallas engine's own generator has no
interpreter: the chip holds it to the same statistics
(tools/dropout_probe.py --check); what runs here is its plan (`tiles`), its
seeds and which engine a site is given."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers, observability
from paddle_tpu.kernels import dropout_mask

IMPLEMENTATIONS = ("downgrade_in_infer", "upscale_in_train")


def _site(shape, p, impl="downgrade_in_infer", seed=None, sites=1):
    """A program of `sites` dropout ops over one input of ones; returns
    run(steps) -> [step][site] outputs (and the input's gradient last)."""
    fluid.reset_default_env()
    if seed is not None:
        fluid.default_main_program().random_seed = seed
    x = layers.data("x", list(shape), append_batch_size=False,
                    dtype="float32")
    x.stop_gradient = False
    outs = [layers.dropout(x, dropout_prob=p, dropout_implementation=impl)
            for _ in range(sites)]
    grads = fluid.calc_gradient(layers.reduce_sum(outs[0]), [x])
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"x": np.ones(shape, np.float32)}
    return lambda steps=1: [
        [np.asarray(v) for v in exe.run(feed=feed, fetch_list=outs + grads)]
        for _ in range(steps)]


@pytest.mark.parametrize("impl", IMPLEMENTATIONS)
@pytest.mark.parametrize("p", [0.1, 0.3, 0.5])
def test_keep_rate_and_one_mask_forward_and_backward(p, impl):
    """1 M elements: the share kept within four sigma of 1 - p; a kept
    element is x (or x / (1 - p)), a dropped one 0; the gradient is the
    same array: zero exactly where `out` is."""
    n = 1024 * 1024
    out, grad = _site((1024, 1024), p, impl)()[0]
    kept = out != 0
    sigma = np.sqrt(p * (1 - p) / n)
    assert abs(kept.mean() - (1 - p)) < 4 * sigma, kept.mean()
    value = np.float32(1 / (1 - p)) if impl == "upscale_in_train" else 1.0
    np.testing.assert_allclose(out[kept], value, rtol=1e-6)
    np.testing.assert_array_equal(grad, out)


def test_sites_steps_and_seeds_draw_their_own_masks():
    """Two sites of one program, two steps of one site and two seeds draw
    different masks (they agree where independent draws would, keep^2 +
    p^2); one seed draws the same."""
    p, shape = 0.3, (256, 256)
    agree = (1 - p) ** 2 + p ** 2
    band = 5 * np.sqrt(agree * (1 - agree) / (256 * 256))

    def independent(a, b):
        return abs(((a != 0) == (b != 0)).mean() - agree) < band

    first, second = _site(shape, p, seed=7, sites=2)(steps=2)
    assert independent(first[0], first[1])      # two sites
    assert independent(first[0], second[0])     # two steps
    again = _site(shape, p, seed=7, sites=2)(steps=2)
    for got, want in zip(first + second, again[0] + again[1]):
        np.testing.assert_array_equal(got, want)
    other = _site(shape, p, seed=8, sites=2)()[0]
    assert independent(first[0], other[0])      # two seeds


def test_is_test_draws_nothing():
    fluid.reset_default_env()
    x = layers.data("x", [4, 8], append_batch_size=False, dtype="float32")
    a = layers.dropout(x, dropout_prob=0.25, is_test=True)
    b = layers.dropout(x, dropout_prob=0.25, is_test=True,
                       dropout_implementation="upscale_in_train")
    xv = np.arange(32, dtype=np.float32).reshape(4, 8)
    got = fluid.Executor(fluid.CPUPlace()).run(feed={"x": xv},
                                               fetch_list=[a, b])
    np.testing.assert_allclose(got[0], xv * 0.75)
    np.testing.assert_array_equal(got[1], xv)


def _mesh_step(shape, p):
    """(pe, x, out): a data-parallel program over four virtual devices
    whose one dropout site reads the sharded batch."""
    from paddle_tpu.parallel import ParallelExecutor, make_mesh

    fluid.reset_default_env()
    x = layers.data("x", list(shape[1:]), dtype="float32")
    w = layers.create_parameter([1], "float32", name="dm_w")
    out = layers.dropout(layers.elementwise_mul(x, w), dropout_prob=p)
    loss = layers.mean(out)
    fluid.optimizer.SGD(learning_rate=0.0).minimize(loss)
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    return ParallelExecutor(loss_name=loss.name, mesh=mesh), out, mesh


def test_on_a_mesh_each_shard_draws_its_own_mask():
    """The batch sharded over four devices, the key replicated: the four
    per-device batches are dropped at different positions."""
    p, shape = 0.5, (8, 64, 128)
    pe, out, _ = _mesh_step(shape, p)
    got = np.asarray(pe.run(fetch_list=[out],
                            feed={"x": np.ones(shape, np.float32)})[0])
    shards = (got != 0).reshape(4, -1)
    assert abs(shards.mean() - (1 - p)) < 4 * np.sqrt(p * (1 - p) / got.size)
    for i in range(4):
        for j in range(i + 1, 4):
            same = (shards[i] == shards[j]).mean()
            assert abs(same - 0.5) < 0.03, (i, j, same)


def _spans_of(lower):
    observability.reset()
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        lower()
        return [dict(s.args) for s in observability.default_tracer().spans()
                if s.name == "dropout.lower"]
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()


def _two_sites():
    fluid.reset_default_env()
    x = layers.data("x", [64, 256], dtype="float32")
    h = layers.dropout(layers.fc(x, size=512, num_flatten_dims=2),
                       dropout_prob=0.1)
    y = layers.dropout(layers.fc(h, size=50, num_flatten_dims=2),
                       dropout_prob=0.25)
    loss = layers.mean(y)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    return exe, loss, {"x": np.ones((4, 64, 256), np.float32)}


def test_the_span_says_what_each_site_drew_and_stores():
    """`dropout.lower`, one a site: on the CPU threefry's bits behind a
    barrier, a byte an element stored."""
    def lower():
        exe, loss, feed = _two_sites()
        exe.run(feed=feed, fetch_list=[loss])

    assert _spans_of(lower) == [
        dict(elements=4 * 64 * 512, prob=0.1, draw="threefry", engine="xla",
             block_rows=0, mask_bytes=4 * 64 * 512),
        dict(elements=4 * 64 * 50, prob=0.25, draw="threefry", engine="xla",
             block_rows=0, mask_bytes=4 * 64 * 50)]


def test_for_a_tpu_a_site_that_tiles_is_the_kernels():
    """The same program as it lowers for the TPU: the site of whole
    128-lane rows is the Pallas kernel's (the core's generator), the site
    50 wide, whose elements make whole tiles in no width, threefry's."""
    def lower():
        exe, loss, feed = _two_sites()
        with fluid.flags.tpu_trace_scope(True):
            compiled, feed_vals, state_vals, rng = exe.capture_program(
                fluid.default_main_program(), feed=feed, fetch_list=[loss])
            jax.eval_shape(compiled.raw_fn, feed_vals, state_vals, rng)

    wide, narrow = _spans_of(lower)
    assert (wide["engine"], wide["draw"], wide["block_rows"],
            wide["mask_bytes"]) == ("pallas", "tpu_prng", 256, 4 * 64 * 512)
    assert (narrow["engine"], narrow["draw"]) == ("xla", "threefry")


def test_on_a_mesh_for_a_tpu_a_shard_is_the_kernels_under_its_own_seed():
    """Four devices, for the TPU: the site lowers to the kernel under a
    shard_map, a shard's rows a device, and the seeds of two shards, and
    of two grid steps, differ."""
    from paddle_tpu.core.executor import _RunPlan

    shape = (8, 64, 128)
    pe, out, mesh = _mesh_step(shape, 0.5)
    prog = fluid.default_main_program()
    batch = {"x": np.ones(shape, np.float32)}
    plan = _RunPlan(prog, sorted(batch), [out.name])
    block0 = prog.desc.block(0)

    def lower():
        with fluid.flags.tpu_trace_scope(True), mesh.mesh:
            jax.eval_shape(pe._compile(plan).fn, *(
                tuple(plan.feed_values(batch, block0)),
                tuple(plan.state_values(fluid.global_scope(), block0)),
                plan.rng_value(fluid.global_scope(), prog)))

    span, = _spans_of(lower)
    assert (span["engine"], span["draw"], span["block_rows"]) == (
        "pallas", "tpu_prng", 2 * 64)
    key = jax.random.PRNGKey(3)
    seeds = [np.asarray(dropout_mask._seeds(key, 4, shard))
             for shard in range(4)]
    assert len({tuple(s[2 * i:2 * i + 2]) for s in seeds
                for i in range(4)}) == 16


@pytest.mark.parametrize("shape, want", [
    ((96, 256, 2048), (24576, 2048, 256)),     # the FFN's hidden
    ((96, 256, 512), (24576, 512, 1024)),      # a sublayer's output
    ((96, 8, 256, 64), (6144, 2048, 256)),     # attention's: a flat view
    ((24, 256, 512), (6144, 512, 1024)),       # a shard of four
    ((4, 64, 50), None), ((7, 128), None), ((), None)])
def test_tiles(shape, want):
    assert dropout_mask.tiles(shape) == want


@pytest.mark.parametrize("p, below", [
    (0.0, 0), (0.1, 429496730), (0.5, 2 ** 31), (1.0, 2 ** 32)])
def test_threshold_has_the_probability_at_32_bits(p, below):
    assert dropout_mask.threshold(p) == below


@pytest.mark.parametrize("p, rate", [(0.0, 1.0), (1.0, 0.0)])
def test_the_ends_of_the_probability(p, rate):
    mask, drawn = dropout_mask.draw(jax.random.PRNGKey(0), (64, 128), p)
    assert mask.dtype == jnp.uint8 and float(jnp.mean(mask)) == rate
    assert drawn.engine == "xla"
