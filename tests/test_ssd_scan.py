"""kernels/ssd_scan.py (Mamba-2's state-space-dual scan, the op ssd_scan)
against the recurrence one token at a time: both engines, the Pallas kernel
pair in the interpreter, the forward and all seven gradients (A, D and the
step's bias among them); groups 1 and 2; the same result at chunks of 16, 64
and the whole row; a sequence that is not whole chunks; a state that decays
to nothing and one that does not decay; a share of the heads against those
heads of the whole scan; what is kept made once under a recomputed unit; the
op through the Executor with its span, its kept values and its softplus; the
gated RMS norm against its formula."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import paddle_tpu as fluid
from paddle_tpu import layers, observability
from paddle_tpu.core import compiler
from paddle_tpu.kernels import engine
from paddle_tpu.kernels import ssd_scan as ssd

from ssd_scan_probe import HARD, inputs, token_recurrence  # noqa: E402

NAMES = ("y", "dx", "ddt", "dA", "dB", "dC", "dD")


def _passes(fn, ops, weight):
    y, pull = jax.vjp(fn, *ops)
    return (y,) + tuple(pull(weight))


def _held(got, want, rtol=3e-5, **loose):
    for name, g, w in zip(NAMES, got, want):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        tol = loose.get(name, rtol)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=tol,
                                   atol=tol * scale, err_msg=name)


def _tiles(shape, chunk):
    B, S, H, P, N, G = shape
    return ssd.tiles(S, H, P, N, G, chunk)


# (shape [B, S, H, P, N, G], chunk, the kernels or the jax.numpy engine)
CASES = {
    "xla_whole_chunks": ((2, 48, 4, 8, 16, 1), 16, False),
    "xla_two_groups": ((1, 64, 4, 8, 16, 2), 16, False),
    "xla_not_whole_chunks": ((2, 40, 4, 8, 16, 2), 16, False),
    "xla_one_chunk": ((1, 24, 2, 8, 8, 1), 256, False),
    "pallas_whole_chunks": ((1, 256, 8, 64, 128, 1), 128, True),
    "pallas_two_groups_of_four_heads": ((2, 128, 8, 64, 128, 2), 128, True),
    "pallas_two_heads_a_step": ((1, 64, 4, 64, 128, 2), 64, True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_against_the_token_recurrence(case):
    shape, chunk, kernels = CASES[case]
    ops, weight = inputs(shape, seed=3)
    tiles = _tiles(shape, chunk) if kernels else None
    assert (tiles is not None) == kernels
    got = _passes(lambda *o: ssd.ssd_scan(
        *o, tiles_=tiles, interpret=kernels, chunk=chunk), ops, weight)
    _held(got, _passes(token_recurrence, ops, weight))


def test_the_result_does_not_depend_on_the_chunk():
    ops, weight = inputs((1, 64, 4, 8, 16, 2), seed=4)
    at = [_passes(lambda *o, c=c: ssd.ssd_scan(*o, chunk=c), ops, weight)
          for c in (16, 64, 256)]
    for other in at[1:]:
        _held(other, at[0])


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("hard", sorted(set(HARD) - {"random"}))
def test_a_state_that_decays_to_nothing_and_one_that_does_not(hard, kernels):
    shape = (1, 256, 4, 64, 128, 1)
    ops, weight = inputs(shape, 5, *HARD[hard])
    tiles = _tiles(shape, 128) if kernels else None
    assert (tiles is not None) == kernels
    got = _passes(lambda *o: ssd.ssd_scan(
        *o, tiles_=tiles, interpret=kernels, chunk=128), ops, weight)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in got)
    # where nothing survives a token (dt A ~ -40) dcum is a difference of
    # row and column sums that all but cancel, and dA, their running sum, a
    # number 1e-3 of the others: the chunked form reads it 1.6% off the
    # recurrence in fp64 (both engines, the chip too)
    _held(got, _passes(token_recurrence, ops, weight), rtol=2e-3, dA=5e-2)
    decay = jnp.exp(ops[1] * ops[2])
    assert float(jnp.median(decay)) < 1e-3 if hard == "decays_to_nothing" \
        else float(jnp.min(decay)) > 0.99


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "pallas"])
def test_a_share_of_the_heads_is_those_heads_of_the_whole_scan(kernels):
    """B and C are every head's: a chip that holds heads 0 .. H / 2 - 1
    computes exactly their outputs."""
    shape = (1, 128, 8, 64, 128, 1)
    (x, dt, a, b, c, d), _ = inputs(shape, seed=6)
    def run(*o):
        tiles = ssd.tiles(128, o[0].shape[2], 64, 128) if kernels else None
        return ssd.ssd_scan(*o, tiles_=tiles, interpret=kernels, chunk=128)

    whole = run(x, dt, a, b, c, d)
    for held in (slice(0, 4), slice(4, 8)):
        share = run(x[:, :, held], dt[:, :, held], a[held], b, c, d[held])
        np.testing.assert_allclose(share, whole[:, :, held], rtol=1e-6,
                                   atol=1e-6)


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "pallas"])
def test_what_is_kept_is_made_once_under_a_recomputed_unit(kernels):
    """y and the chunk starts are tagged: the backward of a rematerialised
    unit around the scan runs no second forward of it."""
    shape = (1, 256, 4, 64, 128, 1)
    ops, weight = inputs(shape, seed=7)
    tiles = _tiles(shape, 128) if kernels else None
    assert (tiles is not None) == kernels

    def unit(*o):
        return jnp.sum(weight * ssd.ssd_scan(*o, tiles_=tiles, chunk=128))

    def passes(wrapped):
        """The scan's passes in the gradient's program: the kernels as they
        lower for a TPU (no chip, no compiler), the jax.numpy engine's
        lax.scans."""
        grad = jax.value_and_grad(wrapped, argnums=(0, 1))
        if not kernels:
            return str(jax.make_jaxpr(grad)(*ops)).count(" scan[")
        return jax.jit(grad).trace(*ops).lower(
            lowering_platforms=("tpu",)).as_text().count("tpu_custom_call")

    assert passes(compiler.rematerialised(unit)) == passes(unit) == 2
    assert passes(jax.checkpoint(unit)) == 3


def test_the_tiles_are_read_from_the_shape():
    t = ssd.tiles(8192, 32, 64, 128, 1, itemsize=2)
    assert (t.chunk, t.block) == (256, 8)
    assert t.bwd_vmem_bytes <= engine.PLAN_VMEM_BUDGET
    assert ssd.tiles(8192, 32, 64, 128, 2).block == 8
    assert ssd.tiles(8192, 12, 64, 128, 1).block == 4
    # a head that is not 64 wide, states that are not whole lane vectors, a
    # ragged sequence, a chunk that is not whole lane vectors, an odd
    # number of heads a group
    assert ssd.tiles(8192, 32, 128, 128) is None
    assert ssd.tiles(8192, 32, 64, 64) is None
    assert ssd.tiles(8200, 32, 64, 128) is None
    assert ssd.tiles(8192, 32, 64, 128, chunk=64) is None
    assert ssd.tiles(64, 32, 64, 128, chunk=64) is not None
    assert ssd.tiles(8192, 6, 64, 128, 2) is None
    assert ssd.kept_bytes(1, 8192, 32, 64, 128, 256, 2) \
        == 2048 * (2 * 8192 + 4 * 32 * 128)
    assert ssd.moved_bytes(1, 8192, 32, 64, 128, 1, 2) \
        == 2 * 8192 * (6 * 2048 + 6 * 128 + 3 * 32)
    assert ssd.flops(1, 8192, 32, 64, 128, 1, 256) == int(
        3 * 8192 * (2 * 128 * 128.5 + 32 * (2 * 64 * 128.5 + 4 * 128 * 64)))


def _op_step(shape, force=None, softplus=False):
    """(the operands, y's weight, [y, the six gradients], the `ssd.lower`
    spans' fields) of the op through the Executor, sum(y * weight) the
    loss; under `softplus` the op is given a zero DtBias."""
    ops, weight = inputs(shape, seed=9)
    fluid.reset_default_env()
    names = ("x", "dt", "a", "b", "c", "d")
    ins = [layers.data(n, list(t.shape), dtype="float32",
                       append_batch_size=False) for n, t in zip(names, ops)]
    for t in ins:
        t.stop_gradient = False
    w = layers.data("w", list(weight.shape), dtype="float32",
                    append_batch_size=False)
    bias = layers.fill_constant([shape[2]], "float32", 0.0) if softplus \
        else None
    y = layers.ssd_scan(*ins, dt_bias=bias)
    loss = layers.reduce_sum(layers.elementwise_mul(y, w))
    fetch = [y] + list(fluid.calc_gradient(loss, ins))
    feed = {**{n: np.asarray(t) for n, t in zip(names, ops)},
            "w": np.asarray(weight)}
    site = engine.site
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        observability.reset()
        if force:
            engine.site = lambda *a, **k: site(*a, force=force, **k)
        got = fluid.Executor(fluid.CPUPlace()).run(feed=feed,
                                                   fetch_list=fetch)
        spans = [dict(s.args) for s in observability.default_tracer().spans()
                 if s.name == "ssd.lower"]
    finally:
        engine.site = site
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()
    return ops, weight, [jnp.asarray(g) for g in got], spans


@pytest.mark.parametrize("force", [None, "interpret"], ids=["xla", "pallas"])
def test_the_op_through_the_executor(force):
    shape = (1, 128, 4, 64, 128, 2)
    ops, weight, got, spans = _op_step(shape, force)
    _held(got, _passes(token_recurrence, ops, weight))
    tiles = ssd.tiles(128, 4, 64, 128, 2)
    assert spans == [dict(
        heads=4, head_dim=64, states=128, groups=2, sq=128, chunk=128,
        scan_bytes=ssd.moved_bytes(1, 128, 4, 64, 128, 2),
        scan_flops=ssd.flops(1, 128, 4, 64, 128, 2, 128), kept="y,starts",
        kept_bytes=ssd.kept_bytes(1, 128, 4, 64, 128, 128),
        engine="pallas" if force else "xla",
        **{k: v if force else 0 for k, v in tiles._asdict().items()
           if k != "chunk"})]


def test_the_ops_step_is_the_softplus_of_dt_and_its_bias():
    shape = (1, 32, 2, 8, 16, 1)
    ops, weight, got, _ = _op_step(shape, softplus=True)

    def with_softplus(x, dt, *rest):
        return token_recurrence(x, jax.nn.softplus(dt), *rest)

    _held(got, _passes(with_softplus, ops, weight))


@pytest.mark.parametrize("groups", [1, 2])
def test_the_gated_rms_norm_gates_first_and_norms_a_group(groups):
    r = np.random.RandomState(groups)
    x, gate, scale = (r.randn(2, 5, 16).astype(np.float32),
                      r.randn(2, 5, 16).astype(np.float32),
                      r.randn(16).astype(np.float32))
    fluid.reset_default_env()
    ins = [layers.data(n, list(t.shape), dtype="float32",
                       append_batch_size=False)
           for n, t in (("x", x), ("gate", gate), ("scale", scale))]
    for t in ins:
        t.stop_gradient = False
    out = layers.gated_rms_norm(*ins, groups=groups, epsilon=1e-5)
    loss = layers.reduce_sum(layers.elementwise_mul(out, out))
    got = fluid.Executor(fluid.CPUPlace()).run(
        feed={"x": x, "gate": gate, "scale": scale},
        fetch_list=[out] + list(fluid.calc_gradient(loss, ins)))

    def formula(x, gate, scale):
        g = (x * jax.nn.silu(gate)).reshape(2, 5, groups, -1)
        n = g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True) + 1e-5)
        return n.reshape(2, 5, 16) * scale

    want, pull = jax.vjp(formula, x, gate, scale)
    for g, w in zip(got, (want,) + pull(2 * want)):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)
