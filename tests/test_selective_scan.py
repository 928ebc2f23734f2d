"""kernels/selective_scan.py (Mamba-1's selective scan, the op
selective_scan) against the recurrence one token at a time: both engines,
the Pallas kernel pair in the interpreter, the forward and every gradient
(A and D among them); a sequence that is not whole chunks; one chunk; a
state that decays to nothing and one that does not decay; the op through
the Executor with its span, its kept values and its softplus."""

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
from paddle_tpu.kernels import engine
from paddle_tpu.kernels import selective_scan as ss

from ssm_scan_probe import HARD, inputs, token_recurrence  # noqa: E402

NAMES = ("y", "dx", "ddt", "dA", "dB", "dC", "dD")


def _passes(fn, ops, weight):
    y, pull = jax.vjp(fn, *ops)
    return (y,) + tuple(pull(weight))


def _held(got, want, rtol=2e-5):
    for name, g, w in zip(NAMES, got, want):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=rtol,
                                   atol=rtol * scale, err_msg=name)


# (shape [B, S, E, N], chunk, the kernels or the jax.numpy engine)
CASES = {
    "xla_whole_chunks": ((2, 32, 256, 8), 8, False),
    "xla_not_whole_chunks": ((2, 40, 256, 16), 16, False),
    "xla_one_chunk": ((1, 24, 128, 8), 64, False),
    "pallas_whole_chunks": ((1, 128, 1024, 16), 64, True),
    "pallas_two_blocks_of_channels": ((2, 16, 2048, 8), 16, True),
    "pallas_one_chunk": ((1, 24, 1024, 8), 64, True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_against_the_token_recurrence(case):
    shape, chunk, kernels = CASES[case]
    ops, weight = inputs(shape, seed=3)
    tiles = ss.tiles(shape[1], shape[2], shape[3], chunk) if kernels else None
    assert (tiles is not None) == kernels
    got = _passes(lambda *o: ss.selective_scan(
        *o, tiles_=tiles, interpret=kernels, chunk=chunk), ops, weight)
    _held(got, _passes(token_recurrence, ops, weight))


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("hard", sorted(set(HARD) - {"random"}))
def test_a_state_that_decays_to_nothing_and_one_that_does_not(hard, kernels):
    shape = (1, 32, 1024, 8)
    ops, weight = inputs(shape, 5, *HARD[hard])
    tiles = ss.tiles(*shape[1:]) if kernels else None
    got = _passes(lambda *o: ss.selective_scan(
        *o, tiles_=tiles, interpret=kernels, chunk=16), ops, weight)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in got)
    _held(got, _passes(token_recurrence, ops, weight))
    x, dt, a = ops[:3]
    decay = jnp.exp(dt[..., None] * a)
    assert float(jnp.median(decay)) < 1e-3 if hard == "decays_to_nothing" \
        else float(jnp.min(decay)) > 0.99


def test_the_tiles_are_read_from_the_shape():
    assert ss.tiles(8192, 5120, 16) == ss.Tiles(
        64, 1024, ss.working_set_bytes(64, 16, 5, False),
        ss.working_set_bytes(64, 16, 5, True))
    assert ss.tiles(8192, 5120, 16).bwd_vmem_bytes <= engine.PLAN_VMEM_BUDGET
    # channels that are not whole blocks, a ragged sequence, states that do
    # not fold, a working set over the budget
    assert ss.tiles(64, 1000, 16) is None
    assert ss.tiles(100, 1024, 16) is None
    assert ss.tiles(64, 1024, 12) is None
    assert ss.tiles(8192, 5120, 16, 256) is None
    # a chunk's scalars are whole 1024-word tiles of SMEM, or the sequence
    assert ss.tiles(8192, 5120, 16, 32) is None
    assert ss.tiles(128, 1024, 8) is None
    assert ss.tiles(64, 1024, 8) is not None
    assert ss.kept_bytes(1, 8192, 5120, 16) == 4 * 5120 * (8192 + 128 * 16)
    assert ss.moved_bytes(1, 8192, 5120, 16) \
        == 8 * 8192 * 5120 * 4 + 6 * 8192 * 16 * 4


def _op_step(shape, force=None, softplus=False):
    """(the operands, y's weight, [y, the six gradients], the `ssm.lower`
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
    y = layers.selective_scan(*ins, dt_bias=bias)
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
                 if s.name == "ssm.lower"]
    finally:
        engine.site = site
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()
    return ops, weight, [jnp.asarray(g) for g in got], spans


@pytest.mark.parametrize("force", [None, "interpret"], ids=["xla", "pallas"])
def test_the_op_through_the_executor(force):
    shape = (1, 64, 1024, 8)
    ops, weight, got, spans = _op_step(shape, force)
    _held(got, _passes(token_recurrence, ops, weight))
    assert spans == [dict(
        channels=1024, states=8, sq=64, chunk=64,
        scan_bytes=ss.moved_bytes(1, 64, 1024, 8), kept="y,starts",
        kept_bytes=ss.kept_bytes(1, 64, 1024, 8, 64),
        engine="pallas" if force else "xla",
        **{k: v if force else 0 for k, v in
           ss.tiles(64, 1024, 8)._asdict().items() if k != "chunk"})]


def test_the_ops_step_is_the_softplus_of_dt_and_its_bias():
    shape = (1, 16, 128, 8)
    ops, weight, got, _ = _op_step(shape, softplus=True)

    def with_softplus(x, dt, *rest):
        return token_recurrence(x, jax.nn.softplus(dt), *rest)

    _held(got, _passes(with_softplus, ops, weight))
