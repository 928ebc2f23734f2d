"""EVA attention (kernels/eva_attention.py, the op `eva_attention`): its one
engine through both of flash_attention's (the jax.numpy fallback and the
Pallas kernels in the interpreter) against the equations written out
densely over the whole sequence,
forward and every gradient (mu and phi included); the limits in which EVA
IS plain causal attention (a chunk of one key, a window as long as the
sequence); the first window's empty prefix of summaries; the logsumexp that
flash_attention hands out, its cotangent, and the exact merge of two key
sets into one softmax; and that a call without the new output is the call
it was."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from paddle_tpu.kernels import eva_attention as eva
from paddle_tpu.kernels.flash_attention import (NEG_INF, flash_attention,
                                                merge_attention)

B, H, D = 2, 3, 16


def _inputs(S, seed=0, heads=H):
    rng = np.random.RandomState(seed)
    q, k, v, g = (jnp.asarray(rng.randn(B, heads, S, D), jnp.float32)
                  for _ in range(4))
    mu, phi = (jnp.asarray(rng.randn(heads, D), jnp.float32)
               for _ in range(2))
    return q, k, v, mu, phi, g


def written_out(q, k, v, mu, phi, window, chunk):
    """The equations over the whole sequence: every chunk pooled, the
    scores of all S queries against all S keys and all S / chunk summaries,
    two masks, one softmax."""
    S = q.shape[2]
    n = S // chunk
    kc = k[:, :, :n * chunk].reshape(B, -1, n, chunk, D)
    vc = v[:, :, :n * chunk].reshape(B, -1, n, chunk, D)
    a = jax.nn.softmax(jnp.einsum("bhncd,hd->bhnc", kc, mu), -1)
    b = jax.nn.softmax(jnp.einsum("bhncd,hd->bhnc", kc, phi), -1)
    k_hat = jnp.einsum("bhnc,bhncd->bhnd", a, kc)
    v_hat = jnp.einsum("bhnc,bhncd->bhnd", b, vc)
    t, s, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :], \
        jnp.arange(n)[None, :]
    sees_key = (s // window == t // window) & (s <= t)
    sees_summary = j < (window // chunk) * (t // window)
    scores = jnp.concatenate([
        jnp.where(sees_key, jnp.einsum("bhqd,bhkd->bhqk", q, k), NEG_INF),
        jnp.where(sees_summary, jnp.einsum("bhqd,bhnd->bhqn", q, k_hat),
                  NEG_INF)], -1) * D ** -0.5
    p = jax.nn.softmax(scores, -1)
    return (jnp.einsum("bhqk,bhkd->bhqd", p[..., :S], v)
            + jnp.einsum("bhqn,bhnd->bhqd", p[..., S:], v_hat))


def causal(q, k, v):
    S = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * D ** -0.5
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(mask, scores, NEG_INF), -1), v)


def _out_and_grads(fn, q, k, v, mu, phi, g):
    """(out, dq, dk, dv, dmu, dphi) of the loss sum(out * g), one compile."""
    @jax.jit
    def run(q, k, v, mu, phi, g):
        out, pull = jax.vjp(fn, q, k, v, mu, phi)
        return (out,) + pull(g)

    with jax.default_matmul_precision("highest"):
        return run(q, k, v, mu, phi, g)


def _close(got, want, tol=2e-5):
    for name, a, b in zip(("out", "dq", "dk", "dv", "dmu", "dphi"), got,
                          want):
        np.testing.assert_allclose(
            a, b, rtol=tol, atol=tol * max(float(jnp.abs(b).max()), 1e-3),
            err_msg=name)


# (S, window, chunk): whole windows; a short last window (S no multiple of
# the window, nor of the kernels' 128-row tiles: the flash calls pad and
# mask); three windows of 136 rows, which no TPU block length divides
SHAPES = [(64, 16, 4), (72, 32, 8), (300, 136, 8)]


@pytest.mark.parametrize("force", ["jax", "interpret"])
@pytest.mark.parametrize("S, window, chunk", SHAPES)
def test_both_engines_are_the_equations_forward_and_backward(
        S, window, chunk, force):
    args = _inputs(S, seed=S)
    got = _out_and_grads(
        lambda *a: eva.eva_attention(*a, window, chunk, force=force), *args)
    want = _out_and_grads(
        lambda *a: written_out(*a, window, chunk), *args)
    _close(got, want)
    # mu and phi take a gradient that is no rounding
    assert float(jnp.abs(got[4]).max()) > 1e-3
    assert float(jnp.abs(got[5]).max()) > 1e-3


@pytest.mark.parametrize("force", ["jax", "interpret"])
@pytest.mark.parametrize("S, window, chunk, why", [
    (48, 16, 1, "a summary of one key is the key"),
    (40, 64, 8, "one window holds the sequence"),
    (64, 64, 8, "the window is the sequence")])
def test_where_eva_is_plain_causal_attention(S, window, chunk, why, force):
    q, k, v, mu, phi, g = _inputs(S, seed=3)
    got = _out_and_grads(
        lambda *a: eva.eva_attention(*a, window, chunk, force=force),
        q, k, v, mu, phi, g)
    want = _out_and_grads(lambda q, k, v, mu, phi: causal(q, k, v),
                          q, k, v, mu, phi, g)
    _close(got[:4], want[:4])
    # nothing is pooled that the result depends on
    assert float(jnp.abs(got[4]).max()) < 1e-6, why
    assert float(jnp.abs(got[5]).max()) < 1e-6, why


def test_the_first_window_sees_no_summary_and_moves_none():
    """The first window's queries run a softmax over NO summary: weight 0,
    not NaN, and no gradient into the pooling from them."""
    S, window, chunk = 64, 32, 4
    q, k, v, mu, phi, g = _inputs(S, seed=5)
    first = g.at[:, :, window:].set(0.0)          # a loss on window 0 alone
    for force in ("jax", "interpret"):
        got = _out_and_grads(
            lambda *a: eva.eva_attention(*a, window, chunk, force=force),
            q, k, v, mu, phi, first)
        assert all(bool(jnp.all(jnp.isfinite(x))) for x in got), force
        np.testing.assert_allclose(
            got[0][:, :, :window],
            causal(q[:, :, :window], k[:, :, :window], v[:, :, :window]),
            rtol=2e-5, atol=2e-5)
        assert float(jnp.abs(got[4]).max()) == 0.0
        assert float(jnp.abs(got[5]).max()) == 0.0
        assert float(jnp.abs(got[2][:, :, window:]).max()) == 0.0


def test_geometry_and_pairs_against_a_count_of_the_masks():
    assert eva.geometry(8192, 2048, 16) == dict(
        window=2048, windows=4, per_window=128, pooled=6144, chunks=384)
    assert eva.geometry(40, 64, 8)["windows"] == 1
    assert eva.geometry(300, 136, 8) == dict(
        window=136, windows=3, per_window=17, pooled=272, chunks=34)
    with pytest.raises(ValueError, match="do not cut"):
        eva.geometry(64, 16, 5)
    for S, window, chunk in SHAPES + [(8192, 2048, 16)]:
        t = np.arange(S)
        own = sum(int(((np.arange(S) // window == x // window)
                       & (np.arange(S) <= x)).sum()) for x in t) \
            if S < 1000 else 4 * 2048 * 2049 // 2
        far = int(((window // chunk) * (t // window)).sum())
        assert eva.pairs(S, window, chunk) == (own, far)
    assert eva.pairs(8192, 2048, 16) == (8_392_704, 1_572_864)


# ---------------------------------------------------------------------------
# flash_attention's logsumexp
# ---------------------------------------------------------------------------
def _dense_lse(q, k, klen, causal_mask):
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * D ** -0.5
    mask = jnp.arange(k.shape[2])[None, None, None, :] < \
        klen[:, None, None, None]
    if causal_mask:
        mask = mask & (jnp.arange(k.shape[2])[None, :]
                       <= jnp.arange(q.shape[2])[:, None])
    scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, -1)
    p = jnp.where(jnp.any(mask, -1, keepdims=True), p, 0.0)
    lse = jnp.where(jnp.any(mask, -1), jax.nn.logsumexp(scores, -1), NEG_INF)
    return p, lse


@pytest.mark.parametrize("force", ["jax", "interpret"])
@pytest.mark.parametrize("causal_mask, Sq, Sk", [(True, 96, 96),
                                                 (False, 80, 40)])
def test_the_logsumexp_and_its_cotangent(force, causal_mask, Sq, Sk):
    """(out, lse) and the gradient of a loss that reads BOTH: dS = P (dP -
    D + dlse).  A batch row with no key (k_lengths 0) hands out NEG_INF and
    zeros, and takes nothing."""
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(B, H, Sq, D), jnp.float32)
    k, v = (jnp.asarray(rng.randn(B, H, Sk, D), jnp.float32)
            for _ in range(2))
    g = jnp.asarray(rng.randn(B, H, Sq, D), jnp.float32)
    gl = jnp.asarray(rng.randn(B, H, Sq), jnp.float32)
    klen = jnp.asarray([Sk - 7, 0], jnp.int32)

    def loss(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            # an empty row's lse is a constant: it may not enter as a value
            there = lse > NEG_INF / 2
            return jnp.sum(out * g) + jnp.sum(jnp.where(there, lse * gl, 0.0))
        return f

    def ours(q, k, v):
        return flash_attention(q, k, v, causal=causal_mask, k_lengths=klen,
                               force=force, return_lse=True)

    def dense(q, k, v):
        p, lse = _dense_lse(q, k, klen, causal_mask)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v), lse

    with jax.default_matmul_precision("highest"):
        out, lse = jax.jit(ours)(q, k, v)
        want_out, want_lse = jax.jit(dense)(q, k, v)
        got = jax.jit(jax.grad(loss(ours), argnums=(0, 1, 2)))(q, k, v)
        want = jax.jit(jax.grad(loss(dense), argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(out, want_out, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(lse[0], want_lse[0], rtol=2e-5, atol=2e-5)
    assert bool(jnp.all(lse[1] == NEG_INF)) and bool(jnp.all(out[1] == 0))
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
        assert bool(jnp.all(a[1] == 0))


def test_two_key_sets_merge_into_the_one_softmax():
    """Attention over keys [0, 24) and over keys [24, 64), each with its
    logsumexp, merged: the attention over all 64, values and gradients."""
    rng = np.random.RandomState(11)
    q, k, v, g = (jnp.asarray(rng.randn(B, H, 64, D), jnp.float32)
                  for _ in range(4))

    def whole(q, k, v):
        return flash_attention(q, k, v, force="jax")

    def merged(force):
        def f(q, k, v):
            return merge_attention([
                flash_attention(q, k[:, :, :24], v[:, :, :24], force=force,
                                return_lse=True),
                flash_attention(q, k[:, :, 24:], v[:, :, 24:], force=force,
                                return_lse=True)])
        return f

    def out_and_grads(fn):
        @jax.jit
        def run(q, k, v):
            out, pull = jax.vjp(fn, q, k, v)
            return (out,) + pull(g)
        return run(q, k, v)

    with jax.default_matmul_precision("highest"):
        want = out_and_grads(whole)
        for force in ("jax", "interpret"):
            got = out_and_grads(merged(force))
            for a, b in zip(got, want):
                np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5,
                                           err_msg=force)


@pytest.mark.parametrize("force", ["jax", "interpret"])
def test_a_call_without_the_logsumexp_is_the_call_it_was(force):
    """flash_attention without `return_lse` traces what it traced before
    the output existed: one result, no logarithm among a jax call's
    operations (the reference path computes its logsumexp only when asked),
    and the Pallas forward's lse stays an internal residual of the
    custom_vjp (`_flash`), which is what the cells' sites call."""
    q, k, v, _, _, _ = _inputs(64)
    plain = jax.make_jaxpr(
        lambda q, k, v: flash_attention(q, k, v, causal=True, force=force))(
            q, k, v)
    asked = jax.make_jaxpr(
        lambda q, k, v: flash_attention(q, k, v, causal=True, force=force,
                                        return_lse=True))(q, k, v)
    assert len(plain.out_avals) == 1 and len(asked.out_avals) == 2
    text = str(plain)
    assert "_flash" in text and "_flash_lse" not in text
    if force == "jax":
        assert " log " not in text and "log_softmax" not in text


def test_the_op_through_its_layer_is_the_equations():
    """`layers.eva_attention` in a program on the CPU (the jax.numpy
    engine), output and the gradient of mu, against the equations."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    S, window, chunk = 64, 16, 4
    q, k, v, mu, phi, g = _inputs(S, seed=9)
    fluid.reset_default_env()
    names = ("q", "k", "v")
    qv, kv, vv = (layers.data(n, [H, S, D], dtype="float32") for n in names)
    for var in (qv, kv, vv):
        var.stop_gradient = False
    attr = fluid.ParamAttr
    from paddle_tpu.initializer import NumpyArrayInitializer as Init
    muv = layers.create_parameter([H, D], "float32", attr=attr(
        name="mu", initializer=Init(np.asarray(mu))))
    phiv = layers.create_parameter([H, D], "float32", attr=attr(
        name="phi", initializer=Init(np.asarray(phi))))
    out = layers.eva_attention(qv, kv, vv, muv, phiv, window=window,
                               chunk=chunk)
    loss = layers.reduce_sum(layers.elementwise_mul(
        out, layers.assign(np.asarray(g))))
    pairs = dict((p.name, grad) for p, grad in fluid.append_backward(loss))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    got_out, got_dmu = exe.run(
        feed={n: np.asarray(x) for n, x in zip(names, (q, k, v))},
        fetch_list=[out, pairs["mu"]])
    want = _out_and_grads(lambda *a: written_out(*a, window, chunk),
                          q, k, v, mu, phi, g)
    np.testing.assert_allclose(got_out, want[0], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got_dmu, want[4], rtol=2e-4, atol=2e-5)
