"""The op gated_delta_attention (the gated delta rule's recurrence as a scan
over chunks, kernels/gated_delta.py) against the recurrence one token at a
time, forward and every gradient, in both of its forms: a decay for every
key channel (Kimi Delta Attention) and ONE decay a head with q and k at
fewer heads than v (Gated DeltaNet); the short causal convolution; what
`kda.lower` / `gdn.lower` say of a site."""

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
from paddle_tpu import layers, observability
from paddle_tpu.kernels import gated_delta as kda


def token_recurrence(q, k, v, g, beta, heads, eps=1e-6):
    """The module docstring's three lines, S dependent steps.  The
    head-decay form the plain way: a head's one decay repeated to its
    channels, a key head's q and k repeated to its value heads."""
    B, S, width = v.shape
    D = width // heads
    if g.shape[-1] == heads != width:
        g = jnp.repeat(g, D, axis=-1)
    if q.shape[-1] != width:
        q, k = (jnp.repeat(t.reshape(B, S, -1, D), width // t.shape[-1],
                           axis=2).reshape(B, S, width) for t in (q, k))

    def split(t):
        return jnp.moveaxis(t.reshape(B, S, heads, D), 1, 0)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x                  # [B, H, D]; b_t [B, H]
        state = jnp.exp(g_t)[..., None] * state
        lacking = v_t - jnp.sum(state * k_t[..., None], axis=-2)
        state = state + (b_t[..., None, None] * k_t[..., None]
                         * lacking[..., None, :])
        return state, jnp.sum(state * q_t[..., None], axis=-2)

    _, out = jax.lax.scan(
        token, jnp.zeros((B, heads, D, D), jnp.float32),
        (unit(split(q)), unit(split(k)), split(v), split(g),
         jnp.moveaxis(beta, 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, width) * D ** -0.5


def _inputs(B, S, H, D, seed, rate=1.0, shift=-2.0, alike=0.0,
            key_heads=None):
    """q, k, v ~ N(0, 1) (`alike`: a share of every key that all keys
    have in common), g = -rate * softplus(N(shift, 1)), beta in (0, 1).
    With `key_heads` the head-decay form: q, k [B, S, key_heads D] and g
    [B, S, H], `rate` a number or one a head."""
    r = np.random.RandomState(seed)
    Hk = key_heads or H
    q, k = (r.randn(B, S, Hk * D) for _ in range(2))
    v = r.randn(B, S, H * D)
    k = k + alike * np.abs(r.randn(1, 1, Hk * D)) * 10
    g = -np.asarray(rate) * np.log1p(np.exp(
        r.randn(B, S, H * (1 if key_heads else D)) + shift))
    beta = 1.0 / (1.0 + np.exp(-(r.randn(B, S, H) + 3 * alike)))
    return tuple(jnp.asarray(t, jnp.float32) for t in (q, k, v, g, beta))


def from_weak_to_strong(H):
    """One decay rate a head, from e^-0.001 to e^-21 a token (softplus(3 +
    N(0, 1)) is ~3: with `shift` 3 the rates below are a third of these)."""
    return np.geomspace(0.001, 21.0, H) / 3.0


def _held_to_the_recurrence(args, H, chunk, rtol=2e-5):
    weight = jnp.asarray(np.random.RandomState(1).randn(*args[2].shape),
                         jnp.float32)

    def chunked(*a):
        return kda.gated_delta_attention(*a, heads=H, chunk=chunk)

    def plain(*a):
        return token_recurrence(*a, heads=H)

    # one forward and one backward a side (a compile each on the CPU)
    (out, pull), (want, pull_plain) = (jax.vjp(f, *args)
                                       for f in (chunked, plain))
    np.testing.assert_allclose(out, want, rtol=rtol,
                               atol=rtol * float(jnp.max(jnp.abs(want))))
    got, ref = pull(weight.astype(out.dtype)), pull_plain(weight)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, ref):
        assert np.all(np.isfinite(a)), name
        np.testing.assert_allclose(
            a, b, rtol=rtol, atol=rtol * float(jnp.max(jnp.abs(b))),
            err_msg=name)


@pytest.mark.parametrize("B,S,H,D,chunk,key_heads", [
    (2, 128, 2, 16, 64, None),  # two chunks of 64
    (1, 64, 2, 8, 16, None),    # a smaller chunk
    (1, 64, 1, 8, 64, None),    # S of one chunk
    (1, 32, 3, 8, 128, None),   # a chunk longer than S: S is the chunk
    # ONE decay a head (g [B, S, H]), key heads 1 : 1, 1 : 2 and 1 : 4
    (1, 64, 2, 8, 16, 2),
    (2, 128, 4, 16, 64, 2),
    (1, 64, 4, 8, 64, 1),
])
def test_the_chunked_scan_is_the_token_recurrence(B, S, H, D, chunk,
                                                  key_heads):
    """Forward and the gradients of q, k, v, g and beta (dq, dk summed over
    a key head's value heads, dg [B, S, H] where g is)."""
    _held_to_the_recurrence(
        _inputs(B, S, H, D, seed=S + chunk, key_heads=key_heads), H, chunk)


@pytest.mark.parametrize("key_heads", [None, 2])
def test_a_strong_decay_that_exp_of_minus_gc_would_overflow(key_heads):
    """A_log large: channels that decay by e^-1500 inside a chunk, beside
    channels that hardly decay.  exp(-Gc), which the factored form K
    exp(Gc) (K exp(-Gc))^T needs, is inf there; the chunked scan forms
    every exponent from a difference <= 0 and stays finite and right.  A
    head's one decay: four heads from e^-0.001 to e^-21 a token in one
    input."""
    if key_heads:
        H = 4
        args = _inputs(1, 256, H, 16, seed=3, rate=from_weak_to_strong(H),
                       shift=3.0, key_heads=key_heads)
        assert float(jnp.max(args[3][..., 0])) > -0.01
    else:
        H = 2
        args = _inputs(1, 256, H, 16, seed=3, rate=16.0, shift=1.0)
    g = args[3]
    assert float(jnp.min(jnp.sum(g[:, :64], axis=1))) < -1000
    with np.errstate(over="ignore"):
        naive = np.exp(-np.cumsum(np.asarray(g[:, :64]), 1))
    assert not np.isfinite(naive).all()
    _held_to_the_recurrence(args, H, 64)


def test_a_channels_decay_goes_with_as_many_key_heads_and_nothing_else():
    q, k, v, g, beta = _inputs(1, 64, 4, 8, seed=1)
    with pytest.raises(ValueError, match="a divisor of H"):
        kda.gated_delta_attention(q[..., :16], k[..., :16], v, g, beta,
                                  heads=4)
    with pytest.raises(ValueError, match="a divisor of H"):
        kda.gated_delta_attention(q[..., :24], k[..., :24], v, g[..., :4],
                                  beta, heads=4)
    assert kda.form(q[..., :16], v, g[..., :4], 4) == (2, True)
    assert kda.form(q, v, g, 4) == (4, False)


def test_keys_alike_and_beta_near_one():
    """Keys with a large common part and beta ~ 0.95: A's entries are
    near 1 and all of one sign, where the inverse as a series in powers of
    A cancels numbers of 1e10 (the block doubling makes only entries of
    the inverse itself)."""
    _held_to_the_recurrence(_inputs(1, 128, 2, 16, seed=7, alike=1.0), 2, 64,
                            rtol=2e-4)


def test_groups_of_chunks_change_no_number(monkeypatch):
    """The parallel part taken a group of chunks at a time: 8 chunks as 1
    group, and (a smaller room) as 4 groups of 2."""
    args = _inputs(1, 128, 2, 8, seed=5)
    assert kda.plan(1, 128, 2, 8, 16) == {"chunk": 16, "chunks": 8,
                                          "group": 8}
    whole = kda.gated_delta_attention(*args, heads=2, chunk=16)
    monkeypatch.setattr(kda, "_GROUP_BYTES", 2 * 4 * 2 * 16 * 8)
    assert kda.plan(1, 128, 2, 8, 16)["group"] == 2
    np.testing.assert_allclose(
        kda.gated_delta_attention(*args, heads=2, chunk=16), whole,
        rtol=1e-6, atol=1e-7)
    _held_to_the_recurrence(args, 2, 16)


def test_a_chunk_that_does_not_divide_the_sequence_is_refused():
    with pytest.raises(ValueError, match="power of two"):
        kda.plan(1, 96, 2, 8, 64)
    with pytest.raises(ValueError, match="power of two"):
        kda.plan(1, 96, 2, 8, 24)


def test_the_real_shapes_plan_and_counts():
    """The cell's site (S 4096; 8192 is the shape ISSUE 47 named first):
    chunks of 64 in groups of 8; one state a group kept (2 MB each, where a
    state a chunk is 134 MB at 4096); the algorithm's 55 GFLOP and 0.57 GB
    a layer."""
    assert kda.plan(1, 4096, 32, 128) == {"chunk": 64, "chunks": 64,
                                          "group": 8}
    assert kda.plan(1, 8192, 32, 128) == {"chunk": 64, "chunks": 128,
                                          "group": 8}
    assert kda.state_bytes(1, 32, 128) == 32 * 128 * 128 * 4
    assert kda.kept_bytes(1, 4096, 32, 128, 64 // 8, 2) == \
        2 * 4096 * 4096 + 8 * 32 * 128 * 128 * 4
    a_chunk = 5 * 64 * 64 * 128 + 64 ** 3 // 3 + 6 * 64 * 128 * 128
    assert kda.flops(1, 4096, 32, 128, 64) == 3 * 32 * 64 * a_chunk
    assert kda.flops(1, 8192, 32, 128, 64) == 2 * kda.flops(1, 4096, 32, 128,
                                                            64)
    wide = 4096 * 4096
    assert kda.moved_bytes(1, 4096, 32, 128, 2) == \
        (4 * 2 + 4) * wide + 4 * 4096 * 32 \
        + (7 * 2 + 8) * wide + 8 * 4096 * 32
    # one decay a head over 16 key heads at S 8192 (qwen3next-train-gdn8k's
    # site): q and k half as wide, g [S, 32], the two products once a key
    # head: 0.54 GB where a decay a channel and repeated keys move 1.14
    wide = 8192 * 4096
    assert kda.moved_bytes(1, 8192, 32, 128, 2, 16, True) == \
        3 * 2 * wide + 8 * 8192 * 32 + 5 * 2 * wide + 16 * 8192 * 32
    assert kda.moved_bytes(1, 8192, 32, 128, 2) > 2.1 * kda.moved_bytes(
        1, 8192, 32, 128, 2, 16, True)
    a_value_head = 3 * 64 * 64 * 128 + 64 ** 3 // 3 + 6 * 64 * 128 * 128
    assert kda.flops(1, 8192, 32, 128, 64, 16) == 3 * 128 * (
        32 * a_value_head + 16 * 2 * 64 * 64 * 128)


# ---------------------------------------------------------------------------
# through a Program
# ---------------------------------------------------------------------------
def _through_a_program(args, H, chunk, grads=False):
    fluid.reset_default_env()
    names = ("q", "k", "v", "g", "beta")
    ins = [layers.data(n, list(a.shape), append_batch_size=False,
                       dtype="float32") for n, a in zip(names, args)]
    for t in ins:
        t.stop_gradient = False
    out = layers.gated_delta_attention(*ins, heads=H, chunk=chunk)
    fetch = [out]
    if grads:
        loss = layers.reduce_sum(layers.square(out))
        fetch += fluid.calc_gradient(loss, ins)
    exe = fluid.Executor(fluid.CPUPlace())
    return exe.run(feed={n: np.asarray(a) for n, a in zip(names, args)},
                   fetch_list=fetch)


@pytest.mark.parametrize("key_heads", [None, 1])
def test_the_op_and_its_gradients_through_the_executor(key_heads):
    args = _inputs(2, 64, 2, 8, seed=9, key_heads=key_heads)
    got = _through_a_program(args, 2, 16, grads=True)
    want = token_recurrence(*args, heads=2)
    np.testing.assert_allclose(got[0], want, rtol=2e-5, atol=1e-6)
    ref = jax.grad(lambda *a: jnp.sum(token_recurrence(*a, heads=2) ** 2),
                   argnums=range(5))(*args)
    for a, b in zip(got[1:], ref):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("key_heads", [None, 1])
def test_kda_lower_says_what_a_site_was_given(key_heads):
    """One span an op: the plan, the engine, what survives a recomputation
    and the static counts the roofline divides; of the head-decay form
    under the name `gdn.lower`, with `decay` and `key_heads`."""
    args = _inputs(2, 128, 2, 16, seed=2, key_heads=key_heads)
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        observability.reset()
        _through_a_program(args, 2, 64)
        spans = {name: [dict(s.args) for s in
                        observability.default_tracer().spans()
                        if s.name == name]
                 for name in ("kda.lower", "gdn.lower")}
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()
    said = dict(
        heads=2, head_dim=16, sq=128, chunk=64, chunks=2, group=2,
        engine="xla", state_bytes=4 * 2 * 2 * 16 * 16, kept="out,states",
        kept_bytes=4 * 2 * 128 * 32 + 1 * 4 * 2 * 2 * 16 * 16)
    if key_heads:
        assert spans == {"kda.lower": [], "gdn.lower": [dict(
            said, flops=kda.flops(2, 128, 2, 16, 64, 1),
            moved_bytes=kda.moved_bytes(2, 128, 2, 16, 4, 1, True),
            decay="head", key_heads=1)]}
    else:
        assert spans == {"gdn.lower": [], "kda.lower": [dict(
            said, flops=kda.flops(2, 128, 2, 16, 64),
            moved_bytes=kda.moved_bytes(2, 128, 2, 16, 4))]}


def test_the_scan_keeps_its_output_and_states_through_a_recomputation():
    """Inside a rematerialised unit the backward runs no second forward of
    the op: the unit's residuals are its inputs and the op's two kept
    values."""
    from paddle_tpu.core.compiler import rematerialised

    args = _inputs(1, 64, 2, 8, seed=4)

    def unit(*a):
        return jnp.sum(kda.gated_delta_attention(*a, heads=2, chunk=16) ** 2)

    def scans(fn):
        text = str(jax.make_jaxpr(jax.grad(fn, argnums=range(5)))(*args))
        return text.count("scan[")

    # forward: a scan over groups and one over chunks; backward: one over
    # groups, a group's chunk states again, its chunks last to first; no
    # second forward pair under the recomputation
    assert scans(rematerialised(unit)) == scans(unit) == 5
    np.testing.assert_allclose(
        jax.grad(rematerialised(unit))(*args), jax.grad(unit)(*args),
        rtol=1e-6)


# ---------------------------------------------------------------------------
# the short convolution
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("taps,activation", [(4, "silu"), (2, "identity"),
                                             (1, "silu")])
def test_short_conv1d_is_the_causal_depthwise_convolution(taps, activation):
    r = np.random.RandomState(taps)
    x = r.randn(2, 12, 6).astype(np.float32)
    w = r.randn(taps, 6).astype(np.float32)
    fluid.reset_default_env()
    xv = layers.data("x", [2, 12, 6], append_batch_size=False)
    wv = layers.data("w", [taps, 6], append_batch_size=False)
    xv.stop_gradient = wv.stop_gradient = False
    out = layers.short_conv1d(xv, wv, activation=activation)
    dx, dw = fluid.calc_gradient(layers.reduce_sum(layers.square(out)),
                             [xv, wv])
    got = fluid.Executor(fluid.CPUPlace()).run(
        feed={"x": x, "w": w}, fetch_list=[out, dx, dw])

    def plain(x, w):
        padded = jnp.concatenate([jnp.zeros((2, taps - 1, 6)), x], axis=1)
        y = sum(w[j] * padded[:, j:j + 12] for j in range(taps))
        return jax.nn.silu(y) if activation == "silu" else y

    np.testing.assert_allclose(got[0], plain(x, w), rtol=1e-5, atol=1e-6)
    # causal: the last tap reads the position itself, none a later one
    moved = plain(jnp.asarray(x).at[:, 7].add(1.0), w)
    np.testing.assert_array_equal(moved[:, :7], plain(x, w)[:, :7])
    assert np.abs(np.asarray(moved - plain(x, w))[:, 7]).max() > 0
    ref = jax.grad(lambda x, w: jnp.sum(plain(x, w) ** 2), (0, 1))(
        jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(got[1], ref[0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got[2], ref[1], rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# around the head-decay form: the decay itself and the SiLU-gated norm
# ---------------------------------------------------------------------------
def test_the_heads_decay_and_the_silu_gated_norm_through_the_executor():
    """layers.gated_delta_decay is -exp(a_log) softplus(x + dt_bias), fp32;
    layers.kda_gated_norm under gate_activation silu and no bias is the norm
    a head times silu(gate); both with their gradients."""
    r = np.random.RandomState(4)
    B, S, H, D = 2, 6, 3, 4
    feed = {"x": r.randn(B, S, H), "a_log": r.randn(H), "dt": r.randn(H),
            "o": r.randn(B, S, H * D), "z": r.randn(B, S, H * D),
            "w": 1 + 0.1 * r.randn(D)}
    feed = {k: v.astype(np.float32) for k, v in feed.items()}
    fluid.reset_default_env()
    ins = {k: layers.data(k, list(v.shape), append_batch_size=False)
           for k, v in feed.items()}
    for t in ins.values():
        t.stop_gradient = False
    g = layers.gated_delta_decay(ins["x"], ins["a_log"], ins["dt"])
    n = layers.kda_gated_norm(ins["o"], ins["z"], None, ins["w"], heads=H,
                              epsilon=1e-6, gate_activation="silu")
    loss = layers.elementwise_add(layers.reduce_sum(layers.square(g)),
                                  layers.reduce_sum(layers.square(n)))
    order = sorted(ins)
    grads = fluid.calc_gradient(loss, [ins[k] for k in order])
    got = fluid.Executor(fluid.CPUPlace()).run(
        feed=feed, fetch_list=[g, n] + grads)

    def plain(a_log, dt, o, w, x, z):
        g = -jnp.exp(a_log) * jax.nn.softplus(x + dt)
        heads = o.reshape(B, S, H, D)
        normed = heads * jax.lax.rsqrt(jnp.mean(
            heads * heads, axis=-1, keepdims=True) + 1e-6) * w
        return g, normed.reshape(B, S, H * D) * jax.nn.silu(z)

    args = [jnp.asarray(feed[k]) for k in order]
    want = plain(*args)
    assert got[0].dtype == np.float32 and float(np.max(got[0])) < 0
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-6)
    ref = jax.grad(lambda *a: sum(jnp.sum(t ** 2) for t in plain(*a)),
                   argnums=range(6))(*args)
    for name, a, b in zip(order, got[2:], ref):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)
