"""Plain reference of ouro-2.6b: forward, exit-gate loss and gradient in fp32
jax.numpy under jax.default_matmul_precision("highest"), written from the
equations in benchmark/configs/ouro-2.6b.json (`equations`, `assumed`) and the
parameter names of paddle_tpu/models/looped_decoder.py, and from nothing else
of the program: no op, no kernel, no recurrence construct, no AMP tier.  A
Python `for` runs the trips and the layers; attention is a softmax over
masked scores.  Parameters come in under the program's names, so the gradient
goes out under them too, and a tied weight's gradient is what jax sums over
the trips that read it.

jax.checkpoint around a layer and around a trip's head only bounds what the
backward pass keeps (a layer's scores are 16 x 2048 x 2048 fp32 a sequence,
a trip's logits 2048 x 49152): it changes no number."""

import jax
import jax.numpy as jnp


def _mm(x, w):
    return jnp.matmul(x, w)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rotary(x, theta):
    """x [B, H, S, D]: pair i is (x[i], x[i + D/2]), turned by the angle
    position * theta^(-2i/D) (the half-split layout of the public
    implementations of this family)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                         / x.shape[-1])
    angle = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, name, cfg):
    B, S, _ = x.shape
    H, dh = cfg["num_attention_heads"], cfg["head_dim"]

    def heads(t):
        return t.reshape(B, S, H, dh).transpose(0, 2, 1, 3)

    q = _rotary(heads(_mm(x, p[name + "_q_w"])), cfg["rope_theta"])
    k = _rotary(heads(_mm(x, p[name + "_k_w"])), cfg["rope_theta"])
    v = heads(_mm(x, p[name + "_v_w"]))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * dh ** -0.5
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    weights = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", weights, v)
    return _mm(ctx.transpose(0, 2, 1, 3).reshape(B, S, H * dh),
               p[name + "_o_w"])


def _mlp(p, x, name):
    gate = jax.nn.silu(_mm(x, p[name + "_gate_w"]))
    return _mm(gate * _mm(x, p[name + "_up_w"]), p[name + "_down_w"])


def _layer(p, h, i, cfg):
    eps, n = cfg["rms_norm_eps"], f"l{i}"
    a = h + _rms_norm(
        _attention(p, _rms_norm(h, p[n + "_n1_scale"], eps), n + "_attn",
                   cfg), p[n + "_n2_scale"], eps)
    return a + _rms_norm(
        _mlp(p, _rms_norm(a, p[n + "_n3_scale"], eps), n + "_mlp"),
        p[n + "_n4_scale"], eps)


def _head(p, h, labels, gated):
    """(cross entropy [B, S] of this trip's logits, its gate [B, S])."""
    logp = jax.nn.log_softmax(_mm(h, p["head_w"]), axis=-1)
    ce = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    lam = jax.nn.sigmoid(jnp.sum(h * p["gate_w"], axis=-1) + p["gate_b"][0]) \
        if gated else jnp.zeros_like(ce)
    return ce, lam


def _token_losses(p, tokens, labels, cfg):
    """[B, S]: sum_t p_t CE_t - beta H(p) of every token."""
    R, L = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    gated = cfg["exit_gate"] and R > 1
    h = jnp.take(p["embed"], tokens, axis=0)
    ces, lams = [], []
    for _ in range(R):
        for i in range(L):
            h = jax.checkpoint(lambda p, h, i=i: _layer(p, h, i, cfg))(p, h)
        h = _rms_norm(h, p["final_scale"], cfg["rms_norm_eps"])
        ce, lam = jax.checkpoint(
            lambda p, h: _head(p, h, labels, gated))(p, h)
        ces.append(ce)
        lams.append(lam)
    if not gated:
        return ces[-1]
    left = jnp.ones_like(ces[0])
    probs = []
    for lam in lams[:-1]:
        probs.append(lam * left)
        left = left * (1.0 - lam)
    probs.append(left)
    expected = sum(q * ce for q, ce in zip(probs, ces))
    neg_entropy = sum(jnp.where(q > 0, q * jnp.log(jnp.maximum(q, 1e-30)),
                                0.0) for q in probs)
    return expected + cfg["entropy_beta"] * neg_entropy


def loss_and_grad(params, batch, cfg, feed_names, trainable, micro):
    """(loss, {name: gradient}) of the mean over the batch's tokens.  The
    loss is a sum over sequences, so the batch is taken in `micro` strided
    parts one after the other (rows i, i + micro, ...), and the parts'
    gradients are summed.  (A scan and not a Python loop: compiled for the
    v5e the loop keeps 8.8 GB of temporaries at four layers where the scan
    keeps 4.4, and the reference has to fit beside the program's state.)"""
    params = {k: v.astype(jnp.float32) for k, v in params.items()}
    fixed = {k: v for k, v in params.items() if k not in trainable}
    free = {k: v for k, v in params.items() if k in trainable}
    parts = {n: jnp.swapaxes(
        v.reshape((v.shape[0] // micro, micro) + v.shape[1:]), 0, 1)
        for n, v in batch.items()}
    tokens, labels = (parts[n] for n in feed_names)
    count = float(tokens.size)

    def part(free, tok, lab):
        # the part's share of the mean, so that the sum over the parts is
        # the gradient itself and no scaled copy of it is made at the end
        return jnp.sum(_token_losses({**fixed, **free}, tok, lab, cfg)) / count

    def body(carry, one):
        cost, g = jax.value_and_grad(part)(free, *one)
        return (carry[0] + cost,
                jax.tree_util.tree_map(jnp.add, carry[1], g)), None

    with jax.default_matmul_precision("highest"):
        zero = jax.tree_util.tree_map(jnp.zeros_like, free)
        (loss, grad), _ = jax.lax.scan(
            body, (jnp.float32(0), zero), (tokens, labels))
    return loss, grad
