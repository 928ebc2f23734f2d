"""Plain reference of evabyte-6.5b: forward, the eight heads' loss and the
gradient in fp32 jax.numpy under jax.default_matmul_precision("highest"),
written from the equations in benchmark/configs/evabyte-6.5b.json
(`equations`, `assumed`) and the parameter names of
paddle_tpu/models/eva_decoder.py, and from nothing else of the program: no
op, no kernel, no recurrence construct, no AMP tier, no folding of windows.

EVA as written: every chunk of `chunk_size` rotated keys is pooled into one
summary key and one summary value by two softmaxes over the chunk's
positions (`_pool`); then, a block of `query_block` queries at a time, the
scores of the block against ALL S keys and ALL S / chunk_size summaries are
dense arrays, each under its mask (a key: same window and not after the
query; a summary: its chunk lies in a window before the query's), and ONE
softmax runs over the two side by side (`_attend`).  The share of the heads
is the program's: heads `head_offset` .. + `heads_held` of the group, whose
terms of the output map are added and no others.

jax.checkpoint around a layer, a block of queries and the head only bounds
what the backward pass keeps (a block's scores are heads x query_block x (S
+ S / chunk_size) fp32); the blocks are a lax.scan so that the executable
holds one block's code and not S / query_block copies: neither changes a
number.  The small functions (_mm, _rotary, _softmax_scale, _pool_weights,
_summaries_seen, _attend, _labels) are what
tools/evabyte_reference_probe.py replaces, one at a time, to make the wrong
rules the tolerances have to refuse."""

import jax
import jax.numpy as jnp

IGNORED = -100     # a label where a head has no target


def _mm(x, w):
    return jnp.matmul(x, w)


def _norm(x, g, eps):
    """RMS norm times (1 + g): norm_add_unit_offset."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + g)


def _rotary(x, theta):
    """x [H, S, D]: pair i is (x[i], x[i + D/2]), turned by the angle
    position * theta^(-2i/D), absolute positions."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                         / x.shape[-1])
    angle = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _softmax_scale(head_dim):
    return head_dim ** -0.5


def _pool_weights(scores):
    """A chunk's weights from its positions' scores [..., chunk]."""
    return jax.nn.softmax(scores, axis=-1)


def _pool(k, v, mu, phi, chunk):
    """(k^, v^) [H, S / chunk, D]: k^_j = sum_s softmax_s(mu . k_s) k_s,
    v^_j = sum_s softmax_s(phi . k_s) v_s over chunk j's positions."""
    H, S, D = k.shape
    kc = k.reshape(H, S // chunk, chunk, D)
    vc = v.reshape(H, S // chunk, chunk, D)
    a = _pool_weights(jnp.einsum("hncd,hd->hnc", kc, mu))
    b = _pool_weights(jnp.einsum("hncd,hd->hnc", kc, phi))
    return (jnp.einsum("hnc,hncd->hnd", a, kc),
            jnp.einsum("hnc,hncd->hnd", b, vc))


def _summaries_seen(t, window, chunk):
    """How many summaries (the first so many) query t sees: every chunk of
    every window before its own, none of its own."""
    return (t // window) * (window // chunk)


def _attend(own, far, v, v_hat):
    """One softmax over a block's masked scores against the keys (`own`
    [H, q, S]) and against the summaries (`far` [H, q, n]), side by side:
    [H, q, D]."""
    p = jax.nn.softmax(jnp.concatenate([own, far], axis=-1), axis=-1)
    S = own.shape[-1]
    return (jnp.einsum("hqs,hsd->hqd", p[..., :S], v)
            + jnp.einsum("hqn,hnd->hqd", p[..., S:], v_hat))


def _eva(p, u, name, cfg):
    """EVA(u) of one sequence u [S, d] for the heads held here."""
    S = u.shape[0]
    H, D = cfg["heads_held"], cfg["head_dim"]
    w, c = min(cfg["window_size"], S), cfg["chunk_size"]
    block = min(int(cfg["reference"].get("query_block", S)), S)

    def heads(t):
        return t.reshape(S, H, D).transpose(1, 0, 2)

    q = _rotary(heads(_mm(u, p[name + "_q_w"])), cfg["rope_theta"])
    k = _rotary(heads(_mm(u, p[name + "_k_w"])), cfg["rope_theta"])
    v = heads(_mm(u, p[name + "_v_w"]))
    # whole chunks only: a tail shorter than a chunk lies in the last
    # window, whose summaries no query sees
    n = S // c
    k_hat, v_hat = _pool(k[:, :n * c], v[:, :n * c], p[name + "_mu"],
                         p[name + "_phi"], c)
    s = jnp.arange(S)
    j = jnp.arange(n)

    def one_block(q_blk, t):
        own = jnp.einsum("hqd,hsd->hqs", q_blk, k) * _softmax_scale(D)
        far = jnp.einsum("hqd,hnd->hqn", q_blk, k_hat) * _softmax_scale(D)
        sees_key = (s[None, :] // w == t[:, None] // w) & \
            (s[None, :] <= t[:, None])
        sees_summary = j[None, :] < _summaries_seen(t, w, c)[:, None]
        return _attend(jnp.where(sees_key, own, -1e30),
                       jnp.where(sees_summary, far, -1e30), v, v_hat)

    pad = -S % block
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    blocks = qp.reshape(H, -1, block, D).transpose(1, 0, 2, 3)
    # a padded query stands at the last position: its row is cut below
    times = jnp.minimum(jnp.arange(S + pad), S - 1).reshape(-1, block)
    _, ctx = jax.lax.scan(
        lambda carry, one: (carry, jax.checkpoint(one_block)(*one)),
        None, (blocks, times))
    ctx = ctx.transpose(1, 0, 2, 3).reshape(H, S + pad, D)[:, :S]
    return _mm(ctx.transpose(1, 0, 2).reshape(S, H * D), p[name + "_o_w"])


def _mlp(p, x, name):
    gate = jax.nn.silu(_mm(x, p[name + "_gate_w"]))
    return _mm(gate * _mm(x, p[name + "_up_w"]), p[name + "_down_w"])


def _layer(p, h, i, cfg):
    eps, n = cfg["rms_norm_eps"], f"l{i}"
    a = h + _eva(p, _norm(h, p[n + "_n1_scale"], eps), n + "_attn", cfg)
    return a + _mlp(p, _norm(a, p[n + "_n2_scale"], eps), n + "_mlp")


def _logits(p, tokens, cfg):
    """[S, P, V] of one sequence of tokens [S]."""
    h = jnp.take(p["embed"], tokens, axis=0)
    for i in range(cfg["num_hidden_layers"]):
        h = jax.checkpoint(lambda p, h, i=i: _layer(p, h, i, cfg))(p, h)
    z = _mm(_norm(h, p["final_scale"], cfg["rms_norm_eps"]), p["head_w"])
    return z.reshape(z.shape[0], cfg["num_pred_heads"], cfg["vocab_size"])


def _labels(tokens, labels):
    """Head i's target at position t, [S, P]: the batch's, which are
    tokens[t + 1 + i], IGNORED past the end."""
    del tokens
    return labels


def _sequence_loss(p, tokens, labels, cfg):
    """The SUM of one sequence's cross entropies over every (t, i) that has
    a target."""
    labels = _labels(tokens, labels)
    there = labels != IGNORED

    def head(p, tokens):
        logp = jax.nn.log_softmax(_logits(p, tokens, cfg), axis=-1)
        ce = -jnp.take_along_axis(
            logp, jnp.where(there, labels, 0)[..., None], axis=-1)[..., 0]
        return jnp.sum(jnp.where(there, ce, 0.0))

    return head(p, tokens)


def loss_and_grad(params, batch, cfg, feed_names, trainable, micro):
    """(loss, {name: gradient}) of the mean over every target of the batch,
    all heads weighing alike.  The loss is a sum over sequences, so they
    are taken one after the other in a scan and their gradients summed
    (`micro` is the harness's; a part is one sequence whatever it says)."""
    del micro
    params = {k: v.astype(jnp.float32) for k, v in params.items()}
    fixed = {k: v for k, v in params.items() if k not in trainable}
    free = {k: v for k, v in params.items() if k in trainable}
    tokens, labels = (batch[n] for n in feed_names)
    count = jnp.sum(labels != IGNORED).astype(jnp.float32)

    def part(free, tok, lab):
        return _sequence_loss({**fixed, **free}, tok, lab, cfg) / count

    def body(carry, one):
        cost, g = jax.value_and_grad(part)(free, *one)
        return (carry[0] + cost,
                jax.tree_util.tree_map(jnp.add, carry[1], g)), None

    with jax.default_matmul_precision("highest"):
        zero = jax.tree_util.tree_map(jnp.zeros_like, free)
        (loss, grad), _ = jax.lax.scan(
            body, (jnp.float32(0), zero), (tokens, labels))
    return loss, grad
