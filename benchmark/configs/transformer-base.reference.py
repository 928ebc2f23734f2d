"""Plain reference of transformer-base: forward, loss and gradient in fp32
jnp, written from Vaswani et al. 2017 and the layer names of
paddle_tpu/models/transformer.py, and from nothing else of the program (no
op, no kernel, no AMP tier, no flag).  Parameters come in under the program's
names, so the gradient goes out under them too.

The program's dropout keeps a unit at its scale with probability 1 - p and
zeroes it otherwise (no 1/(1 - p)); its masks cannot be drawn here, so the
reference takes every dropout at its mean: a factor 1 - p.  The configuration
file's `reference` group holds the tolerances that this costs."""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def _linear(p, x, name, bias=True):
    out = _mm(x, p[name + "_w"])
    return out + p[name + "_b"] if bias else out


def _layer_norm(p, x, name, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * p[name + "_ln_scale"]
            + p[name + "_ln_bias"])


def _attention(q, k, v, k_len, causal, n_head):
    """softmax(q k^T / sqrt(dh)) v over the keys before k_len (and, where
    causal, not after the query)."""
    B, Sq, D = q.shape
    Sk = k.shape[1]
    dh = D // n_head

    def heads(x):
        return x.reshape(B, -1, n_head, dh).transpose(0, 2, 1, 3)

    q, k, v = heads(q), heads(k), heads(v)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HIGHEST)
    scores = scores * dh ** -0.5
    ok = (jnp.arange(Sk)[None, :] < k_len[:, None])[:, None, None, :]
    if causal:
        ok = ok & (jnp.arange(Sk)[None, :] <= jnp.arange(Sq)[:, None])
    weights = jax.nn.softmax(jnp.where(ok, scores, -1e30), axis=-1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", weights, v, precision=HIGHEST)
    return ctx.transpose(0, 2, 1, 3).reshape(B, Sq, D)


def _sums(p, batch, cfg, feed_names):
    """(sum of the label-smoothed cross entropy over the non-pad label
    positions, their count)."""
    src, trg, lbl = (batch[n] for n in feed_names)
    d, H = cfg["d_model"], cfg["n_head"]
    keep = 1.0 - cfg["dropout"]
    eps = cfg["label_smooth_eps"]

    def embed(ids, name):
        e = jnp.take(p[name + "_emb"], ids, axis=0)
        e = jnp.where((ids != 0)[..., None], e, 0.0) * d ** 0.5
        return (e + p[name + "_pos_enc"][None]) * keep

    def self_attn(x, name, k_len, causal):
        q, k, v = jnp.split(_linear(p, x, name + "_qkv"), 3, axis=-1)
        ctx = _attention(q, k, v, k_len, causal, H) * keep
        return _linear(p, ctx, name + "_o")

    def cross_attn(x, mem, name, k_len):
        q = _linear(p, x, name + "_q")
        k, v = jnp.split(_linear(p, mem, name + "_kv"), 2, axis=-1)
        ctx = _attention(q, k, v, k_len, False, H) * keep
        return _linear(p, ctx, name + "_o")

    def ffn(x, name):
        hidden = jax.nn.relu(_linear(p, x, name + "_in")) * keep
        return _linear(p, hidden, name + "_out")

    def add_norm(x, out, name):
        return _layer_norm(p, x + out * keep, name)

    src_len = jnp.sum(src != 0, axis=1)
    trg_len = jnp.sum(trg != 0, axis=1)
    enc = embed(src, "src")
    for i in range(cfg["n_layer"]):
        n = f"enc_l{i}"
        enc = add_norm(enc, self_attn(enc, n + "_attn", src_len, False),
                       n + "_attn")
        enc = add_norm(enc, ffn(enc, n + "_ffn"), n + "_ffn")
    dec = embed(trg, "trg")
    for i in range(cfg["n_layer"]):
        n = f"dec_l{i}"
        dec = add_norm(dec, self_attn(dec, n + "_self", trg_len, True),
                       n + "_self")
        dec = add_norm(dec, cross_attn(dec, enc, n + "_cross", src_len),
                       n + "_cross")
        dec = add_norm(dec, ffn(dec, n + "_ffn"), n + "_ffn")
    logp = jax.nn.log_softmax(_linear(p, dec, "project", bias=False), axis=-1)
    picked = jnp.take_along_axis(logp, lbl[..., None], axis=-1)[..., 0]
    cost = -(1.0 - eps) * picked - eps * jnp.mean(logp, axis=-1)
    mask = (lbl != 0).astype(jnp.float32)
    return jnp.sum(cost * mask), jnp.sum(mask)


def loss_and_grad(params, batch, cfg, feed_names, trainable, micro):
    """(loss, {name: gradient}) of the mean cost over the batch's label
    positions.  The cost is a sum over sentence pairs, so the batch is taken
    in `micro` strided parts one after the other (rows i, i + micro, ...: a
    batch sharded over chips in blocks stays sharded inside each part)."""
    params = {k: v.astype(jnp.float32) for k, v in params.items()}
    fixed = {k: v for k, v in params.items() if k not in trainable}
    free = {k: v for k, v in params.items() if k in trainable}
    parts = {n: jnp.swapaxes(
        v.reshape((v.shape[0] // micro, micro) + v.shape[1:]), 0, 1)
        for n, v in batch.items()}

    def part(free, one):
        cost, count = _sums({**fixed, **free}, one, cfg, feed_names)
        return cost, count

    def body(carry, one):
        (cost, count), g = jax.value_and_grad(part, has_aux=True)(free, one)
        c0, n0, g0 = carry
        return (c0 + cost, n0 + count,
                jax.tree_util.tree_map(jnp.add, g0, g)), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, free)
    (cost, count, grad), _ = jax.lax.scan(
        body, (jnp.float32(0), jnp.float32(0), zero), parts)
    return cost / count, jax.tree_util.tree_map(lambda g: g / count, grad)
