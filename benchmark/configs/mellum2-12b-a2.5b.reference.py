"""Plain reference of mellum2-12b-a2.5b: forward, loss and gradient in fp32
jax.numpy under jax.default_matmul_precision("highest"), written from the
equations in benchmark/configs/mellum2-12b-a2.5b.json (`equations`,
`assumed`, `deployment`) and the parameter names of
paddle_tpu/models/windowed_decoder.py, and from nothing else of the
program: no op, no kernel, no block of keys skipped, no chunk, no row
buffer, no grouped matmul, no AMP tier.  K and V are repeated to the 32
query heads; a layer's mask is written out, 0 <= t - s < sliding_window or
s <= t by the layer's kind; YaRN's frequencies are computed from the
formula in `equations`; attention is a softmax over masked scores; every
held expert runs over every token, weighted by a gate that is 0 where the
token did not choose it.

Departures from the published description, each also in the file's
`assumed`: no q/k norm, no router auxiliary loss, no multi-token-prediction
head, and under `train_router` false no gradient through the gates (to the
router's weight or to its input); the chip's share (`deployment`): experts `expert_offset` .. +
`num_experts` of the router's `router_experts`, the gates normalised over
all 8 chosen, held or not, what the absent experts would add left out, and
the vocabulary's held slice.  With `num_experts` = `router_experts` the
same code is the uncut layer (tier-1 adds the shares up against it).

Memory: everything past the projections is tokenwise but the attention's
keys, so a layer runs as a lax.scan over blocks of `reference.query_block`
queries with a checkpointed body (the block's [32, block, S] score planes
are the largest thing alive), the head likewise, and jax.checkpoint around
a layer bounds what the sequence keeps.  None of it changes a number."""

import math

import jax
import jax.numpy as jnp

KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


def _mm(x, w):
    return jnp.matmul(x, w)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _frequencies(cfg, kind):
    """(f [D/2], what cos and sin are multiplied by) of a layer of `kind`:
    f_i = theta^(-2i/D); on a full layer under YaRN, with low and high the
    pairs that turn beta_fast and beta_slow times over the original length,
    r_i = clip((i - low) / (high - low), 0, 1) and f'_i = f_i (1 - r_i) +
    (f_i / factor) r_i, cos and sin times attention_factor."""
    rope = cfg["rope_parameters"][kind + "_attention"]
    dim, theta = cfg["head_dim"], float(rope["rope_theta"])
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / dim)
    if rope["rope_type"] == "default":
        return f, 1.0
    original = rope["original_max_position_embeddings"]

    def pair(turns):
        return dim * math.log(original / (turns * 2.0 * math.pi)) \
            / (2.0 * math.log(theta))

    low = max(math.floor(pair(rope["beta_fast"])), 0)
    high = min(math.ceil(pair(rope["beta_slow"])), dim - 1)
    r = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return f * (1.0 - r) + f / rope["factor"] * r, rope["attention_factor"]


def _rotary(x, cfg, kind):
    """x [..., S, D]: pair i is (x[i], x[i + D/2]), turned by the angle
    p * f_i, p the token's index."""
    half = x.shape[-1] // 2
    f, factor = _frequencies(cfg, kind)
    angle = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * f
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _heads(t, n):
    """[S, n * width] -> [n, S, width]."""
    return t.reshape(t.shape[0], n, -1).transpose(1, 0, 2)


def _to_query_heads(x, share):
    """Key/value heads [G, S, D] repeated so that query head j reads head
    j // share."""
    return jnp.repeat(x, share, axis=0)


def _projections(p, u, name, cfg, kind):
    """q [32, S, 128], k and v repeated to [32, S, 128]."""
    H, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = _rotary(_heads(_mm(u, p[name + "_q_w"]), H), cfg, kind)
    k = _rotary(_heads(_mm(u, p[name + "_k_w"]), G), cfg, kind)
    v = _heads(_mm(u, p[name + "_v_w"]), G)
    return q, _to_query_heads(k, H // G), _to_query_heads(v, H // G)


def _sees(first, T, S, cfg, kind):
    """mask [T, S]: query first + i sees key s."""
    t = first + jnp.arange(T)[:, None]
    s = jnp.arange(S)[None, :]
    if kind == "sliding":
        return (t - s >= 0) & (t - s < cfg["sliding_window"])
    return s <= t


def _attend(q, k, v, first, cfg, kind):
    """contexts [T, H * D] of a block of queries q [H, T, D] over the
    sequence's k, v."""
    scores = jnp.einsum("htd,hsd->hts", q, k) * cfg["head_dim"] ** -0.5
    mask = _sees(first, q.shape[1], k.shape[1], cfg, kind)
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    ctx = jnp.einsum("hts,hsd->htd", probs, v)
    return ctx.transpose(1, 0, 2).reshape(q.shape[1], -1)


def _scores(logits):
    return jax.nn.softmax(logits, axis=-1)


def _gates(p, x, name, cfg):
    """g [T, router_experts]: the softmax of the router's logits over all
    the experts, kept for the top 8, divided by their sum; 0 elsewhere."""
    s = _scores(_mm(x, p[name + "_router_w"]))
    kth = jnp.sort(s, axis=-1)[..., -cfg["num_experts_per_tok"]]
    g = jnp.where(s >= kth[..., None], s, 0.0)
    if cfg["norm_topk_prob"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    # a share whose router takes no gradient: the gates are constants
    return g if cfg.get("train_router", True) else jax.lax.stop_gradient(g)


def _expert_block(p, x, name, cfg):
    """Every held expert over every token of x [T, d], as one product
    batched over the experts' axis, weighted by its gate."""
    held = jnp.arange(cfg["num_experts"])
    g = _gates(p, x, name, cfg)[:, cfg["expert_offset"] + held]      # [T, E]
    hidden = (jax.nn.silu(_mm(x, p[name + "_experts_gate_w"]))
              * _mm(x, p[name + "_experts_up_w"]))                # [E, T, f]
    return jnp.sum(_mm(hidden, p[name + "_experts_down_w"])
                   * g.T[..., None], axis=0)


def _blocks(x, axis, block):
    """x with `axis` cut into blocks of `block`, the block index first."""
    n = x.shape[axis] // block
    return jnp.moveaxis(x.reshape(
        x.shape[:axis] + (n, block) + x.shape[axis + 1:]), axis, 0)


def _layer(p, h, i, cfg):
    """h' [S, d] of layer i."""
    eps, n, S = cfg["rms_norm_eps"], f"l{i}", h.shape[0]
    kind = KINDS[cfg["layer_types"][i]]
    block = min(cfg["reference"]["query_block"], S)
    q, k, v = _projections(p, _rms_norm(h, p[n + "_n1_scale"], eps),
                           n + "_attn", cfg, kind)

    def rows(_, xs):
        first, h_b, q_b = xs
        a = h_b + _mm(_attend(q_b, k, v, first, cfg, kind),
                      p[n + "_attn_o_w"])
        return None, a + _expert_block(
            p, _rms_norm(a, p[n + "_n2_scale"], eps), n, cfg)

    out = jax.lax.scan(jax.checkpoint(rows), None, (
        jnp.arange(0, S, block), _blocks(h, 0, block),
        _blocks(q, 1, block)))[1]
    return out.reshape(S, -1)


def _head(p, h, labels, cfg):
    """sum over the tokens of the cross entropy, in blocks of tokens."""
    block = min(cfg["reference"]["query_block"], h.shape[0])

    def rows(total, xs):
        h_b, y_b = xs
        h_b = _rms_norm(h_b, p["final_scale"], cfg["rms_norm_eps"])
        logp = jax.nn.log_softmax(_mm(h_b, p["head_w"]), axis=-1)
        return total - jnp.sum(jnp.take_along_axis(
            logp, y_b[:, None], axis=-1)), None

    return jax.lax.scan(jax.checkpoint(rows), jnp.float32(0), (
        _blocks(h, 0, block), _blocks(labels, 0, block)))[0]


def _sequence_loss(p, tokens, labels, cfg):
    """sum over one sequence's tokens of the cross entropy."""
    h = jnp.take(p["embed"], tokens, axis=0)
    for i in range(cfg["num_hidden_layers"]):
        h = jax.checkpoint(lambda p, h, i=i: _layer(p, h, i, cfg))(p, h)
    return jax.checkpoint(lambda p, h: _head(p, h, labels, cfg))(p, h)


def loss_and_grad(params, batch, cfg, feed_names, trainable, micro):
    """(loss, {name: gradient}) of the mean over the batch's tokens of the
    cross entropy, the batch's sequences one at a time by a scan that is
    differentiated as a whole (`micro` is the harness's count of parts; a
    part here is always one sequence)."""
    del micro
    params = {k: v.astype(jnp.float32) for k, v in params.items()}
    fixed = {k: v for k, v in params.items() if k not in trainable}
    free = {k: v for k, v in params.items() if k in trainable}
    tokens, labels = (batch[n] for n in feed_names)
    count = float(tokens.size)

    def total(free):
        def part(cost, one):
            return cost + _sequence_loss({**fixed, **free}, *one,
                                         cfg) / count, None

        return jax.lax.scan(jax.checkpoint(part), jnp.float32(0),
                            (tokens, labels))[0]

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(total)(free)
