"""Plain reference of zaya1-8b: forward, loss and gradient in fp32 jax.numpy
under jax.default_matmul_precision("highest"), written from the equations
in benchmark/configs/zaya1-8b.json (`equations`, `assumed`, `deployment`)
and the parameter names of paddle_tpu/models/compressed_decoder.py, and
from nothing else of the program: no op, no kernel, no convolution
primitive, no row buffer, no grouped matmul, no AMP tier.  The two
convolutions are sums over their taps of shifted products; K and V are
repeated to the 8 query heads; the causal mask is written out; the norm to
length sqrt(D), the key temperature and the rotary over the first half of a
head are written out; the router's state goes from layer to layer through
the Python loop over the layers; every held expert runs over every token,
times a gate that is 0 where the token's argmax is another expert; the one
table is read twice, by the lookup and, transposed, by the head.

Departures from the published descriptions (arXiv:2510.04476 section 3,
arXiv:2511.17127 section 2), each also in the file's `assumed`: no
mixture-of-depths skip class in the router and no learned scale on the
residual stream or its writers (described_as names both, the config has
neither a key nor a size for them); no balancing bias in the router's choice
(selection-only state, zero at the start, its update the trainer's); no
auxiliary loss; under `train_router` false no gradient through the gates.
The chip's share (`deployment`): experts `expert_offset` .. + `num_experts`
of the router's `router_experts`, what the absent experts would add left
out, and the table's held rows.  With `num_experts` = `router_experts` the
same code is the uncut layer (tier-1 adds the shares up against it).

Memory: the convolutions, the mean and the norm run over the whole sequence
(they are [S, 1280]); everything after them is tokenwise but the
attention's keys, so the rest of a layer runs as a lax.scan over blocks of
`reference.query_block` queries with a checkpointed body (a block's [8,
block, S] score planes are the largest thing alive), the head likewise, and
jax.checkpoint around a layer bounds what the sequence keeps.  None of it
changes a number."""

import math

import jax
import jax.numpy as jnp


def _mm(x, w):
    return jnp.matmul(x, w)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _shift(x):
    """x [S, n]: row t is x's row t - 1, zeros before the first."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], axis=0)


def _conv_a(z, w, b):
    """Convolution A over z [rows, C], unpadded: one filter a channel, w
    [k, C]; out[t] = sum_j w[j] * z[t + j] + b, so the LAST tap multiplies
    the newest row."""
    k = w.shape[0]
    rows = z.shape[0] - (k - 1)
    return sum(w[j] * z[j:j + rows] for j in range(k)) + b


def _conv_b(z, w, b):
    """Convolution B over z [rows, C], unpadded: across the channels of one
    head, w [k, heads, D, D] (tap, head, in, out)."""
    k, n, D, _ = w.shape
    rows = z.shape[0] - (k - 1)
    zh = z.reshape(z.shape[0], n, D).transpose(1, 0, 2)        # [n, rows, D]
    out = sum(_mm(zh[:, j:j + rows], w[j]) for j in range(k))
    return out.transpose(1, 0, 2).reshape(rows, n * D) + b


def _mean_inputs(q_t, k_t, q_conv, k_conv):
    """What the q-k mean is taken of: the values BEFORE the convolutions."""
    del q_conv, k_conv
    return q_t, k_t


def _qk_mean(q, k, share):
    """(m_q [S, H, D], m_k [S, G, D]) of q [S, H, D] and k [S, G, D]:
    m_q[j] = (q[j] + k[j // share]) / 2, m_k[g] the mean of m_q over g's
    query heads."""
    m_q = (q + jnp.repeat(k, share, axis=1)) / 2
    S, H, D = q.shape
    return m_q, jnp.mean(m_q.reshape(S, H // share, share, D), axis=2)


def _unit(x):
    """Each head of x [..., D] at length sqrt(D)."""
    D = x.shape[-1]
    return math.sqrt(D) * x / jnp.sqrt(jnp.sum(x * x, axis=-1,
                                               keepdims=True))


def _temperature(k, tau):
    """k [S, G, D] times the key head's temperature tau [G]."""
    return k * tau[None, :, None]


def _rotary(x, cfg):
    """x [n, S, D]: the first rotary_dim = partial_rotary_factor * D
    features turn, pair i of them (x[i], x[i + rotary_dim / 2]) by the
    angle p * theta^(-2i / rotary_dim), p the token's index; the others
    pass."""
    rope = cfg["rope_parameters"]["hybrid"]
    rd = int(rope["partial_rotary_factor"] * x.shape[-1])
    half = rd // 2
    f = float(rope["rope_theta"]) ** (
        -2.0 * jnp.arange(half, dtype=jnp.float32) / rd)
    angle = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * f
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:rd]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rd:]], axis=-1)


def _value(v_t, G):
    """v [S, G, D] of v~ [S, G D]: the first half of the channels the
    token's own, the second half the token before's (at G = 2: head 0 and
    head 1)."""
    half = v_t.shape[-1] // 2
    return jnp.concatenate([v_t[:, :half], _shift(v_t[:, half:])],
                           axis=-1).reshape(v_t.shape[0], G, -1)


def _to_query_heads(x, share):
    """Key/value heads [G, S, D] repeated so that query head j reads head
    j // share."""
    return jnp.repeat(x, share, axis=0)


def _latent(p, u, name, cfg):
    """q [H, S, D], k and v repeated to [H, S, D], of the layer's normed
    input u [S, d]: CCA's steps 1-6."""
    H, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    S, share = u.shape[0], H // G
    q_t, k_t, v_t = (_mm(u, p[f"{name}_{x}_w"]) for x in "qkv")
    lq = q_t.shape[-1]
    a_w, b_w = p[name + "_conv_a_w"], p[name + "_conv_b_w"]
    assert a_w.shape[0] == cfg["cca_time0"] and b_w.shape[0] == cfg["cca_time1"]
    z = jnp.concatenate([q_t, k_t], axis=-1)
    # padded ONCE; what B sees before position 0 is A's output on zeros
    pad = (a_w.shape[0] - 1) + (b_w.shape[0] - 1)
    z = jnp.concatenate([jnp.zeros((pad, z.shape[1]), z.dtype), z], axis=0)
    z = _conv_b(_conv_a(z, a_w, p[name + "_conv_a_b"]), b_w,
                p[name + "_conv_b_b"])
    q_c, k_c = z[:, :lq].reshape(S, H, -1), z[:, lq:].reshape(S, G, -1)
    m_q, m_k = _qk_mean(*_mean_inputs(
        q_t.reshape(S, H, -1), k_t.reshape(S, G, -1), q_c, k_c), share)
    q = _unit(q_c + m_q)
    k = _temperature(_unit(k_c + m_k), p[name + "_tau"])
    q, k = (_rotary(t.transpose(1, 0, 2), cfg) for t in (q, k))
    v = _value(v_t, G).transpose(1, 0, 2)
    return q, _to_query_heads(k, share), _to_query_heads(v, share)


def _attend(q, k, v, first, cfg):
    """contexts [T, H * D] of a block of queries q [H, T, D], the first of
    them at position `first`, over the sequence's k, v [H, S, D]."""
    scores = jnp.einsum("htd,hsd->hts", q, k) * cfg["head_dim"] ** -0.5
    t = first + jnp.arange(q.shape[1])[:, None]
    mask = jnp.arange(k.shape[1])[None, :] <= t
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    ctx = jnp.einsum("hts,hsd->htd", probs, v)
    return ctx.transpose(1, 0, 2).reshape(q.shape[1], -1)


def _act(x):
    """gelu, the erf form."""
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _carry(s, gamma, r_prev):
    return s + gamma * r_prev


def _gate(probs, chosen):
    """The gate of the chosen expert is its probability itself."""
    return probs * chosen


def _router(p, x, r_prev, name, cfg):
    """(g [T, router_experts]: p_e at the token's argmax and 0 elsewhere,
    the router's state r [T, R])."""
    n = name + "_router_"
    r = _carry(_mm(x, p[n + "down_w"]) + p[n + "down_b"], p[n + "gamma"],
               r_prev)
    h = _rms_norm(r, p[n + "norm_scale"], cfg["rms_norm_eps"])
    h = _act(_mm(h, p[n + "fc1_w"]) + p[n + "fc1_b"])
    h = _act(_mm(h, p[n + "fc2_w"]) + p[n + "fc2_b"])
    probs = jax.nn.softmax(_mm(h, p[name + "_router_w"]), axis=-1)
    assert cfg["num_experts_per_tok"] == 1
    chosen = jax.nn.one_hot(jnp.argmax(probs, axis=-1), probs.shape[-1],
                            dtype=probs.dtype)
    g = _gate(probs, chosen)
    # a share whose router takes no gradient: the gates are constants
    return (g if cfg.get("train_router", True)
            else jax.lax.stop_gradient(g)), r


def _expert_block(p, x, r_prev, name, cfg):
    """(the held experts' part [T, d], r [T, R]): every held expert over
    every token of x [T, d], as one product batched over the experts'
    axis, weighted by its gate."""
    held = jnp.arange(cfg["num_experts"])
    g, r = _router(p, x, r_prev, name, cfg)
    g = g[:, cfg["expert_offset"] + held]                            # [T, E]
    hidden = (jax.nn.silu(_mm(x, p[name + "_experts_gate_w"]))
              * _mm(x, p[name + "_experts_up_w"]))                # [E, T, f]
    return jnp.sum(_mm(hidden, p[name + "_experts_down_w"])
                   * g.T[..., None], axis=0), r


def _blocks(x, axis, block):
    """x with `axis` cut into blocks of `block`, the block index first."""
    n = x.shape[axis] // block
    return jnp.moveaxis(x.reshape(
        x.shape[:axis] + (n, block) + x.shape[axis + 1:]), axis, 0)


def _layer(p, h, r_prev, i, cfg):
    """(h' [S, d], r [S, R]) of layer i."""
    eps, n, S = cfg["rms_norm_eps"], f"l{i}", h.shape[0]
    block = min(cfg["reference"]["query_block"], S)
    q, k, v = _latent(p, _rms_norm(h, p[n + "_n1_scale"], eps),
                      n + "_attn", cfg)

    def rows(_, xs):
        first, h_b, r_b, q_b = xs
        a = h_b + _mm(_attend(q_b, k, v, first, cfg), p[n + "_attn_o_w"])
        m, r = _expert_block(p, _rms_norm(a, p[n + "_n2_scale"], eps), r_b,
                             n, cfg)
        return None, (a + m, r)

    out, r = jax.lax.scan(jax.checkpoint(rows), None, (
        jnp.arange(0, S, block), _blocks(h, 0, block),
        _blocks(r_prev, 0, block), _blocks(q, 1, block)))[1]
    return out.reshape(S, -1), r.reshape(S, -1)


def _head_table(p):
    """The head's matrix: the embedding's table itself."""
    return p["embed"]


def _head(p, h, labels, cfg):
    """sum over the tokens of the cross entropy, in blocks of tokens."""
    block = min(cfg["reference"]["query_block"], h.shape[0])
    table = _head_table(p)

    def rows(total, xs):
        h_b, y_b = xs
        h_b = _rms_norm(h_b, p["final_scale"], cfg["rms_norm_eps"])
        logp = jax.nn.log_softmax(_mm(h_b, table.T), axis=-1)
        return total - jnp.sum(jnp.take_along_axis(
            logp, y_b[:, None], axis=-1)), None

    return jax.lax.scan(jax.checkpoint(rows), jnp.float32(0), (
        _blocks(h, 0, block), _blocks(labels, 0, block)))[0]


def _sequence_loss(p, tokens, labels, cfg):
    """sum over one sequence's tokens of the cross entropy."""
    assert cfg["tie_word_embeddings"]
    h = jnp.take(p["embed"], tokens, axis=0)
    r = jnp.zeros((h.shape[0], cfg["router_hidden_size"]), h.dtype)
    for i in range(cfg["num_hidden_layers"]):
        h, r = jax.checkpoint(
            lambda p, h, r, i=i: _layer(p, h, r, i, cfg))(p, h, r)
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
