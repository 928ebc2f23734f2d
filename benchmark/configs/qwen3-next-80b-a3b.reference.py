"""Plain reference of qwen3-next-80b-a3b: forward, loss and gradient in fp32
jax.numpy under jax.default_matmul_precision("highest"), written from the
equations in benchmark/configs/qwen3-next-80b-a3b.json (`equations`,
`assumed`, `deployment`) and the parameter names of
paddle_tpu/models/gated_delta_decoder.py, and from nothing else of the
program: no op, no kernel, no chunk, no convolution primitive, no row
buffer, no grouped matmul, no AMP tier, no hand-written backward.

Gated DeltaNet is the recurrence itself, ONE TOKEN AT A TIME (a lax.scan
over positions whose carry is the value heads' states [Hv, D, D]): decay
the state by the head's one scalar, read the decayed state with the key,
add beta times the key times what the value lacks, read the new state with
the query; q and k are repeated to the value heads in the plain way.  The
convolution over q | k | v is a sum over its taps of shifted products.
Gated attention expands K and V to the query heads and writes the causal
mask out, a block of `query_block` queries against all keys at a time.
Every held expert runs over every token, times a gate that is 0 where the
token did not choose it; the shared expert runs behind its sigmoid gate.

The chip's share (`deployment`): experts `expert_offset` .. + `num_experts`
of the router's `router_experts`, the gates normalised over all the chosen,
held or not, what the absent experts would add left out, and the tables'
held rows.  With `num_experts` = `router_experts` the same code is the
uncut layer (tier-1 adds the shares up against it).

jax.checkpoint around a layer, `key_head_block` key heads of a Gated
DeltaNet mixer with their value heads, a run of `state_block` tokens of
the recurrence, a block of queries and a block of rows of the expert block
and of the head only bounds what the backward pass keeps (a token's states are
[32, 128, 128] fp32, 2.1 MB a layer: one a token would be 17 GB); the
blocks are a lax.scan so that the executable holds one block's code:
neither changes a number.  The small functions (_mm, _state, _decay, _unit,
_norm_gate, _turned, _shared_gate) are what
tools/qwen3next_reference_probe.py replaces, one at a time, to make the
wrong rules the tolerances have to refuse."""

import jax
import jax.numpy as jnp


def _mm(x, w):
    return jnp.matmul(x, w)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w


def _blocks(x, axis, block):
    """x with `axis` cut into blocks of `block`, the block index first."""
    n = x.shape[axis] // block
    return jnp.moveaxis(x.reshape(
        x.shape[:axis] + (n, block) + x.shape[axis + 1:]), axis, 0)


# ---------------------------------------------------------------------------
# Gated DeltaNet
# ---------------------------------------------------------------------------
def _conv_silu(x, w):
    """silu of the depthwise causal convolution of x [S, C] with w [taps,
    C]: the last tap on the position itself, zeros before the first."""
    taps, S = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x])
    return jax.nn.silu(sum(padded[j:j + S] * w[j] for j in range(taps)))


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _decay(a, a_log, dt_bias):
    """g [S, Hv]: one log-decay a head."""
    return -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)


def _state(m):
    """The state a token hands the next: as it is."""
    return m


def _token(m, x):
    """One token of every value head: m [Hv, D keys, D values]; q_t, k_t,
    v_t [Hv, D], g_t, b_t [Hv]."""
    q_t, k_t, v_t, g_t, b_t = x
    m = jnp.exp(g_t)[:, None, None] * m              # the decay comes first
    lacking = v_t - jnp.sum(m * k_t[..., None], axis=1)
    m = _state(m + b_t[:, None, None] * k_t[..., None] * lacking[:, None, :])
    return m, jnp.sum(m * q_t[..., None], axis=1)


def _delta_rule(q, k, v, g, beta, block):
    """o [S, Hv, D] of q, k, v [S, Hv, D] and g, beta [S, Hv], token by
    token from a zero state."""
    S, H, D = v.shape

    def tokens(m, xs):
        return jax.lax.scan(_token, m, xs)

    _, o = jax.lax.scan(
        jax.checkpoint(tokens), jnp.zeros((H, D, D), v.dtype),
        tuple(_blocks(t, 0, block) for t in (q, k, v, g, beta)))
    return o.reshape(S, H, D) * D ** -0.5


def _norm_gate(o, z, w, eps):
    """The norm a head first, then the SiLU gate."""
    return _rms(o, w, eps) * jax.nn.silu(z)


def _by_heads(w, first, width, blocks):
    """Columns first .. first + width of w [rows, .] (whole heads side by
    side) as `blocks` runs of heads: [blocks, rows, width / blocks]."""
    return jnp.moveaxis(w[:, first:first + width].reshape(
        w.shape[0], blocks, -1), 1, 0)


def _gdn(p, u, name, cfg):
    """GDN(u) [S, d] of u [S, d], `key_head_block` key heads (and their
    value heads) at a time: a column of a product, a channel of the
    convolution and a head of the recurrence do not know their neighbours,
    and W_o adds the heads' parts up."""
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    D = cfg["linear_key_head_dim"]
    assert D == cfg["linear_value_head_dim"] and Hv % Hk == 0
    S, d, keys, values = u.shape[0], u.shape[1], Hk * D, Hv * D
    nb = Hk // min(cfg["reference"]["key_head_block"], Hk)
    hk, hv = Hk // nb, Hv // nb
    w, conv, ba = (p[name + s] for s in ("_qkvz_w", "_conv_w", "_ba_w"))
    parts = (
        _by_heads(w, 0, keys, nb), _by_heads(w, keys, keys, nb),
        _by_heads(w, 2 * keys, values, nb),
        _by_heads(w, 2 * keys + values, values, nb),
        _by_heads(conv, 0, keys, nb), _by_heads(conv, keys, keys, nb),
        _by_heads(conv, 2 * keys, values, nb),
        _by_heads(ba, 0, Hv, nb), _by_heads(ba, Hv, Hv, nb),
        p[name + "_a_log"].reshape(nb, hv),
        p[name + "_dt_bias"].reshape(nb, hv),
        p[name + "_o_w"].reshape(nb, hv * D, d))

    def some_heads(total, xs):
        wq, wk, wv, wz, cq, ck, cv, wb, wa, a_log, dt_bias, wo = xs

        def to_value_heads(t):
            return jnp.repeat(t.reshape(S, hk, D), hv // hk, axis=1)

        q = to_value_heads(_conv_silu(_mm(u, wq), cq))
        k = to_value_heads(_conv_silu(_mm(u, wk), ck))
        v = _conv_silu(_mm(u, wv), cv).reshape(S, hv, D)
        z = _mm(u, wz).reshape(S, hv, D)
        beta = jax.nn.sigmoid(_mm(u, wb))
        g = _decay(_mm(u, wa), a_log, dt_bias)
        o = _delta_rule(_unit(q), _unit(k), v, g, beta,
                        min(cfg["reference"]["state_block"], S))
        n = _norm_gate(o, z, p[name + "_on_scale"], cfg["rms_norm_eps"])
        return total + _mm(n.reshape(S, hv * D), wo), None

    return jax.lax.scan(jax.checkpoint(some_heads),
                        jnp.zeros((S, d), u.dtype), parts)[0]


# ---------------------------------------------------------------------------
# gated attention
# ---------------------------------------------------------------------------
def _turned(x, cfg):
    """x [heads, S, D]: the first `partial_rotary_factor` D features of a
    head turned by the position, half-split pairs (x[i], x[i + r / 2]), the
    others as they are."""
    D, S = x.shape[-1], x.shape[-2]
    r = int(D * cfg["partial_rotary_factor"])
    freq = cfg["rope_theta"] ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def _attention_parts(p, u, name, cfg):
    """(q [H, S, D], gate [S, H D], k, v [H, S, D]) of u [S, d]: K and V
    expanded to the query heads."""
    H, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    S, eps = u.shape[0], cfg["rms_norm_eps"]
    qg = _mm(u, p[name + "_q_w"]).reshape(S, H, 2 * D)
    q, gate = qg[..., :D], qg[..., D:].reshape(S, H * D)
    k = _mm(u, p[name + "_k_w"]).reshape(S, G, D)
    v = _mm(u, p[name + "_v_w"]).reshape(S, G, D)
    q = _turned(_rms(q, p[name + "_qn_scale"], eps).transpose(1, 0, 2), cfg)
    k = _turned(_rms(k, p[name + "_kn_scale"], eps).transpose(1, 0, 2), cfg)
    k, v = (jnp.repeat(t, H // G, axis=0) for t in (k, v.transpose(1, 0, 2)))
    return q, gate, k, v


def _attend(q, k, v, first):
    """contexts [T, H D] of a block of queries q [H, T, D], the first of
    them at position `first`, over the sequence's k, v [H, S, D]."""
    scores = jnp.einsum("htd,hsd->hts", q, k) * q.shape[-1] ** -0.5
    t = first + jnp.arange(q.shape[1])[:, None]
    mask = jnp.arange(k.shape[1])[None, :] <= t
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    ctx = jnp.einsum("hts,hsd->htd", probs, v)
    return ctx.transpose(1, 0, 2).reshape(q.shape[1], -1)


# ---------------------------------------------------------------------------
# the expert block
# ---------------------------------------------------------------------------
def _mlp(p, x, name):
    gate = jax.nn.silu(_mm(x, p[name + "_gate_w"]))
    return _mm(gate * _mm(x, p[name + "_up_w"]), p[name + "_down_w"])


def _gates(p, x, name, cfg):
    """g [T, router_experts]: the softmax of the router's logits over all
    the experts, kept for the top k, divided by their sum; 0 elsewhere."""
    s = jax.nn.softmax(_mm(x, p[name + "_router_w"]), axis=-1)
    kth = jnp.sort(s, axis=-1)[..., -cfg["num_experts_per_tok"]]
    g = jnp.where(s >= kth[..., None], s, 0.0)
    if cfg["norm_topk_prob"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    # a share whose router takes no gradient: the gates are constants
    return g if cfg.get("train_router", True) else jax.lax.stop_gradient(g)


def _shared_gate(p, x, name):
    return jax.nn.sigmoid(_mm(x, p[name + "_shared_expert_gate_w"]))


def _expert_block(p, x, name, cfg):
    """Every held expert over every token of x [T, d] (one product batched
    over the experts' axis), times its gate, plus the shared expert times
    its own."""
    held = jnp.arange(cfg["num_experts"])
    g = _gates(p, x, name, cfg)[:, cfg["expert_offset"] + held]    # [T, E]
    hidden = (jax.nn.silu(_mm(x, p[name + "_experts_gate_w"]))
              * _mm(x, p[name + "_experts_up_w"]))              # [E, T, f]
    routed = jnp.sum(_mm(hidden, p[name + "_experts_down_w"])
                     * g.T[..., None], axis=0)
    return routed + _shared_gate(p, x, name) * _mlp(p, x, name + "_shared")


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def _layer(p, h, i, cfg):
    """h' [S, d] of layer i."""
    eps, n, S = cfg["rms_norm_eps"], f"l{i}", h.shape[0]
    block = min(cfg["reference"]["query_block"], S)
    u = _rms(h, p[n + "_n1_scale"], eps)
    if (i + 1) % cfg["full_attention_interval"]:
        mixed = _gdn(p, u, n + "_gdn", cfg)
    else:
        q, gate, k, v = _attention_parts(p, u, n + "_attn", cfg)

        def queries(_, xs):
            first, q_b, gate_b = xs
            return None, _mm(_attend(q_b, k, v, first)
                             * jax.nn.sigmoid(gate_b), p[n + "_attn_o_w"])

        mixed = jax.lax.scan(
            jax.checkpoint(queries), None,
            (jnp.arange(0, S, block), _blocks(q, 1, block),
             _blocks(gate, 0, block)))[1].reshape(S, -1)

    def rows(_, a):
        return None, a + _expert_block(
            p, _rms(a, p[n + "_n2_scale"], eps), n, cfg)

    rows_block = min(cfg["reference"]["expert_block"], S)
    return jax.lax.scan(jax.checkpoint(rows), None,
                        _blocks(h + mixed, 0, rows_block))[1].reshape(S, -1)


def _head(p, h, labels, cfg):
    """sum over the tokens of the cross entropy, in blocks of tokens."""
    block = min(cfg["reference"]["query_block"], h.shape[0])

    def rows(total, xs):
        h_b, y_b = xs
        h_b = _rms(h_b, p["final_scale"], cfg["rms_norm_eps"])
        logp = jax.nn.log_softmax(_mm(h_b, p["head_w"]), axis=-1)
        return total - jnp.sum(jnp.take_along_axis(
            logp, y_b[:, None], axis=-1)), None

    return jax.lax.scan(jax.checkpoint(rows), jnp.float32(0), (
        _blocks(h, 0, block), _blocks(labels, 0, block)))[0]


def _sequence_loss(p, tokens, labels, cfg):
    """sum over one sequence's tokens of the cross entropy."""
    assert not cfg["tie_word_embeddings"] and cfg["hidden_act"] == "silu"
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
