"""Plain reference of kimi-linear-48b-a3b: forward, loss and gradient in
fp32 jax.numpy under jax.default_matmul_precision("highest"), written from
the equations in benchmark/configs/kimi-linear-48b-a3b.json (`equations`,
`assumed`, `deployment`) and the parameter names of
paddle_tpu/models/hybrid_linear_decoder.py, and from nothing else of the
program: no op, no kernel, no chunk, no convolution primitive, no row
buffer, no grouped matmul, no AMP tier.  Kimi Delta Attention is the
recurrence itself, ONE TOKEN AT A TIME (a lax.scan over positions whose
carry is the heads' states [H, D, D]): decay every key channel, read the
decayed state with the key, add beta times the key times what the value
lacks, read the new state with the query.  The short convolutions are sums
over their taps of shifted products; latent attention expands its shared
key part and its values to the heads and writes the causal mask out, with
no rotation of any part; every held expert runs over every token, times a
gate that is 0 where the token did not choose it.

The chip's share (`deployment`): experts `expert_offset` .. + `num_experts`
of the router's `router_experts`, the gates normalised over all the chosen,
held or not, what the absent experts would add left out, and the tables'
held rows.  With `num_experts` = `router_experts` the same code is the
uncut layer (tier-1 adds the 32 shares up against it).

Memory: the projections, the convolutions and the decay run over the whole
sequence ([S, 4096]); the recurrence runs in blocks of
`reference.state_block` tokens, an outer lax.scan over blocks with a
checkpointed body around the inner scan over tokens, so that its backward
holds one state a block and one block's states (a state is 2.1 MB a layer:
one a token would be 17 GB); attention and everything tokenwise run as a
lax.scan over blocks of `reference.query_block` tokens with a checkpointed
body, the head likewise, and jax.checkpoint around a layer bounds what the
sequence keeps.  Size of the executable: a KDA layer's six maps that read u
(q, k, v, the decay's and the gate's first maps, beta) are ONE product over
their weights side by side; as six products the compiled reference was 94
MB in the chip machine's compile cache, which holds 192 MiB, and with the
step's 103 MB the two evicted each other on every run (PERF.md 7 (n)).
None of it changes a number."""

import jax
import jax.numpy as jnp


def _mm(x, w):
    return jnp.matmul(x, w)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _blocks(x, axis, block):
    """x with `axis` cut into blocks of `block`, the block index first."""
    n = x.shape[axis] // block
    return jnp.moveaxis(x.reshape(
        x.shape[:axis] + (n, block) + x.shape[axis + 1:]), axis, 0)


# ---------------------------------------------------------------------------
# Kimi Delta Attention
# ---------------------------------------------------------------------------
def _shifted(x, steps):
    """x [S, C]: row t is x's row t - steps, zeros before the first."""
    if steps == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:steps]), x[:-steps]], axis=0)


def _short_conv(x, w):
    """y[t] = sum over the k taps j of w[j] * x[t - (k - 1) + j]: one
    filter a channel, the last tap on the position itself; then SiLU."""
    k = w.shape[0]
    return jax.nn.silu(sum(w[j] * _shifted(x, k - 1 - j) for j in range(k)))


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _channel_decay(g):
    """g [S, H, D]: one log-decay for every key channel, as it is."""
    return g


def _beta(b):
    return jax.nn.sigmoid(b)


def _lacking(v_t, state, k_t):
    """What the value lacks of what the state already answers to the
    key: v - M^T k, a head."""
    return v_t - jnp.sum(state * k_t[..., None], axis=1)


def _token(state, x):
    """One token of every head: state [H, D keys, D values]; q_t, k_t,
    v_t, g_t [H, D], b_t [H]."""
    q_t, k_t, v_t, g_t, b_t = x
    state = jnp.exp(g_t)[..., None] * state          # the decay comes first
    state = state + (b_t[:, None, None] * k_t[..., None]
                     * _lacking(v_t, state, k_t)[:, None, :])
    return state, jnp.sum(state * q_t[..., None], axis=1)


def _delta_rule(q, k, v, g, beta, block):
    """o [S, H, D] of q, k, v, g [S, H, D] and beta [S, H], token by
    token from a zero state."""
    S, H, D = q.shape

    def tokens(state, xs):
        return jax.lax.scan(_token, state, xs)

    _, o = jax.lax.scan(
        jax.checkpoint(tokens), jnp.zeros((H, D, D), q.dtype),
        tuple(_blocks(t, 0, block) for t in (q, k, v, g, beta)))
    return o.reshape(S, H, D) * D ** -0.5


def _kda(p, u, name, cfg):
    """KDA(u) [S, d] of u [S, d]."""
    lin = cfg["linear_attn_config"]
    H, D = lin["num_heads"], lin["head_dim"]
    S = u.shape[0]

    def heads(t):
        return t.reshape(S, H, D)

    # the six maps that read u as one product over their weights side by
    # side (a column of a product does not know its neighbours)
    maps = ("q_w", "k_w", "v_w", "f_a_w", "gate_a_w", "beta_w")
    widths = [p[f"{name}_{m}"].shape[1] for m in maps]
    out = _mm(u, jnp.concatenate([p[f"{name}_{m}"] for m in maps], axis=1))
    ends = [sum(widths[:i + 1]) for i in range(len(maps))]
    q, k, v, f_a, gate_a, b = (out[:, e - w:e] for e, w in zip(ends, widths))
    q, k, v = (heads(_short_conv(t, p[f"{name}_conv_{x}_w"]))
               for t, x in zip((q, k, v), "qkv"))
    f = _mm(f_a, p[name + "_f_b_w"]) + p[name + "_dt_bias"]
    g = -jnp.exp(p[name + "_a_log"])[:, None] * heads(jax.nn.softplus(f))
    beta = _beta(b)
    o = _delta_rule(_unit(q), _unit(k), v, _channel_decay(g), beta,
                    min(cfg["reference"]["state_block"], S))
    gate = jax.nn.sigmoid(
        _mm(gate_a, p[name + "_gate_b_w"]) + p[name + "_gate_bias"])
    o = _rms_norm(o, p[name + "_on_scale"], cfg["rms_norm_eps"])
    return _mm(o.reshape(S, H * D) * gate, p[name + "_o_w"])


# ---------------------------------------------------------------------------
# latent attention without positions
# ---------------------------------------------------------------------------
def _positions(x):
    """x [..., S, rope width]: `mla_use_nope`, no rotation."""
    return x


def _mla_parts(p, u, name, cfg):
    """(q [H, S, nope + rope], k [H, S, nope + rope], v [H, S, v]) of u
    [S, d]: the one shared rope-wide key part repeated to the heads."""
    assert cfg["mla_use_nope"] and cfg["q_lora_rank"] is None
    S, H = u.shape[0], cfg["num_attention_heads"]
    dn, r = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]

    def heads(t):
        return t.reshape(S, H, -1).transpose(1, 0, 2)

    q = heads(_mm(u, p[name + "_q_w"]))
    q = jnp.concatenate([q[..., :dn], _positions(q[..., dn:])], axis=-1)
    kva = _mm(u, p[name + "_kva_w"])
    kv = heads(_mm(_rms_norm(kva[:, :r], p[name + "_kvn_scale"],
                             cfg["rms_norm_eps"]), p[name + "_kvb_w"]))
    shared = jnp.broadcast_to(_positions(kva[:, r:])[None],
                              (H, S, kva.shape[1] - r))
    return q, jnp.concatenate([kv[..., :dn], shared], axis=-1), kv[..., dn:]


def _attend(q, k, v, first, cfg):
    """contexts [T, H * v] of a block of queries q [H, T, .], the first of
    them at position `first`, over the sequence's k, v [H, S, .]."""
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    scores = jnp.einsum("htd,hsd->hts", q, k) * scale
    t = first + jnp.arange(q.shape[1])[:, None]
    mask = jnp.arange(k.shape[1])[None, :] <= t
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    ctx = jnp.einsum("hts,hsd->htd", probs, v)
    return ctx.transpose(1, 0, 2).reshape(q.shape[1], -1)


# ---------------------------------------------------------------------------
# the feed-forward blocks
# ---------------------------------------------------------------------------
def _mlp(p, x, name):
    gate = jax.nn.silu(_mm(x, p[name + "_gate_w"]))
    return _mm(gate * _mm(x, p[name + "_up_w"]), p[name + "_down_w"])


def _gates(p, x, name, cfg):
    """g [T, router_experts]: for the top-k of s + b the score s (without
    b) over the sum of the chosen ones' scores, times the scaling factor;
    0 for every other expert."""
    assert cfg["moe_router_activation_func"] == "sigmoid"
    assert cfg["num_expert_group"] == cfg["topk_group"] == 1
    s = jax.nn.sigmoid(_mm(x, p[name + "_router_w"]))
    choice = s + p[name + "_router_bias"]
    kth = jnp.sort(choice, axis=-1)[..., -cfg["num_experts_per_token"]]
    g = jnp.where(choice >= kth[..., None], s, 0.0)
    if cfg["moe_renormalize"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return g * cfg["routed_scaling_factor"]


def _expert_block(p, x, name, cfg):
    """Every held expert over every token of x [T, d] (one product batched
    over the experts' axis), times its gate, plus the shared expert."""
    held = jnp.arange(cfg["num_experts"])
    g = _gates(p, x, name, cfg)[:, cfg["expert_offset"] + held]    # [T, E]
    hidden = (jax.nn.silu(_mm(x, p[name + "_experts_gate_w"]))
              * _mm(x, p[name + "_experts_up_w"]))              # [E, T, f]
    routed = jnp.sum(_mm(hidden, p[name + "_experts_down_w"])
                     * g.T[..., None], axis=0)
    return routed + _mlp(p, x, name + "_shared")


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def _kind(i, cfg):
    """Layer i (from 0) by the two lists, which number from 1."""
    lin = cfg["linear_attn_config"]
    if i + 1 in lin["kda_layers"]:
        return "kda"
    assert i + 1 in lin["full_attn_layers"], i
    return "mla"


def _layer(p, h, i, cfg):
    """h' [S, d] of layer i."""
    eps, n, S = cfg["rms_norm_eps"], f"l{i}", h.shape[0]
    block = min(cfg["reference"]["query_block"], S)
    u = _rms_norm(h, p[n + "_n1_scale"], eps)
    firsts = jnp.arange(0, S, block)
    if _kind(i, cfg) == "kda":
        mixed = _kda(p, u, n + "_attn", cfg)
    else:
        q, k, v = _mla_parts(p, u, n + "_attn", cfg)

        def queries(_, xs):
            first, q_b = xs
            return None, _mm(_attend(q_b, k, v, first, cfg),
                             p[n + "_attn_o_w"])

        mixed = jax.lax.scan(jax.checkpoint(queries), None,
                             (firsts, _blocks(q, 1, block)))[1].reshape(S, -1)

    def rows(_, a):
        x = _rms_norm(a, p[n + "_n2_scale"], eps)
        if i < cfg["first_k_dense_replace"]:
            return None, a + _mlp(p, x, n + "_mlp")
        return None, a + _expert_block(p, x, n, cfg)

    return jax.lax.scan(jax.checkpoint(rows), None,
                        _blocks(h + mixed, 0, block))[1].reshape(S, -1)


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
