"""Plain reference of keye-vl-2.0-30b-a3b: forward, loss and gradient in
fp32 jax.numpy under jax.default_matmul_precision("highest"), written from
the equations in benchmark/configs/keye-vl-2.0-30b-a3b.json (`equations`,
`assumed`, `deployment`) and the parameter names of
paddle_tpu/models/sparse_decoder.py, and from nothing else of the program:
no op, no kernel, no chunked softmax, no bitwise selection, no row buffer,
no grouped matmul, no AMP tier.  K and V are repeated to the 32 query
heads; a query's index scores are its whole row over all S positions;
jax.lax.top_k picks the chosen positions and a scatter makes their mask;
attention is a softmax over masked scores; the index's loss is the KL of
the heads' mean probabilities (detached) from the softmax of the index
scores over the mask; every held expert runs over every token, weighted by
a gate that is 0 where the token did not choose it.

Departures from the published description, each also in the file's
`assumed`: no vision tower (text, three equal position streams in the cell;
the code takes three); the index's objective is DeepSeek-V3.2-Exp's sparse
training stage, which the config does not state; the chip's share
(`deployment`): experts `expert_offset` .. + `num_experts` of the router's
`router_experts`, the gates normalised over all 8 chosen, held or not, what
the absent experts would add left out, and the vocabulary's held slice.
With `num_experts` = `router_experts` the same code is the uncut layer
(tier-1 adds the shares up against it).

Memory: everything past the projections is tokenwise but the attention's
keys, so a layer runs as a lax.scan over blocks of `reference.query_block`
queries with a checkpointed body (the block's [32, block, S] score planes
are the largest thing alive), the head likewise, and jax.checkpoint around
a layer bounds what the sequence keeps: one gradient-sized buffer beside
the program's state (PERF.md 4).  None of it changes a number."""

import jax
import jax.numpy as jnp


def _mm(x, w):
    return jnp.matmul(x, w)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _head_norm(x, scale, eps):
    """RMSNorm over each q or k head's features."""
    return _rms_norm(x, scale, eps)


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _sections(cfg, pairs):
    """mrope_section scaled to `pairs` pairs (the index's heads are half
    as wide as the attention's: 8, 12, 12 of its 32)."""
    published = cfg["rope_scaling"]["mrope_section"]
    return [n * pairs // sum(published) for n in published]


def _rotary(x, positions, cfg):
    """x [..., S, D], positions [3, S]: pair i is (x[i], x[i + D/2]),
    turned by the angle p * theta^(-2i/D), p the position stream of the
    section pair i lies in (the first 16 of 64 pairs temporal, the next 24
    height, the last 24 width)."""
    half = x.shape[-1] // 2
    inv_freq = cfg["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32)
                                     * 2.0 / x.shape[-1])
    stream = jnp.repeat(jnp.arange(positions.shape[0]),
                        jnp.asarray(_sections(cfg, half)),
                        total_repeat_length=half)
    angle = positions.astype(jnp.float32).T[:, stream] * inv_freq   # [S, half]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _heads(t, n):
    """[S, n * width] -> [n, S, width]."""
    return t.reshape(t.shape[0], n, -1).transpose(1, 0, 2)


def _to_query_heads(x, share):
    """Key/value heads [G, S, D] repeated so that query head j reads head
    j // share."""
    return jnp.repeat(x, share, axis=0)


def _index_operand(x):
    """What the index's scoring reads of q_i, k_i and w: the value itself
    (tools/keye_reference_probe.py rounds it to bf16 here to say what the
    program's index precision costs)."""
    return x


def _projections(p, u, positions, name, cfg):
    """Everything of the attention block that needs the whole sequence:
    q [32, S, 128], k and v repeated to [32, S, 128], and the index's q_i
    [16, S, 64], k_i [S, 64], w [S, 16] from u' = stop_gradient(u)."""
    H, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa, eps = cfg["sa_config"], cfg["rms_norm_eps"]
    q = _rotary(_head_norm(_heads(_mm(u, p[name + "_q_w"]), H),
                           p[name + "_qn_scale"], eps), positions, cfg)
    k = _rotary(_head_norm(_heads(_mm(u, p[name + "_k_w"]), G),
                           p[name + "_kn_scale"], eps), positions, cfg)
    v = _heads(_mm(u, p[name + "_v_w"]), G)
    ui, n = jax.lax.stop_gradient(u), name + "_index"
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    q_i = _rotary(_heads(_mm(ui, p[n + "_q_w"]), hi), positions, cfg)
    k_i = _rotary(_layer_norm(_mm(ui, p[n + "_k_w"]), p[n + "_kn_scale"],
                              p[n + "_kn_bias"], eps), positions, cfg)
    w = _mm(ui, p[n + "_w_w"]) * (hi * di) ** -0.5
    return (q, _to_query_heads(k, H // G), _to_query_heads(v, H // G),
            _index_operand(q_i), _index_operand(k_i), _index_operand(w))


def _chosen(scores, first, topk):
    """mask [T, S]: the topk causal positions of largest score a row (all
    the causal ones where there are fewer), by lax.top_k and a scatter."""
    T, S = scores.shape
    causal = jnp.arange(S)[None, :] <= first + jnp.arange(T)[:, None]
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, S))
    picked = jnp.zeros((T, S), bool).at[jnp.arange(T)[:, None], idx].set(True)
    return picked & causal


def _index_scores(q_i, k_i, w):
    """I [T, S] = sum_j w[t, j] relu(q_i[j, t] . k_i[s])."""
    return jnp.einsum(
        "tj,jts->ts", w, jax.nn.relu(jnp.einsum("jtd,sd->jts", q_i, k_i)))


def _index_support(mask, first):
    """The positions the index's softmax in the loss runs over: S_t."""
    return mask


def _attend(q, k, v, q_i, k_i, w, first, cfg):
    """(contexts [T, H * D], sum over the block's queries of the index's
    KL) of a block of queries q [H, T, D] over the sequence's k, v."""
    scores_i = _index_scores(q_i, k_i, w)
    mask = _chosen(scores_i, first, cfg["sa_config"]["topk"])
    scores = jnp.einsum("htd,hsd->hts", q, k) * cfg["head_dim"] ** -0.5
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    ctx = jnp.einsum("hts,hsd->htd", probs, v)
    target = jax.lax.stop_gradient(jnp.mean(probs, axis=0))          # p_t
    log_index = jax.nn.log_softmax(jnp.where(
        _index_support(mask, first), scores_i, -1e30), axis=-1)
    live = mask & (target > 0)
    kl = jnp.sum(jnp.where(live, target * (
        jnp.log(jnp.where(live, target, 1.0)) - log_index), 0.0))
    return ctx.transpose(1, 0, 2).reshape(q.shape[1], -1), kl


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
    return g


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


def _layer(p, h, positions, i, cfg):
    """(h' [S, d], sum over the queries of the index's KL)."""
    eps, n, S = cfg["rms_norm_eps"], f"l{i}", h.shape[0]
    block = min(cfg["reference"]["query_block"], S)
    q, k, v, q_i, k_i, w = _projections(
        p, _rms_norm(h, p[n + "_n1_scale"], eps), positions, n + "_attn", cfg)

    def rows(kl, xs):
        first, h_b, q_b, qi_b, w_b = xs
        ctx, kl_b = _attend(q_b, k, v, qi_b, k_i, w_b, first, cfg)
        a = h_b + _mm(ctx, p[n + "_attn_o_w"])
        out = a + _expert_block(p, _rms_norm(a, p[n + "_n2_scale"], eps),
                                n, cfg)
        return kl + kl_b, out

    kl, out = jax.lax.scan(jax.checkpoint(rows), jnp.float32(0), (
        jnp.arange(0, S, block), _blocks(h, 0, block), _blocks(q, 1, block),
        _blocks(q_i, 1, block), _blocks(w, 0, block)))
    return out.reshape(S, -1), kl


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


def _sequence_loss(p, tokens, labels, positions, cfg):
    """sum over one sequence's tokens of (cross entropy + the layers'
    index KL)."""
    h = jnp.take(p["embed"], tokens, axis=0)
    total = jnp.float32(0)
    for i in range(cfg["num_hidden_layers"]):
        h, kl = jax.checkpoint(
            lambda p, h, i=i: _layer(p, h, positions, i, cfg))(p, h)
        total = total + kl
    return total + jax.checkpoint(
        lambda p, h: _head(p, h, labels, cfg))(p, h)


def loss_and_grad(params, batch, cfg, feed_names, trainable, micro):
    """(loss, {name: gradient}) of the mean over the batch's tokens of
    cross entropy + index loss, the batch's sequences one at a time by a
    scan that is differentiated as a whole (`micro` is the harness's count
    of parts; a part here is always one sequence)."""
    del micro
    params = {k: v.astype(jnp.float32) for k, v in params.items()}
    fixed = {k: v for k, v in params.items() if k not in trainable}
    free = {k: v for k, v in params.items() if k in trainable}
    tokens, labels, positions = (batch[n] for n in feed_names)
    count = float(tokens.size)

    def total(free):
        def part(cost, one):
            return cost + _sequence_loss({**fixed, **free}, *one,
                                         cfg) / count, None

        return jax.lax.scan(jax.checkpoint(part), jnp.float32(0),
                            (tokens, labels, positions))[0]

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(total)(free)
