"""Plain reference of xing4.0-29b-a4b: forward, loss and gradient in fp32
jax.numpy under jax.default_matmul_precision("highest"), written from the
equations in benchmark/configs/xing4.0-29b-a4b.json (`equations`,
`assumed`, `deployment`) and the parameter names of
paddle_tpu/models/hyper_expert_decoder.py, and from nothing else of the
program: no op, no kernel, no row buffer, no grouped matmul, no AMP tier.
The four residual streams are one fp32 value [S, n, C]; a sublayer's maps
are n-vectors and n x n matrices A TOKEN ([S, n], [S, n, n]: the streams'
axes last, where the program lays the tokens last), Sinkhorn-Knopp is this
file's own loop over them (columns, then rows, hc_eps beside each sum),
YaRN's frequencies this file's own arithmetic; latent attention expands its
shared key part and its values to the heads and writes the causal mask out;
every held expert runs over every token, times a gate that is 0 where the
token did not choose it.

The chip's share (`deployment`): attention heads 0 .. `heads_held` of
`num_attention_heads` (the parameters it is given hold the held heads'
columns
of W_qb and W_kvb and rows of W_o; what the other heads would add to the
output map's sum is left out), experts `expert_offset` .. +
`n_routed_experts` of the router's `router_experts` (the gates normalised
over all the chosen, held or not), and the tables' held rows.  With
`heads_held` = `num_attention_heads` and `n_routed_experts` =
`router_experts` the same code is the uncut layer (tier-1 adds the eight
shares of the heads and of the experts up against it).

Memory and the executable's size: a value of the streams' size is 235 MB
at the cell's shape, and the reference runs beside the program's 7.87 GB
of state, so a layer makes none but its output: the maps, the read and the
write are tokenwise, so a layer is two lax.scans over blocks of
`reference.query_block` tokens with checkpointed bodies, the first for the
sequence's keys and values ([H, S, .], small), the second for everything
else of both sublayers (it computes the attention sublayer's maps and read
once more); the head likewise, and jax.checkpoint around a layer bounds
what the sequence keeps; the Sinkhorn loop is a lax.scan over its
iterations, one body however many there are.  As one whole-sequence
attention sublayer the reference's temporaries were 4.28 GB and state +
reference 17.40 GB of the chip's 16.91 (chip-less, tools/step_memory.py,
PR 50).  None of it changes a number."""

import math

import jax
import jax.numpy as jnp
import numpy as np


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
# hyper-connections
# ---------------------------------------------------------------------------
def _sinkhorn(m, iters, eps):
    """m [T, n, n] positive -> doubly stochastic: `iters` times, every
    column over its sum, then every row over its sum."""
    def once(m, _):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-1, keepdims=True) + eps), None

    return jax.lax.scan(once, m, None, length=iters)[0]


def _write_gate(z):
    return 2.0 * jax.nn.sigmoid(z)


def _maps(p, x, name, cfg):
    """(H_pre [T, n], H_post [T, n], H_res [T, n, n]) of the streams x
    [T, n, C] under the sublayer `name`'s parameters."""
    T, n, C = x.shape
    u = x.reshape(T, n * C)
    u = u * jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True)
                          + cfg["rms_norm_eps"])
    m = _mm(u, p[name + "_phi"])                              # [T, 2n + n^2]
    pre = jax.nn.sigmoid(p[name + "_a_pre"] * m[:, :n] + p[name + "_b_pre"])
    post = _write_gate(p[name + "_a_post"] * m[:, n:2 * n]
                       + p[name + "_b_post"])
    res = p[name + "_a_res"] * m[:, 2 * n:].reshape(T, n, n) \
        + p[name + "_b_res"]
    res = jnp.exp(jnp.clip(res, cfg["mhc_h_res_clamp_min"],
                           cfg["mhc_h_res_clamp_max"]))
    return pre, post, _sinkhorn(res, cfg["hc_sinkhorn_iters"],
                                cfg["hc_eps"])


def _read(x, maps):
    """x_in [T, C] = sum_j H_pre[j] x[j]."""
    return jnp.einsum("tj,tjc->tc", maps[0], x)


def _write(x, maps, y):
    """x'[i] = sum_j H_res[i, j] x[j] + H_post[i] y."""
    return jnp.einsum("tij,tjc->tic", maps[2], x) \
        + maps[1][:, :, None] * y[:, None, :]


# ---------------------------------------------------------------------------
# latent attention behind a low-rank query, under YaRN
# ---------------------------------------------------------------------------
def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _inv_freq(dim, cfg):
    """The dim / 2 pairs' frequencies under `rope_scaling` (YaRN): pair i
    keeps theta^(-2i/dim) where it turns more than beta_fast times over
    the original length, takes it / factor where it turns fewer than
    beta_slow times, a linear ramp between; float64."""
    rs, theta = cfg["rope_scaling"], float(cfg["rope_theta"])
    pairs = np.arange(dim // 2, dtype=np.float64)
    freq = theta ** (-2.0 * pairs / dim)
    if rs is None:
        return freq

    def pair_turning(times):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (times * 2.0 * math.pi)) \
            / (2.0 * math.log(theta))

    low = max(math.floor(pair_turning(rs["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(rs["beta_slow"])), dim - 1)
    ramp = np.clip((pairs - low) / max(high - low, 0.001), 0.0, 1.0)
    return freq * (1.0 - ramp) + freq / rs["factor"] * ramp


def _positions(x, cfg, first=0):
    """x [..., T, width], tokens `first` .. `first` + T of the sequence,
    turned by their positions: the half-split pairs (i, i + width / 2), cos
    and sin times mscale(mscale) / mscale(mscale_all_dim)."""
    rs = cfg["rope_scaling"]
    width = x.shape[-1]
    half = width // 2
    angle = (first + jnp.arange(x.shape[-2], dtype=jnp.float32))[:, None] \
        * jnp.asarray(_inv_freq(width, cfg), jnp.float32)[None, :]
    mult = 1.0 if rs is None else (_mscale(rs["factor"], rs["mscale"])
                                   / _mscale(rs["factor"],
                                             rs["mscale_all_dim"]))
    cos, sin = jnp.cos(angle) * mult, jnp.sin(angle) * mult
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _softmax_scale(cfg):
    rs = cfg["rope_scaling"]
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    if rs is not None:
        scale *= _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def _heads(t, cfg):
    """[T, H * width] -> [H, T, width] at the H held heads."""
    return t.reshape(t.shape[0], cfg["heads_held"], -1).transpose(
        1, 0, 2)


def _mla_queries(p, u, first, name, cfg):
    """q [H, T, nope + rope] of u [T, d], tokens `first` on: through rank
    q_lora_rank with a norm between, the rope-wide part turned."""
    dn = cfg["qk_nope_head_dim"]
    q = _heads(_mm(_rms_norm(_mm(u, p[name + "_qa_w"]),
                             p[name + "_qn_scale"], cfg["rms_norm_eps"]),
                   p[name + "_qb_w"]), cfg)
    return jnp.concatenate(
        [q[..., :dn], _positions(q[..., dn:], cfg, first)], axis=-1)


def _mla_keys(p, u, first, name, cfg):
    """(k [H, T, nope + rope], v [H, T, v]) of u [T, d], tokens `first` on:
    the one shared rope-wide key part turned and repeated to the heads."""
    dn, r = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    kva = _mm(u, p[name + "_kva_w"])
    kv = _heads(_mm(_rms_norm(kva[:, :r], p[name + "_kvn_scale"],
                              cfg["rms_norm_eps"]), p[name + "_kvb_w"]), cfg)
    shared = jnp.broadcast_to(
        _positions(kva[:, r:], cfg, first)[None],
        (kv.shape[0], kva.shape[0], kva.shape[1] - r))
    return jnp.concatenate([kv[..., :dn], shared], axis=-1), kv[..., dn:]


def _attend(q, k, v, first, cfg):
    """contexts [T, H * v] of a block of queries q [H, T, .], the first of
    them at position `first`, over the sequence's k, v [H, S, .]."""
    scores = jnp.einsum("htd,hsd->hts", q, k) * _softmax_scale(cfg)
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
    assert cfg["scoring_func"] == "sigmoid"
    assert cfg["n_group"] == cfg["topk_group"] == 1
    s = jax.nn.sigmoid(_mm(x, p[name + "_router_w"]))
    choice = s + p[name + "_router_bias"]
    kth = jnp.sort(choice, axis=-1)[..., -cfg["num_experts_per_tok"]]
    g = jnp.where(choice >= kth[..., None], s, 0.0)
    if cfg["norm_topk_prob"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return g * cfg["routed_scaling_factor"]


def _expert_block(p, x, name, cfg):
    """Every held expert over every token of x [T, d] (one product batched
    over the experts' axis), times its gate, plus the shared expert."""
    held = jnp.arange(cfg["n_routed_experts"])
    g = _gates(p, x, name, cfg)[:, cfg["expert_offset"] + held]    # [T, E]
    hidden = (jax.nn.silu(_mm(x, p[name + "_experts_gate_w"]))
              * _mm(x, p[name + "_experts_up_w"]))              # [E, T, f]
    routed = jnp.sum(_mm(hidden, p[name + "_experts_down_w"])
                     * g.T[..., None], axis=0)
    return routed + _mlp(p, x, name + "_shared")


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def _layer(p, x, i, cfg):
    """The streams [S, n, C] after layer i."""
    eps, n, S = cfg["rms_norm_eps"], f"l{i}", x.shape[0]
    block = min(cfg["reference"]["query_block"], S)
    blocks = (jnp.arange(0, S, block), _blocks(x, 0, block))

    def attention_reads(x_b):
        maps = _maps(p, x_b, n + "_hc_attn", cfg)
        return maps, _rms_norm(_read(x_b, maps), p[n + "_n1_scale"], eps)

    def keys(_, xs):
        first, x_b = xs
        return None, _mla_keys(p, attention_reads(x_b)[1], first,
                               n + "_attn", cfg)

    # the sequence's keys and values first ([H, S, .]: small), then
    # everything else of the layer a block of tokens at a time
    k, v = (jnp.moveaxis(t, 0, 1).reshape(t.shape[1], S, -1) for t in
            jax.lax.scan(jax.checkpoint(keys), None, blocks)[1])

    def rows(_, xs):
        first, x_b = xs
        maps, u = attention_reads(x_b)
        q = _mla_queries(p, u, first, n + "_attn", cfg)
        x_b = _write(x_b, maps, _mm(_attend(q, k, v, first, cfg),
                                    p[n + "_attn_o_w"]))
        maps = _maps(p, x_b, n + "_hc_ffn", cfg)
        a = _rms_norm(_read(x_b, maps), p[n + "_n2_scale"], eps)
        if i < cfg["first_k_dense_replace"]:
            return None, _write(x_b, maps, _mlp(p, a, n + "_mlp"))
        return None, _write(x_b, maps, _expert_block(p, a, n, cfg))

    return jax.lax.scan(jax.checkpoint(rows), None,
                        blocks)[1].reshape(x.shape)


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


def _final_stream(p, tokens, cfg):
    """h_L [S, C]: the embedding copied to the hc_mult streams, the layers,
    the streams summed."""
    assert not cfg["tie_word_embeddings"] and cfg["hidden_act"] == "silu"
    e = jnp.take(p["embed"], tokens, axis=0)
    x = jnp.broadcast_to(e[:, None], (e.shape[0], cfg["hc_mult"],
                                      e.shape[1]))
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(lambda p, x, i=i: _layer(p, x, i, cfg))(p, x)
    return jnp.sum(x, axis=1)


def _logits(p, tokens, cfg):
    """[S, vocabulary held] of one sequence (the probe's; the loss takes
    them in blocks)."""
    return _mm(_rms_norm(_final_stream(p, tokens, cfg), p["final_scale"],
                         cfg["rms_norm_eps"]), p["head_w"])


def _sequence_loss(p, tokens, labels, cfg):
    """sum over one sequence's tokens of the cross entropy."""
    h = _final_stream(p, tokens, cfg)
    return jax.checkpoint(lambda p, h: _head(p, h, labels, cfg))(p, h)


def loss_and_grad(params, batch, cfg, feed_names, trainable, micro):
    """(loss, {name: gradient}) of the mean over the batch's tokens of the
    cross entropy, the batch's sequences one at a time by a scan that is
    differentiated as a whole (`micro` is the harness's count of parts; a
    part here is always one sequence).  A sequence keeps its layers'
    inputs, [S, n, C] each, and nothing else (the layers and the head are
    checkpointed one by one), so the part itself is not checkpointed:
    that would run, and compile, every layer's forward a third time."""
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

        return jax.lax.scan(part, jnp.float32(0), (tokens, labels))[0]

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(total)(free)
