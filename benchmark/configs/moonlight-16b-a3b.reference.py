"""Plain reference of moonlight-16b-a3b: forward, loss and gradient in fp32
jax.numpy under jax.default_matmul_precision("highest"), written from the
equations in benchmark/configs/moonlight-16b-a3b.json (`equations`,
`assumed`, `deployment`) and the parameter names of
paddle_tpu/models/expert_decoder.py, and from nothing else of the program:
no op, no kernel, no sort, no row buffer, no grouped matmul, no AMP tier.
A Python `for` runs the layers; attention is a softmax over masked scores;
the experts held here each run over every token (one batched product over
the experts' axis: eight separate ones compile for twice as long), weighted
by a gate that is 0 where the token did not choose it.

The chip's share is the configuration's: experts `expert_offset` ..
`expert_offset + n_routed_experts` of the router's `router_experts`, the
gates normalised over all the chosen, held or not; what the absent experts
would add is left out, here as in the program.  With `n_routed_experts` =
`router_experts` the same code is the uncut layer (tier-1 adds the shares
up against it).

Memory: the gradient is that of a lax.scan over the batch's rows with a
checkpointed body, so the parameters' cotangent accumulates in the carry of
the scan's transpose: ONE gradient-sized buffer and one row's activations,
beside the program's state (PERF.md 4).  jax.checkpoint around a layer and
around the head bounds what a row keeps; neither changes a number."""

import jax
import jax.numpy as jnp


def _mm(x, w):
    return jnp.matmul(x, w)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rotary(x, theta):
    """x [..., S, D]: pair i is (x[i], x[i + D/2]), turned by the angle
    position * theta^(-2i/D) (half-split, `assumed.rotary_layout`)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                         / x.shape[-1])
    angle = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _score_scale(cfg):
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5


def _latent(p, x, name, cfg):
    """(c, k_rope) of W_kva x: the normalised latent and the rotary key
    part, one a token."""
    r = cfg["kv_lora_rank"]
    kva = _mm(x, p[name + "_kva_w"])
    return (_rms_norm(kva[..., :r], p[name + "_kvn_scale"],
                      cfg["rms_norm_eps"]),
            _rotary(kva[..., r:], cfg["rope_theta"]))


def _mla(p, x, name, cfg):
    B, S, _ = x.shape
    H = cfg["num_attention_heads"]
    dn, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]

    def heads(t):                                        # [B, H, S, width]
        return t.reshape(B, S, H, -1).transpose(0, 2, 1, 3)

    q = heads(_mm(x, p[name + "_q_w"]))
    q_nope, q_rope = q[..., :dn], _rotary(q[..., dn:], cfg["rope_theta"])
    c, k_rope = _latent(p, x, name, cfg)
    kv = heads(_mm(c, p[name + "_kvb_w"]))
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scores = (jnp.einsum("bhqd,bhkd->bhqk", q_nope, k_nope)
              + jnp.einsum("bhqd,bkd->bhqk", q_rope, k_rope)) \
        * _score_scale(cfg)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    weights = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", weights, v)
    return _mm(ctx.transpose(0, 2, 1, 3).reshape(B, S, H * dv),
               p[name + "_o_w"])


def _mlp(p, x, name):
    gate = jax.nn.silu(_mm(x, p[name + "_gate_w"]))
    return _mm(gate * _mm(x, p[name + "_up_w"]), p[name + "_down_w"])


def _scores(logits):
    return jax.nn.sigmoid(logits)


def _gates(p, x, name, cfg):
    """g [..., router_experts]: for the top-k of s + b the score s (without
    b) over the sum of the chosen ones' scores, times the scaling factor;
    0 for every other expert."""
    s = _scores(_mm(x, p[name + "_router_w"]))
    choice = s + p[name + "_router_bias"]
    kth = jnp.sort(choice, axis=-1)[..., -cfg["num_experts_per_tok"]]
    g = jnp.where(choice >= kth[..., None], s, 0.0)
    if cfg["norm_topk_prob"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return g * cfg["routed_scaling_factor"]


def _held_experts(cfg):
    return range(cfg["n_routed_experts"])


def _expert_block(p, x, name, cfg):
    """Every held expert over every token, as one product batched over the
    experts' axis (no sort, no grouping), weighted by a gate that is 0
    where the token did not choose the expert."""
    held = jnp.asarray(list(_held_experts(cfg)))
    gate_w, up_w, down_w = (p[f"{name}_experts_{w}_w"][held]
                            for w in ("gate", "up", "down"))
    g = _gates(p, x, name, cfg)[..., cfg["expert_offset"] + held]
    xe = x[..., None, :, :]                               # [..., 1, S, d]
    hidden = jax.nn.silu(_mm(xe, gate_w)) * _mm(xe, up_w)  # [..., E, S, f]
    routed = _mm(hidden, down_w) * jnp.swapaxes(g, -1, -2)[..., None]
    return _mlp(p, x, name + "_shared") + jnp.sum(routed, axis=-3)


def _layer(p, h, i, cfg):
    eps, n = cfg["rms_norm_eps"], f"l{i}"
    a = h + _mla(p, _rms_norm(h, p[n + "_n1_scale"], eps), n + "_attn", cfg)
    x = _rms_norm(a, p[n + "_n2_scale"], eps)
    if i < cfg["first_k_dense_replace"]:
        return a + _mlp(p, x, n + "_mlp")
    return a + _expert_block(p, x, n, cfg)


def _head(p, h, labels, cfg):
    h = _rms_norm(h, p["final_scale"], cfg["rms_norm_eps"])
    logp = jax.nn.log_softmax(_mm(h, p["head_w"]), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def _token_losses(p, tokens, labels, cfg):
    """[B, S]: the cross entropy of every token."""
    h = jnp.take(p["embed"], tokens, axis=0)
    for i in range(cfg["num_hidden_layers"]):
        h = jax.checkpoint(lambda p, h, i=i: _layer(p, h, i, cfg))(p, h)
    return jax.checkpoint(lambda p, h: _head(p, h, labels, cfg))(p, h)


def loss_and_grad(params, batch, cfg, feed_names, trainable, micro):
    """(loss, {name: gradient}) of the mean over the batch's tokens, the
    batch taken in `micro` strided parts (rows i, i + micro, ...) by a
    scan that is differentiated as a whole."""
    params = {k: v.astype(jnp.float32) for k, v in params.items()}
    fixed = {k: v for k, v in params.items() if k not in trainable}
    free = {k: v for k, v in params.items() if k in trainable}
    parts = {n: jnp.swapaxes(
        v.reshape((v.shape[0] // micro, micro) + v.shape[1:]), 0, 1)
        for n, v in batch.items()}
    tokens, labels = (parts[n] for n in feed_names)
    count = float(tokens.size)

    def total(free):
        def part(cost, one):
            return cost + jnp.sum(_token_losses(
                {**fixed, **free}, *one, cfg)) / count, None

        return jax.lax.scan(jax.checkpoint(part), jnp.float32(0),
                            (tokens, labels))[0]

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(total)(free)
