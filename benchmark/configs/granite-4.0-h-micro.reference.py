"""Plain reference of granite-4.0-h-micro: forward, loss and gradient in fp32
jax.numpy under jax.default_matmul_precision("highest"), written from the
equations in benchmark/configs/granite-4.0-h-micro.json (`equations`,
`assumed`) and the parameter names of paddle_tpu/models/ssd_hybrid_decoder.py,
and from nothing else of the program: no op, no kernel, no recurrence
construct, no AMP tier, no hand-written backward, and never the chunked form
of the scan.

Mamba-2's recurrence as written, one token after the other (`_scan_tokens`:
a lax.scan whose step is the recurrence's one line, a head's state [P, N]);
attention as dense masked scores, a block of `query_block` queries against
ALL keys at a time; a Python loop over the layers; the same share of the
heads as the configuration holds (`mamba_heads_held`, `attention_heads_held`,
`key_value_heads_held`: what the other chips of the group would add is left
out here as in the program).

jax.checkpoint around a layer, a run of `scan_block` tokens of the scan, a
block of queries and a block of `head_block` rows of the head and of an MLP
only bounds what the backward pass keeps (a token's state is [H, P, N]
fp32, 1 MB at 32 heads: kept for every token it would be 8.6 GB a layer);
the blocks are a lax.scan so that the executable holds one block's code:
neither changes a number.  The small functions (_mm, _carried, _softmax,
_normed) are what tools/granite_reference_probe.py replaces, one at a
time, to make the wrong rules the tolerances have to refuse."""

import jax
import jax.numpy as jnp

MAMBA, ATTENTION = "mamba", "attention"


def kinds(cfg):
    """A layer's kind: the first `num_hidden_layers` of `layer_types`."""
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _mm(x, w):
    return jnp.matmul(x, w)


def _carried(s):
    """The state a token hands the next: as it is."""
    return s


def _softmax(scores):
    return jax.nn.softmax(scores, axis=-1)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w


def _conv(x, w, b):
    """Depthwise causal convolution of x [S, E] with w [taps, E]: the last
    tap on the position itself, zeros before the first."""
    taps, S = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x])
    return sum(padded[j:j + S] * w[j] for j in range(taps)) + b


def _scan_tokens(x, dt, a, b, c, d, block):
    """y [S, H, P] of the recurrence, token by token from a zero state: x
    [S, H, P], dt [S, H], a, d [H], b, c [S, G, N]; s [H, P, N] =
    exp(dt_t a) s + (dt_t x_t) (x) B_t; y_t = s C_t + D x_t, head h with
    the B and C of group h // (H / G)."""
    S, H, P = x.shape
    G, N = b.shape[1:]

    def token(s, one):
        x_t, dt_t, b_t, c_t = one
        b_t, c_t = (jnp.repeat(t, H // G, axis=0) for t in (b_t, c_t))
        s = _carried(jnp.exp(dt_t * a)[:, None, None] * s
                     + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, c_t) + d[:, None] * x_t

    def run(s, some):
        return jax.lax.scan(token, s, some)

    block = min(block, S)
    pad = -S % block          # tokens of dt = 0 leave the state as it is
    xs = tuple(jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)).reshape(
        (-1, block) + t.shape[1:]) for t in (x, dt, b, c))
    _, y = jax.lax.scan(lambda s, some: jax.checkpoint(run)(s, some),
                        jnp.zeros((H, P, N)), xs)
    return y.reshape((-1, H, P))[:S]


def _mamba_scanned(p, u, name, cfg):
    """(y [S, E], the scan's output with its D term; z [S, E], the gate's
    input)."""
    H, P = cfg["mamba_heads_held"], cfg["mamba_d_head"]
    N, G = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    E, S = H * P, u.shape[0]
    zxbcdt = _mm(u, p[name + "_in_w"])
    z, xbc, dt = (zxbcdt[:, :E], zxbcdt[:, E:2 * E + 2 * G * N],
                  zxbcdt[:, 2 * E + 2 * G * N:])
    xbc = jax.nn.silu(_conv(xbc, p[name + "_conv_w"], p[name + "_conv_b"]))
    x, b, c = xbc[:, :E], xbc[:, E:E + G * N], xbc[:, E + G * N:]
    y = _scan_tokens(
        x.reshape(S, H, P), jax.nn.softplus(dt + p[name + "_dt_b"]),
        -jnp.exp(p[name + "_a_log"]), b.reshape(S, G, N), c.reshape(S, G, N),
        p[name + "_d"], int(cfg["reference"]["scan_block"]))
    return y.reshape(S, E), z


def _mamba_gated(p, u, name, cfg):
    """g [S, E] = y * silu(z): the scan's output under its gate, before the
    norm."""
    y, z = _mamba_scanned(p, u, name, cfg)
    return y * jax.nn.silu(z)


def _gated_norm(g, w, cfg):
    """One mean square over each of `mamba_n_groups` runs of the channels
    held here, a weight a channel."""
    S, E = g.shape
    by_group = g.reshape(S, cfg["mamba_n_groups"], -1)
    return (by_group * jax.lax.rsqrt(
        jnp.mean(by_group * by_group, axis=-1, keepdims=True)
        + cfg["rms_norm_eps"])).reshape(S, E) * w


def _normed(y, z, w, cfg):
    """The gate FIRST, then the norm."""
    return _gated_norm(y * jax.nn.silu(z), w, cfg)


def _mamba(p, u, name, cfg):
    y, z = _mamba_scanned(p, u, name, cfg)
    return _mm(_normed(y, z, p[name + "_norm_scale"], cfg),
               p[name + "_out_w"])


def _attention(p, u, name, cfg):
    """q [S, Hq D] over k, v [S, Hk D]: query head j reads key/value head
    j // (Hq / Hk); scores times `attention_multiplier`; no positions."""
    S = u.shape[0]
    Hq, Hk = cfg["attention_heads_held"], cfg["key_value_heads_held"]
    q, k, v = (_mm(u, p[f"{name}_{m}_w"]) for m in "qkv")
    D = q.shape[1] // Hq
    q = q.reshape(S, Hk, Hq // Hk, D)
    k, v = k.reshape(S, Hk, D), v.reshape(S, Hk, D)
    block = min(int(cfg["reference"]["query_block"]), S)
    s = jnp.arange(S)

    def one_block(q_blk, t):
        scores = jnp.einsum("qgrd,sgd->grqs", q_blk, k) \
            * cfg["attention_multiplier"]
        seen = jnp.where(s[None, :] <= t[:, None], scores, -1e30)
        return jnp.einsum("grqs,sgd->qgrd", _softmax(seen), v)

    pad = -S % block
    blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
        (-1, block) + q.shape[1:])
    # a padded query stands at the last position: its row is cut below
    times = jnp.minimum(jnp.arange(S + pad), S - 1).reshape(-1, block)
    _, out = jax.lax.scan(
        lambda carry, one: (carry, jax.checkpoint(one_block)(*one)),
        None, (blocks, times))
    return _mm(out.reshape(S + pad, Hq * D)[:S], p[name + "_o_w"])


def _by_rows(fn, x, block, *weights):
    """fn(rows, *weights) over blocks of `block` rows of x [S, .], one
    block's values alive at a time."""
    S = x.shape[0]
    block = min(int(block), S)
    pad = -S % block
    blocks = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, x.shape[1])
    _, out = jax.lax.scan(
        lambda carry, rows: (carry, jax.checkpoint(fn)(rows, *weights)),
        None, blocks)
    return out.reshape(S + pad, -1)[:S]


def _mlp(p, x, name, cfg):
    width = cfg["shared_intermediate_size"]

    def rows(x, w1, w2):
        gu = _mm(x, w1)
        return _mm(jax.nn.silu(gu[:, :width]) * gu[:, width:], w2)

    return _by_rows(rows, x, cfg["reference"].get("head_block", x.shape[0]),
                    p[name + "_1_w"], p[name + "_2_w"])


def _layer(p, h, i, kind, cfg):
    eps, n, r = cfg["rms_norm_eps"], f"l{i}", cfg["residual_multiplier"]
    u = _rms(h, p[n + "_n1_scale"], eps)
    mixed = _mamba(p, u, n + "_ssm", cfg) if kind == MAMBA \
        else _attention(p, u, n + "_attn", cfg)
    a = h + r * mixed
    return a + r * _mlp(p, _rms(a, p[n + "_n2_scale"], eps), n + "_mlp", cfg)


def _final_states(p, tokens, cfg):
    """RMS_f(h_L) [S, d] of one sequence of tokens [S]."""
    h = cfg["embedding_multiplier"] * jnp.take(p["embed"], tokens, axis=0)
    for i, kind in enumerate(kinds(cfg)):
        h = jax.checkpoint(
            lambda p, h, i=i, kind=kind: _layer(p, h, i, kind, cfg))(p, h)
    return _rms(h, p["final_scale"], cfg["rms_norm_eps"])


def _logits(p, tokens, cfg):
    """[S, V] of one sequence of tokens [S]: the tied table transposed, the
    product divided by `logits_scaling`."""
    return _mm(_final_states(p, tokens, cfg), p["embed"].T) \
        / cfg["logits_scaling"]


def _sequence_loss(p, tokens, labels, cfg):
    """The SUM of one sequence's cross entropies, `head_block` rows of
    logits at a time."""
    states = _final_states(p, tokens, cfg)
    S = states.shape[0]
    block = min(int(cfg["reference"].get("head_block", S)), S)
    pad = -S % block

    def rows(table, some):
        h, lab, real = some
        logp = jax.nn.log_softmax(
            _mm(h, table.T) / cfg["logits_scaling"], axis=-1)
        ce = -jnp.take_along_axis(logp, lab[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(real, ce, 0.0))

    blocks = (jnp.pad(states, ((0, pad), (0, 0))).reshape(-1, block,
                                                          states.shape[1]),
              jnp.pad(labels, (0, pad)).reshape(-1, block),
              (jnp.arange(S + pad) < S).reshape(-1, block))
    _, parts = jax.lax.scan(
        lambda carry, some: (carry, jax.checkpoint(rows)(p["embed"], some)),
        None, blocks)
    return jnp.sum(parts)


def loss_and_grad(params, batch, cfg, feed_names, trainable, micro):
    """(loss, {name: gradient}) of the mean cross entropy over the batch's
    tokens; the sequences one after the other in a scan, their gradients
    summed (`micro` is the harness's; a part is one sequence whatever it
    says)."""
    del micro
    params = {k: v.astype(jnp.float32) for k, v in params.items()}
    fixed = {k: v for k, v in params.items() if k not in trainable}
    free = {k: v for k, v in params.items() if k in trainable}
    tokens, labels = (batch[n] for n in feed_names)
    count = float(tokens.shape[0] * tokens.shape[1])

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
