"""Plain reference of phi-4-mini-flash: forward, loss and gradient in fp32
jax.numpy under jax.default_matmul_precision("highest"), written from the
equations in benchmark/configs/phi-4-mini-flash.json (`equations`,
`assumed`) and the parameter names of paddle_tpu/models/sambay_decoder.py,
and from nothing else of the program: no op, no kernel, no recurrence
construct, no AMP tier, no hand-written backward.

The selective scan as written, one token after the other (`_scan_tokens`:
a lax.scan whose step is the recurrence's one line); differential attention
as dense masked scores, a block of `query_block` queries against ALL keys
at a time, both maps of all head pairs at once (`_diff_attention`); the
memory and the key/value pair are plain Python values that later layers
read.

jax.checkpoint around a layer, a run of `scan_block` tokens of the scan, a
block of queries, a block of `head_block` rows of the head and of an MLP
and a block of `channel_block` channels of a Mamba mixer only bounds what
the backward pass keeps (a token's state is [E, N] fp32: kept for
every token it would be 2.7 GB a layer; the logits [8192, 25008] fp32 are
0.8 GB); the blocks are a lax.scan so that the executable holds one block's
code: neither changes a number.  The small functions (_mm,
_step, _d_term, _memory, _memory_layer, _window, _lambda, _out_scale,
_keys_of) are what tools/sambay_reference_probe.py replaces, one at a time,
to make the wrong rules the tolerances have to refuse."""

import math

import jax
import jax.numpy as jnp

MAMBA, MEMORY, SLIDING, FULL, GMU, CROSS = (
    "mamba", "memory", "sliding", "full", "gmu", "cross")


def kinds(cfg):
    """A layer's kind: `self_decoder_periods` x (mamba, sliding), the layer
    that hands out the memory, the one that hands out K and V,
    `cross_decoder_periods` x (gmu, cross)."""
    return (MAMBA, SLIDING) * cfg["self_decoder_periods"] \
        + (MEMORY, FULL) + (GMU, CROSS) * cfg["cross_decoder_periods"]


def _mm(x, w):
    return jnp.matmul(x, w)


def _ln(x, p, name, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p[name + "_scale"] \
        + p[name + "_bias"]


def _step(dt, bias):
    return jax.nn.softplus(dt + bias)


def _d_term(d, x):
    return d * x


def _memory(y, z):
    """What a memory layer hands out, of the scan's output y and the gate's
    input z: y, before the gate."""
    del z
    return y


def _memory_layer(layer_kinds):
    """The layer whose memory the GMUs read."""
    return layer_kinds.index(MEMORY)


def _window(kind, cfg):
    return cfg["sliding_window"] if kind == SLIDING else None


def _lambda(lq1, lk1, lq2, lk2, lambda_0):
    return jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) \
        + lambda_0


def _out_scale(lambda_0):
    return 1.0 - lambda_0


def _keys_of(k1, k2):
    """(the keys q1 is scored against, the keys q2 is)."""
    return k1, k2


def lambda_init(layer):
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _conv(x, w, b):
    """Depthwise causal convolution of x [S, E] with w [taps, E]: the last
    tap on the position itself, zeros before the first."""
    taps, S = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x])
    return sum(padded[j:j + S] * w[j] for j in range(taps)) + b


def _scan_tokens(x, dt, a, b, c, d, block):
    """y [S, E] of the recurrence, token by token from a zero state: s =
    exp(dt_t (x) A) * s + (dt_t x_t) (x) B_t; y_t = s C_t + D x_t."""
    S = x.shape[0]

    def token(s, one):
        x_t, dt_t, b_t, c_t = one
        s = jnp.exp(dt_t[:, None] * a) * s + (dt_t * x_t)[:, None] * b_t[None]
        return s, s @ c_t + _d_term(d, x_t)

    def run(s, some):
        return jax.lax.scan(token, s, some)

    block = min(block, S)
    pad = -S % block          # tokens of dt = 0 leave the state as it is
    xs = tuple(jnp.pad(t, ((0, pad), (0, 0))).reshape(
        (-1, block) + t.shape[1:]) for t in (x, dt, b, c))
    _, y = jax.lax.scan(lambda s, some: jax.checkpoint(run)(s, some),
                        jnp.zeros(a.shape), xs)
    return y.reshape(-1, x.shape[1])[:S]


def _mamba(p, u, name, cfg):
    """(Mamba(u), what the layer would hand out as its memory), `channel_
    block` channels at a time: the convolution, the scan and the gate are a
    channel's own, so a block of channels goes from u to its term of the
    output map alone; only B and C (and dt's low-rank input), which every
    channel feeds, are made first, from all blocks."""
    d, E = cfg["hidden_size"], cfg["mamba_expand"] * cfg["hidden_size"]
    N, R = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    S = u.shape[0]
    width = min(int(cfg["reference"].get("channel_block", E)), E)
    n = E // width
    assert n * width == E, (E, width)

    def columns(w):          # [rows, E] -> [n, rows, width]
        return w.reshape(w.shape[0], n, width).transpose(1, 0, 2)

    def block_rows(w):       # [E, columns] -> [n, width, columns]
        return w.reshape((n, width) + w.shape[1:])

    w_in = p[name + "_in_w"]
    made_x = (columns(w_in[:, :E]), columns(p[name + "_conv_w"]),
              block_rows(p[name + "_conv_b"]))

    def x_of(u, w, conv_w, conv_b):
        return jax.nn.silu(_conv(_mm(u, w), conv_w, conv_b))

    def fed(u, w_x, *w):
        return _mm(x_of(u, *w), w_x)

    rbc, _ = jax.lax.scan(
        lambda acc, w: (acc + jax.checkpoint(fed)(u, *w), None),
        jnp.zeros((S, R + 2 * N)), (block_rows(p[name + "_x_w"]),) + made_x)
    r, b, c = rbc[:, :R], rbc[:, R:R + N], rbc[:, R + N:]

    def channels(u, r, b, c, w_z, w_dt, dt_b, a_log, d_, w_out, *w):
        x, z = x_of(u, *w), _mm(u, w_z)
        y = _scan_tokens(x, _step(_mm(r, w_dt), dt_b), -jnp.exp(a_log), b, c,
                         d_, int(cfg["reference"]["scan_block"]))
        return _mm(y * jax.nn.silu(z), w_out), _memory(y, z)

    def one(acc, w):
        out, memory = jax.checkpoint(channels)(u, r, b, c, *w)
        return acc + out, memory

    out, memory = jax.lax.scan(one, jnp.zeros((S, d)), (
        columns(w_in[:, E:]), columns(p[name + "_dt_w"]),
        block_rows(p[name + "_dt_b"]), block_rows(p[name + "_a_log"]),
        block_rows(p[name + "_d"]), block_rows(p[name + "_out_w"])) + made_x)
    return out, memory.transpose(1, 0, 2).reshape(S, E)


def _gmu(p, u, memory, name):
    return _mm(memory * jax.nn.silu(_mm(u, p[name + "_in_w"])),
               p[name + "_out_w"])


def _diff_attention(p, q, k, v, name, layer, window, cfg):
    """q [S, H D], k, v [S, G D] -> [S, H D]: pair j's two maps and their
    difference, normed and scaled."""
    S = q.shape[0]
    H, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = cfg["hidden_size"] // H
    block = min(int(cfg["reference"]["query_block"]), S)
    qp = q.reshape(S, H // 2, 2, D)
    kp = k.reshape(S, G // 2, 2, D)
    vp = v.reshape(S, G // 2, 2 * D)
    rep = (H // 2) // (G // 2)
    # pair j reads key/value pair j // rep
    k1, k2 = _keys_of(jnp.repeat(kp[:, :, 0], rep, axis=1),
                      jnp.repeat(kp[:, :, 1], rep, axis=1))
    vj = jnp.repeat(vp, rep, axis=1)
    lambda_0 = lambda_init(layer)
    lam = _lambda(*(p[f"{name}_lambda_{m}"] for m in ("q1", "k1", "q2", "k2")),
                  lambda_0)
    s = jnp.arange(S)

    def one_block(q_blk, t):
        sees = s[None, :] <= t[:, None]
        if window is not None:
            sees &= t[:, None] - s[None, :] < window

        def attend(q_i, k_i):
            scores = jnp.einsum("qjd,sjd->jqs", q_i, k_i) / math.sqrt(D)
            return jnp.einsum(
                "jqs,sjw->qjw",
                jax.nn.softmax(jnp.where(sees, scores, -1e30), axis=-1), vj)

        diff = attend(q_blk[:, :, 0], k1) - lam * attend(q_blk[:, :, 1], k2)
        normed = diff * jax.lax.rsqrt(
            jnp.mean(diff * diff, axis=-1, keepdims=True)
            + cfg["layer_norm_eps"])
        return normed * p[name + "_subln_scale"] * _out_scale(lambda_0)

    pad = -S % block
    blocks = jnp.pad(qp, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
        (-1, block) + qp.shape[1:])
    # a padded query stands at the last position: its row is cut below
    times = jnp.minimum(jnp.arange(S + pad), S - 1).reshape(-1, block)
    _, out = jax.lax.scan(
        lambda carry, one: (carry, jax.checkpoint(one_block)(*one)),
        None, (blocks, times))
    return out.reshape(S + pad, H * D)[:S]


def _attention(p, u, name, kind, layer, shared, cfg):
    """(Attn(u), the layer's own (k, v) or None in a cross layer)."""
    d = cfg["hidden_size"]
    kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    if kind == CROSS:
        q, (k, v), own = _mm(u, p[name + "_q_w"]) + p[name + "_q_b"], \
            shared, None
    else:
        qkv = _mm(u, p[name + "_qkv_w"]) + p[name + "_qkv_b"]
        q, k, v = qkv[:, :d], qkv[:, d:d + kv], qkv[:, d + kv:]
        own = (k, v)
    ctx = _diff_attention(p, q, k, v, name, layer, _window(kind, cfg), cfg)
    return _mm(ctx, p[name + "_o_w"]) + p[name + "_o_b"], own


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
    width = cfg["intermediate_size"]

    def rows(x, w1, w2):
        gu = _mm(x, w1)
        return _mm(jax.nn.silu(gu[:, :width]) * gu[:, width:], w2)

    return _by_rows(rows, x, cfg["reference"].get("head_block", x.shape[0]),
                    p[name + "_1_w"], p[name + "_2_w"])


def _layer(p, h, i, kind, memory, shared, cfg):
    """(h', the memory this layer makes or None, its (k, v) or None)."""
    eps, n = cfg["layer_norm_eps"], f"l{i}"
    u = _ln(h, p, n + "_n1", eps)
    made = own = None
    if kind in (MAMBA, MEMORY):
        mixed, made = _mamba(p, u, n + "_ssm", cfg)
    elif kind == GMU:
        mixed = _gmu(p, u, memory, n + "_gmu")
    else:
        mixed, own = _attention(p, u, n + "_attn", kind, i, shared, cfg)
    a = h + mixed
    return a + _mlp(p, _ln(a, p, n + "_n2", eps), n + "_mlp", cfg), \
        made, own


def _final_states(p, tokens, cfg):
    """LN_f(h_L) [S, d] of one sequence of tokens [S]."""
    layer_kinds = kinds(cfg)
    h = jnp.take(p["embed"], tokens, axis=0)
    memories, shared = {}, None
    for i, kind in enumerate(layer_kinds):
        memory = memories.get(_memory_layer(layer_kinds))
        h, made, own = jax.checkpoint(
            lambda p, h, memory, shared, i=i, kind=kind: _layer(
                p, h, i, kind, memory, shared, cfg))(p, h, memory, shared)
        if made is not None:
            memories[i] = made
        if kind == FULL:
            shared = own
    return _ln(h, p, "final", cfg["layer_norm_eps"])


def _logits(p, tokens, cfg):
    """[S, V] of one sequence of tokens [S]: the tied table transposed."""
    return _mm(_final_states(p, tokens, cfg), p["embed"].T)


def _sequence_loss(p, tokens, labels, cfg):
    """The SUM of one sequence's cross entropies, `head_block` rows of
    logits at a time."""
    states = _final_states(p, tokens, cfg)
    S = states.shape[0]
    block = min(int(cfg["reference"].get("head_block", S)), S)
    pad = -S % block

    def rows(table, some):
        h, lab, real = some
        logp = jax.nn.log_softmax(_mm(h, table.T), axis=-1)
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
