"""Fused attention op backed by the Pallas flash kernel.

TPU-native addition (the reference composes attention from matmul/softmax
ops, python/paddle/fluid/nets.py scaled_dot_product_attention).  One op =
one flash kernel on TPU; key-padding comes in as lengths instead of an
additive [Sq, Sk] bias tensor, so nothing score-shaped ever hits HBM.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core import amp
from ..core.proto import DataType
from ..core.registry import register_op
from ..observability import span
from .common import data, in_desc, same_shape, set_output


# what layers.rotary_embedding's `yarn` holds, each an attr `yarn_<key>`
YARN_KEYS = ("factor", "original_length", "beta_fast", "beta_slow",
             "attention_factor")


def _fused_attn_infer(op, block):
    q = in_desc(op, block, "Q")
    if q is None:
        return
    set_output(block, op, "Out", list(q.shape), q.dtype)


def _attend(ctx, sp, q, k, v, klen, causal, scale, window=None, heads=None):
    """flash_attention of q [B, H, S, D] over k [B, G, S, D] and v [B, G,
    S, Dv] (G = H, or a divisor of it: grouped-query attention), or with
    `heads` of heads-last q [B, S, heads * D] over k [B, S, G * D] and v
    [B, S, G * Dv], under a shard_map where the program runs on a mesh of
    several devices.  The one door of `fused_attention` and
    `latent_attention` to the kernel: it says on the op's span `sp` what
    the site holds through the recomputation of the unit around it (`kept`,
    `kept_bytes`: the kernel's output and logsumexp where its backward is
    the Pallas kernel, nothing where it is the XLA recompute) and adds the
    values to the context's `kept`, and which layout the kernel takes
    (`layout`: bshd where heads-last operands go to it as they lie, bhsd
    where they are transposed first, or came heads-first)."""
    from ..kernels import engine, flash_attention
    from ..kernels.flash_attention import (
        heads_first_shapes, kept, kept_bytes, takes_heads_last)

    names = kept(q, k, v, causal, window, heads=heads)
    first = (q, k, v) if heads is None else heads_first_shapes(q, k, v, heads)
    sp.set(kept=",".join(names),
           kept_bytes=kept_bytes(first[0], first[2]) if names else 0)
    ctx.kept += len(names)
    head_dim = heads and q.shape[2] // heads

    def attend(q, k, v, klen=None):
        # a device's own heads, where a mesh cuts them
        local = heads and q.shape[2] // head_dim
        sp.set(layout="bshd" if heads and takes_heads_last(
            q, k, v, local, window) else "bhsd")
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               k_lengths=klen, window=window, heads=local)

    # the mesh rule (kernels/engine.py): on several devices a shard_map
    attend = engine.shard_over_mesh(attend, ctx.mesh, first[1].shape[1],
                                    klen is not None, heads is not None)
    return attend(*((q, k, v) + ((klen,) if klen is not None else ())))


@register_op("fused_attention", infer_shape=_fused_attn_infer,
             diff_inputs=["Q", "K", "V"])
def _fused_attention(ctx, ins, attrs):
    """Attention of Q [B, H, Sq, D] over K, V [B, G, Sk, .] (G = H or a
    divisor of it), `causal`, under a `window`, keys cut at KLengths: one
    flash kernel (kernels/flash_attention.py).  With the attr `n_head`
    (which a MODEL sets: no flag) the operands and the output are
    heads-last, Q [B, Sq, n_head * D] over K, V [B, Sk, G * .], the arrays
    the projections write: where a head is one block the kernels take them
    as they lie, at every other shape the call transposes inside itself and
    runs the heads-first path, the same numbers (`attn.lower` says `layout`:
    bshd | bhsd).  Where the site's backward
    is the Pallas kernel (by the shape) the kernel tags its output and
    logsumexp to survive the recomputation of the unit around the op
    (core.compiler.keep): the backward of a recomputed layer runs no second
    forward of this op.  `attn.lower` (a span, at lowering) says what a
    site was given, `kept` and `kept_bytes` what it holds through that
    recomputation ("" and 0 on the XLA recompute backward); the context's
    `kept` counts the values."""
    klen_in = ins.get("KLengths", [None])[0]
    out = _site(ctx, data(ins["Q"][0]), data(ins["K"][0]), data(ins["V"][0]),
                data(klen_in).reshape(-1) if klen_in is not None else None,
                bool(attrs.get("causal", False)), attrs.get("scale") or None,
                int(attrs.get("window", 0)) or None,
                int(attrs.get("n_head", 0)) or None,
                str(attrs.get("rope") or "none"))
    return {"Out": [out]}


def _site(ctx, q, k, v, klen, causal, scale, window, heads, rope):
    """One attention site under its `attn.lower` span: q [B, H, Sq, D], or
    with `heads` [B, Sq, heads * D]."""
    from ..kernels.flash_attention import _visible_pairs, heads_first_shapes

    first = (q, k, v) if heads is None else heads_first_shapes(q, k, v, heads)
    (_, n_head, sq, _), (_, kv_heads, sk, _) = first[0].shape, first[1].shape
    seen = None if window is None or window >= sk else window
    with span("attn.lower", kind="full" if seen is None else "sliding",
              window=int(seen or 0), heads=int(n_head),
              kv_heads=int(kv_heads), sq=int(sq),
              pairs=_visible_pairs(sq, sk, causal, seen), rope=rope) as sp:
        return _attend(ctx, sp, q, k, v, klen, causal, scale, window, heads)


def difference_of_maps(a1, a2, lam, scale, lambda_init, eps):
    """Differential attention's combination (Ye et al., arXiv:2410.05258)
    of two softmax maps' outputs a1, a2 [B, P, S, W] of P head pairs: (1 -
    lambda_init) RMSNorm_W(a1 - lam a2) scale, the statistic and the
    difference fp32, a1's dtype out."""
    diff = a1.astype(jnp.float32) - lam * a2.astype(jnp.float32)
    normed = diff * jax.lax.rsqrt(
        jnp.mean(jnp.square(diff), axis=-1, keepdims=True) + eps)
    return (normed * scale.astype(jnp.float32)
            * (1.0 - lambda_init)).astype(a1.dtype)


@register_op("differential_attention", infer_shape=_fused_attn_infer,
             diff_inputs=["Q", "K", "V", "LambdaQ1", "LambdaK1", "LambdaQ2",
                          "LambdaK2", "Scale"])
def _differential_attention(ctx, ins, attrs):
    """Differential attention (Ye et al., arXiv:2410.05258) of heads-last
    operands, Q [B, Sq, H D] over K, V [B, Sk, G D], H = `n_head` and G a
    divisor of it, both even.  Heads go in adjacent pairs: pair j's q1, q2
    are query heads 2j, 2j + 1; its k1, k2 the two key heads of key/value
    pair j // (H / G) and its value that pair's two value heads side by
    side (2 D wide).  A1 = softmax(q1 k1^T / sqrt(D) + mask) v, A2 likewise
    of q2, k2; lambda = exp(LambdaQ1 . LambdaK1) - exp(LambdaQ2 . LambdaK2)
    + `lambda_init` (four learned [D] vectors, fp32); the pair's output is
    (1 - lambda_init) RMSNorm_2D(A1 - lambda A2) Scale [2 D] (`epsilon`
    under the root).  Out [B, Sq, H D], pair j's 2 D columns at 2 D j.  The
    mask is `causal`, under `window` also t - s < window.

    A composition, no kernel of its own: the two maps are two sites of
    flash_attention at H / 2 heads over G / 2, head D reading values 2 D
    wide (each under its own `attn.lower` span, kept and sharded over a
    mesh as fused_attention's are: `_attend`), lambda, the norm and the
    scale jax.numpy around them."""
    q, k, v = amp.mxu_operands(*(data(ins[s][0]) for s in ("Q", "K", "V")))
    lq1, lk1, lq2, lk2 = (data(ins[s][0]).astype(jnp.float32) for s in (
        "LambdaQ1", "LambdaK1", "LambdaQ2", "LambdaK2"))
    H = int(attrs["n_head"])
    B, Sq, width = q.shape
    D = width // H
    G, Sk = k.shape[2] // D, k.shape[1]
    if H % 2 or G % 2 or H % G:
        raise ValueError(f"differential_attention: {H} query heads over "
                         f"{G} key/value heads do not pair")
    lambda_init = float(attrs["lambda_init"])

    def halves(t, n, s):        # [B, s, n D] -> two [B, n / 2, s, D]
        t = t.reshape(B, s, n // 2, 2, D).transpose(0, 2, 3, 1, 4)
        return t[:, :, 0], t[:, :, 1]

    (q1, q2), (k1, k2) = halves(q, H, Sq), halves(k.astype(q.dtype), G, Sk)
    wide = v.astype(q.dtype).reshape(B, Sk, G // 2, 2 * D).transpose(
        0, 2, 1, 3)
    window = int(attrs.get("window", 0)) or None
    causal = bool(attrs.get("causal", True))
    a1, a2 = (_site(ctx, qi, ki, wide, None, causal, D ** -0.5, window, None,
                    "none") for qi, ki in ((q1, k1), (q2, k2)))
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) \
        + lambda_init
    out = difference_of_maps(a1, a2, lam, data(ins["Scale"][0]), lambda_init,
                             float(attrs.get("epsilon", 1e-5)))
    return {"Out": [out.transpose(0, 2, 1, 3).reshape(B, Sq, width)]}


@register_op("eva_attention", infer_shape=_fused_attn_infer,
             diff_inputs=["Q", "K", "V", "Mu", "Phi"])
def _eva_attention(ctx, ins, attrs):
    """EVA attention in its chunked form (kernels/eva_attention.py has the
    equations): Q, K, V [B, H, S, D], rotated, H the heads held here; Mu,
    Phi [H, D] the learned vectors that pool every `chunk` keys (and their
    values) into one summary; a query runs ONE softmax over the exact keys
    of its own `window` and the summaries of every chunk of every window
    before it.  One engine: two flash_attention calls that hand out their
    logsumexp, merged exactly; for ONE TPU they are the Pallas kernels
    (`engine` flash), anywhere else, and on a mesh of several devices (XLA
    cannot partition a Mosaic kernel), flash_attention's own jax.numpy
    fallback (`engine` xla).  The pooling is jax.numpy in both, under the
    name scope `eva.pool`; the attention under `eva.attend`.  Where the
    flash calls' backward is the Pallas kernel each keeps its output and
    logsumexp through the recomputation of the unit around the op
    (core.compiler.keep), so a recomputed layer runs the pooling again and
    no kernel's forward.  `eva.lower` (a span, at lowering) says what a
    site was given: `windows`, `chunks` (those pooled: every window's but
    the last), `heads_held`, `window_pairs` and `summary_pairs` (a head's
    and a sequence's visible pairs), `pooled_bytes` (what the pooling's
    forward has to move: K and V of the pooled positions in, 1 / chunk of
    them out), `engine`, `kept`, `kept_bytes`."""
    from ..kernels import engine, eva_attention as eva

    q, k, v = amp.mxu_operands(*(data(ins[s][0]) for s in ("Q", "K", "V")))
    mu, phi = data(ins["Mu"][0]), data(ins["Phi"][0])
    B, H, S, D = q.shape
    window, chunk = int(attrs["window"]), int(attrs["chunk"])
    geo = eva.geometry(S, window, chunk)
    own, far = eva.pairs(S, window, chunk)
    flash = engine.wants_kernels("auto", ctx.mesh)
    names, held = eva.kept_by_flash(q, geo) if flash else ((), 0)
    moved = 2 * B * H * geo["pooled"] * D * q.dtype.itemsize
    with span("eva.lower", windows=geo["windows"], chunks=geo["chunks"],
              heads_held=int(H), window=geo["window"], chunk=chunk,
              sq=int(S), window_pairs=own, summary_pairs=far,
              pooled_bytes=moved + moved // chunk,
              engine="flash" if flash else "xla",
              kept=",".join(names), kept_bytes=held):
        ctx.kept += len(names)
        out = eva.eva_attention(q, k.astype(q.dtype), v.astype(q.dtype), mu,
                                phi, window, chunk,
                                force="auto" if flash else "jax")
    return {"Out": [out]}


def _yarn_inv_freq(inv_freq, dim, base, factor, original_length, beta_fast,
                   beta_slow):
    """YaRN's frequencies (Peng et al. 2023), as the transformers library
    initialises them: pair i keeps f_i where it turns more than beta_fast
    times over `original_length` positions, takes f_i / factor where it
    turns fewer than beta_slow times, and a linear ramp of the two between
    the pairs `low` and `high` that turn just that often."""
    def pair_that_turns(times):
        return dim * np.log(original_length / (times * 2.0 * np.pi)) \
            / (2.0 * np.log(base))

    low = max(np.floor(pair_that_turns(beta_fast)), 0)
    high = min(np.ceil(pair_that_turns(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    return inv_freq * (1.0 - ramp) + inv_freq / factor * ramp


def _inv_freq(dim, base, yarn=None):
    """The dim / 2 pairs' frequencies base^(-2i/dim), under `yarn`
    _yarn_inv_freq's; float64."""
    inv_freq = float(base) ** (
        -np.arange(dim // 2, dtype=np.float64) * 2.0 / dim)
    if yarn is not None:
        inv_freq = _yarn_inv_freq(
            inv_freq, dim, float(base), float(yarn["factor"]),
            float(yarn["original_length"]), float(yarn["beta_fast"]),
            float(yarn["beta_slow"]))
    return inv_freq


def _rotary_angles(seq, dim, base, offset: int = 0, yarn=None):
    """[seq, dim / 2] fp32: position offset + t times pair i's frequency."""
    pos = np.arange(seq, dtype=np.float64) + int(offset)
    return jnp.asarray(pos[:, None] * _inv_freq(dim, base, yarn)[None, :],
                       jnp.float32)


def _rotate(x, base: float, offset: int = 0, positions=None, sections=(),
            yarn=None, rotary_dim=None):
    """Rotary position embedding (Su et al. 2021) of x [..., S, D] along
    its last two axes (under `rotary_dim` < D of the first `rotary_dim`
    features alone, as a vector of that width would turn; the others pass:
    computed at the head's full width, x times [cos, cos, 1] plus x with
    the two halves swapped times [-sin, sin, 0], so that no value narrower
    than a head is made), in the half-split layout: pair i is (x[i],
    x[i + D/2]), turned by the angle p * base^(-2i/D) (under `yarn`, a dict
    of factor, original_length, beta_fast, beta_slow and attention_factor:
    p * _yarn_inv_freq's f'_i, cos and sin times attention_factor, so that a
    score of two rotated vectors carries its square).  The position p is
    offset + the index on axis -2; or, given `positions` [B, n, S] (x then
    [B, ..., S, D]) and `sections` (n counts that add up to D/2), the b-th
    row's stream j at that index for the pairs of section j (multi-axis
    rotary: a token's temporal, height and width positions each turn their
    own share of the pairs).  The angles are fp32 whatever x is (at base
    1e6 and p in the thousands bf16 has no digit left of them); x's dtype
    out."""
    seq, dim = x.shape[-2], int(rotary_dim or x.shape[-1])
    half = dim // 2
    inv_freq = _inv_freq(dim, base, yarn)
    if positions is None:
        angle = _rotary_angles(seq, dim, base, offset, yarn)
    else:
        if sum(sections) != half or len(sections) != positions.shape[1]:
            raise ValueError(f"sections {tuple(sections)} do not cut the "
                             f"{half} pairs over {positions.shape[1]} "
                             "position streams")
        stream = np.repeat(np.arange(len(sections)), sections)
        angle = (jnp.swapaxes(positions.astype(jnp.float32), 1, 2)[..., stream]
                 * jnp.asarray(inv_freq, jnp.float32))       # [B, S, half]
        angle = angle.reshape((angle.shape[0],) + (1,) * (x.ndim - 3)
                              + angle.shape[1:])
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if yarn is not None:
        cos, sin = (t * jnp.float32(yarn["attention_factor"])
                    for t in (cos, sin))
    xs = x.astype(amp.stats_dtype(x))
    if dim < x.shape[-1]:
        one = jnp.ones(cos.shape[:-1] + (x.shape[-1] - dim,), cos.dtype)
        swapped = jnp.concatenate(
            [xs[..., half:dim], xs[..., :half], xs[..., dim:]], axis=-1)
        out = (xs * jnp.concatenate([cos, cos, one], axis=-1)
               + swapped * jnp.concatenate(
                   [-sin, sin, jnp.zeros_like(one)], axis=-1))
        return out.astype(x.dtype)
    x1, x2 = xs[..., :half], xs[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _yarn_attrs(attrs):
    """The dict `_rotate` takes from an op's `yarn_<key>` attrs, None where
    the op has none."""
    if float(attrs.get("yarn_factor", 0.0)) <= 0.0:
        return None
    return {key: float(attrs["yarn_" + key]) for key in YARN_KEYS}


@register_op("rotary_embedding", infer_shape=same_shape("X", "Out"),
             diff_inputs=["X"])
def _rotary_embedding(ctx, ins, attrs):
    pos_in = ins.get("Positions", [None])[0]
    yarn = _yarn_attrs(attrs)
    return {"Out": [_rotate(
        data(ins["X"][0]), attrs.get("base", 10000.0),
        attrs.get("offset", 0),
        None if pos_in is None else data(pos_in),
        tuple(int(n) for n in attrs.get("sections", ())), yarn,
        int(attrs.get("rotary_dim", 0)))]}


def _latent_attn_infer(op, block):
    q = in_desc(op, block, "Q")
    if q is None:
        return
    heads = int(op.attr("n_head", 1))
    set_output(block, op, "Out",
               list(q.shape[:-1]) + [heads * int(op.attr("v_head_dim", 0))],
               q.dtype)


@register_op("latent_attention", infer_shape=_latent_attn_infer,
             diff_inputs=["Q", "Latent", "KRope", "KvUpW"])
def _latent_attention(ctx, ins, attrs):
    """Causal multi-head latent attention (MLA, DeepSeek-V2/V3) from its
    projections to the heads' contexts.  Q [B, S, H * (dn + dr)]: a head's
    query is `nope` dn | `rope` dr.  Latent [B, S, r] (normalised): KvUpW
    [r, H * (dn + dv)] takes it to a head's key `nope` dn | value dv.
    KRope [B, S, dr]: ONE rotary key part a token, shared by all heads.
    Rotary on the two rope parts; k = [k_nope | k_rope], scores q.k /
    sqrt(dn + dr); Out [B, S, H * dv].  Under `rope` "none" (default
    "rotary") neither dr-wide part is turned: they enter the scores as
    they are, dr more features of a query and of the shared key, and the
    op knows no position but the causal order (a model that takes its
    positions from other layers).  With the attrs `yarn_<key>` (YARN_KEYS,
    rotary_embedding's) both parts turn at YaRN's frequencies, cos and sin
    times `yarn_attention_factor`; `scale` replaces the softmax scale
    (DeepSeek-V3's YaRN leaves cos and sin alone and multiplies the scale
    by (0.1 mscale_all_dim ln factor + 1)^2).

    The flash kernels take q and k at dn + dr and v at dv as they are: the
    kernels carry a value width of their own (kernels/flash_attention.py),
    which tools/moonlight_kernel_probe.py held against v zero-padded to the
    key width on the chip (PERF.md, PR 31).  What survives the
    recomputation of the unit around the op is `fused_attention`'s: the
    kernel's output and logsumexp where the backward is the Pallas kernel,
    so a recomputed layer runs the op's projections again and not its
    kernel.  `mla.lower` (a span, at lowering) says what a site was given,
    `kept` and `kept_bytes` what it holds through that recomputation."""
    q = data(ins["Q"][0])
    latent = data(ins["Latent"][0])
    k_rope = data(ins["KRope"][0])
    kv_w = data(ins["KvUpW"][0])
    H = int(attrs["n_head"])
    dn, dr, dv = (int(attrs[a]) for a in
                  ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    base = float(attrs.get("rope_base", 10000.0))
    rope = str(attrs.get("rope") or "rotary")
    if rope not in ("rotary", "none"):
        raise ValueError(f"latent_attention: rope {rope!r} is neither "
                         "'rotary' nor 'none'")
    yarn = _yarn_attrs(attrs)
    B, S = q.shape[0], q.shape[1]

    def heads(t):                                    # [B, H, S, width]
        return jnp.swapaxes(t.reshape(B, S, H, -1), 1, 2)

    def turned(t):
        return _rotate(t, base, yarn=yarn) if rope == "rotary" else t

    with span("mla.lower", heads=H, qk_dim=dn + dr, v_dim=dv,
              kv_rank=int(latent.shape[-1]), padded_v=0, rope=rope) as sp:
        lc, wc = amp.mxu_operands(latent, kv_w)
        kv = heads(amp.mxu_output(jnp.matmul(lc, wc), latent, kv_w))
        q = heads(q)
        q = jnp.concatenate([q[..., :dn], turned(q[..., dn:])], -1)
        shared = jnp.broadcast_to(turned(k_rope[:, None]).astype(
            kv.dtype), (B, H, S, dr))
        k = jnp.concatenate([kv[..., :dn], shared], -1)
        q, k = amp.match_kept(q, k)
        out = _attend(ctx, sp, q, k, kv[..., dn:].astype(k.dtype), None,
                      True, float(attrs.get("scale") or (dn + dr) ** -0.5))
    return {"Out": [jnp.swapaxes(out, 1, 2).reshape(B, S, H * dv)]}


def causal_shift(x, steps: int = 1, before=None, axis: int = 1):
    """y[t] = x[t - steps] along `axis`; where t < steps the values
    `before` (it broadcasts against x with `axis` at length 1; None:
    zeros), which stand for every position before the sequence's first."""
    if steps == 0:
        return x
    lead = list(x.shape)
    lead[axis] = steps
    first = (jnp.zeros(lead, x.dtype) if before is None
             else jnp.broadcast_to(before.astype(x.dtype), lead))
    kept = [slice(None)] * x.ndim
    kept[axis] = slice(0, x.shape[axis] - steps)
    return jnp.concatenate([first, x[tuple(kept)]], axis=axis)


def causal_conv1d(x, w, bias=None, before=None):
    """x [B, g, S, c], g groups of c channels with the groups first,
    convolved along S alone, causally, S rows out: y[t] = sum over the k
    taps j of w[j] applied to x[t - (k - 1) + j] (+ bias [g, c or o]), so
    the last tap reads the position itself and no tap a later one; a
    position before the first reads as `before` [g, c] (None: zeros).  w
    [k, g, c] is one filter a channel (depthwise); w [k, g, c, o] mixes the
    channels of each group among themselves.  The k taps are k shifted
    products summed in fp32, no [S, k c] tensor: a depthwise tap is a
    multiply of the shifted input; a group's k taps are ONE product on the
    AMP tier's operands, batched over the groups, x_g [S, c] x [c, k o],
    whose k column blocks are shifted and summed (the rows shifted in are
    `before` through that tap).  fp32 out for a half-width x.  With the
    groups first nothing is transposed between the taps, the statistics of
    a head and the [B, heads, S, D] that attention takes (at the cell's
    shape a third of the time of the same sums over [B, S, C]: PERF.md 6,
    PR 43)."""
    k = w.shape[0]
    acc = amp.stats_dtype(x)

    def rows(t):                     # [g, c] against [B, g, S, c]
        return None if t is None else t.astype(acc)[:, None, :]

    if w.ndim == 3:
        xs = x.astype(acc)
        y = sum(causal_shift(xs, k - 1 - j, rows(before), axis=2) * rows(w[j])
                for j in range(k))
    else:
        c_out = w.shape[-1]
        xc, wc = amp.mxu_operands(x, w)
        taps = jnp.concatenate(list(wc.astype(xc.dtype)), axis=-1)
        prod = jnp.einsum("bgsi,gio->bgso", xc, taps,
                          preferred_element_type=acc)
        fill = None if before is None else jnp.einsum(
            "gi,gio->go", before.astype(acc), taps.astype(acc),
            precision="highest")
        y = sum(causal_shift(
            prod[..., j * c_out:(j + 1) * c_out], k - 1 - j,
            None if fill is None else rows(fill[:, j * c_out:(j + 1) * c_out]),
            axis=2) for j in range(k))
    return y if bias is None else y + rows(bias)


def _cca_infer(op, block):
    q = in_desc(op, block, "Q")
    k = in_desc(op, block, "K")
    if q is None or k is None:
        return
    H, G = int(op.attr("heads", 1)), int(op.attr("kv_heads", 1))
    B, S, lq = q.shape
    set_output(block, op, "QOut", [B, H, S, lq // H], q.dtype)
    for slot in ("KOut", "VOut"):
        set_output(block, op, slot, [B, G, S, k.shape[-1] // G], q.dtype)


@register_op("compressed_conv_qkv", infer_shape=_cca_infer,
             diff_inputs=["Q", "K", "V", "ConvAW", "ConvAB", "ConvBW",
                          "ConvBB", "Tau"])
def _compressed_conv_qkv(ctx, ins, attrs):
    """What compressed convolutional attention (CCA; Zyphra, arXiv:
    2510.04476) does between its down-projections and its scores, for
    `heads` H query heads on `kv_heads` G key/value heads of D.  Q [B, S,
    H D], K and V [B, S, G D] are the projections q~, k~, v~ of the
    layer's input.

    z = [q~ ; k~], padded ONCE with (k0 - 1) + (k1 - 1) zero rows on the
    left; convolution A over it (ConvAW [k0, C], ConvAB [C]: one filter a
    channel), convolution B over A's output (ConvBW [k1, H + G, D, D],
    ConvBB [C]: across the channels of one head), both unpadded, so what B
    sees before position 0 is A's output on zeros, its bias (computed so,
    on [S, D] tensors a head: causal_conv1d's `before`).  The q-k mean of the
    values BEFORE the convolutions: m_q[j] = (q~[j] + k~[j // (H / G)]) /
    2, m_k[g] the mean of m_q over g's query heads; q = z''[:, :H D] +
    m_q, k = z''[:, H D:] + m_k.  Each head L2-normalised to length
    sqrt(D), the keys times Tau [G].  Rotary on the first `rotary_dim`
    features of each head.  The value's second half of channels comes from
    the token before (zeros before the first).

    QOut [B, H, S, D], KOut and VOut [B, G, S, D]: what fused_attention
    takes.  Statistics, sums and angles in fp32, the grouped convolution
    on the AMP tier's operands, under the name scope `cca.mix`.  One
    algorithm, its engine read from the site (kernels/engine.py::site):
    for ONE TPU, where the shape tiles (kernels/cca_mix.py::plan: D a
    multiple of 128, S of a tile of rows, one dtype), the Pallas kernel
    pair of kernels/cca_mix.py, whose backward keeps the op's inputs and
    nothing else; anywhere else (and on a mesh of several devices)
    compressed_conv_mix, the same arithmetic in jax.numpy.  `cca.lower` (a
    span, at lowering) says what a site was given: `engine` (pallas |
    xla), `tile` (the forward's rows a grid step, 0 under xla) and
    `moved_bytes`, what the site's passes have to move through HBM (its
    inputs and outputs: the forward, the forward again where the unit
    around the site is rematerialised, the backward)."""
    from ..kernels import cca_mix, engine
    from ..kernels.flash_attention import _visible_pairs

    q, k, v = (data(ins[s][0]) for s in ("Q", "K", "V"))
    a_w, a_b, b_w, b_b, tau = (data(ins[s][0]) for s in (
        "ConvAW", "ConvAB", "ConvBW", "ConvBB", "Tau"))
    H, G = int(attrs["heads"]), int(attrs["kv_heads"])
    S, D = q.shape[1], q.shape[2] // H
    rotary_dim = int(attrs.get("rotary_dim", 0)) or D
    args = (q, k, v, a_w, a_b, b_w, b_b, tau)
    base = float(attrs.get("rope_base", 10000.0))
    with jax.named_scope("cca.mix"):
        outs = engine.site(
            "cca.lower", ("tile",), ctx.mesh,
            lambda: cca_mix.plan(S, H, G, D, a_w.shape[0], b_w.shape[0],
                                 rotary_dim, q.dtype)
            if q.dtype == k.dtype == v.dtype else None,
            lambda geo, interpret: cca_mix.cca_mix(
                *args, geo, tuple(_inv_freq(rotary_dim, base)), interpret),
            lambda: compressed_conv_mix(*args, H, G, rotary_dim, base),
            heads=H, kv_heads=G, latent_q=int(q.shape[-1]),
            latent_k=int(k.shape[-1]), conv_time0=int(a_w.shape[0]),
            conv_time1=int(b_w.shape[0]), conv_groups=int(b_w.shape[1]),
            rotary_dim=rotary_dim, sq=int(S),
            pairs=_visible_pairs(S, S, True, None),
            moved_bytes=cca_mix.moved_bytes(
                q, k, v, bool(attrs.get("@recompute@"))))
    return dict(zip(("QOut", "KOut", "VOut"), ([o] for o in outs)))


def compressed_conv_mix(q, k, v, a_w, a_b, b_w, b_b, tau, H, G, rotary_dim,
                        base):
    """The op compressed_conv_qkv's arithmetic (its docstring), in
    jax.numpy, heads first from the projections on: (q [B, H, S, D], k and
    v [B, G, S, D]) in q's dtype."""
    B, S, lq = q.shape
    D, share, n = lq // H, H // G, H + G
    acc = amp.stats_dtype(q)

    def heads(t, m):                 # [B, S, m D] -> [B, m, S, D]
        return jnp.swapaxes(t.reshape(B, S, m, D), 1, 2)

    def unit(x):                     # a head at length sqrt(D)
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True))

    z = jnp.concatenate([heads(q, H), heads(k, G)], axis=1).astype(acc)
    a_b = a_b.reshape(n, D)
    conv = causal_conv1d(
        causal_conv1d(z, a_w.reshape(-1, n, D), a_b), b_w,
        b_b.reshape(n, D), before=a_b)
    m_q = (z[:, :H].reshape(B, G, share, S, D)
           + z[:, H:].reshape(B, G, 1, S, D)) / 2
    qn = unit(conv[:, :H].reshape(B, G, share, S, D) + m_q)
    kn = unit(conv[:, H:] + jnp.mean(m_q, axis=2))
    kn = kn * tau.astype(acc)[:, None, None]
    half = v.shape[-1] // 2
    vs = jnp.concatenate([v[..., :half], causal_shift(v[..., half:])],
                         axis=-1)
    return tuple(t.astype(q.dtype) for t in (
        _rotate(qn.reshape(B, H, S, D), base, rotary_dim=rotary_dim),
        _rotate(kn, base, rotary_dim=rotary_dim), heads(vs, G)))


def _sparse_attn_infer(op, block):
    q = in_desc(op, block, "Q")
    if q is None:
        return
    set_output(block, op, "Out", list(q.shape), q.dtype)
    set_output(block, op, "IndexLoss", [], DataType.FP32)


@register_op("sparse_attention", infer_shape=_sparse_attn_infer,
             diff_inputs=["Q", "K", "V", "IndexQ", "IndexK", "IndexW"])
def _sparse_attention(ctx, ins, attrs):
    """Causal grouped-query attention in which every query attends to the
    `topk` keys a learned index ranks highest (DeepSeek-V3.2-Exp's sparse
    attention), with the index's own loss.  Q [B, H, S, D] over K, V
    [B, G, S, D] (query head j reads key/value head j // (H / G)); the
    index IndexQ [B, Hi, S, Di], IndexK [B, S, Di] (one key a token),
    IndexW [B, S, Hi]: I[t, s] = sum_j w[t, j] relu(q_i[j, t].k_i[s]);
    S_t the topk positions s <= t of largest I (all while t < topk), exact;
    Out the softmax of q.k / sqrt(D) over S_t times v; IndexLoss the mean
    over tokens of KL(the heads' mean probabilities over S_t, detached ||
    softmax of I over S_t).  Q, K and V take their gradient from Out alone
    and the index from IndexLoss alone.  Everything is rotated before it
    comes here.  kernels/sparse_attention.py computes it a chunk of
    queries at a time, backward included, and tags the forward's output,
    logsumexp and thresholds to survive the recomputation of the unit
    around the op (core.compiler.keep): the backward of a recomputed layer
    runs no second forward of this op.  `dsa.lower` (a span, at lowering)
    says what a site was given, `kept` and `kept_bytes` what it holds
    through that recomputation; the context's `kept` counts the values."""
    from ..kernels import engine, sparse_attention as dsa

    q, k, v = (data(ins[s][0]) for s in ("Q", "K", "V"))
    qi, ki, w = (data(ins[s][0]) for s in ("IndexQ", "IndexK", "IndexW"))
    topk = int(attrs["topk"])
    _, H, S, D = q.shape
    q_chunk, kv_chunk = (int(attrs.get(a, 512))
                         for a in ("q_chunk", "kv_chunk"))
    tiles = dsa.plan(S, q_chunk, kv_chunk)
    seen = min(S, topk)
    with span("dsa.lower", heads=int(H), kv_heads=int(k.shape[1]),
              index_heads=int(qi.shape[1]), index_dim=int(qi.shape[-1]),
              topk=topk, sq=int(S), q_chunk=tiles["q_chunk"],
              kv_chunk=tiles["kv_block"], keys_causal=S * (S + 1) // 2,
              keys_selected=seen * (seen + 1) // 2 + (S - seen) * topk,
              engine="masked-block" if engine.use_pallas("auto") else "xla",
              indices="recomputed", kept=",".join(dsa.KEPT)) as sp:
        q, k, v = amp.mxu_operands(q, k, v)
        qi, ki = amp.mxu_operands(qi, ki)
        sp.set(kept_bytes=dsa.kept_bytes(q))
        ctx.kept += len(dsa.KEPT)
        out, loss = dsa.sparse_attention(
            q, k.astype(q.dtype), v.astype(q.dtype), qi, ki.astype(qi.dtype),
            w, topk=topk, scale=float(D) ** -0.5, q_chunk=q_chunk,
            kv_chunk=kv_chunk)
    return {"Out": [out], "IndexLoss": [loss]}
