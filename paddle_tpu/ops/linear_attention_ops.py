"""Linear attention: sequence mixers that carry a state of fixed size from
token to token where attention carries the keys.  TPU-native additions (the
2018 reference has no such op): the gated delta rule, with a decay for
every key channel (Kimi Delta Attention) or ONE decay a head whose keys may
serve several value heads (Gated DeltaNet), one op whose form is read from
its operands, and the short causal convolution that precedes it; and, as
one op each, what streams [S, H D] values through the vector unit before
that recurrence (the three convolutions and the channels' decay:
kda_conv_decay; a head's decay: gated_delta_decay) and after it (the gated
norm a head, under a sigmoid with a bias or a SiLU: kda_gated_norm).
short_conv1d, kda_conv_decay and kda_gated_norm are each ONE algorithm
whose engine is read from the site: kernels/kda_mix.py's row-tiled Pallas
kernel pairs for ONE TPU where the shape tiles, the jax.numpy functions of
this file (`short_conv`, `conv_decay`, `gated_norm`: the definitions the
kernels are held to) anywhere else.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core import amp
from ..core.registry import register_op
from ..core.proto import DataType
from ..observability import span
from .attention_ops import causal_conv1d
from .common import ACTS, data, in_desc, same_shape, set_output

_CONV_ACTS = {**ACTS, "silu": jax.nn.silu}
_GATES = {"sigmoid": jax.nn.sigmoid, "silu": jax.nn.silu}


def short_conv(x, w, bias, activation):
    """The op short_conv1d's arithmetic (its docstring), in jax.numpy."""
    y = causal_conv1d(x[:, None], w[:, None],
                      None if bias is None else bias[None])[:, 0]
    return _CONV_ACTS[activation](y).astype(x.dtype)


@register_op("short_conv1d", infer_shape=same_shape("X", "Out"),
             diff_inputs=["X", "W", "Bias"])
def _short_conv1d(ctx, ins, attrs):
    """A causal depthwise convolution along the sequence, then
    `activation` (identity | silu | ...): X [B, S, C], W [k, C], one filter
    of k taps a channel; y[t] = sum_j W[j] x[t - (k - 1) + j], zeros before
    the first position (attention_ops.causal_conv1d: k shifted products
    summed in fp32), plus Bias [C] where there is one.  Out in X's dtype.

    One algorithm, its engine read from the site (kernels/engine.py::site)
    as kda_conv_decay's is: for ONE TPU, under silu or identity, where the
    shape tiles (kernels/kda_mix.py::short_conv_tiles: C whole 128-lane
    vectors, k - 1 <= 8, S whole tiles of rows, bf16 or fp32), a Pallas
    kernel pair over tiles of rows x blocks of channels whose backward
    keeps X, W and Bias and nothing else; anywhere else `short_conv`, the
    same arithmetic in jax.numpy.  `short_conv.lower` (a span, at lowering;
    `what` short_conv) says what a site was given, in `kda.mix.lower`'s
    fields: `engine`, `rows`, `channels`, `halo`, `fwd_vmem_bytes`,
    `bwd_vmem_bytes`, `moved_bytes`."""
    from ..kernels import engine, kda_mix

    x, w = data(ins["X"][0]), data(ins["W"][0])
    bias = ins.get("Bias", [None])[0]
    bias = None if bias is None else data(bias)
    act = str(attrs.get("activation") or "identity")
    out = engine.site(
        "short_conv.lower", kda_mix.Tiles._fields, ctx.mesh,
        lambda: kda_mix.short_conv_tiles(x.shape[1], x.shape[2], w.shape[0],
                                         x.dtype)
        if act in kda_mix.SHORT_CONV_ACTS and engine.one_dtype(x) else None,
        lambda tiles, interpret: kda_mix.short_conv(x, w, bias, act, tiles,
                                                    interpret),
        lambda: short_conv(x, w, bias, act), what="short_conv",
        moved_bytes=kda_mix.short_conv_moved_bytes(
            x, bool(attrs.get("@recompute@"))))
    return {"Out": [out]}


def _gated_delta_infer(op, block):
    v = in_desc(op, block, "V")
    if v is not None:
        set_output(block, op, "Out", list(v.shape), v.dtype)


@register_op("gated_delta_attention", infer_shape=_gated_delta_infer,
             diff_inputs=["Q", "K", "V", "G", "Beta"])
def _gated_delta_attention(ctx, ins, attrs):
    """Kimi Delta Attention's recurrence (arXiv:2510.26692), `heads` H
    heads of D: Q, K, V [B, S, H D], the log-decay G [B, S, H D] (<= 0, one
    for every key channel; fp32 here whatever it comes in) and Beta [B, S,
    H].  A head's q and k go to unit length (fp32 statistics, 1e-6 under
    the root); its state M [D, D] starts at 0 and a token does M~ =
    diag(exp(g_t)) M, M = M~ + beta_t k_t (v_t - M~^T k_t)^T, o_t = D^-1/2
    M^T q_t.  Out [B, S, H D].

    kernels/gated_delta.py runs it `chunk` tokens at a time on matmuls
    (operands on the AMP tier, sums, decays and the state fp32), backward
    included, and tags its output and the state every group of chunks
    starts from to survive the recomputation of the unit around the op
    (core.compiler.keep): the backward of a recomputed layer runs no
    second forward of the op.  Under the name scope `kda.scan`.  `kda.lower` (a span, at
    lowering) says what a site was given: `heads`, `head_dim`, `sq`,
    `chunk`, `chunks`, `group` (chunks the jax.numpy engine's parallel part
    takes at once), `engine` (pallas: the kernel pair on the inputs as
    they come, where the program is for a TPU, heads are whole 128-lane
    vectors and the sequence whole tiles of 128 rows, with `rows` a grid
    step and the `fwd_vmem_bytes` and `bwd_vmem_bytes` of its working
    sets; xla: jax.numpy matmuls and lax.scan on regrouped copies,
    everywhere else), `state_bytes` (one chunk boundary's states), `kept`
    and `kept_bytes` (what it holds through that recomputation) and the
    static `flops` and `moved_bytes` of the site's forward and backward;
    the context's `kept` counts the values.

    The second form (Gated DeltaNet, arXiv:2412.06464), read from the
    operands and from no attribute: G [B, S, H], ONE decay a head, with Q,
    K [B, S, Hk D] at Hk <= H key heads, value head j reading key head j //
    (H / Hk); the same recurrence with exp(g_t) a scalar, the same engines,
    kept values and backward (dG [B, S, H]; dQ, dK summed over a key
    head's value heads), neither a [B, S, H D] decay nor a repeated q or k
    anywhere.  It stands under the name scope `gdn.scan` and its span is
    `gdn.lower`: `kda.lower`'s fields and `decay` head, `key_heads`."""
    from ..kernels import gated_delta as kda

    q, k, v = amp.mxu_operands(*(data(ins[s][0]) for s in ("Q", "K", "V")))
    g, beta = data(ins["G"][0]), data(ins["Beta"][0])
    H = int(attrs["heads"])
    B, S, width = v.shape
    D = width // H
    key_heads, head_decay = kda.form(q, v, g, H)
    tiles = kda.plan(B, S, H, D, int(attrs.get("chunk") or kda.CHUNK))
    size = jnp.dtype(q.dtype).itemsize
    taken = kda.engine(B, S, H, D, tiles["chunk"], q.dtype)
    chosen = {} if taken is None else dict(
        rows=taken.rows, fwd_vmem_bytes=taken.fwd_vmem_bytes,
        bwd_vmem_bytes=taken.bwd_vmem_bytes)
    family = "gdn" if head_decay else "kda"
    said = dict(decay="head", key_heads=key_heads) if head_decay else {}
    with span(f"{family}.lower", heads=H, head_dim=D, sq=int(S),
              engine="xla" if taken is None else "pallas",
              state_bytes=kda.state_bytes(B, H, D), kept=",".join(kda.KEPT),
              kept_bytes=kda.kept_bytes(
                  B, S, H, D, tiles["chunks"] // tiles["group"], size),
              flops=kda.flops(B, S, H, D, tiles["chunk"], key_heads),
              moved_bytes=kda.moved_bytes(B, S, H, D, size, key_heads,
                                          head_decay), **tiles,
              **chosen, **said), \
            jax.named_scope(f"{family}.scan"):
        ctx.kept += len(kda.KEPT)
        out = kda.gated_delta_attention(
            q, k.astype(q.dtype), v.astype(q.dtype), g, beta, heads=H,
            chunk=tiles["chunk"])
    return {"Out": [out]}


def conv_decay(q, k, v, f, wq, wk, wv, dt_bias, a_log, heads):
    """The op kda_conv_decay's arithmetic (its docstring), in jax.numpy:
    (q', k', v in their inputs' dtypes, g fp32)."""
    def conv(x, w):
        y = causal_conv1d(x[:, None], w[:, None])[:, 0]
        return jax.nn.silu(y).astype(x.dtype)

    B, S, C = f.shape
    z = jax.nn.softplus(f.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    rate = -jnp.exp(a_log.astype(jnp.float32))
    g = (z.reshape(B, S, heads, C // heads) * rate[:, None]).reshape(B, S, C)
    return conv(q, wq), conv(k, wk), conv(v, wv), g


def gated_norm(o, gate, gate_bias, scale, heads, eps, activation="sigmoid"):
    """The op kda_gated_norm's arithmetic (its docstring), in jax.numpy,
    in o's dtype: the norm a HEAD first, then the gate, a sigmoid (of gate
    + gate_bias) or a SiLU (no bias).  Mamba-2's gated norm (the silu gate
    first, then one mean square over the whole width) is
    ops/state_space_ops.py::gated_rms_norm."""
    B, S, C = o.shape
    acc = amp.stats_dtype(o)
    x = o.astype(acc).reshape(B, S, heads, C // heads)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps) * scale.astype(acc)
    gate = gate.astype(acc)
    if gate_bias is not None:
        gate = gate + gate_bias.astype(acc)
    return (y.reshape(B, S, C) * _GATES[activation](gate)).astype(o.dtype)


def _conv_decay_infer(op, block):
    q = in_desc(op, block, "Q")
    if q is None:
        return
    for slot in ("QOut", "KOut", "VOut"):
        set_output(block, op, slot, list(q.shape), q.dtype)
    set_output(block, op, "G", list(q.shape), DataType.FP32)


@register_op("kda_conv_decay", infer_shape=_conv_decay_infer,
             diff_inputs=["Q", "K", "V", "F", "ConvQW", "ConvKW", "ConvVW",
                          "DtBias", "ALog"])
def _kda_conv_decay(ctx, ins, attrs):
    """What Kimi Delta Attention does to its projections before the
    recurrence, `heads` H heads of D: Q, K, V [B, S, H D] are q~, k~, v~
    and F [B, S, H D] the decay's low-rank map of the layer's input.
    QOut = silu(conv(Q, ConvQW)), KOut and VOut likewise (short_conv1d's
    convolution: causal, depthwise, ConvQW [k, H D] one filter of k taps a
    channel, zeros before the first position), in Q's dtype; G = -exp(ALog
    [H]) softplus(F + DtBias [H D]), fp32 whatever F comes in, one decay
    for every key channel.  All arithmetic in fp32.

    One algorithm, its engine read from the site (kernels/engine.py::site):
    for ONE TPU, where the shape tiles (kernels/kda_mix.py::conv_tiles: H D
    whole 128-lane vectors, S whole tiles of rows, one dtype), a Pallas
    kernel pair over tiles of rows x blocks of channels whose backward
    keeps the op's inputs and nothing else; anywhere else `conv_decay`, the
    same arithmetic in jax.numpy.  `kda.mix.lower` (a span, at lowering;
    `what` conv_decay) says what a site was given: `engine` (pallas | xla),
    `rows`, `channels` and `halo` of a grid step, the `fwd_vmem_bytes` and
    `bwd_vmem_bytes` of its working sets (0 under xla) and `moved_bytes`,
    what the site's passes have to move through HBM (the forward, the
    forward again where the unit around the site is rematerialised, the
    backward)."""
    from ..kernels import engine, kda_mix

    args = [data(ins[s][0]) for s in (
        "Q", "K", "V", "F", "ConvQW", "ConvKW", "ConvVW", "DtBias", "ALog")]
    args.append(int(attrs["heads"]))
    q, k, v, f, wq = args[:5]
    outs = engine.site(
        "kda.mix.lower", kda_mix.Tiles._fields, ctx.mesh,
        lambda: kda_mix.conv_tiles(q.shape[1], q.shape[2], wq.shape[0],
                                   q.dtype)
        if engine.one_dtype(q, k, v, f) else None,
        lambda tiles, interpret: kda_mix.conv_decay(*args, tiles, interpret),
        lambda: conv_decay(*args), what="conv_decay",
        moved_bytes=kda_mix.conv_moved_bytes(
            q, f, bool(attrs.get("@recompute@"))))
    return dict(zip(("QOut", "KOut", "VOut", "G"), ([o] for o in outs)))


@register_op("gated_delta_decay", infer_shape=same_shape("X", "Out"),
             diff_inputs=["X", "ALog", "DtBias"])
def _gated_delta_decay(ctx, ins, attrs):
    """Gated DeltaNet's log-decay, ONE a head: Out = -exp(ALog [H])
    softplus(X + DtBias [H]) of X [B, S, H], fp32 whatever X comes in
    (gated_delta_attention's G in its head-decay form).  kda_conv_decay
    makes Kimi Delta Attention's, one for every key channel."""
    x, a_log, dt_bias = (data(ins[s][0]).astype(jnp.float32)
                         for s in ("X", "ALog", "DtBias"))
    return {"Out": [-jnp.exp(a_log) * jax.nn.softplus(x + dt_bias)]}


@register_op("kda_gated_norm", infer_shape=same_shape("X", "Out"),
             diff_inputs=["X", "Gate", "GateBias", "Scale"])
def _kda_gated_norm(ctx, ins, attrs):
    """What Kimi Delta Attention does to the recurrence's output X [B, S,
    H D], `heads` H heads of D: Out = X / sqrt(mean_D(X^2) + epsilon) *
    Scale [D] * sigmoid(Gate + GateBias), rms_norm's formula a head with
    one learned scale, Gate [B, S, H D] and GateBias [H D].  Statistics
    and the gate in fp32, Out in X's dtype.  The engine as kda_conv_decay
    reads it (kernels/kda_mix.py::norm_tiles: D whole 128-lane vectors): a
    head's statistic stays in the tile, the backward reads X, Gate and the
    cotangent alone; `kda.mix.lower` with `what` gated_norm.

    Under `gate_activation` silu, with no GateBias, it is Gated DeltaNet's:
    the same norm a head times silu(Gate).  The gate's rule (a key of
    `_GATES`, with or without a GateBias) is a static argument of the one
    kernel pair, so the site runs the kernels wherever the shape tiles."""
    from ..kernels import engine, kda_mix

    bias = ins.get("GateBias", [None])[0]
    act = str(attrs.get("gate_activation") or "sigmoid")
    args = [data(ins["X"][0]), data(ins["Gate"][0]),
            None if bias is None else data(bias), data(ins["Scale"][0]),
            int(attrs["heads"]), float(attrs.get("epsilon", 1e-6))]
    o, gate = args[:2]
    out = engine.site(
        "kda.mix.lower", kda_mix.Tiles._fields, ctx.mesh,
        lambda: kda_mix.norm_tiles(o.shape[1], o.shape[2],
                                   o.shape[2] // args[4], o.dtype)
        if act in _GATES and engine.one_dtype(o, gate) else None,
        lambda tiles, interpret: kda_mix.gated_norm(*args, tiles, interpret,
                                                    act),
        lambda: gated_norm(*args, act), what="gated_norm",
        moved_bytes=kda_mix.norm_moved_bytes(
            o, gate, bool(attrs.get("@recompute@"))))
    return {"Out": [out]}
