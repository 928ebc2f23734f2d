"""Linear attention: sequence mixers that carry a state of fixed size from
token to token where attention carries the keys.  TPU-native additions (the
2018 reference has no such op): the gated delta rule with a decay for every
key channel (Kimi Delta Attention) and the short causal convolution that
precedes it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core import amp
from ..core.registry import register_op
from ..observability import span
from .attention_ops import causal_conv1d
from .common import ACTS, data, in_desc, same_shape, set_output

_CONV_ACTS = {**ACTS, "silu": jax.nn.silu}


@register_op("short_conv1d", infer_shape=same_shape("X", "Out"),
             diff_inputs=["X", "W"])
def _short_conv1d(ctx, ins, attrs):
    """A causal depthwise convolution along the sequence, then
    `activation` (identity | silu | ...): X [B, S, C], W [k, C], one filter
    of k taps a channel; y[t] = sum_j W[j] x[t - (k - 1) + j], zeros before
    the first position (attention_ops.causal_conv1d: k shifted products
    summed in fp32).  Out in X's dtype."""
    x, w = data(ins["X"][0]), data(ins["W"][0])
    y = causal_conv1d(x[:, None], w[:, None])[:, 0]
    return {"Out": [_CONV_ACTS[str(attrs.get("activation") or "identity")](
        y).astype(x.dtype)]}


def _gated_delta_infer(op, block):
    q = in_desc(op, block, "Q")
    if q is not None:
        set_output(block, op, "Out", list(q.shape), q.dtype)


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
    the context's `kept` counts the values."""
    from ..kernels import gated_delta as kda

    q, k, v = amp.mxu_operands(*(data(ins[s][0]) for s in ("Q", "K", "V")))
    g, beta = data(ins["G"][0]), data(ins["Beta"][0])
    H = int(attrs["heads"])
    B, S, width = q.shape
    D = width // H
    tiles = kda.plan(B, S, H, D, int(attrs.get("chunk") or kda.CHUNK))
    size = jnp.dtype(q.dtype).itemsize
    taken = kda.engine(B, S, H, D, tiles["chunk"], q.dtype)
    chosen = {} if taken is None else dict(
        rows=taken.rows, fwd_vmem_bytes=taken.fwd_vmem,
        bwd_vmem_bytes=taken.bwd_vmem)
    with span("kda.lower", heads=H, head_dim=D, sq=int(S),
              engine="xla" if taken is None else "pallas",
              state_bytes=kda.state_bytes(B, H, D), kept=",".join(kda.KEPT),
              kept_bytes=kda.kept_bytes(
                  B, S, H, D, tiles["chunks"] // tiles["group"], size),
              flops=kda.flops(B, S, H, D, tiles["chunk"]),
              moved_bytes=kda.moved_bytes(B, S, H, D, size), **tiles,
              **chosen), \
            jax.named_scope("kda.scan"):
        ctx.kept += len(kda.KEPT)
        out = kda.gated_delta_attention(
            q, k.astype(q.dtype), v.astype(q.dtype), g, beta, heads=H,
            chunk=tiles["chunk"])
    return {"Out": [out]}
