"""State-space sequence mixers: a state of fixed size carried from token to
token with a decay the token chooses.  TPU-native additions (the 2018
reference has no such op): Mamba-1's selective scan (a decay a channel and a
state index: vector-unit work), Mamba-2's state-space-dual scan (ONE decay
a head over a matrix state, B and C shared by the heads of a group:
matmuls a chunk) and the gated RMS norm that follows it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core import amp
from ..core.registry import register_op
from .common import data, same_shape


@register_op("selective_scan", infer_shape=same_shape("X", "Out"),
             diff_inputs=["X", "Dt", "A", "B", "C", "D", "DtBias"])
def _selective_scan(ctx, ins, attrs):
    """Mamba-1's selective scan (Gu & Dao, arXiv:2312.00752;
    kernels/selective_scan.py has the equations and the backward): X, Dt
    [B, S, E], A [E, N] (negative), B, C [B, S, N], D [E]; with DtBias [E]
    the step is softplus(Dt + DtBias), else Dt as it comes.  Every one of E
    channels keeps N numbers from 0: s_t = exp(dt_t A) s_(t-1) + dt_t x_t
    B_t, y_t = s_t C_t + D x_t.  All of it fp32 whatever the operands'
    width; Out [B, S, E] in X's dtype.

    One algorithm, its engine read from the site (kernels/engine.py::site):
    for ONE TPU, where the shape tiles (kernels/selective_scan.py::tiles: E
    whole blocks of 1024 channels, S whole chunks), a Pallas kernel pair
    that carries the states through time in VMEM; anywhere else a lax.scan
    over chunks that carries [E, N].  Neither ever holds a state a token.
    Both keep y and the state every chunk starts from through the
    recomputation of the unit around the op (core.compiler.keep): the
    backward of a recomputed layer runs no second forward of the scan.  The
    step's softplus runs under the name scope `ssm.mix`, the scan under
    `ssm.scan`.  `ssm.lower` (a span, at lowering) says what a site was
    given: `channels`, `states`, `sq`, `chunk`, `engine` (pallas | xla),
    `block` (channels a grid step) and the `fwd_vmem_bytes` and
    `bwd_vmem_bytes` of its working sets (0 under xla), `scan_bytes` (what
    the two passes have to move through HBM at fp32 streams), `kept` and
    `kept_bytes`; the context's `kept` counts the values."""
    from ..kernels import engine, selective_scan as ss

    x, dt = data(ins["X"][0]), data(ins["Dt"][0])
    a, b, c, d = (data(ins[s][0]) for s in ("A", "B", "C", "D"))
    bias = ins.get("DtBias", [None])[0]
    B, S, E = x.shape
    N = a.shape[1]
    if bias is not None:
        with jax.named_scope("ssm.mix"):
            dt = jax.nn.softplus(dt.astype(jnp.float32)
                                 + data(bias).astype(jnp.float32))
    chunk = min(ss.CHUNK, S)
    ctx.kept += len(ss.KEPT)
    with jax.named_scope("ssm.scan"):
        y = engine.site(
            "ssm.lower", ("block", "fwd_vmem_bytes", "bwd_vmem_bytes"),
            ctx.mesh, lambda: ss.tiles(S, E, N),
            lambda tiles, interpret: ss.selective_scan(
                x, dt, a, b, c, d, tiles, interpret),
            lambda: ss.selective_scan(x, dt, a, b, c, d),
            channels=int(E), states=int(N), sq=int(S), chunk=chunk,
            scan_bytes=ss.moved_bytes(B, S, E, N), kept=",".join(ss.KEPT),
            kept_bytes=ss.kept_bytes(B, S, E, N, chunk))
    return {"Out": [y.astype(x.dtype)]}


@register_op("ssd_scan", infer_shape=same_shape("X", "Out"),
             diff_inputs=["X", "Dt", "A", "B", "C", "D", "DtBias"])
def _ssd_scan(ctx, ins, attrs):
    """Mamba-2's state-space-dual scan (Dao & Gu, arXiv:2405.21060;
    kernels/ssd_scan.py has the equations, the chunked form and the
    backward): X [B, S, H, P], Dt [B, S, H], A [H] (negative), B, C [B, S,
    G, N] (head h reads group h // (H / G)), D [H]; with DtBias [H] the
    step is softplus(Dt + DtBias), else Dt as it comes.  Every head keeps
    P x N numbers from 0: s_t = exp(dt_t A) s_(t-1) + dt_t x_t (x) B_t, y_t
    = s_t C_t + D x_t, one decay a head a token.  Decays, running sums and
    the state in fp32; the chunks' matmuls take operands in X's dtype and
    add in fp32; Out [B, S, H, P] in X's dtype.

    One algorithm, its engine read from the site (kernels/engine.py::site):
    for ONE TPU, where the shape tiles (kernels/ssd_scan.py::tiles: heads
    of 64, states whole 128-lane vectors, S whole chunks), a Pallas kernel
    pair that carries every head's state through the chunks in VMEM and
    makes a chunk's scores C B^T once a group; anywhere else a lax.scan
    over chunks that carries [B, H, P, N].  Neither ever holds a state a
    token.  Both keep y and the state every chunk starts from through the
    recomputation of the unit around the op (core.compiler.keep): the
    backward of a recomputed layer runs no second forward of the scan.  The
    step's softplus runs under the name scope `ssd.mix`, the scan under
    `ssd.scan`.  `ssd.lower` (a span, at lowering) says what a site was
    given: `heads`, `head_dim`, `states`, `groups`, `sq`, `chunk` (the
    tokens the engine walks at a time), `engine` (pallas | xla), `block`
    (heads a grid step) and the `fwd_vmem_bytes` and `bwd_vmem_bytes` of
    its working sets (0 under xla), `scan_bytes` (what the two passes have
    to move through HBM at X's width), `scan_flops` (their matmul
    operations at that chunk), `kept` and `kept_bytes`; the context's
    `kept` counts the values."""
    from ..kernels import engine, ssd_scan as ssd

    x, dt = data(ins["X"][0]), data(ins["Dt"][0])
    a, b, c, d = (data(ins[s][0]) for s in ("A", "B", "C", "D"))
    bias = ins.get("DtBias", [None])[0]
    B, S, H, P = x.shape
    G, N = b.shape[2:]
    if bias is not None:
        with jax.named_scope("ssd.mix"):
            dt = jax.nn.softplus(dt.astype(jnp.float32)
                                 + data(bias).astype(jnp.float32))
    chunk, size = min(ssd.CHUNK, S), x.dtype.itemsize
    ctx.kept += len(ssd.KEPT)
    with jax.named_scope("ssd.scan"):
        y = engine.site(
            "ssd.lower", ("block", "fwd_vmem_bytes", "bwd_vmem_bytes"),
            ctx.mesh, lambda: ssd.tiles(S, H, P, N, G, itemsize=size),
            lambda tiles, interpret: ssd.ssd_scan(
                x, dt, a, b, c, d, tiles, interpret),
            lambda: ssd.ssd_scan(x, dt, a, b, c, d),
            heads=int(H), head_dim=int(P), states=int(N), groups=int(G),
            sq=int(S), chunk=chunk,
            scan_bytes=ssd.moved_bytes(B, S, H, P, N, G, size),
            scan_flops=ssd.flops(B, S, H, P, N, G, chunk),
            kept=",".join(ssd.KEPT),
            kept_bytes=ssd.kept_bytes(B, S, H, P, N, chunk, size))
    return {"Out": [y]}


def gated_rms_norm(x, gate, scale, groups, eps):
    """The op gated_rms_norm's arithmetic (its docstring), in jax.numpy, in
    x's dtype."""
    acc = amp.stats_dtype(x)
    gated = x.astype(acc) * jax.nn.silu(gate.astype(acc))
    by_group = gated.reshape(gated.shape[:-1] + (groups, -1))
    normed = by_group * jax.lax.rsqrt(
        jnp.mean(jnp.square(by_group), axis=-1, keepdims=True) + eps)
    return (normed.reshape(gated.shape) * scale.astype(acc)).astype(x.dtype)


@register_op("gated_rms_norm", infer_shape=same_shape("X", "Out"),
             diff_inputs=["X", "Gate", "Scale"])
def _gated_rms_norm(ctx, ins, attrs):
    """What Mamba-2 does to its scan's output X [B, S, E] before the output
    map: the gate FIRST, g = X silu(Gate), then ONE mean square over each
    of `groups` runs of E / groups channels under a weight a channel, Out =
    g / sqrt(mean(g^2) + epsilon) * Scale [E].  Not kda_gated_norm
    (ops/linear_attention_ops.py), which norms a head and then gates with a
    sigmoid.  Statistics and the gate in fp32, Out in X's dtype."""
    x, gate, scale = (data(ins[s][0]) for s in ("X", "Gate", "Scale"))
    return {"Out": [gated_rms_norm(
        x, gate, scale, int(attrs.get("groups", 1)),
        float(attrs.get("epsilon", 1e-5)))]}
