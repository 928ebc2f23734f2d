"""State-space sequence mixers: a state of fixed size a channel carried
from token to token with a decay the token chooses.  TPU-native addition
(the 2018 reference has no such op): Mamba-1's selective scan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

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
