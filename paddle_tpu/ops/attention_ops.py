"""Fused attention op backed by the Pallas flash kernel.

TPU-native addition (the reference composes attention from matmul/softmax
ops, python/paddle/fluid/nets.py scaled_dot_product_attention).  One op =
one flash kernel on TPU; key-padding comes in as lengths instead of an
additive [Sq, Sk] bias tensor, so nothing score-shaped ever hits HBM.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..core import amp
from ..core.registry import register_op
from .common import data, in_desc, same_shape, set_output


def _fused_attn_infer(op, block):
    q = in_desc(op, block, "Q")
    if q is None:
        return
    set_output(block, op, "Out", list(q.shape), q.dtype)


def _shard_over_mesh(attend, mesh, n_head: int, has_lengths: bool):
    """Wrap `attend(q, k, v[, k_lengths])` in a shard_map over `mesh`:
    batch over dp, heads over tp where tp divides them, sequence whole.
    XLA cannot partition a Mosaic kernel by itself ("Mosaic kernels cannot
    be automatically partitioned") — without this the SPMD step of a
    flash-attention model does not compile for more than one chip.  Each
    device runs the kernel on its own [B/dp, H/tp, S, D] block; attention
    never mixes batch rows or heads, so no collective is needed."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import AXIS_DP, AXIS_TP

    dp = AXIS_DP if mesh.has_axis(AXIS_DP) else None
    tp = AXIS_TP if (mesh.has_axis(AXIS_TP)
                     and n_head % mesh.axis_size(AXIS_TP) == 0) else None
    qkv = P(dp, tp, None, None)
    in_specs = (qkv, qkv, qkv) + ((P(dp),) if has_lengths else ())
    # check_vma off: pallas_call has no replication rule
    return jax.shard_map(attend, mesh=mesh.mesh, in_specs=in_specs,
                         out_specs=qkv, check_vma=False)


@register_op("fused_attention", infer_shape=_fused_attn_infer,
             diff_inputs=["Q", "K", "V"])
def _fused_attention(ctx, ins, attrs):
    from ..kernels import flash_attention
    from ..kernels.flash_attention import _use_pallas

    q = data(ins["Q"][0])  # [B, H, Sq, D]
    k = data(ins["K"][0])
    v = data(ins["V"][0])
    klen_in = ins.get("KLengths", [None])[0]
    klen = data(klen_in).reshape(-1) if klen_in is not None else None

    def attend(q, k, v, klen=None):
        return flash_attention(
            q, k, v,
            causal=bool(attrs.get("causal", False)),
            scale=attrs.get("scale") or None,
            k_lengths=klen,
        )

    if (ctx.mesh is not None and ctx.mesh.num_devices > 1
            and _use_pallas("auto")):
        attend = _shard_over_mesh(attend, ctx.mesh, q.shape[1],
                                  klen is not None)
    args = (q, k, v) + ((klen,) if klen is not None else ())
    return {"Out": [attend(*args)]}


@register_op("rotary_embedding", infer_shape=same_shape("X", "Out"),
             diff_inputs=["X"])
def _rotary_embedding(ctx, ins, attrs):
    """Rotary position embedding (Su et al. 2021) of X [..., S, D] along
    its last two axes, position p = offset + index on axis -2, in the
    half-split layout: pair i is (x[i], x[i + D/2]), turned by the angle
    p * base^(-2i/D).  The angles are fp32 whatever X is (at base 1e6 and
    p in the thousands bf16 has no digit left of them); Out has X's dtype."""
    x = data(ins["X"][0])
    seq, dim = x.shape[-2], x.shape[-1]
    half = dim // 2
    inv_freq = float(attrs.get("base", 10000.0)) ** (
        -np.arange(half, dtype=np.float64) * 2.0 / dim)
    pos = np.arange(seq, dtype=np.float64) + int(attrs.get("offset", 0))
    angle = jnp.asarray(pos[:, None] * inv_freq[None, :], jnp.float32)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    xs = x.astype(amp.stats_dtype(x))
    x1, x2 = xs[..., :half], xs[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return {"Out": [out.astype(x.dtype)]}
