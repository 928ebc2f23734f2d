"""Manifold-constrained hyper-connections (mHC: Xie et al., DeepSeek-AI,
arXiv:2512.24880, on hyper-connections, Zhu et al., arXiv:2409.19606): a
layer carries n residual streams a token, X [B, S, n, C], and every
sublayer F reads one value mixed from them and writes its output back to
all of them under three maps computed from the streams themselves.
TPU-native additions (the 2018 reference has one residual stream).

Per token, u = vec(X) in R^{nC}, everything of the maps in fp32:

    m      = (u * rsqrt(mean(u^2) + epsilon)) Phi,  Phi [nC, n + n + n^2]
    H_pre  = sigmoid(a_pre m[0:n] + b_pre)                         [n]
    H_post = 2 sigmoid(a_post m[n:2n] + b_post)                    [n]
    M      = exp(clip(a_res mat_{n x n}(m[2n:]) + b_res, lo, hi))
    `sinkhorn_iters` times: M <- M / (colsum(M) + hc_eps);
                            M <- M / (rowsum(M) + hc_eps);   H_res = M
    x_in   = sum_j H_pre[j] X[j];   y = F(x_in)
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] y

Five ops: `mhc_streams` (a value copied to the n streams), `mhc_maps` (the
2n + n^2 map values a token, WITH THE TOKENS ON THE LANE AXIS: H [B, 2n +
n^2, S], rows 0:n H_pre, n:2n H_post, then H_res row by row; a minor axis
of n would fill n of 128 lanes through 2 x `sinkhorn_iters`
normalisations and their backward), `mhc_read` (x_in), `mhc_maps_read`
(H and x_in under it: the two ops as ONE, with one backward, which is what
a sublayer emits: the streams' gradient through the maps and through the
read is then one value made in one pass, and the backward reads the
streams twice where the two ops' read them three times) and `mhc_write`
(X').  `maps`, `read`, `maps_read` and `write` below are the arithmetic,
in jax.numpy.
One algorithm, its engine read from the site (`_site`): for ONE TPU, where
the shape tiles (kernels/mhc.py::maps_tiles / ::maps_read_tiles /
::mix_tiles: four streams, C whole 128-lane vectors, S whole tiles of
rows, streams of one dtype, the working set inside the VMEM budget), each
of `mhc_maps`, `mhc_maps_read` and `mhc_write` is a Pallas kernel pair
over tiles of rows whose backward makes the tile's forward again from the
op's inputs; anywhere else (the CPU, a mesh of several devices, a shape
that does not tile) the jax.numpy form with gradients by the compiler's
jax.vjp, the op's arithmetic under jax.checkpoint, which is what
`mhc_read` alone runs everywhere.  Either way what a backward keeps is the
op's inputs (the streams at their own element size) and never an fp32 copy
of the streams.
Name scopes:
`mhc.maps` (the RMS, the [T, nC] x [nC, 2n + n^2] product, the
activations, Sinkhorn, and `mhc_maps_read`'s read) and `mhc.mix` (the
write, and `mhc_read` alone).
`mhc.lower` (a span, at lowering, one a `mhc_maps_read` or `mhc_maps` op,
which is one a sublayer) says `streams`, `sinkhorn_iters`, `sublayers` (1:
a reader adds them up) and `moved_bytes`, what a sublayer's maps and
mixing have to move through HBM whatever implements them (`moved_bytes`).
`mhc.kernel.lower` (a span, at lowering, one a site that has kernels, two
a sublayer) says what the site was given: `what` (maps_read | maps |
write), `engine` (pallas | xla), `rows` and `channels` of a grid step and
the `fwd_vmem_bytes` / `bwd_vmem_bytes` of its working sets (0 under xla),
and of a maps_read site `resident` (1 where the forward holds a tile of
rows x all n C channels and reads the streams once, 0 where it is the
maps' kernel and then the read's, or under xla).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core import amp
from ..core.proto import DataType
from ..core.registry import register_op
from ..observability import span
from .common import data, in_desc, same_shape, set_output


def moved_bytes(tokens: int, n: int, width: int, size: int,
                phi_bytes: int) -> int:
    """Bytes one sublayer's hyper-connection has to move through HBM,
    forward and backward, whatever implements it and whatever is
    recomputed: 5 passes over the streams (forward: read X, write X';
    backward: read X, read dX', write dX) and 4 over a [T, C] value (x_in,
    y and their cotangents) at the stream's element size, and Phi once."""
    return (5 * n + 4) * tokens * width * size + phi_bytes


def maps(x, phi, a_pre, a_post, a_res, b_pre, b_post, b_res, epsilon,
         hc_eps, iters, clamp, dtype=jnp.float32):
    """H [B, 2n + n^2, S] of the streams x [B, S, n, C] (module docstring),
    every value in `dtype` (fp32: anything less is a control)."""
    B, S, n, C = x.shape
    u = x.reshape(B, S, n * C).astype(dtype)
    rms = jax.lax.rsqrt(jnp.mean(jnp.square(u), axis=-1) + epsilon)
    # (u rms) Phi = rms (u Phi): the normalised copy of u is never made,
    # and the product comes out with the tokens on the lanes
    m = jnp.einsum("bsk,ko->bos", u, phi.astype(dtype),
                   precision=jax.lax.Precision.HIGHEST) * rms[:, None]

    def rows(bias):
        return bias.astype(dtype).reshape(-1, 1)

    def scalar(a):
        return a.astype(dtype).reshape(())

    pre = jax.nn.sigmoid(scalar(a_pre) * m[:, :n] + rows(b_pre))
    post = 2.0 * jax.nn.sigmoid(scalar(a_post) * m[:, n:2 * n]
                                + rows(b_post))
    res = jnp.exp(jnp.clip(scalar(a_res) * m[:, 2 * n:] + rows(b_res),
                           clamp[0], clamp[1])).reshape(B, n, n, S)
    for _ in range(iters):
        res = res / (jnp.sum(res, axis=1, keepdims=True) + hc_eps)
        res = res / (jnp.sum(res, axis=2, keepdims=True) + hc_eps)
    return jnp.concatenate([pre, post, res.reshape(B, n * n, S)], axis=1)


def _over_tokens(h):
    """A map row [B, S] against a stream [B, S, C], in fp32."""
    return h.astype(jnp.float32)[..., None]


def read(x, h):
    """x_in [B, S, C] = sum_j H_pre[j] X[j], fp32 sums, in x's dtype."""
    n = x.shape[2]
    xs = x.astype(jnp.float32)
    return sum(_over_tokens(h[:, j]) * xs[:, :, j]
               for j in range(n)).astype(x.dtype)


def write(x, h, y):
    """X' [B, S, n, C]: X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y, fp32
    sums, in the dtype a residual add of x and y has (on AMP's keep tier
    the half-width one: amp.match_kept)."""
    n = x.shape[2]
    out = amp.match_kept(x, y)[0].dtype
    xs, ys = x.astype(jnp.float32), y.astype(jnp.float32)
    return jnp.stack([
        sum(_over_tokens(h[:, 2 * n + i * n + j]) * xs[:, :, j]
            for j in range(n)) + _over_tokens(h[:, n + i]) * ys
        for i in range(n)], axis=2).astype(out)


def _site(ctx, what, tiles, plan, pair, form, *args):
    """One site of `what` under the span `mhc.kernel.lower`
    (kernels/engine.py::site), which says the fields of `tiles`
    (kernels/mhc.py's Tiles or FusedTiles): kernels/mhc.py's `pair` on
    `args` where `plan()` tiles the streams, `form`, the op's arithmetic,
    under jax.checkpoint where it does not (module docstring)."""
    from ..kernels import engine

    return engine.site(
        "mhc.kernel.lower", tiles._fields, ctx.mesh, plan,
        lambda tiles, interpret: pair(*args, tiles, interpret),
        lambda: jax.checkpoint(form)(*args), what=what)


def _streams_infer(op, block):
    x = in_desc(op, block, "X")
    if x is not None:
        set_output(block, op, "Out", list(x.shape[:-1])
                   + [int(op.attr("streams", 1)), x.shape[-1]], x.dtype)


@register_op("mhc_streams", infer_shape=_streams_infer, diff_inputs=["X"])
def _mhc_streams(ctx, ins, attrs):
    """X [B, S, C] copied to each of `streams` residual streams: Out [B,
    S, n, C].  On AMP's keep tier the streams are half-width from here on,
    as a residual sum of a matmul's output is: every later layer reads and
    writes them at that size."""
    x = data(ins["X"][0])
    if amp.keep_output():
        x = amp.mxu_operands(x)[0]
    n = int(attrs["streams"])
    return {"Out": [jnp.broadcast_to(
        x[:, :, None], x.shape[:2] + (n, x.shape[2]))]}


def _maps_infer(op, block):
    x = in_desc(op, block, "X")
    if x is not None:
        B, S, n = x.shape[:3]
        set_output(block, op, "H", [B, 2 * n + n * n, S], DataType.FP32)


_MAPS_INPUTS = ["X", "Phi", "APre", "APost", "ARes", "BPre", "BPost", "BRes"]


def _maps_site(ctx, ins, attrs, what, tiles, plan, pair, form):
    """A `mhc_maps` or `mhc_maps_read` site (`what`) under the name scope
    `mhc.maps` and the span `mhc.lower`: kernels/mhc.py's `pair` where
    `plan(S, n, C, iters, dtype)` tiles the streams (its `tiles`), `form(x,
    phi, *small, **cfg)` where it does not."""
    from ..kernels import engine

    x, phi, *small = (data(ins[s][0]) for s in _MAPS_INPUTS)
    B, S, n, C = x.shape
    iters = int(attrs["sinkhorn_iters"])
    with span("mhc.lower", streams=int(n), sinkhorn_iters=iters, sublayers=1,
              moved_bytes=moved_bytes(B * S, n, C, x.dtype.itemsize,
                                      phi.size * phi.dtype.itemsize)), \
            jax.named_scope("mhc.maps"):
        cfg = dict(epsilon=float(attrs.get("epsilon", 1e-6)),
                   hc_eps=float(attrs.get("hc_eps", 1e-6)), iters=iters,
                   clamp=(float(attrs.get("clamp_min", -30.0)),
                          float(attrs.get("clamp_max", 30.0))))
        return _site(
            ctx, what, tiles, lambda: plan(S, n, C, iters, x.dtype)
            if engine.one_dtype(x) else None,
            functools.partial(pair, **cfg), functools.partial(form, **cfg),
            x, phi, *small)


@register_op("mhc_maps", infer_shape=_maps_infer, diff_inputs=_MAPS_INPUTS)
def _mhc_maps(ctx, ins, attrs):
    """The three maps of one sublayer from the streams X [B, S, n, C], Phi
    [nC, 2n + n^2], the scalars APre, APost, ARes [1] and the biases BPre,
    BPost [n], BRes [n, n]: H [B, 2n + n^2, S] fp32, the tokens on the
    minor axis (module docstring).  Attributes `epsilon` (the RMS over
    vec(X), which has no learned weight), `hc_eps`, `sinkhorn_iters`,
    `clamp_min`, `clamp_max`.  Under the name scope `mhc.maps`; the span
    `mhc.lower` is this op's.  The engine is read from the site (module
    docstring; kernels/mhc.py::maps): `mhc.kernel.lower` with `what`
    maps."""
    from ..kernels import mhc

    return {"H": [_maps_site(ctx, ins, attrs, "maps", mhc.Tiles,
                             mhc.maps_tiles, mhc.maps, maps)]}


def maps_read(x, *small, **cfg):
    """(`maps`' H, `read`'s x_in under it): the two, one after the other."""
    h = maps(x, *small, **cfg)
    return h, read(x, h)


def _read_infer(op, block):
    x = in_desc(op, block, "X")
    if x is not None:
        set_output(block, op, "Out", [x.shape[0], x.shape[1], x.shape[3]],
                   x.dtype)


def _maps_read_infer(op, block):
    _maps_infer(op, block)
    _read_infer(op, block)


@register_op("mhc_maps_read", infer_shape=_maps_read_infer,
             diff_inputs=_MAPS_INPUTS)
def _mhc_maps_read(ctx, ins, attrs):
    """`mhc_maps` and `mhc_read` of one sublayer as ONE op with one
    backward: mhc_maps' inputs and attributes, H [B, 2n + n^2, S] fp32 and
    Out [B, S, C] = sum_j H_pre[j] X[j], the two ops' arithmetic exactly.
    The streams' gradient through the maps and through the read leaves as
    one value made in one pass.  Under the name scope `mhc.maps`, with the
    span `mhc.lower` as mhc_maps has it; `mhc.kernel.lower` with `what`
    maps_read and `resident` (kernels/mhc.py::maps_read)."""
    from ..kernels import mhc

    h, out = _maps_site(ctx, ins, attrs, "maps_read", mhc.FusedTiles,
                        mhc.maps_read_tiles, mhc.maps_read, maps_read)
    return {"H": [h], "Out": [out]}


@register_op("mhc_read", infer_shape=_read_infer, diff_inputs=["X", "H"])
def _mhc_read(ctx, ins, attrs):
    """What a sublayer reads of the streams X [B, S, n, C] under the maps
    H of `mhc_maps`: Out [B, S, C] = sum_j H_pre[j] X[j].  Under the name
    scope `mhc.mix`; the jax.numpy form under jax.checkpoint on every
    site (the kernels that read are `mhc_maps_read`'s)."""
    x, h = data(ins["X"][0]), data(ins["H"][0])
    with jax.named_scope("mhc.mix"):
        return {"Out": [jax.checkpoint(read)(x, h)]}


@register_op("mhc_write", infer_shape=same_shape("X", "Out"),
             diff_inputs=["X", "H", "Y"])
def _mhc_write(ctx, ins, attrs):
    """The streams after a sublayer wrote its output Y [B, S, C] back:
    Out[i] = sum_j H_res[i, j] X[j] + H_post[i] Y, [B, S, n, C].  Under the
    name scope `mhc.mix`; the engine as mhc_maps reads it
    (kernels/mhc.py::write: X and Y of one dtype besides),
    `mhc.kernel.lower` with `what` write."""
    from ..kernels import engine, mhc

    x, h, y = (data(ins[s][0]) for s in ("X", "H", "Y"))
    B, S, n, C = x.shape
    with jax.named_scope("mhc.mix"):
        return {"Out": [_site(
            ctx, "write", mhc.Tiles,
            lambda: mhc.mix_tiles(S, n, C, x.dtype, "write")
            if engine.one_dtype(x, y) else None, mhc.write, write, x, h, y)]}
