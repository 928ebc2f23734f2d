"""Neural-net ops: convolution, pooling, normalization, softmax, dropout.

Reference kernels: paddle/fluid/operators/{conv,pool,batch_norm,layer_norm,
group_norm,lrn}_op.* with cuDNN/MKLDNN variants.  On TPU the cuDNN layer has
no equivalent: convs lower to lax.conv_general_dilated (MXU), everything
else to fusible jnp — XLA owns algorithm choice and fusion.
Layout is NCHW to match the reference's default.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core import amp
from ..core.proto import DataType
from ..core.registry import register_op
from ..observability import span
from .common import data, in_desc, same_shape, set_output, wrap_lod


# -- conv --------------------------------------------------------------------
def _conv_out_dim(size, k, pad, stride, dilation):
    if size < 0:
        return -1
    eff = dilation * (k - 1) + 1
    return (size + 2 * pad - eff) // stride + 1


def _conv2d_infer(op, block):
    x = in_desc(op, block, "Input")
    f = in_desc(op, block, "Filter")
    if x is None or f is None:
        return
    strides = op.attr("strides", [1, 1])
    paddings = op.attr("paddings", [0, 0])
    dilations = op.attr("dilations", [1, 1])
    n, _, h, w = x.shape
    oc, _, kh, kw = f.shape
    set_output(
        block, op, "Output",
        [n, oc,
         _conv_out_dim(h, kh, paddings[0], strides[0], dilations[0]),
         _conv_out_dim(w, kw, paddings[1], strides[1], dilations[1])],
        x.dtype,
    )


def _conv2d_lower(ctx, ins, attrs):
    from ..flags import conv_layout

    x = data(ins["Input"][0])
    f = data(ins["Filter"][0])
    strides = attrs.get("strides", [1, 1])
    paddings = attrs.get("paddings", [0, 0])
    dilations = attrs.get("dilations", [1, 1])
    groups = attrs.get("groups", 1) or 1
    xc, fc = amp.mxu_operands(x, f)
    if conv_layout() == "NHWC":
        # TPU-preferred internal layout: compute in NHWC behind boundary
        # transposes.  Between chained conv/BN/relu blocks XLA cancels the
        # back-to-back transposes, so the network body runs NHWC end to
        # end while the program-level contract stays NCHW.
        out = jax.lax.conv_general_dilated(
            jnp.transpose(xc, (0, 2, 3, 1)),
            jnp.transpose(fc, (2, 3, 1, 0)),
            window_strides=strides,
            padding=[(paddings[0], paddings[0]), (paddings[1], paddings[1])],
            rhs_dilation=dilations,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups,
        )
        out = jnp.transpose(out, (0, 3, 1, 2))
    else:
        out = jax.lax.conv_general_dilated(
            xc, fc,
            window_strides=strides,
            padding=[(paddings[0], paddings[0]), (paddings[1], paddings[1])],
            rhs_dilation=dilations,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=groups,
        )
    return {"Output": [amp.mxu_output(out, x, f)]}


register_op("conv2d", infer_shape=_conv2d_infer, diff_inputs=["Input", "Filter"])(_conv2d_lower)


def _depthwise_infer(op, block):
    _conv2d_infer(op, block)


@register_op("depthwise_conv2d", infer_shape=_depthwise_infer, diff_inputs=["Input", "Filter"])
def _depthwise_conv2d(ctx, ins, attrs):
    """Reference: operators/conv_op.cc depthwise registration — groups equals
    input channels; filter is [C*mult, 1, kh, kw]."""
    x = data(ins["Input"][0])
    attrs = dict(attrs)
    attrs["groups"] = x.shape[1]
    return _conv2d_lower(ctx, ins, attrs)


def _conv2d_transpose_infer(op, block):
    x = in_desc(op, block, "Input")
    f = in_desc(op, block, "Filter")
    if x is None or f is None:
        return
    strides = op.attr("strides", [1, 1])
    paddings = op.attr("paddings", [0, 0])
    dilations = op.attr("dilations", [1, 1])
    n, _, h, w = x.shape
    _, oc_per_g, kh, kw = f.shape
    groups = op.attr("groups", 1) or 1

    def out_dim(size, k, pad, stride, dil):
        if size < 0:
            return -1
        return (size - 1) * stride - 2 * pad + dil * (k - 1) + 1

    set_output(
        block, op, "Output",
        [n, oc_per_g * groups,
         out_dim(h, kh, paddings[0], strides[0], dilations[0]),
         out_dim(w, kw, paddings[1], strides[1], dilations[1])],
        x.dtype,
    )


def _conv_transpose_lower(x, f, strides, paddings, dilations, groups, nd):
    """Transposed conv as the classic fractionally-strided conv:
    lhs_dilation=strides, per-dim padding d*(k-1)-p, spatially-flipped
    kernel.  Matches the reference scatter semantics exactly for every
    (stride, pad, dilation) combination — verified against a direct scatter
    reference (jax.lax.conv_transpose's own padding convention differs from
    the reference's output-size formula (in-1)*s - 2p + d*(k-1) + 1).
    Paddle filter layout [in_c, out_c/g, k...] is spec I-O-spatial."""
    spatial = tuple(range(2, 2 + nd))
    k = f.shape[2:]
    pads = [
        (dilations[i] * (k[i] - 1) - paddings[i],) * 2 for i in range(nd)
    ]
    spec = ("NC" + "DHW"[-nd:], "IO" + "DHW"[-nd:], "NC" + "DHW"[-nd:])

    def one_group(xg, fg):
        xgc, fgc = amp.mxu_operands(xg, jnp.flip(fg, spatial))
        return amp.mxu_output(jax.lax.conv_general_dilated(
            xgc, fgc,
            window_strides=(1,) * nd,
            padding=pads,
            lhs_dilation=strides,
            rhs_dilation=dilations,
            dimension_numbers=spec,
        ), xg, fg)

    if groups == 1:
        return one_group(x, f)
    xs = jnp.split(x, groups, axis=1)
    fs = jnp.split(f, groups, axis=0)
    return jnp.concatenate(
        [one_group(xg, fg) for xg, fg in zip(xs, fs)], axis=1
    )


@register_op("conv2d_transpose", infer_shape=_conv2d_transpose_infer, diff_inputs=["Input", "Filter"])
def _conv2d_transpose(ctx, ins, attrs):
    """Gradient-of-conv as a forward op (reference:
    operators/conv_transpose_op.cc).  Filter layout [in_c, out_c/g, kh, kw]."""
    x = data(ins["Input"][0])
    f = data(ins["Filter"][0])
    out = _conv_transpose_lower(
        x, f,
        [int(s) for s in attrs.get("strides", [1, 1])],
        [int(p) for p in attrs.get("paddings", [0, 0])],
        [int(d) for d in attrs.get("dilations", [1, 1])],
        attrs.get("groups", 1) or 1, 2,
    )
    return {"Output": [out]}


def _conv3d_infer(op, block):
    x = in_desc(op, block, "Input")
    f = in_desc(op, block, "Filter")
    if x is None or f is None:
        return
    strides = op.attr("strides", [1, 1, 1])
    paddings = op.attr("paddings", [0, 0, 0])
    dilations = op.attr("dilations", [1, 1, 1])
    n = x.shape[0]
    oc = f.shape[0]
    dims = [
        _conv_out_dim(x.shape[i + 2], f.shape[i + 2], paddings[i], strides[i], dilations[i])
        for i in range(3)
    ]
    set_output(block, op, "Output", [n, oc] + dims, x.dtype)


@register_op("conv3d", infer_shape=_conv3d_infer, diff_inputs=["Input", "Filter"])
def _conv3d(ctx, ins, attrs):
    x = data(ins["Input"][0])
    f = data(ins["Filter"][0])
    strides = attrs.get("strides", [1, 1, 1])
    paddings = attrs.get("paddings", [0, 0, 0])
    dilations = attrs.get("dilations", [1, 1, 1])
    xc, fc = amp.mxu_operands(x, f)
    out = jax.lax.conv_general_dilated(
        xc, fc,
        window_strides=strides,
        padding=[(p, p) for p in paddings],
        rhs_dilation=dilations,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        feature_group_count=attrs.get("groups", 1) or 1,
    )
    return {"Output": [amp.mxu_output(out, x, f)]}


# -- pooling -----------------------------------------------------------------
def _pool_out_dim(size, k, pad, stride, ceil_mode):
    if size < 0:
        return -1
    num = size + 2 * pad - k
    if ceil_mode:
        return -(-num // stride) + 1
    return num // stride + 1


def _pool2d_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    n, c, h, w = x.shape
    if op.attr("global_pooling", False):
        set_output(block, op, "Out", [n, c, 1, 1], x.dtype)
        return
    if op.attr("adaptive", False):
        k = op.attr("ksize", [1, 1])
        set_output(block, op, "Out", [n, c, k[0], k[1]], x.dtype)
        return
    k = op.attr("ksize", [1, 1])
    s = op.attr("strides", [1, 1])
    p = op.attr("paddings", [0, 0])
    cm = op.attr("ceil_mode", False)
    set_output(
        block, op, "Out",
        [n, c, _pool_out_dim(h, k[0], p[0], s[0], cm), _pool_out_dim(w, k[1], p[1], s[1], cm)],
        x.dtype,
    )


def _pool(x, ksize, strides, paddings, pooling_type, exclusive, ceil_mode, spatial,
          nhwc=False):
    """Shared reduce_window pooling for 2d/3d.  nhwc=True pools a
    channels-last operand (window over the middle spatial dims)."""
    spatial_pads = tuple(
        (p, p + (s - 1 if ceil_mode else 0)) for p, s in zip(paddings, strides)
    )
    if nhwc:
        window = (1,) + tuple(ksize) + (1,)
        strides_full = (1,) + tuple(strides) + (1,)
        pads = ((0, 0),) + spatial_pads + ((0, 0),)
    else:
        window = (1, 1) + tuple(ksize)
        strides_full = (1, 1) + tuple(strides)
        pads = ((0, 0), (0, 0)) + spatial_pads
    if pooling_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return jax.lax.reduce_window(x, init, jax.lax.max, window, strides_full, pads)
    # avg pooling
    summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides_full, pads)
    if exclusive:
        ones = jnp.ones(x.shape, dtype=x.dtype)
        counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides_full, pads)
        return summed / jnp.maximum(counts, 1.0)
    denom = 1.0
    for k in ksize:
        denom *= k
    return summed / denom


@register_op("pool2d", infer_shape=_pool2d_infer)
def _pool2d(ctx, ins, attrs):
    from ..flags import conv_layout

    x = data(ins["X"][0])
    if attrs.get("global_pooling", False):
        if attrs.get("pooling_type", "max") == "max":
            out = jnp.max(x, axis=(2, 3), keepdims=True)
        else:
            out = jnp.mean(x, axis=(2, 3), keepdims=True)
        return {"Out": [out]}
    if attrs.get("adaptive", False):
        return _pool2d_adaptive(ctx, ins, attrs)
    pool_args = (
        attrs.get("ksize", [1, 1]), attrs.get("strides", [1, 1]),
        attrs.get("paddings", [0, 0]), attrs.get("pooling_type", "max"),
        attrs.get("exclusive", True), attrs.get("ceil_mode", False),
    )
    if conv_layout() == "NHWC":
        # Pool in NHWC behind boundary transposes so the whole conv/BN/pool
        # body stays NHWC internally: XLA cancels these against the
        # neighbouring conv transposes, where an NCHW reduce_window between
        # NHWC convs would force real relayouts (fwd and in the
        # select-and-scatter backward).
        out = jnp.transpose(
            _pool(jnp.transpose(x, (0, 2, 3, 1)), *pool_args, 2, nhwc=True),
            (0, 3, 1, 2))
    else:
        out = _pool(x, *pool_args, 2)
    return {"Out": [out]}


def _pool3d_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    n, c = x.shape[:2]
    if op.attr("global_pooling", False):
        set_output(block, op, "Out", [n, c, 1, 1, 1], x.dtype)
        return
    if op.attr("adaptive", False):
        k = op.attr("ksize", [1, 1, 1])
        set_output(block, op, "Out", [n, c, k[0], k[1], k[2]], x.dtype)
        return
    k = op.attr("ksize", [1, 1, 1])
    s = op.attr("strides", [1, 1, 1])
    p = op.attr("paddings", [0, 0, 0])
    cm = op.attr("ceil_mode", False)
    dims = [_pool_out_dim(x.shape[i + 2], k[i], p[i], s[i], cm) for i in range(3)]
    set_output(block, op, "Out", [n, c] + dims, x.dtype)


@register_op("pool3d", infer_shape=_pool3d_infer)
def _pool3d(ctx, ins, attrs):
    x = data(ins["X"][0])
    if attrs.get("global_pooling", False):
        fn = jnp.max if attrs.get("pooling_type", "max") == "max" else jnp.mean
        return {"Out": [fn(x, axis=(2, 3, 4), keepdims=True)]}
    if attrs.get("adaptive", False):
        return _pool3d_adaptive(ctx, ins, attrs)
    out = _pool(
        x, attrs.get("ksize", [1, 1, 1]), attrs.get("strides", [1, 1, 1]),
        attrs.get("paddings", [0, 0, 0]), attrs.get("pooling_type", "max"),
        attrs.get("exclusive", True), attrs.get("ceil_mode", False), 3,
    )
    return {"Out": [out]}


@register_op("maxout", infer_shape=lambda op, block: set_output(block, op, "Out", [in_desc(op, block, "X").shape[0], in_desc(op, block, "X").shape[1] // op.attr("groups", 1)] + list(in_desc(op, block, "X").shape[2:]), in_desc(op, block, "X").dtype))
def _maxout(ctx, ins, attrs):
    x = data(ins["X"][0])
    g = attrs["groups"]
    n, c = x.shape[:2]
    out = jnp.max(jnp.reshape(x, (n, c // g, g) + x.shape[2:]), axis=2)
    return {"Out": [out]}


# -- normalization -----------------------------------------------------------
def _batch_norm_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    set_output(block, op, "Y", x.shape, x.dtype)
    c = x.shape[1] if op.attr("data_layout", "NCHW") == "NCHW" else x.shape[-1]
    for slot in ("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"):
        set_output(block, op, slot, [c], x.dtype)


def _bn_core(ctx, ins, attrs):
    """The one copy of the batch-norm math (reference:
    operators/batch_norm_op.cc), shared by the plain batch_norm lowering
    and the fused_bn_add_act twin so the fp32-stats rule and the
    SavedVariance=rsqrt convention can never drift apart.  Returns the
    standard output dict; callers extend Y."""
    x = data(ins["X"][0])
    scale = data(ins["Scale"][0])
    bias = data(ins["Bias"][0])
    mean = data(ins["Mean"][0])
    var = data(ins["Variance"][0])
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False) or ctx.is_test
    layout = attrs.get("data_layout", "NCHW")

    caxis = 1 if layout == "NCHW" else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != caxis)
    bshape = [1] * x.ndim
    bshape[caxis] = -1

    if is_test or attrs.get("use_global_stats", False):
        use_mean, use_var = mean, var
        new_mean, new_var = mean, var
        saved_mean = mean
    else:
        # statistics always accumulate in fp32, even for bf16 activations
        # (amp keep_output mode); the moving-stat state vars are fp32
        xs = x.astype(amp.stats_dtype(x))
        use_mean = jnp.mean(xs, axis=axes)
        use_var = jnp.var(xs, axis=axes)
        new_mean = momentum * mean + (1.0 - momentum) * use_mean
        new_var = momentum * var + (1.0 - momentum) * use_var
        saved_mean = use_mean

    inv = jax.lax.rsqrt(use_var + eps)
    # the normalize+affine runs in fp32 inside the fusion but the HBM
    # write of y matches x's dtype (bf16 in keep_output mode)
    y = (
        x.astype(inv.dtype) - use_mean.reshape(bshape)
    ) * inv.reshape(bshape) * scale.reshape(bshape) + bias.reshape(bshape)
    y = y.astype(x.dtype)
    return {
        "Y": [y],
        "MeanOut": [new_mean],
        "VarianceOut": [new_var],
        "SavedMean": [saved_mean.astype(x.dtype)],
        "SavedVariance": [inv.astype(x.dtype)],
    }


@register_op(
    "batch_norm",
    infer_shape=_batch_norm_infer,
    diff_inputs=["X", "Scale", "Bias"],
)
def _batch_norm(ctx, ins, attrs):
    """Reference: operators/batch_norm_op.cc.  Train mode normalizes with
    batch statistics and emits updated moving stats (MeanOut/VarianceOut
    alias the Mean/Variance state vars); test mode uses the moving stats."""
    return _bn_core(ctx, ins, attrs)


def _fused_bn_add_act_infer(op, block):
    # the residual Z must match X exactly: a broadcastable-but-wrong Z
    # (e.g. [N,C,1,1]) would silently broadcast in the lowering's y + z
    # instead of failing here (ADVICE r4)
    x, z = in_desc(op, block, "X"), in_desc(op, block, "Z")
    if x is not None and z is not None and list(z.shape) != list(x.shape):
        raise ValueError(
            f"fused_bn_add_act: residual Z shape {list(z.shape)} must equal "
            f"X shape {list(x.shape)} (op {op.type})")
    _batch_norm_infer(op, block)


@register_op(
    "fused_bn_add_act",
    infer_shape=_fused_bn_add_act_infer,
    diff_inputs=["X", "Z", "Scale", "Bias"],
)
def _fused_bn_add_act(ctx, ins, attrs):
    """batch_norm + residual add + activation as ONE op (replaces the
    reference's separate batch_norm_op.cu.cc + elementwise_add + relu
    kernel dispatches; later Paddle grew the same fusion as
    fused_bn_add_activation).  Numerically identical to the unfused
    chain — the value is storage: the layer tags the op @recompute@, so
    jax.checkpoint drops the op-INTERNAL buffers (x_hat, the pre-relu
    sum) and backward recomputes them from X/Z — which BN's backward
    must read anyway.  On an HBM-bound model (ResNet-50: 72% of device
    time in these chains in an earlier round's v5e run, not
    re-measured) that removes one-to-two
    activation-sized HBM round-trips per BN."""
    outs = _bn_core(ctx, ins, attrs)
    y = outs["Y"][0]
    z = ins.get("Z", [None])[0]
    act = attrs.get("act") or None
    if z is not None:
        y = y + data(z).astype(y.dtype)  # residual matches activation dtype
    if act == "relu":
        y = jax.nn.relu(y)
    elif act:
        raise ValueError(f"fused_bn_add_act: unsupported act {act!r}")
    outs["Y"] = [y]
    return outs


def _layer_norm_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    set_output(block, op, "Y", x.shape, x.dtype)
    begin = op.attr("begin_norm_axis", 1)
    lead = 1
    ok = all(d >= 0 for d in x.shape[:begin])
    for d in x.shape[:begin]:
        lead *= d
    set_output(block, op, "Mean", [lead if ok else -1], x.dtype)
    set_output(block, op, "Variance", [lead if ok else -1], x.dtype)


@register_op("layer_norm", infer_shape=_layer_norm_infer, diff_inputs=["X", "Scale", "Bias"])
def _layer_norm(ctx, ins, attrs):
    """Reference: operators/layer_norm_op.cc — normalize over dims >=
    begin_norm_axis."""
    x = data(ins["X"][0])
    begin = attrs.get("begin_norm_axis", 1)
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(begin, x.ndim))
    # stats in fp32 even for bf16 activations (amp keep_output mode); the
    # HBM write of Y matches x's dtype
    xs = x.astype(amp.stats_dtype(x))
    mean = jnp.mean(xs, axis=axes, keepdims=True)
    var = jnp.var(xs, axis=axes, keepdims=True)
    y = (xs - mean) * jax.lax.rsqrt(var + eps)
    scale = ins.get("Scale", [None])[0]
    bias = ins.get("Bias", [None])[0]
    tail_shape = (1,) * begin + x.shape[begin:]
    if scale is not None:
        y = y * jnp.reshape(data(scale), tail_shape)
    if bias is not None:
        y = y + jnp.reshape(data(bias), tail_shape)
    return {
        "Y": [y.astype(x.dtype)],
        "Mean": [jnp.reshape(mean, (-1,)).astype(x.dtype)],
        "Variance": [jnp.reshape(var, (-1,)).astype(x.dtype)],
    }


def _rms_norm_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    set_output(block, op, "Y", x.shape, x.dtype)


@register_op("rms_norm", infer_shape=_rms_norm_infer,
             diff_inputs=["X", "Scale"])
def _rms_norm(ctx, ins, attrs):
    """x / sqrt(mean(x^2) + eps) [* scale] over dims >= begin_norm_axis
    (Zhang & Sennrich 2019): layer_norm without the mean and the shift.
    Under `unit_offset` the factor is (1 + scale), a scale that starts at
    0.  TPU-native addition; the 2018 reference has no such op."""
    x = data(ins["X"][0])
    begin = attrs.get("begin_norm_axis", x.ndim - 1)
    eps = attrs.get("epsilon", 1e-6)
    axes = tuple(range(begin, x.ndim))
    # the mean of squares and the scaling in fp32 even for bf16
    # activations (amp keep_output mode); Y is written in x's dtype
    xs = x.astype(amp.stats_dtype(x))
    y = xs * jax.lax.rsqrt(
        jnp.mean(jnp.square(xs), axis=axes, keepdims=True) + eps)
    scale = ins.get("Scale", [None])[0]
    if scale is not None:
        scale = jnp.reshape(data(scale), (1,) * begin + x.shape[begin:])
        y = y * (1.0 + scale if attrs.get("unit_offset", False) else scale)
    return {"Y": [y.astype(x.dtype)]}


def _group_norm_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    set_output(block, op, "Y", x.shape, x.dtype)
    n, g = x.shape[0], op.attr("groups", 1)
    set_output(block, op, "Mean", [n, g], x.dtype)
    set_output(block, op, "Variance", [n, g], x.dtype)


@register_op("group_norm", infer_shape=_group_norm_infer, diff_inputs=["X", "Scale", "Bias"])
def _group_norm(ctx, ins, attrs):
    x = data(ins["X"][0])
    g = attrs.get("groups", 1)
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[:2]
    xg = jnp.reshape(x.astype(amp.stats_dtype(x)),
                     (n, g, c // g) + x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    y = jnp.reshape((xg - mean) * jax.lax.rsqrt(var + eps), x.shape)
    bshape = (1, c) + (1,) * (x.ndim - 2)
    scale = ins.get("Scale", [None])[0]
    bias = ins.get("Bias", [None])[0]
    if scale is not None:
        y = y * jnp.reshape(data(scale), bshape)
    if bias is not None:
        y = y + jnp.reshape(data(bias), bshape)
    return {
        "Y": [y.astype(x.dtype)],
        "Mean": [jnp.reshape(mean, (n, g)).astype(x.dtype)],
        "Variance": [jnp.reshape(var, (n, g)).astype(x.dtype)],
    }


def _norm_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    set_output(block, op, "Out", x.shape, x.dtype)
    axis = op.attr("axis", -1)
    rank = len(x.shape)
    axis = axis + rank if axis < 0 else axis
    shape = [1 if i == axis else d for i, d in enumerate(x.shape)]
    set_output(block, op, "Norm", shape, x.dtype)


@register_op("norm", infer_shape=_norm_infer, diff_inputs=["X"])
def _norm(ctx, ins, attrs):
    """L2-normalize along axis (reference: operators/norm_op.cc)."""
    x = data(ins["X"][0])
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=True) + eps)
    return {"Out": [x / norm], "Norm": [norm]}


@register_op("lrn", infer_shape=same_shape())
def _lrn(ctx, ins, attrs):
    """Local response norm over channels (reference: operators/lrn_op.cc)."""
    x = data(ins["X"][0])
    n_size = attrs.get("n", 5)
    k = attrs.get("k", 2.0)
    alpha = attrs.get("alpha", 1e-4)
    beta = attrs.get("beta", 0.75)
    sq = x * x
    half = n_size // 2
    pads = [(0, 0), (half, n_size - 1 - half), (0, 0), (0, 0)]
    summed = jax.lax.reduce_window(
        sq, 0.0, jax.lax.add, (1, n_size, 1, 1), (1, 1, 1, 1), pads
    )
    return {"Out": [x / jnp.power(k + alpha * summed, beta)]}


# -- softmax / dropout -------------------------------------------------------
@register_op("softmax", infer_shape=same_shape())
def _softmax(ctx, ins, attrs):
    x = ins["X"][0]
    d = data(x)
    # bf16 logits (amp keep_output) exponentiate in fp32; the output
    # dtype still matches the input's desc
    out = jax.nn.softmax(d.astype(amp.stats_dtype(d)),
                         axis=attrs.get("axis", -1)).astype(d.dtype)
    return {"Out": [wrap_lod(x, out)]}


def _dropout_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    set_output(block, op, "Out", x.shape, x.dtype, lod_level=x.lod_level)
    set_output(block, op, "Mask", x.shape, DataType.UINT8)


@register_op("dropout", infer_shape=_dropout_infer, diff_inputs=["X"], random=True)
def _dropout(ctx, ins, attrs):
    """Reference: operators/dropout_op.cc.  Implementations:
    downgrade_in_infer (default; train keeps scale, infer multiplies by 1-p)
    and upscale_in_train (train scales by 1/(1-p), infer is identity).
    The mask is drawn ONCE a site from the site's key and stored, a byte
    an element (kernels/dropout_mask.py): the forward's select, the input
    gradient and every matmul XLA fuses them into read it, none derives
    it again.  `dropout.lower` (a span, at lowering, one a site that
    draws) says `elements`, `prob`, `draw` (the generator), `engine`
    (pallas | xla), `block_rows` and `mask_bytes`, what the site stores."""
    from ..kernels import dropout_mask

    x = ins["X"][0]
    xv = data(x)
    p = attrs.get("dropout_prob", 0.5)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    is_test = attrs.get("is_test", False) or ctx.is_test
    if is_test:
        out = xv if impl == "upscale_in_train" else xv * (1.0 - p)
        return {"Out": [wrap_lod(x, out)], "Mask": [jnp.ones_like(xv, dtype=jnp.uint8)]}
    with span("dropout.lower", elements=int(np.prod(np.shape(xv))),
              prob=float(p)) as sp:
        mask, drawn = dropout_mask.draw(ctx.rng(), np.shape(xv), p,
                                        mesh=ctx.mesh)
        sp.set(draw=drawn.generator, engine=drawn.engine,
               block_rows=drawn.block_rows, mask_bytes=int(mask.size))
    kept = xv / max(1.0 - p, 1e-8) if impl == "upscale_in_train" else xv
    out = jnp.where(mask != 0, kept, jnp.zeros((), xv.dtype))
    return {"Out": [wrap_lod(x, out)], "Mask": [mask]}


# -- interpolation -----------------------------------------------------------
def _interp_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    oh = op.attr("out_h", -1)
    ow = op.attr("out_w", -1)
    set_output(block, op, "Out", [x.shape[0], x.shape[1], oh, ow], x.dtype)


def _interp(ctx, ins, attrs, method):
    """Reference: operators/interpolate_op.h:171 — ratio = (in-1)/(out-1)
    (align-corners sampling; the snapshot predates the align_corners attr),
    bilinear lerps the floor/ceil neighbours, nearest rounds ratio*k+0.5.
    jax.image.resize is NOT equivalent (half-pixel centers), so the
    gathers are explicit."""
    x = data(ins["X"][0])
    oh, ow = attrs.get("out_h"), attrs.get("out_w")
    out_size = ins.get("OutSize", [None])[0]
    if out_size is not None:
        sz = np.asarray(out_size).reshape(-1)
        oh, ow = int(sz[0]), int(sz[1])
    ih, iw = x.shape[2], x.shape[3]

    def ratio(i, o):
        return (i - 1) / (o - 1) if o > 1 else 0.0

    rh, rw = ratio(ih, oh), ratio(iw, ow)
    if method == "nearest":
        idx_h = np.floor(rh * np.arange(oh) + 0.5).astype(np.int32)
        idx_w = np.floor(rw * np.arange(ow) + 0.5).astype(np.int32)
        out = x[:, :, idx_h.clip(0, ih - 1)][:, :, :, idx_w.clip(0, iw - 1)]
        return {"Out": [out]}

    src_h = rh * np.arange(oh)
    src_w = rw * np.arange(ow)
    lo_h = np.floor(src_h).astype(np.int32).clip(0, ih - 1)
    lo_w = np.floor(src_w).astype(np.int32).clip(0, iw - 1)
    hi_h = np.minimum(lo_h + 1, ih - 1)
    hi_w = np.minimum(lo_w + 1, iw - 1)
    wh = jnp.asarray((src_h - lo_h).astype(np.float32)).reshape(1, 1, -1, 1)
    ww = jnp.asarray((src_w - lo_w).astype(np.float32)).reshape(1, 1, 1, -1)
    xlo, xhi = x[:, :, lo_h], x[:, :, hi_h]
    top = xlo[:, :, :, lo_w] * (1.0 - ww) + xlo[:, :, :, hi_w] * ww
    bot = xhi[:, :, :, lo_w] * (1.0 - ww) + xhi[:, :, :, hi_w] * ww
    out = (top * (1.0 - wh) + bot * wh).astype(x.dtype)
    return {"Out": [out]}


@register_op("bilinear_interp", infer_shape=_interp_infer, diff_inputs=["X"])
def _bilinear_interp(ctx, ins, attrs):
    return _interp(ctx, ins, attrs, "bilinear")


@register_op("nearest_interp", infer_shape=_interp_infer, diff_inputs=["X"])
def _nearest_interp(ctx, ins, attrs):
    return _interp(ctx, ins, attrs, "nearest")


# -- pooling variants (indexed / adaptive / unpool / spp) --------------------
def _adaptive_bounds(size, bins):
    """Reference math/pooling.h AdaptiveStartIndex/AdaptiveEndIndex:
    start = floor(i*size/bins), end = ceil((i+1)*size/bins).  size and bins
    are static, so every slice bound below is a compile-time constant."""
    return [
        (int(np.floor(i * size / bins)), int(np.ceil((i + 1) * size / bins)))
        for i in range(bins)
    ]


def _adaptive_pool(x, bins, pooling_type, spatial):
    """Adaptive pooling over the trailing `spatial` dims; bins per dim are
    static so this unrolls into bins^spatial static slices (bins are small —
    XLA fuses the gathers into one pass)."""
    red = jnp.max if pooling_type == "max" else jnp.mean
    dims = x.shape[-spatial:]
    bounds = [_adaptive_bounds(d, b) for d, b in zip(dims, bins)]

    if spatial == 2:
        rows = []
        for s0, e0 in bounds[0]:
            cols = [
                red(x[..., s0:e0, s1:e1], axis=(-2, -1))
                for s1, e1 in bounds[1]
            ]
            rows.append(jnp.stack(cols, axis=-1))
        return jnp.stack(rows, axis=-2)
    rows = []
    for s0, e0 in bounds[0]:
        mids = []
        for s1, e1 in bounds[1]:
            cols = [
                red(x[..., s0:e0, s1:e1, s2:e2], axis=(-3, -2, -1))
                for s2, e2 in bounds[2]
            ]
            mids.append(jnp.stack(cols, axis=-1))
        rows.append(jnp.stack(mids, axis=-2))
    return jnp.stack(rows, axis=-3)


def _pool2d_adaptive(ctx, ins, attrs):
    x = data(ins["X"][0])
    out = _adaptive_pool(
        x, [int(k) for k in attrs["ksize"]],
        attrs.get("pooling_type", "max"), 2,
    )
    return {"Out": [out]}


def _pool3d_adaptive(ctx, ins, attrs):
    x = data(ins["X"][0])
    out = _adaptive_pool(
        x, [int(k) for k in attrs["ksize"]],
        attrs.get("pooling_type", "max"), 3,
    )
    return {"Out": [out]}


def _pool_with_index_infer(spatial):
    def infer(op, block):
        x = in_desc(op, block, "X")
        if x is None:
            return
        n, c = x.shape[:2]
        if op.attr("adaptive", False) or op.attr("global_pooling", False):
            dims = (
                [1] * spatial
                if op.attr("global_pooling", False)
                else [int(k) for k in op.attr("ksize")]
            )
        else:
            k = op.attr("ksize", [1] * spatial)
            s = op.attr("strides", [1] * spatial)
            p = op.attr("paddings", [0] * spatial)
            dims = [
                _pool_out_dim(x.shape[i + 2], k[i], p[i], s[i], False)
                for i in range(spatial)
            ]
        set_output(block, op, "Out", [n, c] + dims, x.dtype)
        set_output(block, op, "Mask", [n, c] + dims, DataType.INT32)
    return infer


def _max_pool_with_index(ctx, ins, attrs, spatial):
    """Max pooling that also emits the argmax's flat index within the input
    feature map (reference: math/pooling.h MaxPool2dWithIndexFunctor —
    index = h*W + w of the winning input element).  Lowered as
    patch-extraction + argmax; the value path is take_along_axis over
    patches so the grad scatters to the argmax positions exactly like the
    reference's backward kernel."""
    x = data(ins["X"][0])
    if attrs.get("global_pooling", False):
        ksize = list(x.shape[-spatial:])
        strides = ksize
        paddings = [0] * spatial
        adaptive = False
    else:
        ksize = [int(k) for k in attrs["ksize"]]
        strides = [int(s) for s in attrs.get("strides", [1] * spatial)]
        paddings = [int(p) for p in attrs.get("paddings", [0] * spatial)]
        adaptive = bool(attrs.get("adaptive", False))
    N, C = x.shape[:2]
    in_dims = x.shape[2:]

    # flat input index grid, same spatial shape as x (int32: a float grid
    # loses exactness above 2^24 on large feature maps)
    flat = np.arange(int(np.prod(in_dims)), dtype=np.int32).reshape(in_dims)
    idx = jnp.broadcast_to(jnp.asarray(flat), x.shape)

    if adaptive:
        bins = ksize
        bounds = [_adaptive_bounds(d, b) for d, b in zip(in_dims, bins)]

        def cell(slices):
            xs = x[(...,) + slices]
            red_axes = tuple(range(-spatial, 0))
            flatc = xs.reshape(xs.shape[: x.ndim - spatial] + (-1,))
            am = jnp.argmax(flatc, axis=-1)
            vals = jnp.take_along_axis(flatc, am[..., None], axis=-1)[..., 0]
            idxc = idx[(...,) + slices].reshape(flatc.shape)
            ids = jnp.take_along_axis(idxc, am[..., None], axis=-1)[..., 0]
            return vals, ids

        if spatial == 2:
            vs, is_ = [], []
            for s0, e0 in bounds[0]:
                vrow, irow = [], []
                for s1, e1 in bounds[1]:
                    v, i = cell((slice(s0, e0), slice(s1, e1)))
                    vrow.append(v)
                    irow.append(i)
                vs.append(jnp.stack(vrow, axis=-1))
                is_.append(jnp.stack(irow, axis=-1))
            out = jnp.stack(vs, axis=-2)
            mask = jnp.stack(is_, axis=-2)
        else:
            vs, is_ = [], []
            for s0, e0 in bounds[0]:
                vmid, imid = [], []
                for s1, e1 in bounds[1]:
                    vrow, irow = [], []
                    for s2, e2 in bounds[2]:
                        v, i = cell(
                            (slice(s0, e0), slice(s1, e1), slice(s2, e2))
                        )
                        vrow.append(v)
                        irow.append(i)
                    vmid.append(jnp.stack(vrow, axis=-1))
                    imid.append(jnp.stack(irow, axis=-1))
                vs.append(jnp.stack(vmid, axis=-2))
                is_.append(jnp.stack(imid, axis=-2))
            out = jnp.stack(vs, axis=-3)
            mask = jnp.stack(is_, axis=-3)
        return {"Out": [out], "Mask": [mask.astype(jnp.int32)]}

    # strided case: extract patches, argmax within each
    pad_full = [(0, 0), (0, 0)] + [(p, p) for p in paddings]
    xp = jnp.pad(x, pad_full, constant_values=-np.inf)
    ip = jnp.pad(idx, pad_full, constant_values=-1)

    K = int(np.prod(ksize))
    # gather all K shifted strided views: [K, N, C, *out_dims]
    out_dims = [
        (x.shape[2 + i] + 2 * paddings[i] - ksize[i]) // strides[i] + 1
        for i in range(spatial)
    ]

    def shifted(arr, offs):
        sl = [slice(None), slice(None)]
        for i in range(spatial):
            sl.append(
                slice(offs[i], offs[i] + (out_dims[i] - 1) * strides[i] + 1,
                      strides[i])
            )
        return arr[tuple(sl)]

    offsets = list(np.ndindex(*ksize))
    vals = jnp.stack([shifted(xp, o) for o in offsets])  # [K, N, C, ...]
    idxs = jnp.stack([shifted(ip, o) for o in offsets])
    am = jnp.argmax(vals, axis=0)  # [N, C, ...]
    out = jnp.take_along_axis(vals, am[None], axis=0)[0]
    mask = jnp.take_along_axis(idxs, am[None], axis=0)[0]
    return {"Out": [out], "Mask": [mask.astype(jnp.int32)]}


@register_op("max_pool2d_with_index",
             infer_shape=_pool_with_index_infer(2), diff_inputs=["X"])
def _max_pool2d_with_index(ctx, ins, attrs):
    return _max_pool_with_index(ctx, ins, attrs, 2)


@register_op("max_pool3d_with_index",
             infer_shape=_pool_with_index_infer(3), diff_inputs=["X"])
def _max_pool3d_with_index(ctx, ins, attrs):
    return _max_pool_with_index(ctx, ins, attrs, 3)


def _unpool_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    k = op.attr("ksize", [1, 1])
    s = op.attr("strides", [1, 1])
    p = op.attr("paddings", [0, 0])
    n, c, h, w = x.shape
    dims = [
        (h - 1) * s[0] - 2 * p[0] + k[0] if h > 0 else -1,
        (w - 1) * s[1] - 2 * p[1] + k[1] if w > 0 else -1,
    ]
    set_output(block, op, "Out", [n, c] + dims, x.dtype)


@register_op("unpool", infer_shape=_unpool_infer, diff_inputs=["X"])
def _unpool(ctx, ins, attrs):
    """Max-unpooling: scatter X into a zero output at the positions recorded
    by max_pool2d_with_index's Mask (reference: math/unpooling.h
    Unpool2dMaxFunctor — indices are flat within the output H*W)."""
    x = data(ins["X"][0])  # [N, C, H, W]
    indices = data(ins["Indices"][0]).astype(jnp.int32)
    k = [int(v) for v in attrs.get("ksize", [1, 1])]
    s = [int(v) for v in attrs.get("strides", [1, 1])]
    p = [int(v) for v in attrs.get("paddings", [0, 0])]
    N, C, H, W = x.shape
    OH = (H - 1) * s[0] - 2 * p[0] + k[0]
    OW = (W - 1) * s[1] - 2 * p[1] + k[1]

    xf = x.reshape(N, C, H * W)
    inf = indices.reshape(N, C, H * W)
    out = jnp.zeros((N, C, OH * OW), dtype=x.dtype)
    n_ix = jnp.arange(N)[:, None, None]
    c_ix = jnp.arange(C)[None, :, None]
    out = out.at[n_ix, c_ix, inf].set(xf)
    return {"Out": [out.reshape(N, C, OH, OW)]}


def _spp_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    ph = op.attr("pyramid_height", 1)
    total = sum(4 ** p for p in range(ph))
    set_output(block, op, "Out", [x.shape[0], x.shape[1] * total], x.dtype)


@register_op("spp", infer_shape=_spp_infer, diff_inputs=["X"])
def _spp(ctx, ins, attrs):
    """Spatial pyramid pooling (reference: operators/spp_op.h): level p pools
    to a 2^p x 2^p grid with kernel=ceil(in/bins), stride=kernel,
    pad=(kernel*bins-in+1)/2, then flattens and concatenates all levels."""
    x = data(ins["X"][0])
    ph = int(attrs.get("pyramid_height", 1))
    ptype = attrs.get("pooling_type", "max")
    N, C, H, W = x.shape
    outs = []
    for pl in range(ph):
        bins = 2 ** pl
        kh = int(np.ceil(H / bins))
        kw = int(np.ceil(W / bins))
        pad_h = (kh * bins - H + 1) // 2
        pad_w = (kw * bins - W + 1) // 2
        lvl = _pool(
            x, [kh, kw], [kh, kw], [pad_h, pad_w], ptype,
            exclusive=False, ceil_mode=False, spatial=2,
        )
        outs.append(lvl.reshape(N, C * bins * bins))
    return {"Out": [jnp.concatenate(outs, axis=1)]}


def _conv3d_transpose_infer(op, block):
    x = in_desc(op, block, "Input")
    f = in_desc(op, block, "Filter")
    if x is None or f is None:
        return
    strides = op.attr("strides", [1, 1, 1])
    paddings = op.attr("paddings", [0, 0, 0])
    dilations = op.attr("dilations", [1, 1, 1])
    groups = op.attr("groups", 1) or 1
    n = x.shape[0]
    oc_per_g = f.shape[1]

    def out_dim(size, k, pad, stride, dil):
        if size < 0:
            return -1
        return (size - 1) * stride - 2 * pad + dil * (k - 1) + 1

    dims = [
        out_dim(x.shape[i + 2], f.shape[i + 2], paddings[i], strides[i],
                dilations[i])
        for i in range(3)
    ]
    set_output(block, op, "Output", [n, oc_per_g * groups] + dims, x.dtype)


@register_op("conv3d_transpose", infer_shape=_conv3d_transpose_infer,
             diff_inputs=["Input", "Filter"])
def _conv3d_transpose(ctx, ins, attrs):
    """3-D transposed conv (reference: operators/conv_transpose_op.cc:358
    Conv3DTransposeOpMaker).  Filter layout [in_c, out_c/g, kd, kh, kw]."""
    x = data(ins["Input"][0])
    f = data(ins["Filter"][0])
    out = _conv_transpose_lower(
        x, f,
        [int(s) for s in attrs.get("strides", [1, 1, 1])],
        [int(p) for p in attrs.get("paddings", [0, 0, 0])],
        [int(d) for d in attrs.get("dilations", [1, 1, 1])],
        attrs.get("groups", 1) or 1, 3,
    )
    return {"Output": [out]}


@register_op("depthwise_conv2d_transpose",
             infer_shape=_conv2d_transpose_infer,
             diff_inputs=["Input", "Filter"])
def _depthwise_conv2d_transpose(ctx, ins, attrs):
    """Depthwise transposed conv (reference: conv_transpose_op.cc registers
    it as conv2d_transpose with groups == channels)."""
    return _conv2d_transpose(ctx, ins, attrs)


def _conv2d_fusion_infer(op, block):
    _conv2d_infer(op, block)


@register_op("conv2d_fusion", infer_shape=_conv2d_fusion_infer,
             diff_inputs=["Input", "Filter", "Bias", "ResidualData"])
def _conv2d_fusion(ctx, ins, attrs):
    """y = act(conv(x) + residual + bias) in one op (reference:
    operators/conv_fusion_op.cc — a cuDNN fused-conv binding; on TPU the
    same composition is what XLA fuses anyway, the op just keeps program
    parity with the reference's fuse passes)."""
    if attrs.get("split_channels"):
        raise NotImplementedError(
            "conv2d_fusion split_channels (multi-output split) is not "
            "lowered; emit a separate split op")
    out = data(_conv2d_lower(ctx, ins, attrs)["Output"][0])
    if ins.get("ResidualData") and ins["ResidualData"][0] is not None:
        out, r = amp.match_kept(out, data(ins["ResidualData"][0]))
        out = out + r
    if ins.get("Bias") and ins["Bias"][0] is not None:
        out, b = amp.match_kept(out, data(ins["Bias"][0]).reshape(1, -1, 1, 1))
        out = out + b
    act = attrs.get("activation", "relu") or "identity"
    acts = {
        "identity": lambda x: x,
        "relu": jax.nn.relu,
        "relu6": lambda x: jnp.clip(x, 0.0, 6.0),
        "relux": lambda x: jnp.clip(x, 0.0, attrs.get("alpha", 6.0)),
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
    }
    if act not in acts:
        raise NotImplementedError(f"conv2d_fusion activation '{act}'")
    return {"Output": [acts[act](out)]}
