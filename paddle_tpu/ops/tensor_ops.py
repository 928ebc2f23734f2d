"""Tensor creation / manipulation ops.

Reference kernels: paddle/fluid/operators/{reshape,concat,split,gather,...}_op.*
plus fill/random initializer ops.  Random ops draw from the compiler-threaded
PRNG stream (LoweringContext.rng) instead of the reference's stateful
curand/std::mt19937 seeds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.lod import LoDValue
from ..core.proto import DataType, dtype_to_runtime
from ..core.registry import register_op
from ..core.selected_rows import SelectedRowsValue
from .common import (data, in_desc, lengths, lod_padded_axis, same_shape,
                     set_output, wrap_lod)


# -- fills -------------------------------------------------------------------
def _fill_constant_infer(op, block):
    set_output(
        block, op, "Out", list(op.attr("shape", [1])),
        DataType(op.attr("dtype", int(DataType.FP32))),
    )


@register_op("fill_constant", infer_shape=_fill_constant_infer, no_grad=True)
def _fill_constant(ctx, ins, attrs):
    dtype = dtype_to_runtime(DataType(attrs.get("dtype", int(DataType.FP32))))
    shape = [int(d) for d in attrs.get("shape", [1])]
    return {"Out": [jnp.full(shape, attrs.get("value", 0.0), dtype=dtype)]}


def _fill_like_infer(op, block):
    x = in_desc(op, block, "X") or in_desc(op, block, "Input")
    if x is None:
        return
    set_output(block, op, "Out", x.shape, x.dtype)


@register_op("fill_zeros_like", infer_shape=_fill_like_infer, no_grad=True)
def _fill_zeros_like(ctx, ins, attrs):
    x = ins["X"][0]
    return {"Out": [wrap_lod(x, jnp.zeros_like(data(x)))]}


def _fill_bsl_infer(op, block):
    x = in_desc(op, block, "Input")
    shape = list(op.attr("shape", [1]))
    if x is not None:
        in_idx = op.attr("input_dim_idx", 0)
        out_idx = op.attr("output_dim_idx", 0)
        if in_idx < len(x.shape):
            shape[out_idx] = x.shape[in_idx]
    set_output(block, op, "Out", shape, DataType(op.attr("dtype", int(DataType.FP32))))


@register_op("fill_constant_batch_size_like", infer_shape=_fill_bsl_infer, no_grad=True)
def _fill_constant_batch_size_like(ctx, ins, attrs):
    """Fill with the batch dim copied from a runtime input
    (reference: operators/fill_constant_batch_size_like_op.cc)."""
    x = data(ins["Input"][0])
    shape = [int(d) for d in attrs.get("shape", [1])]
    shape[attrs.get("output_dim_idx", 0)] = x.shape[attrs.get("input_dim_idx", 0)]
    dtype = dtype_to_runtime(DataType(attrs.get("dtype", int(DataType.FP32))))
    return {"Out": [jnp.full(shape, attrs.get("value", 0.0), dtype=dtype)]}


@register_op("assign", infer_shape=_fill_like_infer)
def _assign(ctx, ins, attrs):
    x = ins["X"][0]
    return {"Out": [x]}


@register_op("detach", infer_shape=_fill_like_infer, no_grad=True)
def _detach(ctx, ins, attrs):
    """X as a constant of the gradient (jax.lax.stop_gradient).  A
    variable's `stop_gradient` mark stops append_backward's ops; this stops
    the gradient where a whole block is differentiated at once, inside a
    layers.Recurrence body."""
    return {"Out": [jax.lax.stop_gradient(data(ins["X"][0]))]}


def _assign_value_infer(op, block):
    set_output(
        block, op, "Out", list(op.attr("shape", [1])),
        DataType(op.attr("dtype", int(DataType.FP32))),
    )


@register_op("assign_value", infer_shape=_assign_value_infer, no_grad=True)
def _assign_value(ctx, ins, attrs):
    dtype = DataType(attrs.get("dtype", int(DataType.FP32)))
    vals = (
        attrs.get("fp32_values")
        or attrs.get("int32_values")
        or attrs.get("values")
        or []
    )
    arr = jnp.asarray(np.asarray(vals, dtype=dtype_to_runtime(dtype)).reshape(attrs["shape"]))
    return {"Out": [arr]}


# -- random ------------------------------------------------------------------
def _random_infer(op, block):
    set_output(
        block, op, "Out", list(op.attr("shape", [1])),
        DataType(op.attr("dtype", int(DataType.FP32))),
    )


@register_op("uniform_random", infer_shape=_random_infer, no_grad=True, random=True)
def _uniform_random(ctx, ins, attrs):
    dtype = dtype_to_runtime(DataType(attrs.get("dtype", int(DataType.FP32))))
    shape = [int(d) for d in attrs["shape"]]
    out = jax.random.uniform(
        ctx.rng(), shape, dtype=dtype,
        minval=attrs.get("min", -1.0), maxval=attrs.get("max", 1.0),
    )
    return {"Out": [out]}


@register_op("uniform_random_batch_size_like", infer_shape=_fill_bsl_infer, no_grad=True, random=True)
def _uniform_random_bsl(ctx, ins, attrs):
    x = data(ins["Input"][0])
    shape = [int(d) for d in attrs["shape"]]
    shape[attrs.get("output_dim_idx", 0)] = x.shape[attrs.get("input_dim_idx", 0)]
    dtype = dtype_to_runtime(DataType(attrs.get("dtype", int(DataType.FP32))))
    out = jax.random.uniform(
        ctx.rng(), shape, dtype=dtype,
        minval=attrs.get("min", -1.0), maxval=attrs.get("max", 1.0),
    )
    return {"Out": [out]}


@register_op("gaussian_random", infer_shape=_random_infer, no_grad=True, random=True)
def _gaussian_random(ctx, ins, attrs):
    dtype = dtype_to_runtime(DataType(attrs.get("dtype", int(DataType.FP32))))
    shape = [int(d) for d in attrs["shape"]]
    out = attrs.get("mean", 0.0) + attrs.get("std", 1.0) * jax.random.normal(
        ctx.rng(), shape, dtype=dtype
    )
    return {"Out": [out]}


@register_op("truncated_gaussian_random", infer_shape=_random_infer, no_grad=True, random=True)
def _truncated_gaussian_random(ctx, ins, attrs):
    dtype = dtype_to_runtime(DataType(attrs.get("dtype", int(DataType.FP32))))
    shape = [int(d) for d in attrs["shape"]]
    out = attrs.get("mean", 0.0) + attrs.get("std", 1.0) * jax.random.truncated_normal(
        ctx.rng(), -2.0, 2.0, shape, dtype=dtype
    )
    return {"Out": [out]}


@register_op("sampling_id", infer_shape=lambda op, block: set_output(block, op, "Out", [in_desc(op, block, "X").shape[0]], DataType.INT64), no_grad=True, random=True)
def _sampling_id(ctx, ins, attrs):
    x = data(ins["X"][0])
    return {"Out": [jax.random.categorical(ctx.rng(), jnp.log(x + 1e-20), axis=-1)]}


# -- shape manipulation ------------------------------------------------------
def _resolve_reshape(in_shape, target):
    """Fluid reshape semantics: 0 copies the input dim, one -1 infers."""
    out = []
    for i, d in enumerate(target):
        if d == 0:
            out.append(in_shape[i])
        else:
            out.append(int(d))
    if -1 in out:
        known = 1
        for d in out:
            if d != -1:
                known *= d
        total = 1
        for d in in_shape:
            total *= d
        out[out.index(-1)] = total // known
    return out


def _reshape_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    target = list(op.attr("shape", []))
    shape = list(x.shape)
    if all(d >= 0 for d in shape):
        shape = _resolve_reshape(shape, target)
    else:
        shape = [shape[i] if d == 0 else d for i, d in enumerate(target)]
    # row-preserving feature reshapes keep the sequence view (the only
    # LoD case the lowering supports)
    lod = x.lod_level if (target and target[0] in (-1, 0)) else 0
    set_output(block, op, "Out", shape, x.dtype, lod_level=lod)
    if op.output("XShape"):
        set_output(block, op, "XShape", [0] + list(x.shape), x.dtype)


def _reshape_lower(ctx, ins, attrs):
    xv = ins["X"][0]
    x = data(xv)
    target = list(attrs["shape"])
    if isinstance(xv, LoDValue):
        # the desc-level target addresses the unpadded [sum(T), F...]
        # layout; a padded flat reshape would interleave pad slots into
        # the output.  Row-preserving feature reshapes ([-1/0, F'...])
        # keep the sequence view; anything that re-chunks rows has no
        # padded equivalent.
        if xv.sub_lengths:
            raise NotImplementedError(
                "reshape on multi-level LoD inputs is not supported")
        feat = x.shape[2:]
        feat_total = int(np.prod(feat)) if feat else 1
        if target and target[0] in (-1, 0):
            new_feat = []
            for i, d in enumerate(target[1:], start=1):
                # 0 copies the input dim at the same desc position
                # (unpadded dim i = padded dim i + 1)
                new_feat.append(int(x.shape[i + 1]) if d == 0 else int(d))
            if -1 in new_feat:
                known = 1
                for d in new_feat:
                    if d != -1:
                        known *= d
                new_feat[new_feat.index(-1)] = feat_total // max(known, 1)
            if int(np.prod(new_feat or [1])) == feat_total:
                out = jnp.reshape(x, x.shape[:2] + tuple(new_feat))
                return {"Out": [wrap_lod(xv, out)]}
        raise NotImplementedError(
            f"reshape of a sequence to {target} re-chunks its rows; use "
            "sequence_reshape for row re-chunking or sequence_unpad first")
    shape = _resolve_reshape(x.shape, target)
    return {"Out": [jnp.reshape(x, shape)]}


register_op("reshape", infer_shape=_reshape_infer, diff_inputs=["X"])(_reshape_lower)
register_op("reshape2", infer_shape=_reshape_infer, diff_inputs=["X"])(_reshape_lower)


def _squeeze_axes(shape, axes):
    if axes:
        axes = [a + len(shape) if a < 0 else a for a in axes]
        return [d for i, d in enumerate(shape) if not (i in axes and d == 1)]
    return [d for d in shape if d != 1]


def _squeeze_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    set_output(block, op, "Out", _squeeze_axes(list(x.shape), op.attr("axes", [])), x.dtype)
    if op.output("XShape"):
        set_output(block, op, "XShape", [0] + list(x.shape), x.dtype)


def _squeeze_lower(ctx, ins, attrs):
    x = data(ins["X"][0])
    return {"Out": [jnp.reshape(x, _squeeze_axes(x.shape, attrs.get("axes", [])))]}


register_op("squeeze", infer_shape=_squeeze_infer, diff_inputs=["X"])(_squeeze_lower)
register_op("squeeze2", infer_shape=_squeeze_infer, diff_inputs=["X"])(_squeeze_lower)


def _unsqueeze_shape(shape, axes):
    out = list(shape)
    for a in sorted(axes):
        a = a + len(out) + 1 if a < 0 else a
        out.insert(a, 1)
    return out


def _unsqueeze_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    set_output(block, op, "Out", _unsqueeze_shape(x.shape, op.attr("axes", [])), x.dtype)
    if op.output("XShape"):
        set_output(block, op, "XShape", [0] + list(x.shape), x.dtype)


def _unsqueeze_lower(ctx, ins, attrs):
    x = data(ins["X"][0])
    return {"Out": [jnp.reshape(x, _unsqueeze_shape(x.shape, attrs.get("axes", [])))]}


register_op("unsqueeze", infer_shape=_unsqueeze_infer, diff_inputs=["X"])(_unsqueeze_lower)
register_op("unsqueeze2", infer_shape=_unsqueeze_infer, diff_inputs=["X"])(_unsqueeze_lower)


def _flatten_shape(shape, axis):
    lead = 1
    for d in shape[:axis]:
        lead *= d
    tail = 1
    for d in shape[axis:]:
        tail *= d
    return [lead, tail]


def _flatten_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    shape = list(x.shape)
    axis = op.attr("axis", 1)
    if all(d >= 0 for d in shape):
        out = _flatten_shape(shape, axis)
    else:
        out = [-1, -1]
        if axis == 1 and len(shape) >= 1 and shape[0] < 0:
            tail = 1
            ok = all(d >= 0 for d in shape[1:])
            for d in shape[1:]:
                tail *= d
            out = [-1, tail if ok else -1]
    set_output(block, op, "Out", out, x.dtype)
    if op.output("XShape"):
        set_output(block, op, "XShape", [0] + list(x.shape), x.dtype)


def _flatten_lower(ctx, ins, attrs):
    x = data(ins["X"][0])
    return {"Out": [jnp.reshape(x, _flatten_shape(x.shape, attrs.get("axis", 1)))]}


register_op("flatten", infer_shape=_flatten_infer, diff_inputs=["X"])(_flatten_lower)
register_op("flatten2", infer_shape=_flatten_infer, diff_inputs=["X"])(_flatten_lower)


def _transpose_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    axis = op.attr("axis", [])
    set_output(block, op, "Out", [x.shape[a] for a in axis], x.dtype)
    if op.output("XShape"):
        set_output(block, op, "XShape", [0] + list(x.shape), x.dtype)


def _transpose_lower(ctx, ins, attrs):
    x = data(ins["X"][0])
    return {"Out": [jnp.transpose(x, attrs["axis"])]}


register_op("transpose", infer_shape=_transpose_infer, diff_inputs=["X"])(_transpose_lower)
register_op("transpose2", infer_shape=_transpose_infer, diff_inputs=["X"])(_transpose_lower)


def _concat_infer(op, block):
    xs = [in_desc(op, block, "X", i) for i in range(len(op.input("X")))]
    xs = [x for x in xs if x is not None]
    if not xs:
        return
    axis = op.attr("axis", 0)
    rank = len(xs[0].shape)
    axis = axis + rank if axis < 0 else axis
    shape = list(xs[0].shape)
    tot = 0
    for x in xs:
        d = x.shape[axis]
        if d < 0:
            tot = -1
            break
        tot += d
    shape[axis] = tot
    # sequences stay sequences: feature-axis concat keeps the lod view,
    # and axis-0 row concat merges batches of sequences
    set_output(block, op, "Out", shape, xs[0].dtype,
               lod_level=xs[0].lod_level)


@register_op("concat", infer_shape=_concat_infer)
def _concat(ctx, ins, attrs):
    vals = [v for v in ins["X"] if v is not None]
    xs = [data(v) for v in vals]
    axis = attrs.get("axis", 0)
    lod_in = next((v for v in vals if isinstance(v, LoDValue)), None)
    if lod_in is not None:
        # the desc-level axis addresses the reference's unpadded
        # [sum(T), F...] layout; feature axes shift right past the time
        # dims on padded data (lod_padded_axis handles N-level nesting)
        level = 1 + len(lod_in.sub_lengths)
        p_axis = lod_padded_axis(axis, level, xs[0].ndim)
        if p_axis == 0:
            # row concat: the reference appends the sequences of every
            # input into one batch (concatenated lod).  Pad to a common
            # time extent, stack along N, merge the lengths.
            if level != 1 or not all(
                isinstance(v, LoDValue) for v in vals
            ):
                raise NotImplementedError(
                    "concat(axis=0) on LoD inputs supports 1-level "
                    "sequences only")
            tmax = max(d.shape[1] for d in xs)
            padded = [
                jnp.pad(d, [(0, 0), (0, tmax - d.shape[1])]
                        + [(0, 0)] * (d.ndim - 2))
                for d in xs
            ]
            out = jnp.concatenate(padded, axis=0)
            lens = jnp.concatenate(
                [jnp.asarray(v.lengths).reshape(-1) for v in vals])
            return {"Out": [LoDValue(out, lens)]}
        out = jnp.concatenate(xs, axis=p_axis)
        return {"Out": [wrap_lod(lod_in, out)]}
    out = jnp.concatenate(xs, axis=axis)
    return {"Out": [out]}


def _split_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    axis = op.attr("axis", 0)
    rank = len(x.shape)
    axis = axis + rank if axis < 0 else axis
    num = op.attr("num", 0)
    sections = op.attr("sections", [])
    outs = op.output("Out")
    # feature-axis splits of a sequence stay sequences (see _concat_infer)
    lod = x.lod_level if axis >= 1 else 0
    for i in range(len(outs)):
        shape = list(x.shape)
        if sections:
            shape[axis] = sections[i]
        elif num:
            shape[axis] = x.shape[axis] // num if x.shape[axis] >= 0 else -1
        set_output(block, op, "Out", shape, x.dtype, idx=i, lod_level=lod)


@register_op("split", infer_shape=_split_infer)
def _split(ctx, ins, attrs):
    xv = ins["X"][0]
    x = data(xv)
    axis = attrs.get("axis", 0)
    lod = isinstance(xv, LoDValue)
    if lod:
        # same desc-axis -> padded-axis remap as _concat
        level = 1 + len(xv.sub_lengths)
        axis = lod_padded_axis(axis, level, x.ndim)
    sections = attrs.get("sections", [])
    if sections:
        idx = np.cumsum(sections)[:-1].tolist()
        outs = jnp.split(x, idx, axis=axis)
    else:
        outs = jnp.split(x, attrs.get("num", 1), axis=axis)
    if lod and axis >= 1:
        outs = [wrap_lod(xv, o) for o in outs]
    return {"Out": list(outs)}


def _stack_infer(op, block):
    xs = [in_desc(op, block, "X", i) for i in range(len(op.input("X")))]
    xs = [x for x in xs if x is not None]
    if not xs:
        return
    axis = op.attr("axis", 0)
    shape = list(xs[0].shape)
    axis = axis + len(shape) + 1 if axis < 0 else axis
    shape.insert(axis, len(xs))
    set_output(block, op, "Y", shape, xs[0].dtype)


@register_op("stack", infer_shape=_stack_infer)
def _stack(ctx, ins, attrs):
    xs = [data(v) for v in ins["X"] if v is not None]
    return {"Y": [jnp.stack(xs, axis=attrs.get("axis", 0))]}


def _unstack_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    axis = op.attr("axis", 0)
    rank = len(x.shape)
    axis = axis + rank if axis < 0 else axis
    shape = [d for i, d in enumerate(x.shape) if i != axis]
    for i in range(len(op.output("Y"))):
        set_output(block, op, "Y", shape, x.dtype, idx=i)


@register_op("unstack", infer_shape=_unstack_infer)
def _unstack(ctx, ins, attrs):
    x = data(ins["X"][0])
    axis = attrs.get("axis", 0)
    num = attrs.get("num", x.shape[axis])
    outs = [jnp.squeeze(s, axis=axis) for s in jnp.split(x, num, axis=axis)]
    return {"Y": outs}


def _slice_infer(op, block):
    x = in_desc(op, block, "Input")
    if x is None:
        return
    shape = list(x.shape)
    axes = op.attr("axes", [])
    starts = op.attr("starts", [])
    ends = op.attr("ends", [])
    for a, s, e in zip(axes, starts, ends):
        d = shape[a]
        if d < 0:
            continue
        s2 = max(0, s + d if s < 0 else s)
        e2 = min(d, e + d if e < 0 else e)
        shape[a] = max(0, e2 - s2)
    set_output(block, op, "Out", shape, x.dtype)


@register_op("slice", infer_shape=_slice_infer, diff_inputs=["Input"])
def _slice(ctx, ins, attrs):
    x = data(ins["Input"][0])
    idx = [slice(None)] * x.ndim
    for a, s, e in zip(attrs["axes"], attrs["starts"], attrs["ends"]):
        idx[a] = slice(s, e)
    return {"Out": [x[tuple(idx)]]}


def _gather_infer(op, block):
    x = in_desc(op, block, "X")
    index = in_desc(op, block, "Index")
    if x is None or index is None:
        return
    set_output(block, op, "Out", [index.shape[0]] + list(x.shape[1:]), x.dtype)


@register_op("gather", infer_shape=_gather_infer, diff_inputs=["X"])
def _gather(ctx, ins, attrs):
    x, idx = data(ins["X"][0]), data(ins["Index"][0])
    return {"Out": [jnp.take(x, idx.reshape(-1), axis=0)]}


@register_op("scatter", infer_shape=same_shape(), diff_inputs=["X", "Updates"])
def _scatter(ctx, ins, attrs):
    """Out = X with rows at Ids replaced (or accumulated) by Updates
    (reference: operators/scatter_op.cc)."""
    x = data(ins["X"][0])
    ids = data(ins["Ids"][0]).reshape(-1)
    upd = data(ins["Updates"][0])
    if attrs.get("overwrite", True):
        out = x.at[ids].set(upd)
    else:
        out = x.at[ids].add(upd)
    return {"Out": [out]}


def _pad_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    paddings = op.attr("paddings", [])
    shape = [
        d if d < 0 else d + paddings[2 * i] + paddings[2 * i + 1]
        for i, d in enumerate(x.shape)
    ]
    set_output(block, op, "Out", shape, x.dtype)


@register_op("pad", infer_shape=_pad_infer)
def _pad(ctx, ins, attrs):
    x = data(ins["X"][0])
    p = attrs["paddings"]
    widths = [(p[2 * i], p[2 * i + 1]) for i in range(x.ndim)]
    return {"Out": [jnp.pad(x, widths, constant_values=attrs.get("pad_value", 0.0))]}


def _pad2d_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    p = op.attr("paddings", [0, 0, 0, 0])
    shape = list(x.shape)
    if op.attr("data_format", "NCHW") == "NCHW":
        h_axis, w_axis = 2, 3
    else:
        h_axis, w_axis = 1, 2
    if shape[h_axis] >= 0:
        shape[h_axis] += p[0] + p[1]
    if shape[w_axis] >= 0:
        shape[w_axis] += p[2] + p[3]
    set_output(block, op, "Out", shape, x.dtype)


@register_op("pad2d", infer_shape=_pad2d_infer)
def _pad2d(ctx, ins, attrs):
    x = data(ins["X"][0])
    p = attrs.get("paddings", [0, 0, 0, 0])
    mode = attrs.get("mode", "constant")
    nchw = attrs.get("data_format", "NCHW") == "NCHW"
    widths = [(0, 0), (0, 0), (p[0], p[1]), (p[2], p[3])] if nchw else [
        (0, 0), (p[0], p[1]), (p[2], p[3]), (0, 0)
    ]
    jmode = {"constant": "constant", "reflect": "reflect", "edge": "edge"}[mode]
    kw = {"constant_values": attrs.get("pad_value", 0.0)} if mode == "constant" else {}
    return {"Out": [jnp.pad(x, widths, mode=jmode, **kw)]}


@register_op("pad_constant_like", infer_shape=same_shape("X", "Out"), diff_inputs=["Y"])
def _pad_constant_like(ctx, ins, attrs):
    x, y = data(ins["X"][0]), data(ins["Y"][0])
    widths = [(0, xd - yd) for xd, yd in zip(x.shape, y.shape)]
    return {"Out": [jnp.pad(y, widths, constant_values=attrs.get("pad_value", 0.0))]}


def _expand_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    times = op.attr("expand_times", [])
    shape = [d if d < 0 else d * t for d, t in zip(x.shape, times)]
    set_output(block, op, "Out", shape, x.dtype)


@register_op("expand", infer_shape=_expand_infer)
def _expand(ctx, ins, attrs):
    x = data(ins["X"][0])
    return {"Out": [jnp.tile(x, attrs["expand_times"])]}


@register_op("reverse", infer_shape=same_shape())
def _reverse(ctx, ins, attrs):
    x = data(ins["X"][0])
    axes = attrs.get("axis", [0])
    if isinstance(axes, int):
        axes = [axes]
    out = x
    for a in axes:
        out = jnp.flip(out, axis=a)
    return {"Out": [out]}


def _one_hot_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    depth = op.attr("depth", 1)
    shape = list(x.shape)
    if shape and shape[-1] == 1:
        shape = shape[:-1]
    set_output(block, op, "Out", shape + [depth], DataType.FP32)


@register_op("one_hot", infer_shape=_one_hot_infer, no_grad=True)
def _one_hot(ctx, ins, attrs):
    x = data(ins["X"][0])
    # squeeze the fluid [N, 1] id column — decided by the DESC rank, not the
    # runtime shape (a [N] input with N == 1 must not collapse to a scalar)
    desc_rank = None
    op = getattr(ctx, "cur_op", None) if ctx is not None else None
    if op is not None:
        names = op.input("X")
        v = ctx.block._find_var_recursive(names[0]) if names else None
        if v is not None and v.desc.shape:
            desc_rank = len(v.desc.shape)
    squeeze = (
        x.ndim == desc_rank if desc_rank is not None else x.ndim > 1
    ) and x.ndim and x.shape[-1] == 1 and (desc_rank or 2) > 1
    if squeeze:
        x = jnp.squeeze(x, axis=-1)
    return {"Out": [jax.nn.one_hot(x, attrs["depth"], dtype=jnp.float32)]}


@register_op("shape", infer_shape=lambda op, block: set_output(block, op, "Out", [len(in_desc(op, block, "Input").shape)], DataType.INT32), no_grad=True)
def _shape(ctx, ins, attrs):
    x = data(ins["Input"][0])
    return {"Out": [jnp.asarray(x.shape, dtype=jnp.int32)]}


def _lookup_infer(op, block):
    w = in_desc(op, block, "W")
    ids = in_desc(op, block, "Ids")
    if w is None or ids is None:
        return
    shape = list(ids.shape)
    if shape and shape[-1] == 1:
        shape = shape[:-1]
    set_output(block, op, "Out", shape + [w.shape[1]], w.dtype, lod_level=ids.lod_level)


@register_op("lookup_table", infer_shape=_lookup_infer, diff_inputs=["W"])
def _lookup_table(ctx, ins, attrs):
    """Embedding lookup (reference: operators/lookup_table_op.cc)."""
    w = data(ins["W"][0])
    ids = data(ins["Ids"][0])
    squeeze_last = ids.ndim >= 1 and ids.shape[-1] == 1
    if squeeze_last:
        ids = jnp.squeeze(ids, axis=-1)
    padding_idx = attrs.get("padding_idx", -1)
    out = jnp.take(w, ids, axis=0)
    if padding_idx is not None and padding_idx >= 0:
        mask = (ids != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    return {"Out": [wrap_lod(ins["Ids"][0], out)]}


@register_op("lookup_table_grad", no_grad=True)
def _lookup_table_grad(ctx, ins, attrs):
    """Custom grad rule for lookup_table (replaces the vjp replay).

    The reference emits SelectedRows sparse grads
    (operators/lookup_table_op.cc:80 + framework/selected_rows.h:32) so a
    [V, D] table gradient is (ids, rows), not a dense table — essential at
    CTR vocab sizes.  With is_sparse=True this returns a SelectedRowsValue
    ([N] ids + [N, D] rows, V absent from every runtime buffer); sparse
    optimizer lowerings (ops/optimizer_ops.py) then update only the touched
    rows.  With is_sparse=False it scatter-adds into a dense table grad,
    identical to the vjp of jnp.take."""
    w_desc = ins["W"][0]
    og = data(ins["Out@GRAD"][0])
    ids = data(ins["Ids"][0])
    if ids.ndim >= 1 and ids.shape[-1] == 1:
        ids = jnp.squeeze(ids, axis=-1)
    height, dim = data(w_desc).shape
    ids_flat = jnp.reshape(ids, (-1,))
    if ids_flat.dtype.itemsize <= 4:
        ids_flat = ids_flat.astype(jnp.int32)
    # 64-bit ids (x64 mode) keep their width: the scatter target height
    # may exceed 2**31 for hashed/CTR id spaces
    rows = jnp.reshape(og, (-1, dim))
    padding_idx = attrs.get("padding_idx", -1)
    if padding_idx is not None and padding_idx >= 0:
        # grads at the padding id are dropped, as in the forward mask;
        # pointing them at the sentinel makes the scatter drop them
        ids_flat = jnp.where(ids_flat == padding_idx, height, ids_flat)
    srv = SelectedRowsValue(ids_flat, rows, height)
    if attrs.get("is_sparse", False):
        return {"W@GRAD": [srv]}
    return {"W@GRAD": [srv.to_dense()]}


@register_op("multiplex", infer_shape=lambda op, block: set_output(block, op, "Out", in_desc(op, block, "X").shape, in_desc(op, block, "X").dtype), diff_inputs=["X"])
def _multiplex(ctx, ins, attrs):
    ids = data(ins["Ids"][0]).reshape(-1)
    xs = jnp.stack([data(v) for v in ins["X"]], axis=0)
    rows = jnp.arange(xs.shape[1])
    return {"Out": [xs[ids[: xs.shape[1]], rows]]}


def _crop_infer(op, block):
    shape = list(op.attr("shape", []))
    x = in_desc(op, block, "X")
    if x is None:
        return
    set_output(block, op, "Out", shape or list(x.shape), x.dtype)


@register_op("crop", infer_shape=_crop_infer, diff_inputs=["X"])
def _crop(ctx, ins, attrs):
    x = data(ins["X"][0])
    offsets = attrs.get("offsets", [0] * x.ndim)
    shape = attrs.get("shape", list(x.shape))
    # -1 keeps the full extent from the offset (desc batch dims are -1)
    idx = tuple(
        slice(o, None) if s < 0 else slice(o, o + s)
        for o, s in zip(offsets, shape)
    )
    return {"Out": [x[idx]]}


def _space_to_depth_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    b = op.attr("blocksize", 1)
    n, c, h, w = x.shape
    if c > 0 and c % (b * b):
        # reference InferShape enforce (space_to_depth_op.cc:41): the
        # reorg kernel scatters with depth-to-space indexing, so input
        # channels must be divisible by blocksize^2 even in the
        # space-to-depth direction
        raise ValueError(
            f"space_to_depth: input channels {c} must be divisible by "
            f"blocksize^2 ({b * b})")
    if (h > 0 and h % b) or (w > 0 and w % b):
        # companion enforces, space_to_depth_op.cc:44-49
        raise ValueError(
            f"space_to_depth: input H/W ({h}x{w}) must be divisible by "
            f"blocksize ({b})")
    set_output(block, op, "Out", [n, c * b * b, h // b if h > 0 else -1, w // b if w > 0 else -1], x.dtype)


@register_op("space_to_depth", infer_shape=_space_to_depth_infer)
def _space_to_depth(ctx, ins, attrs):
    """Darknet-reorg layout compatibility (reference:
    operators/space_to_depth_op.h:40-56): the kernel writes the input
    through DEPTH-TO-SPACE scatter indexing — channel k decomposes as
    (offset, c2) with h2 = j*bs + offset/bs, w2 = i*bs + offset%bs into a
    [N, C/bs^2, H*bs, W*bs] view — and the Out buffer is then READ with
    the declared [N, C*bs^2, H/bs, W/bs] shape.  YOLO-era models were
    trained against exactly this scramble, so it is the contract; a
    textbook block-to-channel space_to_depth does NOT match."""
    x = data(ins["X"][0])
    b = attrs["blocksize"]
    n, c, h, w = x.shape
    out_c = c // (b * b)
    y = jnp.reshape(x, (n, b, b, out_c, h, w))       # k = (oy, ox, c2)
    y = jnp.transpose(y, (0, 3, 4, 1, 5, 2))         # [n, c2, h, oy, w, ox]
    y = jnp.reshape(y, (n, out_c, h * b, w * b))     # depth-to-space image
    return {"Out": [jnp.reshape(y, (n, c * b * b, h // b, w // b))]}


def _range_static_len(op):
    a = op.attrs
    if all(f"const_{k}" in a for k in ("start", "end", "step")):
        import math

        return max(0, math.ceil((a["const_end"] - a["const_start"]) / a["const_step"]))
    return -1


def _range_infer(op, block):
    set_output(
        block, op, "Out", [_range_static_len(op)],
        DataType(op.attr("dtype", int(DataType.FP32))),
    )


@register_op("range", infer_shape=_range_infer, no_grad=True)
def _range(ctx, ins, attrs):
    def bound(slot):
        if f"const_{slot.lower()}" in attrs:
            return attrs[f"const_{slot.lower()}"]
        try:
            return float(np.asarray(data(ins[slot][0])).reshape(()))
        except Exception as e:
            raise NotImplementedError(
                "range requires compile-time-constant Start/End/Step: the "
                "output length sets a static XLA shape, so data-dependent "
                "bounds cannot be lowered"
            ) from e

    start, end, step = bound("Start"), bound("End"), bound("Step")
    dtype = dtype_to_runtime(DataType(attrs.get("dtype", int(DataType.FP32))))
    return {"Out": [jnp.arange(start, end, step, dtype=dtype)]}


@register_op("increment", infer_shape=same_shape())
def _increment(ctx, ins, attrs):
    x = data(ins["X"][0])
    # keep the input dtype: int64 counters must not promote to float
    step = np.asarray(attrs.get("step", 1.0)).astype(jnp.asarray(x).dtype if not hasattr(x, "dtype") else x.dtype)
    return {"Out": [x + step]}


@register_op("label_smooth", infer_shape=same_shape())
def _label_smooth(ctx, ins, attrs):
    x = data(ins["X"][0])
    eps = attrs.get("epsilon", 0.0)
    dist = ins.get("PriorDist", [None])[0]
    if dist is not None:
        out = (1.0 - eps) * x + eps * data(dist)
    else:
        out = (1.0 - eps) * x + eps / x.shape[-1]
    return {"Out": [out]}


@register_op("is_empty", infer_shape=lambda op, block: set_output(block, op, "Out", [1], DataType.BOOL), no_grad=True)
def _is_empty(ctx, ins, attrs):
    x = data(ins["X"][0])
    return {"Out": [jnp.asarray([x.size == 0])]}


@register_op("gaussian_random_batch_size_like", infer_shape=_fill_bsl_infer, no_grad=True, random=True)
def _gaussian_random_bsl(ctx, ins, attrs):
    x = data(ins["Input"][0])
    shape = [int(d) for d in attrs["shape"]]
    shape[attrs.get("output_dim_idx", 0)] = x.shape[attrs.get("input_dim_idx", 0)]
    dtype = dtype_to_runtime(DataType(attrs.get("dtype", int(DataType.FP32))))
    out = attrs.get("mean", 0.0) + attrs.get("std", 1.0) * jax.random.normal(
        ctx.rng(), shape, dtype=dtype
    )
    return {"Out": [out]}


def _bool_scalar_infer(op, block):
    set_output(block, op, "Out", [1], DataType.BOOL)


@register_op("isinf", infer_shape=_bool_scalar_infer, no_grad=True)
def _isinf(ctx, ins, attrs):
    return {"Out": [jnp.reshape(jnp.any(jnp.isinf(data(ins["X"][0]))), (1,))]}


@register_op("isnan", infer_shape=_bool_scalar_infer, no_grad=True)
def _isnan(ctx, ins, attrs):
    return {"Out": [jnp.reshape(jnp.any(jnp.isnan(data(ins["X"][0]))), (1,))]}


@register_op("isfinite", infer_shape=_bool_scalar_infer, no_grad=True)
def _isfinite(ctx, ins, attrs):
    return {"Out": [jnp.reshape(jnp.all(jnp.isfinite(data(ins["X"][0]))), (1,))]}
