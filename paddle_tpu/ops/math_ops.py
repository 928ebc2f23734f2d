"""Dense linear algebra + scalar math ops.

Reference kernels: paddle/fluid/operators/{mul,matmul,scale,sum,cast,...}_op.*
— each a CPU/CUDA kernel pair over cuBLAS/Eigen.  Here each op is one JAX
lowering; matmuls hit the MXU directly and XLA fuses the surrounding
elementwise work.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core import amp
from ..core.lod import LoDValue
from ..core.proto import DataType, convert_dtype, dtype_to_runtime
from ..core.registry import register_op
from .common import data, in_desc, same_shape, set_output, wrap_lod


def _flatten2(x, num_col_dims: int):
    shape = x.shape
    lead = 1
    for d in shape[:num_col_dims]:
        lead *= d
    tail = 1
    for d in shape[num_col_dims:]:
        tail *= d
    return jnp.reshape(x, (lead, tail))


def _mul_infer(op, block):
    x = in_desc(op, block, "X")
    y = in_desc(op, block, "Y")
    if x is None or y is None:
        return
    xn = op.attr("x_num_col_dims", 1)
    yn = op.attr("y_num_col_dims", 1)
    out_shape = list(x.shape[:xn]) + list(y.shape[yn:])
    set_output(block, op, "Out", out_shape, x.dtype)


@register_op("mul", infer_shape=_mul_infer)
def _mul(ctx, ins, attrs):
    """out = flatten2(X) @ flatten2(Y) (reference: operators/mul_op.cc).

    A LoD input's padded runtime value carries one extra leading time dim vs
    its token-major desc ([-1, F] desc vs [N, T, F] value), so num_col_dims
    shifts by one and the output keeps the sequence lengths."""
    xv = ins["X"][0]
    x, y = data(xv), data(ins["Y"][0])
    xn = attrs.get("x_num_col_dims", 1)
    yn = attrs.get("y_num_col_dims", 1)
    if isinstance(xv, LoDValue):
        xn += 1
    x2 = _flatten2(x, xn)
    y2 = _flatten2(y, yn)
    x2c, y2c = amp.mxu_operands(x2, y2)
    out = amp.mxu_output(jnp.matmul(x2c, y2c), x2, y2)
    out_shape = x.shape[:xn] + y.shape[yn:]
    return {"Out": [wrap_lod(xv, jnp.reshape(out, out_shape))]}


def _matmul_infer(op, block):
    x = in_desc(op, block, "X")
    y = in_desc(op, block, "Y")
    if x is None or y is None:
        return
    tx, ty = op.attr("transpose_X", False), op.attr("transpose_Y", False)
    xs, ys = list(x.shape), list(y.shape)
    if len(xs) >= 2 and tx:
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if len(ys) >= 2 and ty:
        ys[-1], ys[-2] = ys[-2], ys[-1]
    if len(xs) == 1 and len(ys) == 1:
        out = [1]
    elif len(xs) == 1:
        out = ys[:-2] + ys[-1:]
    elif len(ys) == 1:
        out = xs[:-1]
    else:
        batch = xs[:-2] if len(xs) >= len(ys) else ys[:-2]
        out = batch + [xs[-2], ys[-1]]
    wide = op.attr("out_dtype", None)
    set_output(block, op, "Out", out, convert_dtype(wide) if wide else x.dtype)


@register_op("matmul", infer_shape=_matmul_infer)
def _matmul(ctx, ins, attrs):
    """Batched matmul with optional transposes and scale
    (reference: operators/matmul_op.cc).  Under `out_dtype` (TPU-native
    addition) the product's accumulator is handed out as that dtype
    whatever the AMP tier makes of the operands: fp32 logits of bf16
    operands, not rounded on the way."""
    x, y = data(ins["X"][0]), data(ins["Y"][0])
    if attrs.get("transpose_X", False) and x.ndim >= 2:
        x = jnp.swapaxes(x, -1, -2)
    if attrs.get("transpose_Y", False) and y.ndim >= 2:
        y = jnp.swapaxes(y, -1, -2)
    xc, yc = amp.mxu_operands(x, y)
    if attrs.get("out_dtype"):
        out = jnp.matmul(xc, yc, preferred_element_type=attrs["out_dtype"])
    else:
        out = amp.mxu_output(jnp.matmul(xc, yc), x, y)
    alpha = attrs.get("alpha", 1.0)
    if alpha != 1.0:
        out = out * alpha
    return {"Out": [out]}


@register_op("scale", infer_shape=same_shape())
def _scale(ctx, ins, attrs):
    x = ins["X"][0]
    scale = attrs.get("scale", 1.0)
    bias = attrs.get("bias", 0.0)
    if attrs.get("bias_after_scale", True):
        out = data(x) * scale + bias
    else:
        out = (data(x) + bias) * scale
    return {"Out": [wrap_lod(x, out)]}


def _sum_infer(op, block):
    x = in_desc(op, block, "X")
    if x is not None:
        set_output(block, op, "Out", x.shape, x.dtype, lod_level=x.lod_level)


@register_op("sum", infer_shape=_sum_infer)
def _sum(ctx, ins, attrs):
    """Add N tensors (reference: operators/sum_op.cc; also the grad
    accumulator inserted by append_backward).  All-SelectedRows inputs stay
    sparse (row concat, the reference sum_op SelectedRows branch); a mix of
    sparse and dense densifies."""
    from ..core.selected_rows import SelectedRowsValue

    vals = [v for v in ins["X"] if v is not None]
    if vals and all(isinstance(v, SelectedRowsValue) for v in vals):
        out = vals[0]
        for v in vals[1:]:
            out = out.concat(v)
        return {"Out": [out]}
    xs = [data(v) for v in vals]
    out = xs[0]
    for v in xs[1:]:
        out = out + v
    return {"Out": [wrap_lod(ins["X"][0], out)]}


def _cast_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    set_output(block, op, "Out", x.shape, DataType(op.attr("out_dtype", int(DataType.FP32))), lod_level=x.lod_level)


@register_op("cast", infer_shape=_cast_infer)
def _cast(ctx, ins, attrs):
    x = ins["X"][0]
    np_dtype = dtype_to_runtime(DataType(attrs["out_dtype"]))
    return {"Out": [wrap_lod(x, data(x).astype(np_dtype))]}


@register_op("clip", infer_shape=same_shape())
def _clip(ctx, ins, attrs):
    x = ins["X"][0]
    return {"Out": [wrap_lod(x, jnp.clip(data(x), attrs["min"], attrs["max"]))]}


@register_op("clip_by_norm", infer_shape=same_shape())
def _clip_by_norm(ctx, ins, attrs):
    x = data(ins["X"][0])
    max_norm = attrs["max_norm"]
    norm = jnp.sqrt(jnp.sum(x * x))
    scale = jnp.where(norm > max_norm, max_norm / jnp.maximum(norm, 1e-12), 1.0)
    return {"Out": [x * scale]}


@register_op("squared_l2_norm", infer_shape=lambda op, block: set_output(block, op, "Out", [1], in_desc(op, block, "X").dtype))
def _squared_l2_norm(ctx, ins, attrs):
    x = data(ins["X"][0])
    return {"Out": [jnp.reshape(jnp.sum(x * x), (1,))]}


@register_op("l1_norm", infer_shape=lambda op, block: set_output(block, op, "Out", [1], in_desc(op, block, "X").dtype))
def _l1_norm(ctx, ins, attrs):
    x = data(ins["X"][0])
    return {"Out": [jnp.reshape(jnp.sum(jnp.abs(x)), (1,))]}


def _mean_infer(op, block):
    x = in_desc(op, block, "X")
    if x is not None:
        set_output(block, op, "Out", [1], x.dtype)


@register_op("mean", infer_shape=_mean_infer)
def _mean(ctx, ins, attrs):
    # half-width inputs (amp keep_output) accumulate in fp32; the output
    # rounds back to the input dtype
    d = data(ins["X"][0])
    out = jnp.mean(d.astype(amp.stats_dtype(d))).astype(d.dtype)
    return {"Out": [jnp.reshape(out, (1,))]}


@register_op("cumsum", infer_shape=same_shape())
def _cumsum(ctx, ins, attrs):
    x = data(ins["X"][0])
    axis = attrs.get("axis", -1)
    out = jnp.cumsum(x, axis=axis)
    if attrs.get("reverse", False):
        out = jnp.flip(jnp.cumsum(jnp.flip(x, axis), axis=axis), axis)
    if attrs.get("exclusive", False):
        out = out - x
    return {"Out": [out]}


def _bilinear_infer(op, block):
    x = in_desc(op, block, "X")
    w = in_desc(op, block, "Weight")
    if x is None or w is None:
        return
    set_output(block, op, "Out", [x.shape[0], w.shape[0]], x.dtype)


@register_op("bilinear_tensor_product", infer_shape=_bilinear_infer)
def _bilinear_tensor_product(ctx, ins, attrs):
    """out[b,k] = x[b,:] @ W[k] @ y[b,:] + bias
    (reference: operators/bilinear_tensor_product_op.cc)."""
    x, y, w = data(ins["X"][0]), data(ins["Y"][0]), data(ins["Weight"][0])
    out = jnp.einsum("bi,kij,bj->bk", x, w, y)
    bias = ins.get("Bias", [None])[0]
    if bias is not None:
        out = out + data(bias)
    return {"Out": [out]}
