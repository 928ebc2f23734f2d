"""Tensor creation/manipulation layer fns
(reference: python/paddle/fluid/layers/tensor.py — 22 defs)."""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from ..core.framework import Variable
from ..core.proto import DataType, convert_dtype
from ..layer_helper import LayerHelper

__all__ = [
    "create_tensor",
    "create_parameter",
    "create_global_var",
    "cast",
    "concat",
    "sums",
    "assign",
    "detach",
    "fill_constant",
    "fill_constant_batch_size_like",
    "ones",
    "zeros",
    "reverse",
    "argmax",
    "argmin",
    "argsort",
    "reshape",
    "squeeze",
    "unsqueeze",
    "flatten",
    "transpose",
    "split",
    "stack",
    "unstack",
    "expand",
    "slice",
    "shape",
    "gather",
    "scatter",
    "one_hot_v2",
    "has_inf",
    "has_nan",
    "isfinite",
    "range",
    "increment",
    "cumsum",
    "scale",
    "reduce_sum",
    "reduce_mean",
    "reduce_max",
    "reduce_min",
    "reduce_prod",
    "tensor_array_to_tensor",
    "sum",
    "merge_selected_rows",
    "get_tensor_from_selected_rows",
    "load",
]


def create_tensor(dtype, name=None, persistable=False):
    helper = LayerHelper("create_tensor", name=name)
    return helper.block.create_var(
        name=name or helper.name, dtype=dtype, persistable=persistable, shape=[]
    )


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    from ..param_attr import ParamAttr

    helper = LayerHelper("create_parameter", name=name)
    attr = ParamAttr._to_attr(attr)
    if name is not None and attr.name is None:
        attr.name = name
    return helper.create_parameter(attr, shape, dtype, is_bias, default_initializer)


def create_global_var(shape, value, dtype, persistable=False, force_cpu=False, name=None):
    from ..initializer import ConstantInitializer

    helper = LayerHelper("global_var", name=name)
    var = helper.create_global_variable(
        persistable=persistable, dtype=dtype, shape=list(shape)
    )
    helper.set_variable_initializer(var, ConstantInitializer(value))
    return var


def cast(x, dtype):
    helper = LayerHelper("cast", input=x)
    dtype = convert_dtype(dtype)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="cast", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"in_dtype": int(x.dtype), "out_dtype": int(dtype)},
    )
    return out


def concat(input, axis=0, name=None):
    helper = LayerHelper("concat", name=name)
    out = helper.create_variable_for_type_inference(input[0].dtype)
    helper.append_op(
        type="concat", inputs={"X": list(input)}, outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return out


def sums(input, out=None):
    helper = LayerHelper("sum")
    if out is None:
        out = helper.create_variable_for_type_inference(input[0].dtype)
    helper.append_op(type="sum", inputs={"X": list(input)}, outputs={"Out": [out]})
    return out


def assign(input, output=None):
    helper = LayerHelper("assign")
    if isinstance(input, Variable):
        if output is None:
            output = helper.create_variable_for_type_inference(input.dtype)
        helper.append_op(type="assign", inputs={"X": [input]}, outputs={"Out": [output]})
    else:
        arr = np.asarray(input)
        if output is None:
            output = helper.create_variable_for_type_inference(convert_dtype(arr.dtype))
        attrs = {"shape": list(arr.shape), "dtype": int(convert_dtype(arr.dtype))}
        if arr.dtype in (np.int32, np.int64):
            attrs["int32_values"] = arr.astype(np.int64).reshape(-1).tolist()
        else:
            attrs["fp32_values"] = arr.astype(np.float64).reshape(-1).tolist()
        helper.append_op(type="assign_value", outputs={"Out": [output]}, attrs=attrs)
    return output


def detach(x):
    """x with no gradient through it: a copy that later operations
    differentiate as a constant, also inside a Recurrence body, which is
    differentiated as a whole (TPU-native; ops/tensor_ops.py detach)."""
    helper = LayerHelper("detach")
    out = helper.create_variable_for_type_inference(x.dtype,
                                                    stop_gradient=True)
    helper.append_op(type="detach", inputs={"X": [x]},
                     outputs={"Out": [out]})
    return out


def fill_constant(shape, dtype, value, force_cpu=False, out=None):
    helper = LayerHelper("fill_constant")
    dtype = convert_dtype(dtype)
    if out is None:
        out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="fill_constant",
        outputs={"Out": [out]},
        attrs={"shape": list(shape), "dtype": int(dtype), "value": float(value),
               "force_cpu": force_cpu},
    )
    out.stop_gradient = True
    return out


def fill_constant_batch_size_like(input, shape, dtype, value,
                                  input_dim_idx=0, output_dim_idx=0):
    helper = LayerHelper("fill_constant_batch_size_like")
    dtype = convert_dtype(dtype)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="fill_constant_batch_size_like",
        inputs={"Input": [input]},
        outputs={"Out": [out]},
        attrs={"shape": list(shape), "dtype": int(dtype), "value": float(value),
               "input_dim_idx": input_dim_idx, "output_dim_idx": output_dim_idx},
    )
    out.stop_gradient = True
    return out


def ones(shape, dtype, force_cpu=False):
    return fill_constant(shape, dtype, 1.0, force_cpu)


def zeros(shape, dtype, force_cpu=False):
    return fill_constant(shape, dtype, 0.0, force_cpu)


def reverse(x, axis):
    helper = LayerHelper("reverse", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="reverse", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"axis": axis if isinstance(axis, (list, tuple)) else [axis]},
    )
    return out


def argmax(x, axis=0):
    helper = LayerHelper("arg_max", input=x)
    out = helper.create_variable_for_type_inference(DataType.INT64, stop_gradient=True)
    helper.append_op(type="arg_max", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"axis": axis})
    return out


def argmin(x, axis=0):
    helper = LayerHelper("arg_min", input=x)
    out = helper.create_variable_for_type_inference(DataType.INT64, stop_gradient=True)
    helper.append_op(type="arg_min", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"axis": axis})
    return out


def argsort(x, axis=-1, name=None):
    helper = LayerHelper("argsort", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    idx = helper.create_variable_for_type_inference(DataType.INT64, stop_gradient=True)
    helper.append_op(
        type="argsort", inputs={"X": [x]},
        outputs={"Out": [out], "Indices": [idx]}, attrs={"axis": axis},
    )
    return out, idx


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape2", input=x, act=act, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op(
        type="reshape2", inputs={"X": [x]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"shape": list(shape)},
    )
    return helper.append_activation(out)


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze2", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    xshape = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op(
        type="squeeze2", inputs={"X": [input]},
        outputs={"Out": [out], "XShape": [xshape]}, attrs={"axes": list(axes)},
    )
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze2", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    xshape = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op(
        type="unsqueeze2", inputs={"X": [input]},
        outputs={"Out": [out], "XShape": [xshape]}, attrs={"axes": list(axes)},
    )
    return out


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten2", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op(
        type="flatten2", inputs={"X": [x]},
        outputs={"Out": [out], "XShape": [xshape]}, attrs={"axis": axis},
    )
    return out


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose2", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op(
        type="transpose2", inputs={"X": [x]},
        outputs={"Out": [out], "XShape": [xshape]}, attrs={"axis": list(perm)},
    )
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", input=input, name=name)
    if isinstance(num_or_sections, int):
        num = num_or_sections
        sections = []
    else:
        num = 0
        sections = list(num_or_sections)
    outs = [
        helper.create_variable_for_type_inference(input.dtype)
        for _ in builtins_range(num or len(sections))
    ]
    helper.append_op(
        type="split", inputs={"X": [input]}, outputs={"Out": outs},
        attrs={"num": num, "sections": sections, "axis": dim},
    )
    return outs


def stack(x, axis=0):
    helper = LayerHelper("stack")
    out = helper.create_variable_for_type_inference(x[0].dtype)
    helper.append_op(type="stack", inputs={"X": list(x)}, outputs={"Y": [out]},
                     attrs={"axis": axis})
    return out


def unstack(x, axis=0, num=None):
    helper = LayerHelper("unstack", input=x)
    if num is None:
        num = x.shape[axis]
    outs = [helper.create_variable_for_type_inference(x.dtype) for _ in builtins_range(num)]
    helper.append_op(type="unstack", inputs={"X": [x]}, outputs={"Y": outs},
                     attrs={"axis": axis, "num": num})
    return outs


def expand(x, expand_times, name=None):
    helper = LayerHelper("expand", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="expand", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"expand_times": list(expand_times)})
    return out


def slice(input, axes, starts, ends):
    helper = LayerHelper("slice", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="slice", inputs={"Input": [input]}, outputs={"Out": [out]},
        attrs={"axes": list(axes), "starts": list(starts), "ends": list(ends)},
    )
    return out


def shape(input):
    helper = LayerHelper("shape", input=input)
    out = helper.create_variable_for_type_inference(DataType.INT32, stop_gradient=True)
    helper.append_op(type="shape", inputs={"Input": [input]}, outputs={"Out": [out]})
    return out


def gather(input, index):
    helper = LayerHelper("gather", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="gather", inputs={"X": [input], "Index": [index]}, outputs={"Out": [out]}
    )
    return out


def scatter(input, index, updates, name=None, overwrite=True):
    helper = LayerHelper("scatter", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="scatter",
        inputs={"X": [input], "Ids": [index], "Updates": [updates]},
        outputs={"Out": [out]}, attrs={"overwrite": overwrite},
    )
    return out


def one_hot_v2(input, depth):
    from .nn import one_hot

    return one_hot(input, depth)


def _scalar_reduce_bool(op_core, x):
    from .nn import _simple_act

    helper = LayerHelper(op_core, input=x)
    out = helper.create_variable_for_type_inference(DataType.BOOL, stop_gradient=True)
    helper.append_op(type=op_core, inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def has_inf(x):
    return _scalar_reduce_bool("isinf", x)


def has_nan(x):
    return _scalar_reduce_bool("isnan", x)


def isfinite(x):
    return _scalar_reduce_bool("isfinite", x)


import builtins


def builtins_range(n):
    return builtins.range(n)


def range(start, end, step, dtype):
    helper = LayerHelper("range")
    dtype = convert_dtype(dtype)

    # python-scalar bounds ride as attrs so the lowering sees static values
    # (a Variable bound would be a tracer under jit, and the output length
    # fixes an XLA shape); Variable bounds must be compile-time constants
    attrs = {"dtype": int(dtype)}
    inputs = {}
    for slot, v in (("Start", start), ("End", end), ("Step", step)):
        if isinstance(v, Variable):
            inputs[slot] = [v]
        else:
            attrs[f"const_{slot.lower()}"] = float(v)

    out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op(type="range", inputs=inputs, outputs={"Out": [out]}, attrs=attrs)
    return out


def increment(x, value=1.0, in_place=True):
    helper = LayerHelper("increment", input=x)
    out = x if in_place else helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="increment", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"step": float(value)})
    return out


def cumsum(x, axis=None, exclusive=None, reverse=None):
    helper = LayerHelper("cumsum", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    attrs = {}
    if axis is not None:
        attrs["axis"] = axis
    if exclusive is not None:
        attrs["exclusive"] = exclusive
    if reverse is not None:
        attrs["reverse"] = reverse
    helper.append_op(type="cumsum", inputs={"X": [x]}, outputs={"Out": [out]}, attrs=attrs)
    return out


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale", input=x, act=act, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="scale", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"scale": float(scale), "bias": float(bias),
               "bias_after_scale": bias_after_scale},
    )
    return helper.append_activation(out)


def _reduce(op_type, input, dim=None, keep_dim=False, name=None):
    helper = LayerHelper(op_type, input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    if dim is None:
        attrs = {"dim": [0], "keep_dim": keep_dim, "reduce_all": True}
    else:
        dims = dim if isinstance(dim, (list, tuple)) else [dim]
        attrs = {"dim": list(dims), "keep_dim": keep_dim, "reduce_all": False}
    helper.append_op(type=op_type, inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs=attrs)
    return out


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_sum", input, dim, keep_dim, name)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_mean", input, dim, keep_dim, name)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_max", input, dim, keep_dim, name)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_min", input, dim, keep_dim, name)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_prod", input, dim, keep_dim, name)


def tensor_array_to_tensor(input, axis=0, use_stack=False, name=None):
    """Concat/stack a LoDTensorArray into one tensor; also returns the
    per-step sizes (reference: layers/tensor.py tensor_array_to_tensor over
    tensor_array_to_tensor_op.cc)."""
    helper = LayerHelper("tensor_array_to_tensor", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    out_index = helper.create_variable_for_type_inference("int32")
    helper.append_op(
        type="tensor_array_to_tensor",
        inputs={"X": [input]},
        outputs={"Out": [out], "OutIndex": [out_index]},
        attrs={"axis": int(axis), "use_stack": bool(use_stack)},
    )
    return out, out_index


def sum(x):
    """Elementwise sum of a list of tensors (reference: layers/tensor.py
    sum over operators/sum_op.cc); single-tensor input passes through the
    same op for API parity."""
    return sums(x if isinstance(x, (list, tuple)) else [x])


def merge_selected_rows(x, name=None):
    """Dedup a SelectedRows value's rows by id-sum (reference:
    layers/nn.py merge_selected_rows over merge_selected_rows_op.cc)."""
    helper = LayerHelper("merge_selected_rows", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="merge_selected_rows", inputs={"X": [x]},
                     outputs={"Out": [out]})
    return out


def get_tensor_from_selected_rows(x, name=None):
    """SelectedRows -> dense row tensor (reference: layers/nn.py
    get_tensor_from_selected_rows)."""
    helper = LayerHelper("get_tensor_from_selected_rows", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="get_tensor_from_selected_rows",
                     inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def load(out, file_path, load_as_fp16=False):
    """Load a saved blob into `out` at run time (reference:
    layers/tensor.py load over operators/load_op.cc; the blob is the .npy
    written by io.save_vars)."""
    helper = LayerHelper("load", input=out)
    helper.append_op(
        type="load", inputs={}, outputs={"Out": [out]},
        attrs={"file_path": file_path, "load_as_fp16": bool(load_as_fp16)},
    )
    return out
