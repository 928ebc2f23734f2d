"""Core NN layer functions (reference: python/paddle/fluid/layers/nn.py —
148 defs; this module covers the workhorses, widened over rounds)."""

from __future__ import annotations

from typing import List, Optional, Sequence, Union

import numpy as np

from ..core.framework import Variable
from ..core.proto import DataType
from ..initializer import ConstantInitializer, NormalInitializer, XavierInitializer
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr

__all__ = [
    "fc",
    "embedding",
    "linear_chain_crf",
    "crf_decoding",
    "chunk_eval",
    "warpctc",
    "ctc_greedy_decoder",
    "beam_search",
    "beam_search_decode",
    "fused_attention",
    "eva_attention",
    "rotary_embedding",
    "latent_attention",
    "short_conv1d",
    "selective_scan",
    "ssd_scan",
    "gated_rms_norm",
    "differential_attention",
    "handed_on",
    "kept",
    "gated_delta_attention",
    "compressed_conv_qkv",
    "kda_conv_decay",
    "kda_gated_norm",
    "gated_delta_decay",
    "mhc_streams",
    "mhc_maps",
    "mhc_maps_read",
    "mhc_read",
    "mhc_write",
    "sparse_attention",
    "moe_router",
    "moe_experts",
    "moe_bias_update",
    "edit_distance",
    "conv2d",
    "conv3d",
    "conv2d_transpose",
    "pool2d",
    "pool3d",
    "batch_norm",
    "fused_bn_add_act",
    "layer_norm",
    "rms_norm",
    "group_norm",
    "dropout",
    "softmax",
    "cross_entropy",
    "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits",
    "square_error_cost",
    "smooth_l1",
    "log_loss",
    "huber_loss",
    "accuracy",
    "auc",
    "topk",
    "matmul",
    "mul",
    "l2_normalize",
    "lrn",
    "label_smooth",
    "one_hot",
    "nce",
    "prelu",
    "brelu",
    "leaky_relu",
    "relu",
    "elu",
    "relu6",
    "pow",
    "stanh",
    "hard_sigmoid",
    "swish",
    "soft_relu",
    "maxout",
    "image_resize",
    "resize_bilinear",
    "resize_nearest",
    "pad",
    "pad2d",
    "pad_constant_like",
    "mean_iou",
    "clip",
    "clip_by_norm",
    "elementwise_add",
    "elementwise_sub",
    "elementwise_mul",
    "elementwise_div",
    "elementwise_max",
    "elementwise_min",
    "elementwise_pow",
    "elementwise_mod",
    "elementwise_floordiv",
    "cos_sim",
    "selu",
    "random_crop",
    "hash",
    "add_position_encoding",
    "similarity_focus",
    "adaptive_pool2d",
    "adaptive_pool3d",
    "conv3d_transpose",
    "unpool",
    "spp",
    "hsigmoid",
    "rank_loss",
    "margin_rank_loss",
    "bpr_loss",
    "dice_loss",
    "bilinear_tensor_product",
    "multiplex",
    "sampling_id",
    "space_to_depth",
    "crop",
    "image_resize_short",
]


def _pair(x, n=2):
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x] * n


def fc(
    input,
    size: int,
    num_flatten_dims: int = 1,
    param_attr=None,
    bias_attr=None,
    act: Optional[str] = None,
    is_test: bool = False,
    name: Optional[str] = None,
):
    """Fully-connected layer (reference: layers/nn.py fc) — composed from
    `mul` ops (one per input) + sum + bias + activation, exactly like the
    reference's generated program."""
    helper = LayerHelper("fc", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    inputs = helper.multiple_input()
    dtype = helper.input_dtype()
    param_attrs = helper.param_attr
    if not isinstance(param_attrs, list):
        param_attrs = [param_attrs] * len(inputs)

    mul_results = []
    for inp, pattr in zip(inputs, param_attrs):
        in_shape = list(inp.shape)
        fan_in = int(np.prod([abs(d) for d in in_shape[num_flatten_dims:]]))
        w = helper.create_parameter(pattr, shape=[fan_in, size], dtype=dtype)
        out = helper.create_variable_for_type_inference(dtype)
        helper.append_op(
            type="mul",
            inputs={"X": [inp], "Y": [w]},
            outputs={"Out": [out]},
            attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1},
        )
        mul_results.append(out)

    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(dtype)
        helper.append_op(type="sum", inputs={"X": mul_results}, outputs={"Out": [pre_bias]})
    pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims)
    return helper.append_activation(pre_act)


def embedding(
    input,
    size: Sequence[int],
    is_sparse: bool = False,
    is_distributed: bool = False,
    padding_idx: Optional[int] = None,
    param_attr=None,
    dtype="float32",
    name: Optional[str] = None,
):
    """Embedding lookup (reference: layers/nn.py embedding -> lookup_table).
    is_sparse=True emits SelectedRows sparse gradients — (ids, rows) pairs
    whose size is the batch's id count, never the vocab (matches
    operators/lookup_table_op.cc:80).  sgd/adagrad apply them row-wise;
    adam/momentum stay dense-equivalent by default (their moments decay
    even at zero grad) and update only touched rows under
    Adam(lazy_mode=True).  Sharded tables go through paddle_tpu.parallel."""
    helper = LayerHelper("embedding", param_attr=param_attr, name=name)
    w = helper.create_parameter(
        helper.param_attr, shape=list(size), dtype=dtype,
        default_initializer=XavierInitializer(),
    )
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="lookup_table",
        inputs={"W": [w], "Ids": [input]},
        outputs={"Out": [out]},
        attrs={
            "is_sparse": is_sparse,
            "is_distributed": is_distributed,
            "padding_idx": -1 if padding_idx is None else padding_idx,
        },
    )
    return out


def conv2d(
    input,
    num_filters: int,
    filter_size,
    stride=1,
    padding=0,
    dilation=1,
    groups: int = 1,
    param_attr=None,
    bias_attr=None,
    use_cudnn: bool = True,
    act: Optional[str] = None,
    name: Optional[str] = None,
):
    """2-D convolution, NCHW (reference: layers/nn.py conv2d).  use_cudnn is
    accepted and ignored — XLA picks the conv algorithm on TPU."""
    helper = LayerHelper("conv2d", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    num_channels = input.shape[1]
    fsize = _pair(filter_size)
    filter_shape = [num_filters, num_channels // groups] + fsize
    fan_in = (num_channels // groups) * fsize[0] * fsize[1]
    std = (2.0 / fan_in) ** 0.5
    w = helper.create_parameter(
        helper.param_attr, shape=filter_shape, dtype=dtype,
        default_initializer=NormalInitializer(0.0, std),
    )
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="conv2d",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={
            "strides": _pair(stride),
            "paddings": _pair(padding),
            "dilations": _pair(dilation),
            "groups": groups,
        },
    )
    pre_act = out
    if helper.bias_attr is not None:
        b = helper.create_parameter(helper.bias_attr, shape=[num_filters], dtype=dtype, is_bias=True)
        pre_act = helper.create_variable_for_type_inference(dtype)
        helper.append_op(
            type="elementwise_add",
            inputs={"X": [out], "Y": [b]},
            outputs={"Out": [pre_act]},
            attrs={"axis": 1},
        )
    return helper.append_activation(pre_act)


def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None):
    helper = LayerHelper("conv3d", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    num_channels = input.shape[1]
    fsize = _pair(filter_size, 3)
    filter_shape = [num_filters, num_channels // groups] + fsize
    w = helper.create_parameter(helper.param_attr, shape=filter_shape, dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="conv3d",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={
            "strides": _pair(stride, 3),
            "paddings": _pair(padding, 3),
            "dilations": _pair(dilation, 3),
            "groups": groups,
        },
    )
    pre_act = out
    if helper.bias_attr is not None:
        b = helper.create_parameter(helper.bias_attr, shape=[num_filters], dtype=dtype, is_bias=True)
        pre_act = helper.create_variable_for_type_inference(dtype)
        helper.append_op(
            type="elementwise_add", inputs={"X": [out], "Y": [b]},
            outputs={"Out": [pre_act]}, attrs={"axis": 1},
        )
    return helper.append_activation(pre_act)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None):
    helper = LayerHelper("conv2d_transpose", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    num_channels = input.shape[1]
    stride = _pair(stride)
    padding = _pair(padding)
    dilation = _pair(dilation)
    if filter_size is None:
        if output_size is None:
            raise ValueError("filter_size or output_size required")
        # invert out = (in-1)*stride - 2*pad + dilation*(k-1) + 1 for k
        output_size = _pair(output_size)
        h, w_ = input.shape[2], input.shape[3]
        filter_size = [
            (output_size[0] - (h - 1) * stride[0] + 2 * padding[0] - 1) // dilation[0] + 1,
            (output_size[1] - (w_ - 1) * stride[1] + 2 * padding[1] - 1) // dilation[1] + 1,
        ]
    else:
        filter_size = _pair(filter_size)
    w = helper.create_parameter(
        helper.param_attr,
        shape=[num_channels, num_filters // groups] + filter_size,
        dtype=dtype,
    )
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="conv2d_transpose",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": stride, "paddings": padding, "dilations": dilation, "groups": groups},
    )
    pre_act = out
    if helper.bias_attr is not None:
        b = helper.create_parameter(helper.bias_attr, shape=[num_filters], dtype=dtype, is_bias=True)
        pre_act = helper.create_variable_for_type_inference(dtype)
        helper.append_op(
            type="elementwise_add", inputs={"X": [out], "Y": [b]},
            outputs={"Out": [pre_act]}, attrs={"axis": 1},
        )
    return helper.append_activation(pre_act)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1, pool_padding=0,
           global_pooling=False, use_cudnn=True, ceil_mode=False,
           exclusive=True, name=None):
    helper = LayerHelper("pool2d", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="pool2d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={
            "pooling_type": pool_type,
            "ksize": _pair(pool_size),
            "strides": _pair(pool_stride),
            "paddings": _pair(pool_padding),
            "global_pooling": global_pooling,
            "ceil_mode": ceil_mode,
            "exclusive": exclusive,
        },
    )
    return out


def pool3d(input, pool_size=-1, pool_type="max", pool_stride=1, pool_padding=0,
           global_pooling=False, use_cudnn=True, ceil_mode=False,
           exclusive=True, name=None):
    helper = LayerHelper("pool3d", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="pool3d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={
            "pooling_type": pool_type,
            "ksize": _pair(pool_size, 3),
            "strides": _pair(pool_stride, 3),
            "paddings": _pair(pool_padding, 3),
            "global_pooling": global_pooling,
            "ceil_mode": ceil_mode,
            "exclusive": exclusive,
        },
    )
    return out


def _bn_build(helper, input, data_layout, moving_mean_name,
              moving_variance_name):
    """The ONE copy of BN parameter/state creation (scale, bias, moving
    mean/variance with initializers, saved stats, output var) shared by
    batch_norm and its fused twin: returns (inputs dict, outputs dict,
    out var)."""
    dtype = input.dtype
    c = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    scale = helper.create_parameter(
        helper.param_attr or ParamAttr(),
        shape=[c], dtype=dtype, default_initializer=ConstantInitializer(1.0),
    )
    bias = helper.create_parameter(
        helper.bias_attr or ParamAttr(),
        shape=[c], dtype=dtype, is_bias=True,
    )
    from ..core.framework import unique_name

    mean = helper.main_program.global_block().create_var(
        name=moving_mean_name or unique_name(f"{helper.name}.mean"),
        shape=[c], dtype=dtype, persistable=True, stop_gradient=True,
    )
    helper.set_variable_initializer(mean, ConstantInitializer(0.0))
    variance = helper.main_program.global_block().create_var(
        name=moving_variance_name or unique_name(f"{helper.name}.var"),
        shape=[c], dtype=dtype, persistable=True, stop_gradient=True,
    )
    helper.set_variable_initializer(variance, ConstantInitializer(1.0))

    saved_mean = helper.create_variable_for_type_inference(
        dtype, stop_gradient=True)
    saved_var = helper.create_variable_for_type_inference(
        dtype, stop_gradient=True)
    out = helper.create_variable_for_type_inference(dtype)
    inputs = {
        "X": [input], "Scale": [scale], "Bias": [bias],
        "Mean": [mean], "Variance": [variance],
    }
    outputs = {
        "Y": [out],
        "MeanOut": [mean],
        "VarianceOut": [variance],
        "SavedMean": [saved_mean],
        "SavedVariance": [saved_var],
    }
    return inputs, outputs, out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               in_place=False, name=None, moving_mean_name=None,
               moving_variance_name=None, do_model_average_for_mean_and_var=False,
               use_global_stats=False):
    """Batch normalization (reference: layers/nn.py batch_norm).  Moving
    mean/variance are persistable state vars updated in-graph."""
    helper = LayerHelper("batch_norm", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    inputs, outputs, out = _bn_build(helper, input, data_layout,
                                     moving_mean_name, moving_variance_name)
    helper.append_op(
        type="batch_norm",
        inputs=inputs,
        outputs=outputs,
        attrs={
            "momentum": momentum, "epsilon": epsilon, "is_test": is_test,
            "data_layout": data_layout, "use_global_stats": use_global_stats,
        },
    )
    return helper.append_activation(out)


def fused_bn_add_act(x, y=None, act="relu", is_test=False, momentum=0.9,
                     epsilon=1e-5, param_attr=None, bias_attr=None,
                     data_layout="NCHW", name=None, moving_mean_name=None,
                     moving_variance_name=None, use_global_stats=False):
    """batch_norm(x) [+ y] -> act, fused into one op whose backward
    RECOMPUTES the normalize/add/act chain instead of storing it (the op
    carries @recompute@; see ops/nn_ops.py _fused_bn_add_act).  Replaces
    the batch_norm -> elementwise_add -> relu tail of a residual block
    (reference kernels being subsumed: operators/batch_norm_op.cu.cc:1 +
    elementwise/add + activation; later Paddle's
    contrib.layers.fused_bn_add_act has this same surface).  Numerics
    match the unfused chain exactly — parity-tested."""
    helper = LayerHelper("fused_bn_add_act", input=x, param_attr=param_attr,
                         bias_attr=bias_attr, act=None, name=name)
    inputs, outputs, out = _bn_build(helper, x, data_layout,
                                     moving_mean_name, moving_variance_name)
    if y is not None:
        inputs["Z"] = [y]
    helper.append_op(
        type="fused_bn_add_act",
        inputs=inputs,
        outputs=outputs,
        attrs={
            "momentum": momentum, "epsilon": epsilon, "is_test": is_test,
            "data_layout": data_layout, "use_global_stats": use_global_stats,
            "act": act, "@recompute@": True,
        },
    )
    return out


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1, epsilon=1e-5,
               param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("layer_norm", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    norm_shape = [int(np.prod([abs(d) for d in input.shape[begin_norm_axis:]]))]
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(
            helper.param_attr, shape=norm_shape, dtype=dtype,
            default_initializer=ConstantInitializer(1.0),
        )
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(helper.bias_attr, shape=norm_shape, dtype=dtype, is_bias=True)
        inputs["Bias"] = [b]
    mean = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    var = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="layer_norm",
        inputs=inputs,
        outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
        attrs={"begin_norm_axis": begin_norm_axis, "epsilon": epsilon},
    )
    return helper.append_activation(out)


def rms_norm(input, begin_norm_axis=-1, epsilon=1e-6, param_attr=None,
             name=None, unit_offset=False):
    """input / sqrt(mean(input^2) + epsilon) over dims >= begin_norm_axis,
    times a learned scale that starts at 1 (param_attr=False: no scale);
    under `unit_offset` times (1 + scale), the scale started at 0.
    TPU-native addition (ops/nn_ops.py rms_norm)."""
    helper = LayerHelper("rms_norm", input=input, param_attr=param_attr,
                         name=name)
    dtype = input.dtype
    begin = begin_norm_axis % len(input.shape)
    inputs = {"X": [input]}
    if param_attr is not False:
        inputs["Scale"] = [helper.create_parameter(
            helper.param_attr,
            shape=[int(np.prod([abs(d) for d in input.shape[begin:]]))],
            dtype=dtype, default_initializer=ConstantInitializer(
                0.0 if unit_offset else 1.0))]
    out = helper.create_variable_for_type_inference(dtype)
    attrs = {"begin_norm_axis": begin, "epsilon": epsilon}
    if unit_offset:
        attrs["unit_offset"] = True
    helper.append_op(type="rms_norm", inputs=inputs, outputs={"Y": [out]},
                     attrs=attrs)
    return out


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, data_layout="NCHW", name=None):
    helper = LayerHelper("group_norm", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    c = input.shape[1]
    inputs = {"X": [input]}
    if helper.param_attr is not None:
        s = helper.create_parameter(
            helper.param_attr, shape=[c], dtype=dtype,
            default_initializer=ConstantInitializer(1.0),
        )
        inputs["Scale"] = [s]
    if helper.bias_attr is not None:
        b = helper.create_parameter(helper.bias_attr, shape=[c], dtype=dtype, is_bias=True)
        inputs["Bias"] = [b]
    mean = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    var = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="group_norm",
        inputs=inputs,
        outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
        attrs={"groups": groups, "epsilon": epsilon},
    )
    return helper.append_activation(out)


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    mask = helper.create_variable_for_type_inference(DataType.UINT8, stop_gradient=True)
    helper.append_op(
        type="dropout",
        inputs={"X": [x]},
        outputs={"Out": [out], "Mask": [mask]},
        attrs={
            "dropout_prob": dropout_prob,
            "is_test": is_test,
            "seed": seed if seed is not None else 0,
            "dropout_implementation": dropout_implementation,
        },
    )
    return out


def softmax(input, use_cudnn=True, name=None, axis=-1):
    helper = LayerHelper("softmax", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="softmax", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return out


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="cross_entropy",
        inputs={"X": [input], "Label": [label]},
        outputs={"Y": [out]},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index},
    )
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=False,
                               return_softmax=False, smooth_eps=0.0):
    """smooth_eps (TPU extension, hard labels only): fold uniform label
    smoothing into the op analytically —
        loss = (1-eps) * CE(label) + eps * mean_V(-log p)
    identical to one_hot -> label_smooth -> soft-label CE but WITHOUT
    materializing any [*, V] label tensor (at vocab 32k and bench batch
    that chain moves ~1 GB/step of HBM).

    What the backward reads (hard labels, ops/loss_ops.py::_hard_ce): the
    forward makes one pass over the logits and saves e = exp(x - max) in
    the LOGITS' dtype (bf16 under AMP, the dtype of the Softmax output;
    fp32 for fp32 logits) behind an optimization barrier, with its fp32
    row sum s; dLogits = (g / s) * e - g * target evaluates no exponential
    and is rounded to the logits' dtype.  The statistics (max, sum, lse,
    the loss) stay fp32.  soft_label=True keeps jax's own gradient."""
    if smooth_eps and soft_label:
        # validate BEFORE creating any program vars: a rejected call must
        # not leave orphan Softmax/Loss descs behind
        raise ValueError("smooth_eps folds smoothing over HARD labels; "
                         "pre-smoothed soft labels must not smooth twice")
    helper = LayerHelper("softmax_with_cross_entropy", input=logits)
    softmax_out = helper.create_variable_for_type_inference(logits.dtype)
    loss = helper.create_variable_for_type_inference(logits.dtype)
    helper.append_op(
        type="softmax_with_cross_entropy",
        inputs={"Logits": [logits], "Label": [label]},
        outputs={"Softmax": [softmax_out], "Loss": [loss]},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index,
               "smooth_eps": float(smooth_eps)},
    )
    if return_softmax:
        return loss, softmax_out
    return loss


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100, name=None):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="sigmoid_cross_entropy_with_logits",
        inputs={"X": [x], "Label": [label]},
        outputs={"Out": [out]},
        attrs={"ignore_index": ignore_index},
    )
    return out


def square_error_cost(input, label):
    """(input-label)^2 via sub+square ops (reference: layers/nn.py
    square_error_cost builds the same two-op pattern)."""
    helper = LayerHelper("square_error_cost", input=input)
    minus_out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="elementwise_sub",
        inputs={"X": [input], "Y": [label]},
        outputs={"Out": [minus_out]},
    )
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="square", inputs={"X": [minus_out]}, outputs={"Out": [out]})
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1", input=x)
    diff = helper.create_variable_for_type_inference(x.dtype)
    loss = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x], "Y": [y]}
    if inside_weight is not None:
        inputs["InsideWeight"] = [inside_weight]
    if outside_weight is not None:
        inputs["OutsideWeight"] = [outside_weight]
    helper.append_op(
        type="smooth_l1_loss",
        inputs=inputs,
        outputs={"Diff": [diff], "Out": [loss]},
        attrs={"sigma": sigma if sigma is not None else 1.0},
    )
    return loss


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper("log_loss", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="log_loss",
        inputs={"Predicted": [input], "Labels": [label]},
        outputs={"Loss": [out]},
        attrs={"epsilon": epsilon},
    )
    return out


def huber_loss(input, label, delta):
    helper = LayerHelper("huber_loss", input=input)
    residual = helper.create_variable_for_type_inference(input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="huber_loss",
        inputs={"X": [input], "Y": [label]},
        outputs={"Out": [out], "Residual": [residual]},
        attrs={"delta": delta},
    )
    return out


def topk(input, k, name=None):
    helper = LayerHelper("top_k", input=input, name=name)
    values = helper.create_variable_for_type_inference(input.dtype)
    indices = helper.create_variable_for_type_inference(DataType.INT64, stop_gradient=True)
    helper.append_op(
        type="top_k",
        inputs={"X": [input]},
        outputs={"Out": [values], "Indices": [indices]},
        attrs={"k": k},
    )
    return values, indices


def accuracy(input, label, k=1, correct=None, total=None):
    """Classification accuracy: top_k + accuracy op (reference:
    layers/metric_op.py accuracy)."""
    helper = LayerHelper("accuracy", input=input)
    topk_out, topk_indices = topk(input, k=k)
    acc_out = helper.create_variable_for_type_inference(DataType.FP32, stop_gradient=True)
    correct = correct or helper.create_variable_for_type_inference(DataType.INT32, stop_gradient=True)
    total = total or helper.create_variable_for_type_inference(DataType.INT32, stop_gradient=True)
    helper.append_op(
        type="accuracy",
        inputs={"Out": [topk_out], "Indices": [topk_indices], "Label": [label]},
        outputs={"Accuracy": [acc_out], "Correct": [correct], "Total": [total]},
    )
    return acc_out


def auc(input, label, curve="ROC", num_thresholds=4095, topk=1, slide_steps=1):
    """Streaming AUC with persistable stat buffers (reference:
    layers/metric_op.py auc)."""
    helper = LayerHelper("auc", input=input)
    stat_pos = helper.create_global_variable(
        persistable=True, dtype=DataType.INT64, shape=[num_thresholds + 1]
    )
    stat_neg = helper.create_global_variable(
        persistable=True, dtype=DataType.INT64, shape=[num_thresholds + 1]
    )
    for v in (stat_pos, stat_neg):
        helper.set_variable_initializer(v, ConstantInitializer(0.0))
        v.stop_gradient = True
    auc_out = helper.create_variable_for_type_inference(DataType.FP64, stop_gradient=True)
    helper.append_op(
        type="auc",
        inputs={
            "Predict": [input], "Label": [label],
            "StatPos": [stat_pos], "StatNeg": [stat_neg],
        },
        outputs={
            "AUC": [auc_out], "StatPosOut": [stat_pos], "StatNegOut": [stat_neg],
        },
        attrs={"curve": curve, "num_thresholds": num_thresholds},
    )
    return auc_out, auc_out, [stat_pos, stat_neg]


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None,
           out_dtype=None):
    """`out_dtype` (TPU-native addition, e.g. "float32"): the product comes
    out in that dtype whatever the AMP tier makes of the operands (bf16
    operands, the fp32 accumulator handed out as it is)."""
    helper = LayerHelper("matmul", input=x, name=name)
    out = helper.create_variable_for_type_inference(out_dtype or x.dtype)
    attrs = {"transpose_X": transpose_x, "transpose_Y": transpose_y,
             "alpha": float(alpha)}
    if out_dtype:
        attrs["out_dtype"] = str(out_dtype)
    helper.append_op(type="matmul", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="mul",
        inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out]},
        attrs={"x_num_col_dims": x_num_col_dims, "y_num_col_dims": y_num_col_dims},
    )
    return out


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    norm = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="norm",
        inputs={"X": [x]},
        outputs={"Out": [out], "Norm": [norm]},
        attrs={"axis": axis, "epsilon": epsilon},
    )
    return out


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="lrn", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"n": n, "k": k, "alpha": alpha, "beta": beta},
    )
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32", name=None):
    helper = LayerHelper("label_smooth", input=label, name=name)
    out = helper.create_variable_for_type_inference(dtype)
    inputs = {"X": [label]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist]
    helper.append_op(
        type="label_smooth", inputs=inputs, outputs={"Out": [out]},
        attrs={"epsilon": float(epsilon)},
    )
    return out


def one_hot(input, depth):
    helper = LayerHelper("one_hot", input=input)
    out = helper.create_variable_for_type_inference(DataType.FP32)
    helper.append_op(
        type="one_hot", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"depth": depth},
    )
    return out


def nce(input, label, num_total_classes, sample_weight=None, param_attr=None,
        bias_attr=None, num_neg_samples=None, name=None, sampler="uniform",
        custom_dist=None, seed=0, is_sparse=False):
    helper = LayerHelper("nce", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    dim = input.shape[1]
    w = helper.create_parameter(helper.param_attr, shape=[num_total_classes, dim],
                                dtype=input.dtype)
    inputs = {"Input": [input], "Label": [label], "Weight": [w]}
    if helper.bias_attr is not None:
        b = helper.create_parameter(helper.bias_attr, shape=[num_total_classes, 1],
                                    dtype=input.dtype, is_bias=True)
        inputs["Bias"] = [b]
    cost = helper.create_variable_for_type_inference(input.dtype)
    sample_logits = helper.create_variable_for_type_inference(input.dtype)
    sample_labels = helper.create_variable_for_type_inference(DataType.INT64, stop_gradient=True)
    helper.append_op(
        type="nce",
        inputs=inputs,
        outputs={"Cost": [cost], "SampleLogits": [sample_logits], "SampleLabels": [sample_labels]},
        attrs={
            "num_total_classes": num_total_classes,
            "num_neg_samples": num_neg_samples or 10,
            "seed": seed,
        },
    )
    return cost


def prelu(x, mode, param_attr=None, name=None):
    helper = LayerHelper("prelu", input=x, param_attr=param_attr, name=name)
    if mode == "all":
        alpha_shape = [1]
    elif mode == "channel":
        alpha_shape = [1, x.shape[1], 1, 1]
    else:
        alpha_shape = [1] + list(x.shape)[1:]
    alpha = helper.create_parameter(
        helper.param_attr, shape=alpha_shape, dtype=x.dtype,
        default_initializer=ConstantInitializer(0.25),
    )
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="prelu", inputs={"X": [x], "Alpha": [alpha]},
        outputs={"Out": [out]}, attrs={"mode": mode},
    )
    return out


def _simple_act(op_type, x, attrs=None, name=None):
    helper = LayerHelper(op_type, input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type=op_type, inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs=attrs or {})
    return out


def relu(x, name=None):
    return _simple_act("relu", x, name=name)


def brelu(x, t_min=0.0, t_max=24.0, name=None):
    return _simple_act("brelu", x, {"t_min": t_min, "t_max": t_max}, name)


def leaky_relu(x, alpha=0.02, name=None):
    return _simple_act("leaky_relu", x, {"alpha": alpha}, name)


def elu(x, alpha=1.0, name=None):
    return _simple_act("elu", x, {"alpha": alpha}, name)


def relu6(x, threshold=6.0, name=None):
    return _simple_act("relu6", x, {"threshold": threshold}, name)


def pow(x, factor=1.0, name=None):
    return _simple_act("pow", x, {"factor": factor}, name)


def stanh(x, scale_a=0.67, scale_b=1.7159, name=None):
    return _simple_act("stanh", x, {"scale_a": scale_a, "scale_b": scale_b}, name)


def hard_sigmoid(x, slope=0.2, offset=0.5, name=None):
    return _simple_act("hard_sigmoid", x, {"slope": slope, "offset": offset}, name)


def swish(x, beta=1.0, name=None):
    return _simple_act("swish", x, {"beta": beta}, name)


def soft_relu(x, threshold=40.0, name=None):
    return _simple_act("soft_relu", x, {"threshold": threshold}, name)


def maxout(x, groups, name=None):
    helper = LayerHelper("maxout", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="maxout", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"groups": groups})
    return out


def image_resize(input, out_shape=None, scale=None, name=None,
                 resample="BILINEAR", actual_shape=None, align_corners=True):
    op_type = {"BILINEAR": "bilinear_interp", "NEAREST": "nearest_interp"}[resample]
    helper = LayerHelper(op_type, input=input, name=name)
    if out_shape is None:
        out_shape = [int(input.shape[2] * scale), int(input.shape[3] * scale)]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type=op_type, inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"out_h": out_shape[0], "out_w": out_shape[1]},
    )
    return out


def resize_bilinear(input, out_shape=None, scale=None, name=None, actual_shape=None):
    return image_resize(input, out_shape, scale, name, "BILINEAR", actual_shape)


def resize_nearest(input, out_shape=None, scale=None, name=None, actual_shape=None):
    return image_resize(input, out_shape, scale, name, "NEAREST", actual_shape)


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="pad", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"paddings": list(paddings), "pad_value": float(pad_value)},
    )
    return out


def pad2d(input, paddings=[0, 0, 0, 0], mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    helper = LayerHelper("pad2d", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="pad2d", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"paddings": list(paddings), "mode": mode,
               "pad_value": float(pad_value), "data_format": data_format},
    )
    return out


def pad_constant_like(x, y, pad_value=0.0, name=None):
    helper = LayerHelper("pad_constant_like", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="pad_constant_like", inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out]}, attrs={"pad_value": float(pad_value)},
    )
    return out


def mean_iou(input, label, num_classes):
    helper = LayerHelper("mean_iou", input=input)
    out_mean_iou = helper.create_variable_for_type_inference(DataType.FP32)
    out_wrong = helper.create_variable_for_type_inference(DataType.INT32)
    out_correct = helper.create_variable_for_type_inference(DataType.INT32)
    helper.append_op(
        type="mean_iou",
        inputs={"Predictions": [input], "Labels": [label]},
        outputs={"OutMeanIou": [out_mean_iou], "OutWrong": [out_wrong],
                 "OutCorrect": [out_correct]},
        attrs={"num_classes": num_classes},
    )
    return out_mean_iou, out_wrong, out_correct


def clip(x, min, max, name=None):
    helper = LayerHelper("clip", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="clip", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"min": float(min), "max": float(max)})
    return out


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="clip_by_norm", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"max_norm": float(max_norm)})
    return out


def _elementwise(op_type, x, y, axis=-1, act=None, name=None):
    helper = LayerHelper(op_type, input=x, act=act, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type=op_type, inputs={"X": [x], "Y": [y]}, outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return helper.append_activation(out)


def elementwise_add(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_add", x, y, axis, act, name)


def elementwise_sub(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_sub", x, y, axis, act, name)


def elementwise_mul(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_mul", x, y, axis, act, name)


def elementwise_div(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_div", x, y, axis, act, name)


def elementwise_max(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_max", x, y, axis, act, name)


def elementwise_min(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_min", x, y, axis, act, name)


def elementwise_pow(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_pow", x, y, axis, act, name)


def elementwise_mod(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_mod", x, y, axis, act, name)


def elementwise_floordiv(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_floordiv", x, y, axis, act, name)


# ---------------------------------------------------------------------------
# structured prediction (reference: layers/nn.py linear_chain_crf,
# crf_decoding, chunk_eval, warpctc, ctc_greedy_decoder)
# ---------------------------------------------------------------------------
def linear_chain_crf(input, label, param_attr=None):
    helper = LayerHelper("linear_chain_crf", **locals())
    size = input.shape[-1]
    transition = helper.create_parameter(
        attr=helper.param_attr, shape=[size + 2, size], dtype=input.dtype
    )
    alpha = helper.create_variable_for_type_inference(input.dtype)
    emission_exps = helper.create_variable_for_type_inference(input.dtype)
    transition_exps = helper.create_variable_for_type_inference(input.dtype)
    log_likelihood = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="linear_chain_crf",
        inputs={"Emission": [input], "Transition": transition, "Label": [label]},
        outputs={
            "Alpha": [alpha],
            "EmissionExps": [emission_exps],
            "TransitionExps": [transition_exps],
            "LogLikelihood": [log_likelihood],
        },
    )
    return log_likelihood


def crf_decoding(input, param_attr, label=None):
    helper = LayerHelper("crf_decoding", **locals())
    transition = helper.get_parameter(param_attr.name)
    viterbi_path = helper.create_variable_for_type_inference("int64")
    inputs = {"Emission": [input], "Transition": transition}
    if label is not None:
        inputs["Label"] = [label]
    helper.append_op(
        type="crf_decoding", inputs=inputs,
        outputs={"ViterbiPath": [viterbi_path]},
    )
    return viterbi_path


def chunk_eval(input, label, chunk_scheme, num_chunk_types,
               excluded_chunk_types=None):
    helper = LayerHelper("chunk_eval", **locals())
    precision = helper.create_variable_for_type_inference("float32")
    recall = helper.create_variable_for_type_inference("float32")
    f1_score = helper.create_variable_for_type_inference("float32")
    num_infer_chunks = helper.create_variable_for_type_inference("int64")
    num_label_chunks = helper.create_variable_for_type_inference("int64")
    num_correct_chunks = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="chunk_eval",
        inputs={"Inference": [input], "Label": [label]},
        outputs={
            "Precision": [precision],
            "Recall": [recall],
            "F1-Score": [f1_score],
            "NumInferChunks": [num_infer_chunks],
            "NumLabelChunks": [num_label_chunks],
            "NumCorrectChunks": [num_correct_chunks],
        },
        attrs={
            "num_chunk_types": num_chunk_types,
            "chunk_scheme": chunk_scheme,
            "excluded_chunk_types": excluded_chunk_types or [],
        },
    )
    return (precision, recall, f1_score, num_infer_chunks, num_label_chunks,
            num_correct_chunks)


def warpctc(input, label, blank=0, norm_by_times=False):
    helper = LayerHelper("warpctc", **locals())
    loss_out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="warpctc",
        inputs={"Logits": [input], "Label": [label]},
        outputs={"Loss": [loss_out]},
        attrs={"blank": blank, "norm_by_times": norm_by_times},
    )
    return loss_out


def ctc_greedy_decoder(input, blank, name=None):
    """argmax per step -> ctc_align (reference: layers/nn.py
    ctc_greedy_decoder)."""
    from . import tensor as tensor_layers

    helper = LayerHelper("ctc_greedy_decoder", **locals())
    topk_indices = tensor_layers.argmax(input, axis=-1)
    out = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="ctc_align",
        inputs={"Input": [topk_indices]},
        outputs={"Output": [out]},
        attrs={"blank": blank, "merge_repeated": True},
    )
    return out


def beam_search(pre_ids, pre_scores, ids, scores, beam_size, end_id,
                level=0, name=None):
    """One beam-selection step (reference: layers/nn.py beam_search over
    operators/beam_search_op.cc).  Returns (selected_ids, selected_scores);
    the parent-index tensor is retrievable as the third output var."""
    helper = LayerHelper("beam_search", **locals())
    selected_ids = helper.create_variable_for_type_inference("int64")
    selected_scores = helper.create_variable_for_type_inference("float32")
    parent_idx = helper.create_variable_for_type_inference("int64")
    inputs = {
        "pre_ids": [pre_ids],
        "pre_scores": [pre_scores],
        "scores": [scores],
    }
    if ids is not None:
        inputs["ids"] = [ids]
    helper.append_op(
        type="beam_search",
        inputs=inputs,
        outputs={
            "selected_ids": [selected_ids],
            "selected_scores": [selected_scores],
            "parent_idx": [parent_idx],
        },
        attrs={"level": level, "beam_size": beam_size, "end_id": end_id},
    )
    selected_ids._parent_idx = parent_idx
    return selected_ids, selected_scores


def beam_search_decode(ids, scores, beam_size, end_id, name=None,
                       parent_idx=None):
    """Backtrack beam arrays into sentences (reference: layers/nn.py
    beam_search_decode)."""
    helper = LayerHelper("beam_search_decode", **locals())
    sentence_ids = helper.create_variable_for_type_inference("int64")
    sentence_scores = helper.create_variable_for_type_inference("float32")
    inputs = {"Ids": [ids], "Scores": [scores]}
    if parent_idx is not None:
        inputs["ParentIdx"] = [parent_idx]
    helper.append_op(
        type="beam_search_decode",
        inputs=inputs,
        outputs={
            "SentenceIds": [sentence_ids],
            "SentenceScores": [sentence_scores],
        },
        attrs={"beam_size": beam_size, "end_id": end_id},
    )
    return sentence_ids, sentence_scores


def fused_attention(q, k, v, causal=False, scale=None, k_lengths=None,
                    name=None, window=None, rope=None, n_head=None):
    """Flash-attention in one op: q [B, H, S, D] over k/v [B, G, S, D], G =
    H or a divisor of it (grouped-query attention: query head j reads
    key/value head j // (H / G); K and V are never repeated), optional [B]
    valid key counts instead of an additive bias.  `window` (with causal):
    a query sees itself and the window - 1 keys before it.  `rope` labels
    the site's `attn.lower` span with the rotary rule that turned q and k
    ("plain", "yarn"); it changes no number.

    With `n_head` the operands are heads-LAST, the arrays the projections
    write: q [B, S, n_head * D] over k/v [B, S, G * D], and so is the
    output, [B, S, n_head * D]: no transposition before or after the op.
    Where a head is one block (S 256 at head 64) the kernels take them as
    they lie; at any other shape the op transposes inside itself and gives
    the heads-first numbers (TPU-native; see
    paddle_tpu/kernels/flash_attention.py)."""
    helper = LayerHelper("fused_attention", input=q, name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if k_lengths is not None:
        inputs["KLengths"] = [k_lengths]
    attrs = {"causal": causal, "scale": float(scale) if scale else 0.0}
    if window:
        attrs["window"] = int(window)
    if rope:
        attrs["rope"] = str(rope)
    if n_head:
        attrs["n_head"] = int(n_head)
    helper.append_op(type="fused_attention", inputs=inputs,
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def eva_attention(q, k, v, mu, phi, window, chunk, name=None):
    """EVA attention in its chunked form (Zheng et al., ICLR 2023, as
    EvaByte runs it): q, k, v [B, H, S, D], rotated; mu, phi [H, D] pool
    every `chunk` keys and values into one summary by two softmaxes over
    the chunk; a query runs one softmax over the exact causal keys of its
    own `window` and the summaries of every chunk of every window before
    it, scores times D^-1/2.  [B, H, S, D] (TPU-native; ops/attention_ops.py eva_attention,
    kernels/eva_attention.py)."""
    helper = LayerHelper("eva_attention", input=q, name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    helper.append_op(
        type="eva_attention",
        inputs={"Q": [q], "K": [k], "V": [v], "Mu": [mu], "Phi": [phi]},
        outputs={"Out": [out]},
        attrs={"window": int(window), "chunk": int(chunk)})
    return out


def _yarn_attrs(yarn):
    """The `yarn_<key>` attrs of rotary_embedding's and latent_attention's
    `yarn` dict; none where it is None."""
    from ..ops.attention_ops import YARN_KEYS

    if not yarn:
        return {}
    return {"yarn_" + key: float(yarn[key]) for key in YARN_KEYS}


def rotary_embedding(x, base=10000.0, offset=0, positions=None,
                     sections=None, name=None, yarn=None, rotary_dim=None):
    """Rotary position embedding of x [..., S, D] (heads first, then
    positions, then the head's features), half-split pairs, position
    offset + index along axis -2, angles in fp32.  With `positions`
    [B, n, S] and `sections` (n counts that add up to D / 2), x [B, ..., S,
    D]: the pairs of section j turn by position stream j (multi-axis
    rotary).  With `yarn` (a dict of factor, original_length, beta_fast,
    beta_slow, attention_factor): YaRN's scaled frequencies, cos and sin
    times attention_factor.  With `rotary_dim` < D the first `rotary_dim`
    features of a head turn, as a head of that width would, and the others
    pass (a partial rotary factor) (TPU-native; see ops/attention_ops.py
    rotary_embedding)."""
    helper = LayerHelper("rotary_embedding", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x]}
    attrs = {"base": float(base), "offset": int(offset)}
    if rotary_dim:
        attrs["rotary_dim"] = int(rotary_dim)
    attrs.update(_yarn_attrs(yarn))
    if positions is not None:
        inputs["Positions"] = [positions]
        attrs["sections"] = [int(n) for n in sections]
    helper.append_op(type="rotary_embedding", inputs=inputs,
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def sparse_attention(q, k, v, index_q, index_k, index_w, topk, q_chunk=512,
                     kv_chunk=512, name=None):
    """Causal grouped-query attention over the `topk` keys a learned index
    chooses for each query: q [B, H, S, D] over k, v [B, G, S, D]; the
    index's queries [B, Hi, S, Di], its one key a token [B, S, Di] and its
    head weights [B, S, Hi], all rotated already.  Returns (the heads'
    contexts [B, H, S, D], the index's loss, a scalar: KL of the heads'
    mean probabilities from the index's softmax over the chosen keys).
    q, k, v take their gradient from the first, the index from the second
    (TPU-native; ops/attention_ops.py sparse_attention)."""
    helper = LayerHelper("sparse_attention", input=q, name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    loss = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        type="sparse_attention",
        inputs={"Q": [q], "K": [k], "V": [v], "IndexQ": [index_q],
                "IndexK": [index_k], "IndexW": [index_w]},
        outputs={"Out": [out], "IndexLoss": [loss]},
        attrs={"topk": int(topk), "q_chunk": int(q_chunk),
               "kv_chunk": int(kv_chunk)},
    )
    return out, loss


def latent_attention(q, latent, k_rope, kv_up_w, n_head, qk_nope_head_dim,
                     qk_rope_head_dim, v_head_dim, rope_base=10000.0,
                     name=None, rope="rotary", yarn=None, scale=None):
    """Causal multi-head latent attention (MLA) from its projections: q
    [B, S, H * (nope + rope)], the normalised latent [B, S, rank], the one
    rotary key part a token k_rope [B, S, rope], and the parameter
    kv_up_w [rank, H * (nope + v)]; returns the heads' contexts
    [B, S, H * v].  `rope` "none" leaves both rope-wide parts unturned
    (no positions: the scores are q.k over all nope + rope features as
    projected, under the causal mask alone); "rotary" turns them at
    `rope_base`, under `yarn` (rotary_embedding's dict: factor,
    original_length, beta_fast, beta_slow, attention_factor) at YaRN's
    frequencies with cos and sin times attention_factor.  `scale`: the
    softmax scale, (nope + rope)^-1/2 where none is given (TPU-native;
    ops/attention_ops.py latent_attention)."""
    helper = LayerHelper("latent_attention", input=q, name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    attrs = {"n_head": int(n_head),
             "qk_nope_head_dim": int(qk_nope_head_dim),
             "qk_rope_head_dim": int(qk_rope_head_dim),
             "v_head_dim": int(v_head_dim), "rope_base": float(rope_base)}
    if rope != "rotary":
        attrs["rope"] = str(rope)
    attrs.update(_yarn_attrs(yarn))
    if scale is not None:
        attrs["scale"] = float(scale)
    helper.append_op(
        type="latent_attention",
        inputs={"Q": [q], "Latent": [latent], "KRope": [k_rope],
                "KvUpW": [kv_up_w]},
        outputs={"Out": [out]}, attrs=attrs,
    )
    return out


def short_conv1d(x, weight, activation="silu", name=None, bias=None):
    """A causal depthwise convolution along the sequence and an
    activation: x [B, S, C], weight [k, C] (one filter of k taps a
    channel, the last tap on the position itself, zeros before the first
    position), `bias` [C] added before `activation` identity | silu |
    sigmoid | tanh | relu; [B, S, C] out (TPU-native;
    ops/linear_attention_ops.py short_conv1d)."""
    helper = LayerHelper("short_conv1d", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x], "W": [weight]}
    if bias is not None:
        inputs["Bias"] = [bias]
    helper.append_op(
        type="short_conv1d", inputs=inputs,
        outputs={"Out": [out]}, attrs={"activation": str(activation)},
    )
    return out


def selective_scan(x, dt, a, b, c, d, dt_bias=None, name=None):
    """Mamba-1's selective scan: x, dt [B, S, E], a [E, N] (negative), b,
    c [B, S, N], d [E]; a channel's N states from 0, s_t = exp(dt_t a)
    s_(t-1) + dt_t x_t b_t, y_t = s_t c_t + d x_t; with `dt_bias` [E] the
    step is softplus(dt + dt_bias).  fp32 inside, [B, S, E] out in x's
    dtype; no state a token is ever stored, backward included
    (TPU-native; ops/state_space_ops.py selective_scan,
    kernels/selective_scan.py)."""
    helper = LayerHelper("selective_scan", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x], "Dt": [dt], "A": [a], "B": [b], "C": [c], "D": [d]}
    if dt_bias is not None:
        inputs["DtBias"] = [dt_bias]
    helper.append_op(type="selective_scan", inputs=inputs,
                     outputs={"Out": [out]}, attrs={})
    return out


def ssd_scan(x, dt, a, b, c, d, dt_bias=None, name=None):
    """Mamba-2's state-space-dual scan: x [B, S, H, P], dt [B, S, H], a [H]
    (negative), b, c [B, S, G, N] (head h reads group h // (H / G)), d
    [H]; a head's P x N state from 0, s_t = exp(dt_t a) s_(t-1) + dt_t x_t
    (x) b_t, y_t = s_t c_t + d x_t, ONE decay a head a token; with
    `dt_bias` [H] the step is softplus(dt + dt_bias).  Decays and the
    state fp32, [B, S, H, P] out in x's dtype; no state a token is ever
    stored, backward included (TPU-native; ops/state_space_ops.py ssd_scan,
    kernels/ssd_scan.py)."""
    helper = LayerHelper("ssd_scan", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x], "Dt": [dt], "A": [a], "B": [b], "C": [c], "D": [d]}
    if dt_bias is not None:
        inputs["DtBias"] = [dt_bias]
    helper.append_op(type="ssd_scan", inputs=inputs,
                     outputs={"Out": [out]}, attrs={})
    return out


def gated_rms_norm(x, gate, scale, groups=1, epsilon=1e-5, name=None):
    """Mamba-2's output norm: g = x silu(gate), the gate FIRST, then g /
    sqrt(mean(g^2) + epsilon) over each of `groups` runs of channels times
    scale [E]; x, gate [B, S, E] (TPU-native; ops/state_space_ops.py
    gated_rms_norm)."""
    helper = LayerHelper("gated_rms_norm", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="gated_rms_norm",
        inputs={"X": [x], "Gate": [gate], "Scale": [scale]},
        outputs={"Out": [out]},
        attrs={"groups": int(groups), "epsilon": float(epsilon)})
    return out


def handed_on(x, what, readers, name=None):
    """x as it is, marked as a value that one layers.Recurrence hands out
    and `readers` later ones read from outside their bodies (`what`:
    memory | kv): the span `shared.lower` says its bytes and readers at
    lowering (TPU-native; ops/control_flow_ops.py handed_on)."""
    helper = LayerHelper("handed_on", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="handed_on", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"what": str(what), "readers": int(readers)})
    return out


def kept(x, name=None):
    """x as it is, marked to survive the recomputation of the unit around
    it (a layers.Recurrence under recompute_scope): the unit saves it
    beside its inputs, x's bytes held from the forward to the backward, and
    the backward does not make it again; anywhere else the identity
    (TPU-native; ops/control_flow_ops.py kept)."""
    helper = LayerHelper("kept", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="kept", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={})
    return out


def differential_attention(q, k, v, lambda_q1, lambda_k1, lambda_q2,
                           lambda_k2, scale, n_head, lambda_init,
                           causal=True, window=None, epsilon=1e-5,
                           name=None):
    """Differential attention (Ye et al., arXiv:2410.05258) of heads-last
    q [B, S, n_head * D] over k, v [B, S, G * D]: adjacent heads pair up,
    a pair's two softmax maps (head D) read the pair's two value heads
    side by side (2 D), out = (1 - lambda_init) RMSNorm(A1 - lambda A2)
    scale [2 D] with lambda = exp(lambda_q1 . lambda_k1) - exp(lambda_q2 .
    lambda_k2) + lambda_init; `causal`, under `window` a query sees itself
    and the window - 1 keys before it.  [B, S, n_head * D] out
    (TPU-native; ops/attention_ops.py differential_attention: two flash
    sites and jax.numpy around them)."""
    helper = LayerHelper("differential_attention", input=q, name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    attrs = {"n_head": int(n_head), "lambda_init": float(lambda_init),
             "causal": bool(causal), "epsilon": float(epsilon)}
    if window:
        attrs["window"] = int(window)
    helper.append_op(
        type="differential_attention",
        inputs={"Q": [q], "K": [k], "V": [v], "LambdaQ1": [lambda_q1],
                "LambdaK1": [lambda_k1], "LambdaQ2": [lambda_q2],
                "LambdaK2": [lambda_k2], "Scale": [scale]},
        outputs={"Out": [out]}, attrs=attrs)
    return out


def gated_delta_attention(q, k, v, g, beta, heads, chunk=64, name=None):
    """The gated delta rule's recurrence over q, k, v [B, S, H D], the
    log-decay g (<= 0, fp32) and beta [B, S, H]: each head's q and k to
    unit length; a head's state M [D, D] from 0; a token does M~ =
    diag(exp(g)) M, M = M~ + beta k (v - M~^T k)^T, o = D^-1/2 M^T q.  Two
    forms, read from the shapes: g [B, S, H D], a decay for every key
    channel (Kimi Delta Attention); or g [B, S, H], ONE decay a head, with
    q, k [B, S, Hk D] at Hk <= H key heads, value head j reading key head
    j // (H / Hk) (Gated DeltaNet; layers.gated_delta_decay makes that g).
    [B, S, H D] out; `chunk` tokens at a time (a power of two that divides
    S), backward included (TPU-native; ops/linear_attention_ops.py
    gated_delta_attention, kernels/gated_delta.py)."""
    helper = LayerHelper("gated_delta_attention", input=v, name=name)
    out = helper.create_variable_for_type_inference(v.dtype)
    helper.append_op(
        type="gated_delta_attention",
        inputs={"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta]},
        outputs={"Out": [out]},
        attrs={"heads": int(heads), "chunk": int(chunk)},
    )
    return out


def kda_conv_decay(q, k, v, f, conv_q_w, conv_k_w, conv_v_w, dt_bias, a_log,
                   heads, name=None):
    """What Kimi Delta Attention does before its recurrence, as one op: q,
    k, v [B, S, H D] (the projections) each through short_conv1d's causal
    depthwise convolution (conv_*_w [taps, H D]) and SiLU, and the
    log-decay g = -exp(a_log [H]) softplus(f + dt_bias [H D]) of f [B, S,
    H D], fp32, one for every key channel.  Returns (q', k', v, g) for
    gated_delta_attention (TPU-native; ops/linear_attention_ops.py
    kda_conv_decay: for a TPU a Pallas kernel pair over tiles of rows x
    blocks of channels where H D is whole 128-lane vectors and S whole
    tiles, kernels/kda_mix.py, the same arithmetic in jax.numpy
    elsewhere)."""
    helper = LayerHelper("kda_conv_decay", input=q, name=name)
    outs = [helper.create_variable_for_type_inference(q.dtype)
            for _ in range(3)]
    outs.append(helper.create_variable_for_type_inference("float32"))
    helper.append_op(
        type="kda_conv_decay",
        inputs={"Q": [q], "K": [k], "V": [v], "F": [f],
                "ConvQW": [conv_q_w], "ConvKW": [conv_k_w],
                "ConvVW": [conv_v_w], "DtBias": [dt_bias], "ALog": [a_log]},
        outputs={"QOut": [outs[0]], "KOut": [outs[1]], "VOut": [outs[2]],
                 "G": [outs[3]]},
        attrs={"heads": int(heads)},
    )
    return tuple(outs)


def kda_gated_norm(x, gate, gate_bias, scale, heads, epsilon=1e-6,
                   name=None, gate_activation="sigmoid"):
    """What Kimi Delta Attention does after its recurrence, as one op: x
    [B, S, H D] normalised a head (rms_norm's formula over D, one learned
    scale [D]) times sigmoid(gate [B, S, H D] + gate_bias [H D]); [B, S,
    H D] out (TPU-native; ops/linear_attention_ops.py kda_gated_norm: for
    a TPU a Pallas kernel pair where D is whole 128-lane vectors and S
    whole tiles, kernels/kda_mix.py, jax.numpy elsewhere).  Gated
    DeltaNet's: `gate_bias` None and `gate_activation` "silu", the same
    norm a head times silu(gate)."""
    helper = LayerHelper("kda_gated_norm", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x], "Gate": [gate], "Scale": [scale]}
    attrs = {"heads": int(heads), "epsilon": float(epsilon)}
    if gate_bias is not None:
        inputs["GateBias"] = [gate_bias]
    if gate_activation != "sigmoid":
        attrs["gate_activation"] = str(gate_activation)
    helper.append_op(type="kda_gated_norm", inputs=inputs,
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def gated_delta_decay(x, a_log, dt_bias, name=None):
    """Gated DeltaNet's log-decay, one a head: -exp(a_log [H]) softplus(x
    [B, S, H] + dt_bias [H]), fp32; gated_delta_attention's g in its
    head-decay form (TPU-native; ops/linear_attention_ops.py
    gated_delta_decay)."""
    helper = LayerHelper("gated_delta_decay", input=x, name=name)
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        type="gated_delta_decay",
        inputs={"X": [x], "ALog": [a_log], "DtBias": [dt_bias]},
        outputs={"Out": [out]}, attrs={})
    return out


def mhc_streams(x, streams, name=None):
    """x [B, S, C] copied to each of `streams` residual streams: [B, S, n,
    C], what a hyper-connected model's layers carry; half-width from here
    on under AMP's keep tier (TPU-native; ops/hyper_connection_ops.py
    mhc_streams)."""
    helper = LayerHelper("mhc_streams", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="mhc_streams", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"streams": int(streams)})
    return out


def _maps_op(helper, outputs, x, phi, a_pre, a_post, a_res, b_pre, b_post,
             b_res, sinkhorn_iters, epsilon, hc_eps, clamp_min, clamp_max):
    helper.append_op(
        type=helper.layer_type,
        inputs={"X": [x], "Phi": [phi], "APre": [a_pre], "APost": [a_post],
                "ARes": [a_res], "BPre": [b_pre], "BPost": [b_post],
                "BRes": [b_res]},
        outputs=outputs,
        attrs={"sinkhorn_iters": int(sinkhorn_iters),
               "epsilon": float(epsilon), "hc_eps": float(hc_eps),
               "clamp_min": float(clamp_min),
               "clamp_max": float(clamp_max)},
    )


def mhc_maps(x, phi, a_pre, a_post, a_res, b_pre, b_post, b_res,
             sinkhorn_iters=20, epsilon=1e-6, hc_eps=1e-6, clamp_min=-30.0,
             clamp_max=30.0, name=None):
    """The three maps of one manifold-constrained hyper-connection from
    the streams x [B, S, n, C]: with m = RMS-normalised vec(x) times phi
    [nC, 2n + n^2], H_pre = sigmoid(a_pre m + b_pre) [n], H_post = 2
    sigmoid(a_post m + b_post) [n] and H_res = exp(clip(a_res m + b_res))
    [n, n] after `sinkhorn_iters` Sinkhorn-Knopp iterations (columns, then
    rows, `hc_eps` beside each sum): doubly stochastic.  Returns H [B, 2n +
    n^2, S] fp32, the tokens on the minor axis: rows 0:n H_pre, n:2n
    H_post, then H_res row by row, for mhc_read and mhc_write (TPU-native;
    ops/hyper_connection_ops.py mhc_maps: for ONE TPU a Pallas kernel pair
    over tiles of rows where the shape tiles, four streams of C a multiple
    of 128 and S of a tile, kernels/mhc.py; the same arithmetic in
    jax.numpy elsewhere: the CPU, a mesh of several devices)."""
    helper = LayerHelper("mhc_maps", input=x, name=name)
    out = helper.create_variable_for_type_inference("float32")
    _maps_op(helper, {"H": [out]}, x, phi, a_pre, a_post, a_res, b_pre,
             b_post, b_res, sinkhorn_iters, epsilon, hc_eps, clamp_min,
             clamp_max)
    return out


def mhc_maps_read(x, phi, a_pre, a_post, a_res, b_pre, b_post, b_res,
                  sinkhorn_iters=20, epsilon=1e-6, hc_eps=1e-6,
                  clamp_min=-30.0, clamp_max=30.0, name=None):
    """mhc_maps and mhc_read of one sublayer as one op: returns (H [B, 2n +
    n^2, S] fp32 as mhc_maps gives it, sum_j H_pre[j] x[j] [B, S, C] as
    mhc_read does under it), the two ops' arithmetic exactly.  The streams'
    gradient through both leaves as one value (TPU-native;
    ops/hyper_connection_ops.py mhc_maps_read: for ONE TPU one Pallas
    kernel pair of kernels/mhc.py where the shape tiles, whose forward
    reads the streams once where a tile of rows x all n C channels fits
    VMEM; the two ops' jax.numpy forms elsewhere)."""
    helper = LayerHelper("mhc_maps_read", input=x, name=name)
    h = helper.create_variable_for_type_inference("float32")
    out = helper.create_variable_for_type_inference(x.dtype)
    _maps_op(helper, {"H": [h], "Out": [out]}, x, phi, a_pre, a_post, a_res,
             b_pre, b_post, b_res, sinkhorn_iters, epsilon, hc_eps, clamp_min,
             clamp_max)
    return h, out


def mhc_read(x, h, name=None):
    """What a sublayer reads of the streams x [B, S, n, C] under mhc_maps'
    h: sum_j H_pre[j] x[j], [B, S, C] (TPU-native;
    ops/hyper_connection_ops.py mhc_read, in jax.numpy everywhere: a model
    that reads under maps it has just made takes mhc_maps_read)."""
    helper = LayerHelper("mhc_read", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="mhc_read", inputs={"X": [x], "H": [h]},
                     outputs={"Out": [out]})
    return out


def mhc_write(x, h, y, name=None):
    """The streams after a sublayer wrote y [B, S, C] back under mhc_maps'
    h: x'[i] = sum_j H_res[i, j] x[j] + H_post[i] y, [B, S, n, C]
    (TPU-native; ops/hyper_connection_ops.py mhc_write; the engine as
    mhc_maps': a Pallas kernel pair of kernels/mhc.py for one TPU where
    the shape tiles and x and y share a dtype, jax.numpy elsewhere)."""
    helper = LayerHelper("mhc_write", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="mhc_write", inputs={"X": [x], "H": [h], "Y": [y]},
                     outputs={"Out": [out]})
    return out


def compressed_conv_qkv(q, k, v, conv_a_w, conv_a_b, conv_b_w, conv_b_b,
                        tau, heads, kv_heads, rotary_dim=None,
                        rope_base=10000.0, name=None):
    """Compressed convolutional attention's sequence mixing, between the
    down-projections q [B, S, H D], k, v [B, S, G D] of a layer's input and
    the scores: two causal convolutions along the sequence over [q ; k]
    (conv_a_w [k0, C] and conv_a_b [C], one filter a channel; conv_b_w [k1,
    H + G, D, D] and conv_b_b [C], across the channels of one head; padded
    once on the left), the q-k mean of the unconvolved values added after,
    each head normalised to length sqrt(D), the keys times tau [G], rotary
    on the first `rotary_dim` features of a head, and the second half of
    v's channels taken from the token before.  Returns (q [B, H, S, D], k
    and v [B, G, S, D]) for fused_attention (TPU-native;
    ops/attention_ops.py compressed_conv_qkv: for a TPU one Pallas kernel
    pair over tiles of rows where D is a multiple of 128 and S of a tile,
    kernels/cca_mix.py, the same arithmetic in jax.numpy elsewhere)."""
    helper = LayerHelper("compressed_conv_qkv", input=q, name=name)
    outs = [helper.create_variable_for_type_inference(q.dtype)
            for _ in range(3)]
    attrs = {"heads": int(heads), "kv_heads": int(kv_heads),
             "rope_base": float(rope_base)}
    if rotary_dim:
        attrs["rotary_dim"] = int(rotary_dim)
    helper.append_op(
        type="compressed_conv_qkv",
        inputs={"Q": [q], "K": [k], "V": [v], "ConvAW": [conv_a_w],
                "ConvAB": [conv_a_b], "ConvBW": [conv_b_w],
                "ConvBB": [conv_b_b], "Tau": [tau]},
        outputs={"QOut": [outs[0]], "KOut": [outs[1]], "VOut": [outs[2]]},
        attrs=attrs,
    )
    return tuple(outs)


def moe_router(x, weight, bias, top_k, scaling=1.0, norm_topk_prob=True,
               scoring="sigmoid", name=None, carried=0):
    """Router over all the experts weight [d, E] has, its scores the
    sigmoid of x.weight or, under `scoring` "softmax", the softmax over
    the E experts: (the top_k experts of score + bias a token [..., k]
    int32, their weights [..., k]
    fp32: the scores without the bias, normalised over the chosen and
    times `scaling`, the tokens that chose each expert [E]).  `bias` is
    state without a gradient, or None for a router that has none.
    `carried` labels the site's `router.lower` span with the width of the
    state a router's network took from the layer before (0: none); it
    changes no number (TPU-native; ops/moe_ops.py)."""
    helper = LayerHelper("moe_router", input=x, name=name)
    idx = helper.create_variable_for_type_inference("int32",
                                                    stop_gradient=True)
    top_w = helper.create_variable_for_type_inference("float32")
    load = helper.create_variable_for_type_inference("float32",
                                                     stop_gradient=True)
    helper.append_op(
        type="moe_router",
        inputs={"X": [x], "Weight": [weight],
                **({} if bias is None else {"Bias": [bias]})},
        outputs={"TopIdx": [idx], "TopWeight": [top_w], "Load": [load]},
        attrs={"top_k": int(top_k), "scaling": float(scaling),
               "norm_topk_prob": bool(norm_topk_prob),
               "scoring": str(scoring), "carried": int(carried),
               "trained": bool(getattr(weight, "trainable", True))},
    )
    return idx, top_w, load


def moe_experts(x, top_idx, top_weight, gate_w, up_w, down_w, experts_total,
                expert_offset=0, scoring="sigmoid", name=None):
    """The held experts' part of sum_i g_i E_i(x): gate_w / up_w [held, d,
    f] and down_w [held, f, d] are experts expert_offset .. expert_offset +
    held of `experts_total`; every token routed to one of them is computed,
    none is dropped; `scoring` is the router's rule, for the `moe.lower`
    span alone (TPU-native; ops/moe_ops.py)."""
    helper = LayerHelper("moe_experts", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="moe_experts",
        inputs={"X": [x], "TopIdx": [top_idx], "TopWeight": [top_weight],
                "GateW": [gate_w], "UpW": [up_w], "DownW": [down_w]},
        outputs={"Out": [out]},
        attrs={"experts_total": int(experts_total),
               "expert_offset": int(expert_offset),
               "scoring": str(scoring)},
    )
    return out


def moe_bias_update(bias, load, gamma, name=None):
    """bias_i += gamma * sign(mean load - load_i), in place: the
    auxiliary-loss-free balancing of a router's selection bias (load [E]
    or [n, E], summed over n)."""
    helper = LayerHelper("moe_bias_update", input=bias, name=name)
    helper.append_op(
        type="moe_bias_update", inputs={"Bias": [bias], "Load": [load]},
        outputs={"BiasOut": [bias]}, attrs={"gamma": float(gamma)},
    )
    return bias


def edit_distance(input, label, normalized=True, ignored_tokens=None,
                  name=None):
    """Levenshtein distance per sequence pair + batch sequence count
    (reference: layers/nn.py edit_distance over edit_distance_op.cc)."""
    helper = LayerHelper("edit_distance", **locals())
    out = helper.create_variable_for_type_inference("float32")
    seq_num = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="edit_distance",
        inputs={"Hyps": [input], "Refs": [label]},
        outputs={"Out": [out], "SequenceNum": [seq_num]},
        attrs={"normalized": normalized,
               "ignored_tokens": ignored_tokens or []},
    )
    return out, seq_num


def cos_sim(X, Y, name=None):
    """Row-wise cosine similarity (reference: layers/nn.py cos_sim over
    operators/cos_sim_op.cc); Y may be [1, D] to broadcast."""
    helper = LayerHelper("cos_sim", **locals())
    out = helper.create_variable_for_type_inference(X.dtype)
    xnorm = helper.create_variable_for_type_inference(X.dtype)
    ynorm = helper.create_variable_for_type_inference(X.dtype)
    helper.append_op(
        type="cos_sim", inputs={"X": [X], "Y": [Y]},
        outputs={"Out": [out], "XNorm": [xnorm], "YNorm": [ynorm]},
    )
    return out


def selu(x, scale=None, alpha=None, name=None):
    """Scaled ELU (reference: layers/nn.py selu over operators/selu_op.cc)."""
    helper = LayerHelper("selu", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    attrs = {}
    if scale is not None:
        attrs["scale"] = float(scale)
    if alpha is not None:
        attrs["alpha"] = float(alpha)
    helper.append_op(type="selu", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs=attrs)
    return out


def random_crop(x, shape, seed=None, name=None):
    """Random per-instance crop of the trailing dims to `shape`
    (reference: layers/nn.py random_crop over operators/random_crop_op.h)."""
    helper = LayerHelper("random_crop", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x]}
    outputs = {"Out": [out]}
    if seed is not None:
        inputs["Seed"] = [seed]
        outputs["SeedOut"] = [
            helper.create_variable_for_type_inference("int64")
        ]
    helper.append_op(type="random_crop", inputs=inputs, outputs=outputs,
                     attrs={"shape": list(shape)})
    return out


def hash(input, hash_size, num_hash=1, name=None):
    """Hash int rows into [N, num_hash, 1] int64 buckets
    (reference: layers/nn.py hash over operators/hash_op.h)."""
    helper = LayerHelper("hash", input=input, name=name)
    out = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="hash", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"num_hash": num_hash, "mod_by": hash_size},
    )
    return out


def add_position_encoding(input, alpha=1.0, beta=1.0, name=None):
    """alpha*x + beta*sinusoid(pos) (reference: layers/nn.py
    add_position_encoding over operators/add_position_encoding_op.h)."""
    helper = LayerHelper("add_position_encoding", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="add_position_encoding", inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"alpha": float(alpha), "beta": float(beta)},
    )
    return out


def similarity_focus(input, axis, indexes, name=None):
    """Similarity-focus 0/1 mask (reference: layers/nn.py similarity_focus
    over operators/similarity_focus_op.h)."""
    helper = LayerHelper("similarity_focus", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="similarity_focus", inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"axis": int(axis), "indexes": [int(i) for i in indexes]},
    )
    return out


def adaptive_pool2d(input, pool_size, pool_type="max", require_index=False,
                    name=None):
    """Adaptive pooling to a fixed output grid (reference: layers/nn.py
    adaptive_pool2d over pool_op.cc's `adaptive` attr; require_index=True
    uses max_pool2d_with_index and also returns the argmax mask)."""
    helper = LayerHelper("adaptive_pool2d", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    attrs = {
        "pooling_type": pool_type,
        "ksize": _pair(pool_size),
        "adaptive": True,
    }
    if require_index:
        if pool_type != "max":
            raise ValueError("require_index needs pool_type='max'")
        mask = helper.create_variable_for_type_inference("int32")
        helper.append_op(
            type="max_pool2d_with_index", inputs={"X": [input]},
            outputs={"Out": [out], "Mask": [mask]}, attrs=attrs,
        )
        return out, mask
    helper.append_op(type="pool2d", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def adaptive_pool3d(input, pool_size, pool_type="max", require_index=False,
                    name=None):
    """3-D adaptive pooling (see adaptive_pool2d)."""
    helper = LayerHelper("adaptive_pool3d", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    attrs = {
        "pooling_type": pool_type,
        "ksize": _pair(pool_size, 3),
        "adaptive": True,
    }
    if require_index:
        if pool_type != "max":
            raise ValueError("require_index needs pool_type='max'")
        mask = helper.create_variable_for_type_inference("int32")
        helper.append_op(
            type="max_pool3d_with_index", inputs={"X": [input]},
            outputs={"Out": [out], "Mask": [mask]}, attrs=attrs,
        )
        return out, mask
    helper.append_op(type="pool3d", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def conv3d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None):
    """3-D transposed convolution (reference: layers/nn.py conv3d_transpose
    over conv_transpose_op.cc:358)."""
    helper = LayerHelper("conv3d_transpose", input=input,
                         param_attr=param_attr, bias_attr=bias_attr, act=act,
                         name=name)
    dtype = input.dtype
    num_channels = input.shape[1]
    stride = _pair(stride, 3)
    padding = _pair(padding, 3)
    dilation = _pair(dilation, 3)
    if filter_size is None:
        if output_size is None:
            raise ValueError("filter_size or output_size required")
        # invert out = (in-1)*stride - 2*pad + dilation*(k-1) + 1 for k
        output_size = _pair(output_size, 3)
        filter_size = [
            (output_size[i] - (input.shape[i + 2] - 1) * stride[i]
             + 2 * padding[i] - 1) // dilation[i] + 1
            for i in range(3)
        ]
    else:
        filter_size = _pair(filter_size, 3)
    w = helper.create_parameter(
        helper.param_attr,
        shape=[num_channels, num_filters // groups] + filter_size,
        dtype=dtype,
    )
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="conv3d_transpose",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": stride, "paddings": padding, "dilations": dilation,
               "groups": groups},
    )
    pre_act = out
    if helper.bias_attr is not None:
        b = helper.create_parameter(helper.bias_attr, shape=[num_filters],
                                    dtype=dtype, is_bias=True)
        pre_act = helper.create_variable_for_type_inference(dtype)
        helper.append_op(type="elementwise_add",
                         inputs={"X": [out], "Y": [b]},
                         outputs={"Out": [pre_act]}, attrs={"axis": 1})
    return helper.append_activation(pre_act)


def unpool(input, indices, ksize, strides=1, paddings=0, name=None):
    """Max-unpooling with indices from adaptive_pool2d(require_index=True) or
    max_pool2d_with_index (reference: operators/unpool_op.cc)."""
    helper = LayerHelper("unpool", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="unpool",
        inputs={"X": [input], "Indices": [indices]},
        outputs={"Out": [out]},
        attrs={"unpooling_type": "max", "ksize": _pair(ksize),
               "strides": _pair(strides), "paddings": _pair(paddings)},
    )
    return out


def spp(input, pyramid_height, pool_type="max", name=None):
    """Spatial pyramid pooling (reference: operators/spp_op.cc)."""
    helper = LayerHelper("spp", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="spp", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"pyramid_height": int(pyramid_height),
               "pooling_type": pool_type},
    )
    return out


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None,
             name=None, path_table=None, path_code=None, is_custom=False,
             is_sparse=False):
    """Hierarchical sigmoid loss layer (reference: layers/nn.py hsigmoid
    over operators/hierarchical_sigmoid_op.cc).  Default: complete binary
    tree over num_classes (W is [num_classes-1, D]); custom trees pass
    path_table/path_code.  is_sparse is accepted for API parity — grads
    here are dense (the embedding path owns the SelectedRows story)."""
    helper = LayerHelper("hsigmoid", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    dtype = input.dtype
    dim = input.shape[1]
    if is_custom and (path_table is None or path_code is None):
        raise ValueError("is_custom=True needs path_table/path_code")
    num_nodes = (
        path_table.shape[0] if is_custom else num_classes - 1
    )
    w = helper.create_parameter(helper.param_attr, shape=[num_nodes, dim],
                                dtype=dtype)
    inputs = {"X": [input], "W": [w], "Label": [label]}
    if helper.bias_attr is not None:
        b = helper.create_parameter(helper.bias_attr, shape=[num_nodes, 1],
                                    dtype=dtype, is_bias=True)
        inputs["Bias"] = [b]
    if is_custom:
        inputs["PTable"] = [path_table]
        inputs["PathCode"] = [path_code]
    out = helper.create_variable_for_type_inference(dtype)
    pre_out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="hierarchical_sigmoid",
        inputs=inputs,
        outputs={"Out": [out], "PreOut": [pre_out]},
        attrs={"num_classes": num_classes, "is_sparse": is_sparse},
    )
    return out


def rank_loss(label, left, right, name=None):
    """RankNet pairwise loss (reference: layers/nn.py rank_loss over
    operators/rank_loss_op.cc)."""
    helper = LayerHelper("rank_loss", **locals())
    out = helper.create_variable_for_type_inference(left.dtype)
    helper.append_op(
        type="rank_loss",
        inputs={"Label": [label], "Left": [left], "Right": [right]},
        outputs={"Out": [out]},
    )
    return out


def margin_rank_loss(label, left, right, margin=0.1, name=None):
    """Margin ranking loss (reference: layers/nn.py margin_rank_loss)."""
    helper = LayerHelper("margin_rank_loss", **locals())
    out = helper.create_variable_for_type_inference(left.dtype)
    act = helper.create_variable_for_type_inference(left.dtype)
    helper.append_op(
        type="margin_rank_loss",
        inputs={"Label": [label], "X1": [left], "X2": [right]},
        outputs={"Out": [out], "Activated": [act]},
        attrs={"margin": float(margin)},
    )
    return out


def bpr_loss(input, label, name=None):
    """Bayesian personalized ranking loss (reference: layers/nn.py bpr_loss
    over operators/bpr_loss_op.cc)."""
    helper = LayerHelper("bpr_loss", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="bpr_loss", inputs={"X": [input], "Label": [label]},
        outputs={"Y": [out]},
    )
    return out


def dice_loss(input, label, epsilon=1e-5):
    """Dice coefficient loss, 1 - 2|X*Y|/(|X|+|Y|) (reference:
    layers/nn.py dice_loss — a pure composition of elementwise/reduce
    layers, same here)."""
    from ..layers import one_hot, reduce_mean, reduce_sum, scale

    # label arrives [N, 1] (fluid id-column convention); one_hot folds it
    label_oh = one_hot(label, depth=input.shape[-1])
    reduce_dims = list(range(1, len(input.shape)))
    inse = reduce_sum(elementwise_mul(input, label_oh), dim=reduce_dims)
    denom = elementwise_add(
        reduce_sum(input, dim=reduce_dims),
        reduce_sum(label_oh, dim=reduce_dims),
    )
    # epsilon on the DENOMINATOR only (reference dice_loss): an empty
    # ground-truth mask yields loss 1, not 0
    frac = elementwise_div(
        scale(inse, scale=2.0),
        scale(denom, scale=1.0, bias=float(epsilon)),
    )
    return reduce_mean(scale(frac, scale=-1.0, bias=1.0))


def bilinear_tensor_product(x, y, size, act=None, name=None,
                            param_attr=None, bias_attr=None):
    """out_k = x W_k y + b (reference: layers/nn.py bilinear_tensor_product
    over operators/bilinear_tensor_product_op.cc)."""
    helper = LayerHelper("bilinear_tensor_product", input=x,
                         param_attr=param_attr, bias_attr=bias_attr,
                         act=act, name=name)
    dtype = x.dtype
    w = helper.create_parameter(
        helper.param_attr, shape=[size, x.shape[1], y.shape[1]], dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype)
    inputs = {"X": [x], "Y": [y], "Weight": [w]}
    if helper.bias_attr is not None:
        b = helper.create_parameter(helper.bias_attr, shape=[1, size],
                                    dtype=dtype, is_bias=True)
        inputs["Bias"] = [b]
    helper.append_op(type="bilinear_tensor_product", inputs=inputs,
                     outputs={"Out": [out]})
    return helper.append_activation(out)


def multiplex(inputs, index):
    """Row-wise select among candidate tensors (reference: layers/nn.py
    multiplex over operators/multiplex_op.cc)."""
    helper = LayerHelper("multiplex")
    out = helper.create_variable_for_type_inference(inputs[0].dtype)
    helper.append_op(
        type="multiplex",
        inputs={"X": list(inputs), "Ids": [index]},
        outputs={"Out": [out]},
    )
    return out


def sampling_id(x, min=0.0, max=1.0, seed=0, dtype="float32", name=None):
    """Sample a category index per row from a probability matrix
    (reference: layers/nn.py sampling_id)."""
    helper = LayerHelper("sampling_id", input=x, name=name)
    out = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="sampling_id", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"min": float(min), "max": float(max), "seed": seed},
    )
    if dtype not in ("int64", DataType.INT64):
        from .tensor import cast

        return cast(out, dtype)
    return out


def space_to_depth(x, blocksize, name=None):
    """Rearrange spatial blocks into channels (reference: layers/nn.py
    space_to_depth over operators/space_to_depth_op.cc)."""
    helper = LayerHelper("space_to_depth", input=x, name=name)
    n, c, h, w = x.shape
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="space_to_depth", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"blocksize": int(blocksize)},
    )
    return out


def crop(x, shape=None, offsets=None, name=None):
    """Static crop (reference: layers/nn.py crop over operators/crop_op.cc)."""
    helper = LayerHelper("crop", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    if shape is None:
        shape = list(x.shape)
    if hasattr(shape, "dtype"):  # Variable reference form: use its shape
        shape = list(shape.shape)
    if offsets is None:
        offsets = [0] * len(shape)
    helper.append_op(
        type="crop", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"shape": [int(s) for s in shape],
               "offsets": [int(o) for o in offsets]},
    )
    return out


def image_resize_short(input, out_short_len, resample="BILINEAR"):
    """Resize so the SHORT side equals out_short_len, keeping aspect
    (reference: layers/nn.py image_resize_short)."""
    in_shape = list(input.shape)
    hw = in_shape[2:4]
    short_idx = hw.index(min(hw))
    out_shape = list(hw)
    out_shape[short_idx] = out_short_len
    out_shape[1 - short_idx] = int(
        round(hw[1 - short_idx] * (out_short_len / float(hw[short_idx])))
    )
    return image_resize(input, out_shape=out_shape, resample=resample)
