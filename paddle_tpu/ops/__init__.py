"""Operator library: importing this package registers every op lowering.

The reference's equivalent is the static-registrar operator library
paddle/fluid/operators/ (353 registered ops); here each module is a set of
JAX lowering rules consumed by paddle_tpu.core.compiler.
"""

from . import (  # noqa: F401
    activation_ops,
    attention_ops,
    beam_search_ops,
    compare_ops,
    control_flow_ops,
    crf_ops,
    detection_ops,
    elementwise_ops,
    framework_ops,
    hyper_connection_ops,
    linear_attention_ops,
    loss_ops,
    math_ops,
    metric_ops,
    misc_ops,
    moe_ops,
    nn_ops,
    optimizer_ops,
    proposal_ops,
    quant_ops,
    reduce_ops,
    rnn_ops,
    sequence_ops,
    state_space_ops,
    tensor_ops,
    vision_ops,
)

from ..core.registry import OpRegistry


def registered_ops():
    return OpRegistry.registered_ops()
