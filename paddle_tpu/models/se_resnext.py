"""SE-ResNeXt-50 (reference: benchmark/fluid/models/se_resnext.py —
cardinality-32 ResNeXt bottlenecks with squeeze-and-excitation)."""

from __future__ import annotations

import numpy as np

from .. import layers
from .common import ModelSpec, class_batch

__all__ = ["se_resnext"]


def _conv_bn(input, num_filters, filter_size, stride=1, groups=1, act=None):
    conv = layers.conv2d(
        input=input,
        num_filters=num_filters,
        filter_size=filter_size,
        stride=stride,
        padding=(filter_size - 1) // 2,
        groups=groups,
        bias_attr=False,
    )
    return layers.batch_norm(input=conv, act=act)


def _squeeze_excitation(input, num_channels, reduction_ratio):
    pool = layers.pool2d(
        input=input, pool_type="avg", global_pooling=True
    )
    squeeze = layers.fc(
        input=pool, size=num_channels // reduction_ratio, act="relu"
    )
    excitation = layers.fc(input=squeeze, size=num_channels, act="sigmoid")
    # scale channels: [N, C] -> [N, C, 1, 1]
    exc = layers.unsqueeze(layers.unsqueeze(excitation, axes=[2]), axes=[3])
    return layers.elementwise_mul(input, exc)


def _shortcut(input, ch_out, stride):
    ch_in = input.shape[1]
    if ch_in != ch_out or stride != 1:
        return _conv_bn(input, ch_out, 1, stride)
    return input


def _bottleneck(input, num_filters, stride, cardinality, reduction_ratio):
    conv0 = _conv_bn(input, num_filters, 1, act="relu")
    conv1 = _conv_bn(
        conv0, num_filters, 3, stride=stride, groups=cardinality, act="relu"
    )
    conv2 = _conv_bn(conv1, num_filters * 2, 1)
    scaled = _squeeze_excitation(conv2, num_filters * 2, reduction_ratio)
    short = _shortcut(input, num_filters * 2, stride)
    return layers.relu(layers.elementwise_add(short, scaled))


def se_resnext(
    class_num: int = 1000,
    layers_cfg=(3, 4, 6, 3),
    cardinality: int = 32,
    reduction_ratio: int = 16,
    img_shape=(3, 224, 224),
) -> ModelSpec:
    img = layers.data("image", list(img_shape), dtype="float32")
    label = layers.data("label", [1], dtype="int64")

    conv = _conv_bn(img, 64, 7, stride=2, act="relu")
    conv = layers.pool2d(
        input=conv, pool_size=3, pool_stride=2, pool_padding=1,
        pool_type="max",
    )
    num_filters_list = [128, 256, 512, 1024]
    for block, depth in enumerate(layers_cfg):
        for i in range(depth):
            conv = _bottleneck(
                conv,
                num_filters_list[block],
                stride=2 if i == 0 and block != 0 else 1,
                cardinality=cardinality,
                reduction_ratio=reduction_ratio,
            )
    pool = layers.pool2d(input=conv, pool_type="avg", global_pooling=True)
    drop = layers.dropout(pool, dropout_prob=0.2)
    out = layers.fc(input=drop, size=class_num, act="softmax")

    cost = layers.cross_entropy(input=out, label=label)
    loss = layers.mean(cost)
    acc = layers.accuracy(input=out, label=label)

    def synthetic_batch(batch_size: int, seed: int = 0):
        return class_batch(batch_size, img_shape, class_num, seed=seed)

    return ModelSpec(
        name="se_resnext",
        feed_names=["image", "label"],
        loss=loss,
        metrics={"acc": acc},
        synthetic_batch=synthetic_batch,
    )
