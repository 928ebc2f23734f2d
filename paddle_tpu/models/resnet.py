"""ResNet (reference configs: benchmark/fluid/models/resnet.py for
cifar10-scale, benchmark/fluid/models/se_resnext.py's imagenet layout).

ResNet-50 is the framework's flagship conv model and the north-star
benchmark (images/sec/chip).  TPU notes: NCHW layouts feed XLA's conv
lowering directly; batch_norm fuses into the conv epilogue; all FLOPs land
on the MXU."""

from __future__ import annotations

import functools

from .. import layers
from .common import ModelSpec, class_batch


def conv_bn_layer(input, ch_out, filter_size, stride, padding, act="relu"):
    """conv2d -> batch_norm(+act): the one formulation of the block.  The
    fused one-op forms lost their chip A/B to this chain by 17-49%
    (PERF.md 6, PR 29); the separate reference-shaped batch_norm op is
    also what the inference transpiler's conv+BN fold pattern-matches."""
    conv = layers.conv2d(
        input=input, num_filters=ch_out, filter_size=filter_size,
        stride=stride, padding=padding, act=None, bias_attr=False,
    )
    return layers.batch_norm(input=conv, act=act)


def _shortcut(input, ch_out, stride):
    ch_in = input.shape[1]
    if ch_in != ch_out or stride != 1:
        return conv_bn_layer(input, ch_out, 1, stride, 0, act=None)
    return input


def basicblock(input, ch_out, stride):
    s = _shortcut(input, ch_out, stride)
    conv1 = conv_bn_layer(input, ch_out, 3, stride, 1)
    conv2 = layers.conv2d(conv1, num_filters=ch_out, filter_size=3,
                          stride=1, padding=1, act=None, bias_attr=False)
    bn2 = layers.batch_norm(input=conv2, act=None)
    return layers.elementwise_add(s, bn2, act="relu")


def bottleneck(input, ch_out, stride):
    s = _shortcut(input, ch_out * 4, stride)
    conv1 = conv_bn_layer(input, ch_out, 1, 1, 0)
    conv2 = conv_bn_layer(conv1, ch_out, 3, stride, 1)
    conv3 = layers.conv2d(conv2, num_filters=ch_out * 4, filter_size=1,
                          stride=1, padding=0, act=None, bias_attr=False)
    bn3 = layers.batch_norm(input=conv3, act=None)
    return layers.elementwise_add(s, bn3, act="relu")


def _layer_warp(block_func, input, ch_out, count, stride):
    res = block_func(input, ch_out, stride)
    for _ in range(1, count):
        res = block_func(res, ch_out, 1)
    return res


def resnet_imagenet(
    img=None, label=None, depth: int = 50, class_num: int = 1000,
    img_shape=(3, 224, 224),
) -> ModelSpec:
    """ImageNet-scale ResNet: 7x7/2 stem + maxpool + 4 bottleneck stages +
    global average pool + FC."""
    if img is None:
        img = layers.data("image", list(img_shape), dtype="float32")
    if label is None:
        label = layers.data("label", [1], dtype="int64")

    cfg = {
        18: ([2, 2, 2, 2], basicblock),
        34: ([3, 4, 6, 3], basicblock),
        50: ([3, 4, 6, 3], bottleneck),
        101: ([3, 4, 23, 3], bottleneck),
        152: ([3, 8, 36, 3], bottleneck),
    }
    stages, block_func = cfg[depth]

    conv1 = conv_bn_layer(img, ch_out=64, filter_size=7, stride=2, padding=3)
    pool1 = layers.pool2d(
        input=conv1, pool_type="max", pool_size=3, pool_stride=2, pool_padding=1
    )
    res1 = _layer_warp(block_func, pool1, 64, stages[0], 1)
    res2 = _layer_warp(block_func, res1, 128, stages[1], 2)
    res3 = _layer_warp(block_func, res2, 256, stages[2], 2)
    res4 = _layer_warp(block_func, res3, 512, stages[3], 2)
    pool2 = layers.pool2d(
        input=res4, pool_size=7, pool_type="avg", pool_stride=1, global_pooling=True
    )
    out = layers.fc(input=pool2, size=class_num, act="softmax")

    cost = layers.cross_entropy(input=out, label=label)
    avg_cost = layers.mean(cost)
    acc = layers.accuracy(input=out, label=label)
    acc5 = layers.accuracy(input=out, label=label, k=5)

    return ModelSpec(
        name=f"resnet{depth}_imagenet",
        feed_names=[img.name, label.name],
        loss=avg_cost,
        metrics={"acc1": acc, "acc5": acc5},
        synthetic_batch=functools.partial(
            class_batch, img_shape=tuple(img_shape), num_classes=class_num,
            img_name=img.name, label_name=label.name,
        ),
        extras={"predict": out},
    )


def resnet_cifar10(
    img=None, label=None, depth: int = 32, class_num: int = 10,
) -> ModelSpec:
    """CIFAR-scale ResNet (6n+2 basicblock layout)."""
    if img is None:
        img = layers.data("image", [3, 32, 32], dtype="float32")
    if label is None:
        label = layers.data("label", [1], dtype="int64")
    assert (depth - 2) % 6 == 0, "depth must be 6n+2"
    n = (depth - 2) // 6

    conv1 = conv_bn_layer(img, ch_out=16, filter_size=3, stride=1, padding=1)
    res1 = _layer_warp(basicblock, conv1, 16, n, 1)
    res2 = _layer_warp(basicblock, res1, 32, n, 2)
    res3 = _layer_warp(basicblock, res2, 64, n, 2)
    pool = layers.pool2d(
        input=res3, pool_size=8, pool_type="avg", pool_stride=1, global_pooling=True
    )
    out = layers.fc(input=pool, size=class_num, act="softmax")

    cost = layers.cross_entropy(input=out, label=label)
    avg_cost = layers.mean(cost)
    acc = layers.accuracy(input=out, label=label)

    return ModelSpec(
        name=f"resnet{depth}_cifar10",
        feed_names=[img.name, label.name],
        loss=avg_cost,
        metrics={"acc": acc},
        synthetic_batch=functools.partial(
            class_batch, img_shape=(3, 32, 32), num_classes=class_num,
            img_name=img.name, label_name=label.name,
        ),
        extras={"predict": out},
    )
