"""Model zoo: each benchmark config builds, trains a few steps, and the loss
drops on a memorizable synthetic batch (reference analogue: tests/book/*,
benchmark/fluid smoke runs)."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import models


def _train(spec, steps=3, bs=4, lr=0.01):
    fluid.optimizer.Adam(learning_rate=lr).minimize(spec.loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = spec.synthetic_batch(bs)
    losses = []
    for _ in range(steps):
        (lv,) = exe.run(feed=batch, fetch_list=[spec.loss])
        losses.append(float(np.ravel(lv)[0]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    return losses


def test_lenet5_trains():
    _train(models.lenet5(), lr=0.001)


def test_resnet_cifar10_trains():
    _train(models.resnet_cifar10(depth=8))


def test_resnet_imagenet_builds_and_trains_small():
    spec = models.resnet_imagenet(depth=18, class_num=10, img_shape=(3, 32, 32))
    _train(spec, bs=2)


_CONV_BN_BUILDERS = {
    "resnet18": lambda **kw: models.resnet_imagenet(depth=18, **kw),
    "resnet34": lambda **kw: models.resnet_imagenet(depth=34, **kw),
    "resnet50": lambda **kw: models.resnet_imagenet(depth=50, **kw),
    "resnet101": lambda **kw: models.resnet_imagenet(depth=101, **kw),
    "resnet152": lambda **kw: models.resnet_imagenet(depth=152, **kw),
    "resnet_cifar10": lambda **kw: models.resnet_cifar10(depth=32, **kw),
    "se_resnext": lambda **kw: models.se_resnext(**kw),
}


@pytest.mark.parametrize("name", sorted(_CONV_BN_BUILDERS))
def test_conv_builders_emit_one_formulation(name):
    """A ResNet / SE-ResNeXt block is written once: conv2d -> batch_norm
    [-> elementwise_add] -> relu.  No fused one-op form in the program,
    and a batch_norm behind every convolution."""
    fluid.reset_default_env()
    _CONV_BN_BUILDERS[name]()
    types = [op.type for op in
             fluid.default_main_program().global_block().ops]
    assert "fused_bn_add_act" not in types
    assert "conv_bn_add_act" not in types
    assert types.count("batch_norm") == types.count("conv2d") > 0
    for i, t in enumerate(types):
        if t == "conv2d":
            assert types[i + 1] == "batch_norm", (i, types[i:i + 3])


def test_conv_builders_take_no_fuse_option():
    for name, build in sorted(_CONV_BN_BUILDERS.items()):
        fluid.reset_default_env()
        with pytest.raises(TypeError, match="fuse_bn"):
            build(fuse_bn=True)


def test_vgg16_trains():
    _train(models.vgg16(), bs=2)


def test_transformer_trains():
    spec = models.transformer(models.TransformerConfig(
        src_vocab_size=64, trg_vocab_size=64, max_length=16,
        n_layer=2, n_head=4, d_model=32, d_inner=64,
    ))
    _train(spec, lr=0.003)


def test_transformer_decoder_is_causal():
    """Perturbing a FUTURE target token must not change logits at earlier
    decoder positions (guards the causal mask; a broken mask trains fine on
    a memorizable batch, so loss-based tests cannot catch it)."""
    spec = models.transformer(models.TransformerConfig(
        src_vocab_size=32, trg_vocab_size=32, max_length=8,
        n_layer=1, n_head=2, d_model=16, d_inner=32, dropout=0.0,
    ))
    logits = spec.extras["logits"]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = spec.synthetic_batch(2)
    (base,) = exe.run(feed=batch, fetch_list=[logits])
    batch2 = {k: v.copy() for k, v in batch.items()}
    batch2["trg_word"][:, 5] = (batch2["trg_word"][:, 5] % 30) + 1
    (pert,) = exe.run(feed=batch2, fetch_list=[logits])
    # positions 0..4 see only tokens < 5: must be bit-identical
    np.testing.assert_array_equal(base[:, :5, :], pert[:, :5, :])
    # position >= 5 must actually change (mask isn't just blocking everything)
    assert np.abs(base[:, 5:, :] - pert[:, 5:, :]).max() > 0


def test_transformer_fuse_qkv_parity():
    """fuse_qkv=True (one [d,3d] qkv matmul / [d,2d] kv matmul) must be
    numerically identical to the three separate projections: build both,
    stitch the unfused weights into the fused layout, compare logits."""
    kw = dict(src_vocab_size=32, trg_vocab_size=32, max_length=8,
              n_layer=1, n_head=2, d_model=16, d_inner=32, dropout=0.0)
    exe = fluid.Executor(fluid.CPUPlace())

    spec_u = models.transformer(models.TransformerConfig(fuse_qkv=False, **kw))
    exe.run(fluid.default_startup_program())
    batch = spec_u.synthetic_batch(2)
    (base,) = exe.run(feed=batch, fetch_list=[spec_u.extras["logits"]])
    scope_u = fluid.global_scope()

    main, startup = fluid.Program(), fluid.Program()
    scope_f = fluid.Scope()
    with fluid.scope_guard(scope_f), fluid.program_guard(main, startup):
        spec_f = models.transformer(models.TransformerConfig(fuse_qkv=True, **kw))
        exe.run(startup)
        # copy shared-name params; stitch q/k/v -> qkv and k/v -> kv
        for name in scope_f.local_var_names():
            if scope_u.has_var(name) and scope_u.find_var(name) is not None:
                scope_f.set_var(name, np.asarray(scope_u.find_var(name)))
        for name in list(scope_f.local_var_names()):
            for fused, parts in (("_qkv", "qkv"), ("_kv", "kv")):
                if name.endswith(f"{fused}_w"):
                    stem = name[: -len(f"{fused}_w")]
                    scope_f.set_var(name, np.concatenate(
                        [np.asarray(scope_u.find_var(f"{stem}_{p}_w"))
                         for p in parts], axis=1))
                elif name.endswith(f"{fused}_b"):
                    stem = name[: -len(f"{fused}_b")]
                    scope_f.set_var(name, np.concatenate(
                        [np.asarray(scope_u.find_var(f"{stem}_{p}_b"))
                         for p in parts], axis=0))
        (fused,) = exe.run(program=main, feed=batch,
                           fetch_list=[spec_f.extras["logits"]])
    np.testing.assert_allclose(base, fused, rtol=1e-5, atol=1e-5)


def test_transformer_masks_ignore_pad():
    """Loss is averaged over non-pad tokens only: doubling padding must not
    change a zero-dropout model's loss scale wildly (sanity on masking)."""
    spec = models.transformer(models.TransformerConfig(
        src_vocab_size=32, trg_vocab_size=32, max_length=8,
        n_layer=1, n_head=2, d_model=16, d_inner=32, dropout=0.0,
    ))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = spec.synthetic_batch(4)
    (tc,) = exe.run(feed=batch, fetch_list=[spec.metrics["token_count"]])
    lbl = batch["lbl_word"]
    assert int(np.ravel(tc)[0]) == int((lbl != 0).sum())


def test_transformer_fused_smooth_ce_parity():
    """fuse_smooth_ce=True (smoothing folded into softmax_with_cross_entropy,
    no [B,S,V] label tensors) must match the reference-shaped one_hot ->
    label_smooth -> soft-label CE chain: same loss and same gradients,
    checked over a short SGD trajectory with identical seeds."""
    kw = dict(src_vocab_size=48, trg_vocab_size=48, max_length=8,
              n_layer=1, n_head=2, d_model=16, d_inner=32, dropout=0.0,
              label_smooth_eps=0.1)

    def run(fused):
        fluid.reset_default_env()
        fluid.default_main_program().random_seed = 7
        fluid.default_startup_program().random_seed = 7
        spec = models.transformer(
            models.TransformerConfig(fuse_smooth_ce=fused, **kw))
        fluid.optimizer.SGDOptimizer(learning_rate=0.01).minimize(spec.loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        batch = spec.synthetic_batch(2, seed=3)
        return [
            float(np.ravel(np.asarray(exe.run(
                feed=batch, fetch_list=[spec.loss])[0]))[0])
            for _ in range(3)
        ]

    ref, fused = run(False), run(True)
    np.testing.assert_allclose(ref, fused, rtol=1e-5, atol=1e-6)
