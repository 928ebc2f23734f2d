"""InferenceTranspiler conv+batch_norm fold
(reference: transpiler/inference_transpiler.py:300 _fuse_batch_norm,
test analogue: the reference exercises the fold through
test_inference_model_io / book image-classification inference runs).

Trains a small convnet a few steps so the BN moving statistics are
non-trivial, then checks the folded inference program (a) no longer
contains batch_norm ops, (b) produces the same outputs, and (c) keeps
residual-style multi-consumer conv outputs unfused."""

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers


def _train_convnet(steps=3, with_bias=False, branchy=False):
    x = layers.data("x", [3, 8, 8], dtype="float32")
    y = layers.data("y", [1], dtype="int64")
    bias_attr = True if with_bias else False
    c1 = layers.conv2d(x, num_filters=4, filter_size=3, padding=1,
                       bias_attr=bias_attr)
    b1 = layers.batch_norm(c1)
    h = layers.relu(b1)
    if branchy:
        # conv output consumed by BN *and* a residual add: must not fold
        c2 = layers.conv2d(h, num_filters=4, filter_size=3, padding=1,
                           bias_attr=False)
        b2 = layers.batch_norm(c2)
        h = layers.elementwise_add(layers.relu(b2), c2)
    pool = layers.pool2d(h, pool_size=8, pool_type="avg")
    pred = layers.fc(pool, size=3, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, y))
    fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(7)
    xv = rng.randn(4, 3, 8, 8).astype("float32")
    yv = rng.randint(0, 3, size=(4, 1)).astype("int64")
    for _ in range(steps):
        exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])
    return exe, pred, xv


def _bn_count(program):
    return sum(op.type == "batch_norm" for op in program.global_block().ops)


def _run_fold_case(with_bias):
    exe, pred, xv = _train_convnet(with_bias=with_bias)
    infer = fluid.io.get_inference_program([pred])
    (ref,) = exe.run(program=infer, feed={"x": xv}, fetch_list=[pred])

    assert _bn_count(infer) == 1
    t = fluid.InferenceTranspiler()
    t.transpile(infer, fluid.CPUPlace())
    assert _bn_count(infer) == 0
    # the fold leaves one channel-bias add where the bn used to be (the fc
    # layer contributes its own bias add; only the conv-side one matters)
    conv_out = next(op for op in infer.global_block().ops
                    if op.type == "conv2d").output("Output")[0]
    adds = [op for op in infer.global_block().ops
            if op.type == "elementwise_add" and conv_out in op.input("X")]
    assert len(adds) == 1 and adds[0].attr("axis") == 1

    (out,) = exe.run(program=infer, feed={"x": xv}, fetch_list=[pred])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_fold_conv_without_bias():
    _run_fold_case(with_bias=False)


def test_fold_conv_with_bias():
    _run_fold_case(with_bias=True)


def test_multi_consumer_conv_not_folded():
    exe, pred, xv = _train_convnet(branchy=True)
    infer = fluid.io.get_inference_program([pred])
    (ref,) = exe.run(program=infer, feed={"x": xv}, fetch_list=[pred])

    assert _bn_count(infer) == 2
    fluid.InferenceTranspiler().transpile(infer, fluid.CPUPlace())
    # first conv folds; the residual conv (two consumers) must survive
    assert _bn_count(infer) == 1

    (out,) = exe.run(program=infer, feed={"x": xv}, fetch_list=[pred])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_unused_bn_params_pruned_from_desc():
    exe, pred, xv = _train_convnet()
    infer = fluid.io.get_inference_program([pred])
    block = infer.global_block()
    bn_op = next(op for op in block.ops if op.type == "batch_norm")
    stat_vars = [bn_op.input("Scale")[0], bn_op.input("Mean")[0],
                 bn_op.input("Variance")[0]]
    for name in stat_vars:
        assert block.desc.has_var(name)
    fluid.InferenceTranspiler().transpile(infer, fluid.CPUPlace())
    for name in stat_vars:
        assert not block.desc.has_var(name)


def test_protected_fetch_target_not_folded():
    """A conv output that is itself a fetch target must keep its values:
    passing it via protected_vars disqualifies the fold."""
    exe, pred, xv = _train_convnet()
    infer = fluid.io.get_inference_program([pred])
    conv_out = next(op for op in infer.global_block().ops
                    if op.type == "conv2d").output("Output")[0]
    fluid.InferenceTranspiler().transpile(
        infer, fluid.CPUPlace(), protected_vars=[conv_out])
    assert _bn_count(infer) == 1  # fold skipped


def test_analysis_predictor_applies_fold(tmp_path):
    """AnalysisPredictor with enable_ir_optim folds BN at build time and
    still matches the unoptimized NativePredictor (reference analogue:
    AnalysisPredictor::OptimizeInferenceProgram)."""
    from paddle_tpu.inference import (AnalysisConfig, NativeConfig,
                                      create_paddle_predictor)

    exe, pred, xv = _train_convnet()
    d = str(tmp_path / "model")
    fluid.io.save_inference_model(d, ["x"], [pred], exe)

    native = create_paddle_predictor(NativeConfig(model_dir=d))
    (ref,) = native.run_dict({"x": xv})
    assert _bn_count(native.program) == 1

    analysis = create_paddle_predictor(AnalysisConfig(model_dir=d))
    assert _bn_count(analysis.program) == 0
    (out,) = analysis.run_dict({"x": xv})
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_originals_survive_fold_for_live_training():
    """The reference's documented usage: transpile an inference clone()
    against the SHARED global scope while the training program is still
    live (reference _fuse_param writes '<name>_fuse_bn' copies,
    inference_transpiler.py:435).  The original Filter/Bias values must
    survive untouched so continued training and save_persistables see the
    true weights."""
    exe, pred, xv = _train_convnet(with_bias=True)
    infer = fluid.io.get_inference_program([pred])
    block = infer.global_block()
    conv = next(op for op in block.ops if op.type == "conv2d")
    w_name = conv.input("Filter")[0]
    scope = fluid.global_scope()
    w_before = np.array(np.asarray(scope.find_var(w_name)))

    fluid.InferenceTranspiler().transpile(infer, fluid.CPUPlace())

    # conv now reads a renamed persistable copy; the original is untouched
    new_w = conv.input("Filter")[0]
    assert new_w == w_name + "_fuse_bn"
    assert block.desc.has_var(new_w) and block.desc.vars[new_w].persistable
    np.testing.assert_array_equal(
        np.asarray(scope.find_var(w_name)), w_before)
    assert not np.array_equal(np.asarray(scope.find_var(new_w)), w_before)

    # training on the ORIGINAL program still runs and moves the true weights
    y = np.zeros((4, 1), dtype="int64")
    exe.run(feed={"x": xv, "y": y},
            fetch_list=[fluid.default_main_program().global_block().ops[-1]
                        .output("ParamOut")[0]])


def test_weight_shared_filter_folds_safely():
    """Two convs sharing one Filter parameter, each followed by its own BN:
    with copy-based folding each conv gets its OWN '<w>_fuse_bn' copy
    (unique-suffixed on collision), the shared original is never scaled,
    and both folds run."""
    x = layers.data("x", [3, 8, 8], dtype="float32")
    y = layers.data("y", [1], dtype="int64")
    shared = fluid.ParamAttr(name="shared_w")
    c1 = layers.conv2d(x, num_filters=4, filter_size=3, padding=1,
                       bias_attr=False, param_attr=shared)
    c2 = layers.conv2d(x, num_filters=4, filter_size=3, padding=1,
                       bias_attr=False, param_attr=shared)
    h = layers.elementwise_add(layers.batch_norm(c1), layers.batch_norm(c2))
    pool = layers.pool2d(h, pool_size=8, pool_type="avg")
    pred = layers.fc(pool, size=3, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, y))
    fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(9)
    xv = rng.randn(4, 3, 8, 8).astype("float32")
    yv = rng.randint(0, 3, size=(4, 1)).astype("int64")
    exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])

    infer = fluid.io.get_inference_program([pred])
    (ref,) = exe.run(program=infer, feed={"x": xv}, fetch_list=[pred])
    shared_before = np.array(
        np.asarray(fluid.global_scope().find_var("shared_w")))
    fluid.InferenceTranspiler().transpile(infer, fluid.CPUPlace())
    assert _bn_count(infer) == 0  # both fold, each into its own copy
    convs = [op for op in infer.global_block().ops if op.type == "conv2d"]
    names = sorted(op.input("Filter")[0] for op in convs)
    assert names == ["shared_w_fuse_bn", "shared_w_fuse_bn_2"]
    np.testing.assert_array_equal(
        np.asarray(fluid.global_scope().find_var("shared_w")), shared_before)
    (out,) = exe.run(program=infer, feed={"x": xv}, fetch_list=[pred])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_fused_bn_add_act_folds():
    """The default-built conv stacks emit fused_bn_add_act (Z-free); the
    transpiler must fold those exactly like batch_norm, re-emitting the
    activation as a standalone relu after the folded bias add."""
    fluid.reset_default_env()
    x = layers.data("x", [3, 8, 8], dtype="float32")
    y = layers.data("y", [1], dtype="int64")
    conv = layers.conv2d(x, num_filters=4, filter_size=3, padding=1,
                         bias_attr=False)
    h = layers.fused_bn_add_act(conv, None, act="relu")
    pool = layers.pool2d(h, pool_size=8, pool_type="avg")
    pred = layers.fc(pool, size=3, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, y))
    fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(4)
    xv = rng.randn(4, 3, 8, 8).astype("float32")
    yv = rng.randint(0, 3, size=(4, 1)).astype("int64")
    for _ in range(3):
        exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])

    infer = fluid.io.get_inference_program([pred])
    (ref,) = exe.run(program=infer, feed={"x": xv}, fetch_list=[pred])
    assert sum(op.type == "fused_bn_add_act"
               for op in infer.global_block().ops) == 1
    fluid.InferenceTranspiler().transpile(infer, fluid.CPUPlace())
    ops = [op.type for op in infer.global_block().ops]
    assert "fused_bn_add_act" not in ops and "batch_norm" not in ops
    # folded shape: conv -> add(folded bias) -> relu
    ci = ops.index("conv2d")
    assert ops[ci + 1] == "elementwise_add" and ops[ci + 2] == "relu"
    (out,) = exe.run(program=infer, feed={"x": xv}, fetch_list=[pred])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_fused_bn_with_residual_not_folded_but_test_mode():
    """A fused op WITH a residual input cannot fold (BN applies before the
    add), but transpile must still flip it to test mode."""
    fluid.reset_default_env()
    x = layers.data("x", [4, 8, 8], dtype="float32")
    y = layers.data("y", [1], dtype="int64")
    conv = layers.conv2d(x, num_filters=4, filter_size=3, padding=1,
                         bias_attr=False)
    h = layers.fused_bn_add_act(conv, x, act="relu")
    pool = layers.pool2d(h, pool_size=8, pool_type="avg")
    pred = layers.fc(pool, size=3, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, y))
    fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xv = np.random.RandomState(6).randn(4, 4, 8, 8).astype("float32")
    yv = np.zeros((4, 1), dtype="int64")
    exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])

    infer = fluid.io.get_inference_program([pred])
    (ref,) = exe.run(program=infer, feed={"x": xv}, fetch_list=[pred])
    fluid.InferenceTranspiler().transpile(infer, fluid.CPUPlace())
    fused = [op for op in infer.global_block().ops
             if op.type == "fused_bn_add_act"]
    assert len(fused) == 1 and fused[0].attr("is_test") is True
    (out,) = exe.run(program=infer, feed={"x": xv}, fetch_list=[pred])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_resnet_build_transpiles_to_foldless_graph():
    """models.resnet emits conv2d -> batch_norm and nothing else, the
    shape the fold pattern-matches: a trained ResNet loses every
    batch_norm under the transpiler and predicts the same."""
    from paddle_tpu import models

    fluid.reset_default_env()
    spec = models.resnet_cifar10(depth=8, class_num=4)
    fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(spec.loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    b = spec.synthetic_batch(4, seed=0)
    exe.run(feed=b, fetch_list=[spec.loss])

    infer = fluid.io.get_inference_program([spec.extras["predict"]])
    (ref,) = exe.run(program=infer, feed={"image": b["image"]},
                     fetch_list=[spec.extras["predict"]])
    types = [op.type for op in infer.global_block().ops]
    assert types.count("batch_norm") == types.count("conv2d") > 0
    fluid.InferenceTranspiler().transpile(infer, fluid.CPUPlace())
    assert not any(op.type == "batch_norm"
                   for op in infer.global_block().ops)
    (out,) = exe.run(program=infer, feed={"image": b["image"]},
                     fetch_list=[spec.extras["predict"]])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
