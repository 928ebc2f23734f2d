"""fused_bn_add_act: the one-op BN + residual + activation with a
recompute-tagged backward (ops/nn_ops.py _fused_bn_add_act; replaces the
reference's batch_norm_op.cu.cc + elementwise_add + relu dispatches).

The contract is NUMERICAL IDENTITY with the unfused chain — same losses,
same trained weights, same moving statistics — with the storage trade
happening purely inside jax.checkpoint."""

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers


def _train(fused: bool, steps=4, with_residual=True, seed=11):
    fluid.reset_default_env()
    fluid.default_main_program().random_seed = seed
    fluid.default_startup_program().random_seed = seed
    x = layers.data("x", [4, 8, 8], dtype="float32")
    y = layers.data("y", [1], dtype="int64")
    conv = layers.conv2d(x, num_filters=4, filter_size=3, padding=1,
                         bias_attr=False,
                         param_attr=fluid.ParamAttr(name="w_conv"))
    res = x if with_residual else None
    if fused:
        h = layers.fused_bn_add_act(
            conv, res, act="relu",
            param_attr=fluid.ParamAttr(name="bn_scale"),
            bias_attr=fluid.ParamAttr(name="bn_bias"),
            moving_mean_name="bn_mean", moving_variance_name="bn_var")
    else:
        b = layers.batch_norm(conv, act=None,
                              param_attr=fluid.ParamAttr(name="bn_scale"),
                              bias_attr=fluid.ParamAttr(name="bn_bias"),
                              moving_mean_name="bn_mean",
                              moving_variance_name="bn_var")
        h = layers.relu(layers.elementwise_add(b, res) if res is not None
                        else b)
    pool = layers.pool2d(h, pool_size=8, pool_type="avg")
    pred = layers.fc(pool, size=3, act="softmax",
                     param_attr=fluid.ParamAttr(name="w_fc"))
    loss = layers.mean(layers.cross_entropy(pred, y))
    fluid.optimizer.MomentumOptimizer(
        learning_rate=0.1, momentum=0.9).minimize(loss)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(5)
    xv = rng.randn(8, 4, 8, 8).astype("float32")
    yv = rng.randint(0, 3, size=(8, 1)).astype("int64")
    losses = [
        float(np.ravel(np.asarray(
            exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])[0]))[0])
        for _ in range(steps)
    ]
    scope = fluid.global_scope()
    state = {
        n: np.array(np.asarray(scope.find_var(n)))
        for n in ("w_conv", "bn_scale", "bn_bias", "bn_mean", "bn_var",
                  "w_fc")
    }
    return losses, state, (exe, pred, xv)


def _assert_matches(with_residual):
    ref_losses, ref_state, _ = _train(False, with_residual=with_residual)
    fus_losses, fus_state, _ = _train(True, with_residual=with_residual)
    np.testing.assert_allclose(ref_losses, fus_losses, rtol=1e-5, atol=1e-6)
    assert ref_losses[-1] < ref_losses[0]  # training actually moved
    for n in ref_state:
        np.testing.assert_allclose(
            ref_state[n], fus_state[n], rtol=1e-5, atol=1e-6,
            err_msg=f"state {n} diverged between fused and unfused")


def test_fused_matches_unfused_with_residual():
    _assert_matches(with_residual=True)


def test_fused_matches_unfused_without_residual():
    _assert_matches(with_residual=False)


def test_fused_op_is_recompute_tagged():
    fluid.reset_default_env()
    x = layers.data("x", [4, 8, 8], dtype="float32")
    layers.fused_bn_add_act(layers.conv2d(x, 4, 3, padding=1), x)
    ops = fluid.default_main_program().global_block().ops
    fused = [op for op in ops if op.type == "fused_bn_add_act"]
    assert len(fused) == 1
    assert fused[0].attr("@recompute@") is True


def test_fused_test_mode_uses_moving_stats():
    """for_test clone: normalize with the moving stats, no stat update —
    exercised through the inference-program path like batch_norm."""
    _, _, (exe, pred, xv) = _train(True, steps=3)
    infer = fluid.io.get_inference_program([pred])
    mean_before = np.array(
        np.asarray(fluid.global_scope().find_var("bn_mean")))
    (o1,) = exe.run(program=infer, feed={"x": xv}, fetch_list=[pred])
    (o2,) = exe.run(program=infer, feed={"x": xv}, fetch_list=[pred])
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-6, atol=1e-7)  # deterministic
    np.testing.assert_array_equal(
        np.asarray(fluid.global_scope().find_var("bn_mean")), mean_before)


def test_fused_bn_fuzz_parity_vs_composed_ops():
    """Seeded fuzz: random shapes / eps / momentum / residual presence /
    act, fwd + one SGD step, fused op vs the composed batch_norm +
    elementwise_add + relu chain.  12 cases (20 until PR 54: 30 s of
    the driver's run for 80 small compiles; every option's every value is
    still drawn)."""
    rng = np.random.RandomState(123)
    for case in range(12):
        c = int(rng.choice([1, 3, 8]))
        h = int(rng.choice([4, 7, 8]))
        bs = int(rng.choice([2, 5, 8]))
        eps = float(rng.choice([1e-5, 1e-3]))
        momentum = float(rng.choice([0.9, 0.99]))
        with_res = bool(rng.randint(2))
        act = "relu" if rng.randint(2) else None
        xv = rng.randn(bs, c, h, h).astype("float32")

        outs = {}
        for fused in (True, False):
            fluid.reset_default_env()
            fluid.default_main_program().random_seed = 10 + case
            fluid.default_startup_program().random_seed = 10 + case
            x = layers.data("x", [c, h, h], dtype="float32")
            if fused:
                y = layers.fused_bn_add_act(
                    x, x if with_res else None, act=act,
                    epsilon=eps, momentum=momentum,
                    param_attr=fluid.ParamAttr(name="fz_s"),
                    bias_attr=fluid.ParamAttr(name="fz_b"),
                    moving_mean_name="fz_m", moving_variance_name="fz_v")
            else:
                b = layers.batch_norm(
                    x, act=None, epsilon=eps, momentum=momentum,
                    param_attr=fluid.ParamAttr(name="fz_s"),
                    bias_attr=fluid.ParamAttr(name="fz_b"),
                    moving_mean_name="fz_m", moving_variance_name="fz_v")
                y = layers.elementwise_add(b, x) if with_res else b
                if act:
                    y = layers.relu(y)
            loss = layers.reduce_mean(layers.square(y))
            fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            (yv,) = exe.run(feed={"x": xv}, fetch_list=[y])
            outs[fused] = (
                np.asarray(yv),
                np.array(np.asarray(fluid.global_scope().find_var("fz_s"))),
                np.array(np.asarray(fluid.global_scope().find_var("fz_m"))),
            )
        tag = (f"case {case}: c={c} h={h} bs={bs} eps={eps} "
               f"mom={momentum} res={with_res} act={act}")
        for a, b in zip(outs[True], outs[False]):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                       err_msg=tag)


def test_fused_bn_rejects_mismatched_residual_shape():
    """ADVICE r4: a broadcastable-but-wrong Z (e.g. [N,C,1,1]) must fail
    shape inference, not silently broadcast inside the lowering."""
    import pytest

    fluid.reset_default_env()
    x = layers.data("x", [4, 8, 8], dtype="float32")
    conv = layers.conv2d(x, num_filters=4, filter_size=3, padding=1)
    bad_z = layers.pool2d(x, pool_size=8, pool_type="avg")  # [N,4,1,1]
    with pytest.raises(ValueError, match="residual Z shape"):
        layers.fused_bn_add_act(conv, bad_z, act="relu")
