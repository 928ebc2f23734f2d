"""ParallelExecutor parity: same model trained serially and SPMD over an
8-device virtual mesh must converge to matching losses (reference analogue:
unittests/parallel_executor_test_base.py, test_parallel_executor_mnist.py)."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.parallel import make_mesh


def _build_model(seed=0):
    fluid.default_main_program().random_seed = seed
    fluid.default_startup_program().random_seed = seed
    x = fluid.layers.data("x", [8], dtype="float32")
    label = fluid.layers.data("label", [1], dtype="float32")
    h = fluid.layers.fc(x, size=16, act="relu",
                        param_attr=fluid.ParamAttr(name="w1"),
                        bias_attr=fluid.ParamAttr(name="b1"))
    pred = fluid.layers.fc(h, size=1,
                           param_attr=fluid.ParamAttr(name="w2"),
                           bias_attr=fluid.ParamAttr(name="b2"))
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, label))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return loss


def _data(n=64):
    rng = np.random.RandomState(42)
    x = rng.rand(n, 8).astype(np.float32)
    w = rng.rand(8, 1).astype(np.float32)
    y = (x @ w + 0.1).astype(np.float32)
    return x, y


def test_mesh_shapes():
    m = make_mesh({"dp": 4, "tp": 2})
    assert m.num_devices == 8
    assert m.axis_size("dp") == 4 and m.axis_size("tp") == 2
    m2 = make_mesh({"dp": -1})
    assert m2.axis_size("dp") == 8


def test_parallel_matches_serial():
    x, y = _data()

    loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    serial_losses = [
        float(np.ravel(exe.run(feed={"x": x, "label": y}, fetch_list=[loss])[0])[0])
        for _ in range(5)
    ]
    serial_scope = fluid.global_scope()
    w_serial = np.asarray(serial_scope.find_var("w1"))

    # fresh identical program, trained through ParallelExecutor
    from paddle_tpu.core import framework, scope as scope_mod

    framework.switch_main_program(fluid.Program())
    framework.switch_startup_program(fluid.Program())
    scope_mod._current_scope = scope_mod.Scope()

    loss2 = _build_model()
    exe2 = fluid.Executor(fluid.CPUPlace())
    exe2.run(fluid.default_startup_program())
    pe = fluid.ParallelExecutor(loss_name=loss2.name, mesh=make_mesh({"dp": 8}))
    par_losses = [
        float(np.ravel(pe.run(fetch_list=[loss2], feed={"x": x, "label": y})[0])[0])
        for _ in range(5)
    ]
    w_par = np.asarray(fluid.global_scope().find_var("w1"))

    np.testing.assert_allclose(serial_losses, par_losses, rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(w_serial, w_par, rtol=2e-4, atol=1e-5)


def test_parallel_list_of_feed_dicts():
    x, y = _data(16)
    loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    pe = fluid.ParallelExecutor(loss_name=loss.name, mesh=make_mesh({"dp": 8}))
    feeds = [
        {"x": x[i * 2:(i + 1) * 2], "label": y[i * 2:(i + 1) * 2]} for i in range(8)
    ]
    (lv,) = pe.run(fetch_list=[loss], feed=feeds)
    assert np.isfinite(lv)


def test_tensor_parallel_sharded_param():
    """Variable.sharding routes a weight onto the tp axis; program still
    compiles and matches the replicated answer."""
    x, y = _data(32)
    loss = _build_model()
    prog = fluid.default_main_program()
    prog.global_block().var("w1").sharding = [None, "tp"]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    pe = fluid.ParallelExecutor(loss_name=loss.name, mesh=make_mesh({"dp": 2, "tp": 4}))
    losses = [
        float(np.ravel(pe.run(fetch_list=[loss], feed={"x": x, "label": y})[0])[0])
        for _ in range(3)
    ]
    assert losses[-1] < losses[0]


def test_indivisible_batch_raises_clear_error():
    """A 10-row batch over an 8-way dp mesh must fail with the framework's
    even-shard message, not a raw pjit sharding ValueError (reference
    analogue: data_balance redistributing uneven tail batches,
    details/data_balance_op_handle.cc)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    x = layers.data("x", [4], dtype="float32")
    y = layers.data("y", [1], dtype="float32")
    pred = layers.fc(x, size=1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    fluid.optimizer.SGDOptimizer(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name)
    rng = np.random.RandomState(0)
    with pytest.raises(ValueError, match="not divisible by its dim-0 mesh axes"):
        pe.run(feed={"x": rng.randn(10, 4).astype("float32"),
                     "y": rng.randn(10, 1).astype("float32")},
               fetch_list=[loss.name])


def test_pe_run_keeps_a_tp_sharded_weight_sharded_across_steps():
    """Under a dp x tp mesh a tensor-parallel weight comes back from every
    step of ParallelExecutor.run as it went in, committed to its sharding
    (so the next step stages nothing), and the trajectory is the serial
    executor's on the same program, batches and seed."""
    import jax
    from jax.sharding import PartitionSpec

    rng = np.random.RandomState(4)
    feeds = [{"x": rng.randn(4, 16).astype("float32"),
              "y": rng.randn(4, 1).astype("float32")} for _ in range(3)]

    def build():
        fluid.reset_default_env()
        fluid.default_main_program().random_seed = 11
        fluid.default_startup_program().random_seed = 11
        from paddle_tpu import layers
        x = layers.data("x", [16], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        h = layers.fc(x, size=8, act="relu")
        pred = layers.fc(h, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
        prog = fluid.default_main_program()
        prog.global_block().var("fc_0.w_0").sharding = [None, "tp"]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        return exe, loss

    exe, loss = build()
    want = []
    for i in range(6):
        (lv,) = exe.run(feed=feeds[i % 3], fetch_list=[loss])
        want.append((np.asarray(lv), np.asarray(
            fluid.global_scope().find_var("fc_0.w_0"))))

    _, loss = build()
    mesh = make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    pe = fluid.ParallelExecutor(loss_name=loss.name, mesh=mesh)
    for i in range(6):
        (lv,) = pe.run(feed=feeds[i % 3], fetch_list=[loss.name])
        w = fluid.global_scope().find_var("fc_0.w_0")
        assert w.committed and w.sharding.mesh == mesh.mesh
        assert w.sharding.spec == PartitionSpec(None, "tp")
        assert w.addressable_shards[0].data.shape == (16, 4)
        np.testing.assert_allclose(np.asarray(lv), want[i][0],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(w), want[i][1],
                                   rtol=1e-5, atol=1e-6)


def test_parallel_conv_fused_bn_matches_serial():
    """The flagship conv path under SPMD: conv + fused_bn_add_act trained
    data-parallel over the 8-device mesh must match the serial trajectory.
    BN statistics reduce over the GLOBAL batch automatically (jnp.mean of
    a dp-sharded tensor — XLA inserts the cross-shard reduction), i.e.
    sync-BN semantics, so losses and weights agree with one-device runs."""
    def build(seed=5):
        fluid.default_main_program().random_seed = seed
        fluid.default_startup_program().random_seed = seed
        img = fluid.layers.data("img", [3, 8, 8], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="int64")
        conv = fluid.layers.conv2d(img, 4, 3, padding=1, bias_attr=False,
                                   param_attr=fluid.ParamAttr(name="pc_w"))
        h = fluid.layers.fused_bn_add_act(
            conv, None, act="relu",
            param_attr=fluid.ParamAttr(name="pc_scale"),
            bias_attr=fluid.ParamAttr(name="pc_bias"),
            moving_mean_name="pc_mean", moving_variance_name="pc_var")
        pool = fluid.layers.pool2d(h, pool_size=8, pool_type="avg")
        pred = fluid.layers.fc(pool, size=3, act="softmax",
                               param_attr=fluid.ParamAttr(name="pc_fc"))
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, y))
        fluid.optimizer.MomentumOptimizer(0.1, momentum=0.9).minimize(loss)
        return loss

    rng = np.random.RandomState(2)
    xv = rng.randn(16, 3, 8, 8).astype("float32")
    yv = rng.randint(0, 3, size=(16, 1)).astype("int64")

    fluid.reset_default_env()
    loss = build()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    serial = [
        float(np.ravel(exe.run(feed={"img": xv, "y": yv},
                               fetch_list=[loss])[0])[0])
        for _ in range(4)
    ]
    w_serial = np.asarray(fluid.global_scope().find_var("pc_w")).copy()
    mean_serial = np.asarray(fluid.global_scope().find_var("pc_mean")).copy()

    fluid.reset_default_env()
    loss2 = build()
    exe2 = fluid.Executor(fluid.CPUPlace())
    exe2.run(fluid.default_startup_program())
    pe = fluid.ParallelExecutor(loss_name=loss2.name,
                                mesh=make_mesh({"dp": 8}))
    par = [
        float(np.ravel(pe.run(fetch_list=[loss2],
                              feed={"img": xv, "y": yv})[0])[0])
        for _ in range(4)
    ]
    np.testing.assert_allclose(serial, par, rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(fluid.global_scope().find_var("pc_w")), w_serial,
        rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(fluid.global_scope().find_var("pc_mean")), mean_serial,
        rtol=2e-4, atol=1e-6)
