"""Executor end-to-end: lowering, feeds/fetches, persistable state, RNG.
(reference analogue: book tests + executor tests)."""

import numpy as np
import pytest

import paddle_tpu as fluid


def test_fill_and_fetch():
    out = fluid.layers.fill_constant([2, 3], "float32", 7.0)
    exe = fluid.Executor(fluid.CPUPlace())
    (res,) = exe.run(fetch_list=[out])
    np.testing.assert_allclose(res, np.full((2, 3), 7.0, np.float32))


def test_feed_forward_fc():
    x = fluid.layers.data("x", [4], dtype="float32")
    y = fluid.layers.fc(x, size=3)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xv = np.random.RandomState(0).rand(5, 4).astype(np.float32)
    (res,) = exe.run(feed={"x": xv}, fetch_list=[y])
    assert res.shape == (5, 3)


def test_startup_program_initializes_params():
    x = fluid.layers.data("x", [4], dtype="float32")
    fluid.layers.fc(x, size=3, param_attr=fluid.ParamAttr(name="fcw"))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    w = fluid.global_scope().find_var("fcw")
    assert w is not None and np.asarray(w).shape == (4, 3)


def test_uninitialized_param_raises():
    x = fluid.layers.data("x", [4], dtype="float32")
    y = fluid.layers.fc(x, size=3)
    exe = fluid.Executor(fluid.CPUPlace())
    with pytest.raises(RuntimeError, match="not initialized"):
        exe.run(feed={"x": np.zeros((2, 4), np.float32)}, fetch_list=[y])


def test_sgd_training_step_decreases_loss():
    np.random.seed(0)
    x = fluid.layers.data("x", [4], dtype="float32")
    label = fluid.layers.data("label", [1], dtype="float32")
    pred = fluid.layers.fc(x, size=1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, label))
    sgd = fluid.optimizer.SGD(learning_rate=0.05)
    sgd.minimize(loss)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xv = np.random.rand(16, 4).astype(np.float32)
    yv = (xv @ np.array([[1.0], [2.0], [-1.0], [0.5]], np.float32) + 0.3).astype(np.float32)
    losses = []
    for _ in range(30):
        (lv,) = exe.run(feed={"x": xv, "label": yv}, fetch_list=[loss])
        losses.append(float(np.ravel(lv)[0]))
    assert losses[-1] < losses[0] * 0.3, losses[:3] + losses[-3:]


def test_rng_stream_advances_between_runs():
    out = fluid.layers.ops.uniform_random([4], min=0.0, max=1.0)
    exe = fluid.Executor(fluid.CPUPlace())
    (a,) = exe.run(fetch_list=[out])
    (b,) = exe.run(fetch_list=[out])
    assert not np.allclose(a, b)


def test_dropout_train_vs_test():
    x = fluid.layers.data("x", [100], dtype="float32")
    out = fluid.layers.dropout(x, dropout_prob=0.5)
    prog = fluid.default_main_program()
    test_prog = prog.clone(for_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    xv = np.ones((2, 100), np.float32)
    (train_out,) = exe.run(prog, feed={"x": xv}, fetch_list=[out])
    assert (train_out == 0).any()
    (test_out,) = exe.run(test_prog, feed={"x": xv}, fetch_list=[out])
    np.testing.assert_allclose(test_out, xv * 0.5, rtol=1e-6)


def test_fetch_param_value():
    w = fluid.layers.create_parameter([3], "float32", name="pw",
                                      default_initializer=fluid.initializer.Constant(2.0))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    (res,) = exe.run(fetch_list=["pw"])
    np.testing.assert_allclose(res, [2.0, 2.0, 2.0])


def test_in_place_attr_mutation_recompiles():
    """VERDICT round-1 weak #5: the program cache must key on content, not
    object identity — an in-place attr edit has to trigger recompilation."""
    import paddle_tpu.layers as layers

    x = layers.data("x", [4], dtype="float32")
    out = layers.scale(x, scale=2.0)
    exe = fluid.Executor(fluid.CPUPlace())
    xv = np.ones((2, 4), dtype="float32")
    (r1,) = exe.run(feed={"x": xv}, fetch_list=[out])
    np.testing.assert_allclose(r1, 2 * xv)

    # mutate the scale op's attr in place (op count unchanged)
    block = fluid.default_main_program().global_block()
    for op in block.ops:
        if op.type == "scale":
            op._set_attr("scale", 5.0)
    (r2,) = exe.run(feed={"x": xv}, fetch_list=[out])
    np.testing.assert_allclose(r2, 5 * xv)


def test_amp_bf16_parity_and_dtype():
    """AMP: matmul computes in bf16 (output rounds through bf16) but params,
    state, and the rest of the graph stay fp32; loss stays within bf16
    tolerance of the fp32 run."""
    import paddle_tpu.layers as layers

    def build_and_run():
        from paddle_tpu.core import framework, scope as scope_mod
        framework.switch_main_program(fluid.Program())
        framework.switch_startup_program(fluid.Program())
        scope_mod._current_scope = scope_mod.Scope()
        x = layers.data("x", [16], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        pred = layers.fc(layers.fc(x, size=32, act="relu"), size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(3)
        xv = rng.randn(8, 16).astype("float32")
        yv = rng.randn(8, 1).astype("float32")
        losses = [
            float(np.ravel(np.asarray(
                exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])[0]
            ))[0])
            for _ in range(5)
        ]
        params = fluid.default_main_program().global_block().all_parameters()
        pval = np.asarray(fluid.global_scope().find_var(params[0].name))
        return losses, pval

    ref_losses, ref_p = build_and_run()
    fluid.enable_amp("bfloat16")
    try:
        amp_losses, amp_p = build_and_run()
    finally:
        fluid.disable_amp()

    assert amp_p.dtype == np.float32  # master weights stay fp32
    # bf16 has ~3 decimal digits; training for 5 steps stays close
    np.testing.assert_allclose(amp_losses, ref_losses, rtol=0.05, atol=0.05)
    assert amp_losses[-1] < amp_losses[0]  # still learns


def test_amp_keep_output_conv_bn_parity():
    """Aggressive AMP (keep_output=True): activations stay bf16 through the
    conv->bn->relu chain, BN stats accumulate fp32, master weights fp32;
    training stays close to the fp32 run."""
    import paddle_tpu.layers as layers

    def build_and_run():
        fluid.reset_default_env()
        img = layers.data("img", [3, 8, 8], dtype="float32")
        y = layers.data("y", [1], dtype="int64")
        c = layers.conv2d(img, num_filters=8, filter_size=3, padding=1)
        b = layers.batch_norm(c, act="relu")
        p = layers.pool2d(b, pool_size=8, pool_type="avg")
        pred = layers.fc(p, size=4, act="softmax")
        loss = layers.mean(layers.cross_entropy(pred, y))
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(3)
        xv = rng.rand(8, 3, 8, 8).astype("float32")
        yv = rng.randint(0, 4, (8, 1)).astype("int64")
        losses = [
            float(np.ravel(np.asarray(
                exe.run(feed={"img": xv, "y": yv}, fetch_list=[loss])[0]
            ))[0])
            for _ in range(6)
        ]
        params = fluid.default_main_program().global_block().all_parameters()
        pvals = {
            p.name: np.asarray(fluid.global_scope().find_var(p.name))
            for p in params
        }
        (act_v,) = exe.run(feed={"img": xv, "y": yv}, fetch_list=[b],
                           return_numpy=False)
        return losses, pvals, str(np.asarray(act_v).dtype)

    ref_losses, ref_p, ref_dt = build_and_run()
    assert ref_dt == "float32"
    fluid.enable_amp("bfloat16", keep_output=True)
    try:
        amp_losses, amp_p, amp_dt = build_and_run()
    finally:
        fluid.disable_amp()

    # the batch_norm output really is half-width — keep_output is not a
    # silent no-op (the conv bias add must not re-widen the chain)
    assert amp_dt == "bfloat16"
    for name, v in amp_p.items():
        assert v.dtype == np.float32, name  # master weights stay fp32
    np.testing.assert_allclose(amp_losses, ref_losses, rtol=0.08, atol=0.08)
    assert amp_losses[-1] < amp_losses[0]


def test_amp_keep_output_layer_norm_parity():
    """keep_output AMP through the matmul->layer_norm chain (the
    transformer block pattern): fp32 stats, bf16 activation writes."""
    import paddle_tpu.layers as layers

    def build_and_run():
        fluid.reset_default_env()
        x = layers.data("x", [16], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        h = layers.fc(x, size=32)
        h = layers.layer_norm(h)
        h = layers.fc(h, size=16, act="relu")
        pred = layers.fc(h, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(learning_rate=0.05).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(11)
        xv = rng.randn(8, 16).astype("float32")
        yv = rng.randn(8, 1).astype("float32")
        losses = [
            float(np.ravel(np.asarray(
                exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])[0]
            ))[0])
            for _ in range(6)
        ]
        (hn,) = exe.run(feed={"x": xv, "y": yv}, fetch_list=[h],
                        return_numpy=False)
        return losses, str(np.asarray(hn).dtype)

    ref, ref_dt = build_and_run()
    assert ref_dt == "float32"
    fluid.enable_amp("bfloat16", keep_output=True)
    try:
        got, got_dt = build_and_run()
    finally:
        fluid.disable_amp()
    assert got_dt == "bfloat16"  # the post-norm activation stays half-width
    np.testing.assert_allclose(got, ref, rtol=0.08, atol=0.08)
    assert got[-1] < got[0]


def test_run_advances_scheduler_and_dropout_key_over_six_steps():
    """Executor.run carries every persistable (the scheduler's step count
    among them) and the PRNG key from one step to the next: six steps
    against SGD stepped by hand in numpy, with the learning rate the decay
    schedule names for each step and the dropout mask that step drew."""
    import paddle_tpu.layers as layers

    x = fluid.layers.data("x", [8], dtype="float32")
    y = fluid.layers.data("y", [1], dtype="float32")
    h = layers.fc(x, size=16, act="relu",
                  param_attr=fluid.ParamAttr(name="sd_w1"),
                  bias_attr=fluid.ParamAttr(name="sd_b1"))
    d = layers.dropout(h, dropout_prob=0.3)
    pred = layers.fc(d, size=1,
                     param_attr=fluid.ParamAttr(name="sd_w2"),
                     bias_attr=fluid.ParamAttr(name="sd_b2"))
    loss = layers.mean(layers.square_error_cost(pred, y))
    lr = fluid.layers.exponential_decay(
        learning_rate=0.1, decay_steps=2, decay_rate=0.5, staircase=True)
    fluid.optimizer.SGD(learning_rate=lr).minimize(loss)

    rng = np.random.RandomState(11)
    feeds = [{"x": rng.rand(4, 8).astype(np.float32),
              "y": rng.rand(4, 1).astype(np.float32)} for _ in range(2)]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    names = ("sd_w1", "sd_b1", "sd_w2", "sd_b2")
    w1, b1, w2, b2 = (np.asarray(scope.find_var(n)).astype(np.float64)
                      for n in names)

    masks = []
    for i in range(6):
        feed = feeds[i % 2]
        hv, dv, lrv = exe.run(feed=feed, fetch_list=[h, d, lr])
        np.testing.assert_allclose(np.ravel(lrv)[0], 0.1 * 0.5 ** (i // 2),
                                   rtol=1e-6, err_msg=f"step {i}")
        # what the step multiplied each live unit by (a dead one passes no
        # gradient whatever its mask)
        mult = np.where(hv > 0, dv / np.where(hv > 0, hv, 1.0), 0.0)
        masks.append(dv != 0)
        xv, yv = feed["x"].astype(np.float64), feed["y"].astype(np.float64)
        z = xv @ w1 + b1
        dn = np.maximum(z, 0.0) * mult
        dpred = 2.0 * (dn @ w2 + b2 - yv) / len(xv)
        dz = (dpred @ w2.T) * mult * (z > 0)
        step = float(np.ravel(lrv)[0])
        w2, b2 = w2 - step * dn.T @ dpred, b2 - step * dpred.sum(0)
        w1, b1 = w1 - step * xv.T @ dz, b1 - step * dz.sum(0)
        for n, want in zip(names, (w1, b1, w2, b2)):
            np.testing.assert_allclose(
                np.asarray(scope.find_var(n)), want, rtol=1e-4, atol=1e-6,
                err_msg=f"state {n} diverged at step {i}")
    # the key advanced: the same batch (steps 0, 2, 4) drew three masks
    assert not np.array_equal(masks[0], masks[2])
    assert not np.array_equal(masks[2], masks[4])


def test_run_reads_a_refilled_feed_buffer_anew():
    """The refill-the-buffer loading pattern: a numpy feed mutated in place
    between two runs reaches the device on the second (a feed is staged
    per call, never remembered by identity)."""
    x = fluid.layers.data("x", [2], dtype="float32")
    out = fluid.layers.reduce_mean(x)
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"x": np.ones((2, 2), np.float32)}
    (a,) = exe.run(feed=feed, fetch_list=[out])
    feed["x"][:] = 5.0
    (b,) = exe.run(feed=feed, fetch_list=[out])
    np.testing.assert_allclose(np.ravel(a)[0], 1.0)
    np.testing.assert_allclose(np.ravel(b)[0], 5.0)


def test_fetch_var_reads_persistable():
    """reference: test_fetch_var.py — _fetch_var reads a persistable var's
    current value straight from the scope."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    val = np.array([1, 3, 5]).astype("int32")
    x = layers.create_tensor(dtype="int32", persistable=True, name="x")
    layers.assign(input=val, output=x)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_main_program(), feed={}, fetch_list=[])
    got = fluid.executor._fetch_var("x")
    np.testing.assert_array_equal(got, val)

    # module facade parity: as_numpy refuses LoD-carrying values
    from paddle_tpu.core.lod import LoDValue
    lv = LoDValue(np.zeros((3, 2), "float32"), np.array([2, 1]), ())
    try:
        fluid.executor.as_numpy(lv)
        raise AssertionError("expected RuntimeError for LoD value")
    except RuntimeError:
        pass


def test_seeded_training_is_deterministic():
    """Same program.random_seed => bitwise-identical init, dropout stream,
    and loss trajectory across two from-scratch runs (the reference's
    FLAGS_cpu_deterministic / random_seed contract)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    def run_once():
        fluid.reset_default_env()
        fluid.default_main_program().random_seed = 42
        fluid.default_startup_program().random_seed = 42
        x = layers.data("x", [8], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        h = layers.dropout(layers.fc(x, size=16, act="relu"),
                           dropout_prob=0.3)
        pred = layers.fc(h, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.AdamOptimizer(learning_rate=0.01).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(0)
        xv = rng.randn(16, 8).astype("float32")
        yv = rng.randn(16, 1).astype("float32")
        return [np.asarray(exe.run(feed={"x": xv, "y": yv},
                                   fetch_list=[loss])[0]).item()
                for _ in range(4)]

    assert run_once() == run_once()


def test_cost_analysis_reports_bytes_and_flops():
    """Executor.cost_analysis returns the compiled step's XLA cost
    accounting (bytes accessed / flops) for the exact cached executable
    (VERDICT r5 item 4: bytes/step instrument)."""
    fluid.reset_default_env()
    x = fluid.layers.data("x", [16], dtype="float32")
    y = fluid.layers.data("y", [1], dtype="float32")
    h = fluid.layers.fc(x, size=32, act="relu")
    pred = fluid.layers.fc(h, size=1)
    loss = fluid.layers.mean(fluid.layers.square(pred - y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"x": np.ones((8, 16), "float32"), "y": np.ones((8, 1), "float32")}
    exe.run(feed=feed, fetch_list=[loss])
    ca = exe.cost_analysis(feed=feed, fetch_list=[loss])
    assert ca.get("bytes accessed", 0) > 0
    assert ca.get("flops", 0) > 0


def test_cost_analysis_rejects_compiled_program():
    fluid.reset_default_env()
    import pytest

    exe = fluid.Executor(fluid.CPUPlace())
    with pytest.raises(TypeError, match="plain Program"):
        exe.cost_analysis(program=fluid.CompiledProgram(fluid.Program()))


def test_no_recompile_on_second_run():
    """The written-back (committed) PRNG key must not change the lowering
    cache key: two identical exe.run calls = exactly ONE XLA compile
    (review r5: the uncommitted fresh key vs committed written-back key
    caused a silent full recompile on every program's second step)."""
    import os
    import subprocess
    import sys

    src = r"""
import os, sys
sys.path.insert(0, os.environ["REPO"])
import jax
jax.config.update("jax_platforms", "cpu")
compiles = {"n": 0}
from jax._src import monitoring
monitoring.register_event_duration_secs_listener(
    lambda event, dur, **kw: compiles.__setitem__("n", compiles["n"] + 1)
    if "backend_compile" in event else None)
import numpy as np
import paddle_tpu as fluid
x = fluid.layers.data("x", [8], dtype="float32")
h = fluid.layers.fc(x, size=8, act="tanh")
loss = fluid.layers.mean(h)
fluid.optimizer.SGD(0.1).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(fluid.default_startup_program())
feed = {"x": np.ones((4, 8), "float32")}
exe.run(feed=feed, fetch_list=[loss])   # first call: compiles once
print("WARMUP_COMPILES", compiles["n"])  # instrumentation liveness
base = compiles["n"]
for _ in range(3):
    exe.run(feed=feed, fetch_list=[loss])
print("MAIN_REPEAT_COMPILES", compiles["n"] - base)
"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", src],
                         capture_output=True, text=True, timeout=300,
                         env=dict(os.environ, REPO=repo))
    assert out.returncode == 0, out.stderr[-1500:]
    warm = int(out.stdout.split("WARMUP_COMPILES")[1].split()[0])
    assert warm >= 1, (
        "the backend_compile listener never fired - instrumentation is "
        "dead and the zero-recompile assertions below would be vacuous")
    n = int(out.stdout.split("MAIN_REPEAT_COMPILES")[1].split()[0])
    assert n == 0, f"repeated identical runs must not recompile, got {n}"


_ONE_COMPILE_PRELUDE = r"""
import os, sys
sys.path.insert(0, os.environ["REPO"])
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
compiles = {"n": 0}
from jax._src import monitoring
monitoring.register_event_duration_secs_listener(
    lambda event, dur, **kw: compiles.__setitem__("n", compiles["n"] + 1)
    if "backend_compile" in event else None)
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import observability as obs
x = fluid.layers.data("x", [8], dtype="float32")
h = fluid.layers.fc(x, size=8, act="tanh",
                    param_attr=fluid.ParamAttr(name="w"))
loss = fluid.layers.mean(h)
fluid.optimizer.SGD(0.1).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(fluid.default_startup_program())
scope = fluid.global_scope()
fluid.set_flags({"FLAGS_observability": True})
def stage_spans():
    out = [(s.args["n"], s.args["moved"])
           for s in obs.default_tracer().spans() if s.name == "executor.stage"]
    obs.reset()
    return out
obs.reset()
"""

_ONE_COMPILE_CASES = {
    # what the old blanket device_put guarded: a first step from host-numpy
    # state, steady steps, then a checkpoint load in the middle
    "host_state_then_checkpoint_load": r"""
for n in scope.local_var_names():
    scope.set_var(n, np.asarray(scope.find_var(n)))
feed = {"x": jax.device_put(np.ones((4, 8), "float32"),
                            fluid.CPUPlace().jax_device())}
base = compiles["n"]
exe.run(feed=feed, fetch_list=[loss])
print("FIRST_COMPILES", compiles["n"] - base)
base = compiles["n"]
for _ in range(2):
    exe.run(feed=feed, fetch_list=[loss])
scope.set_var("w", np.asarray(scope.find_var("w")))
for _ in range(2):
    exe.run(feed=feed, fetch_list=[loss])
print("LATER_COMPILES", compiles["n"] - base)
print("STAGE", stage_spans())
""",
    # startup by Executor, steps by ParallelExecutor; the first from a host
    # batch, the rest from one in place (two executables before the rule
    # staged feeds too: pjit keyed on the host batch)
    "serial_to_spmd_handoff": r"""
from paddle_tpu.parallel import make_mesh
mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
pe = fluid.ParallelExecutor(loss_name=loss.name, mesh=mesh)
feed = {"x": jax.device_put(np.ones((4, 8), "float32"),
                            mesh.batch_sharding())}
base = compiles["n"]
pe.run(feed={"x": np.ones((4, 8), "float32")}, fetch_list=[loss])
print("FIRST_COMPILES", compiles["n"] - base)
base = compiles["n"]
for _ in range(3):
    pe.run(feed=feed, fetch_list=[loss])
print("LATER_COMPILES", compiles["n"] - base)
print("STAGE", stage_spans())
""",
}


@pytest.mark.parametrize("case", sorted(_ONE_COMPILE_CASES))
def test_one_compile_whatever_the_state_arrives_as(case):
    """The staging rule places only what is not in place, and the step
    still has ONE executable: a first step from host state (or from the
    serial executor's single-device state, under a mesh), steady steps and
    a checkpoint load in the middle all hit it.  `moved` on the stage span
    is exact: everything on the first step, the loaded value alone on the
    step after the load, nothing on a steady step."""
    import ast
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", _ONE_COMPILE_PRELUDE + _ONE_COMPILE_CASES[case]],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, REPO=repo))
    assert out.returncode == 0, out.stderr[-1500:]
    first = int(out.stdout.split("FIRST_COMPILES")[1].split()[0])
    assert first >= 1, "the backend_compile listener never fired"
    later = int(out.stdout.split("LATER_COMPILES")[1].split()[0])
    assert later == 0, (
        f"every later step must hit the first step's executable, "
        f"{later} compiled")
    stage = ast.literal_eval(out.stdout.split("STAGE")[1].strip())
    n = stage[0][0]
    assert all(s[0] == n for s in stage)
    if case == "host_state_then_checkpoint_load":
        # the feed is in place; of the first step all the rest is host
        assert [s[1] for s in stage] == [n - 1, 0, 0, 1, 0]
    else:
        assert [s[1] for s in stage] == [n, 0, 0, 0]


@pytest.mark.parametrize("what", [
    "Executor", "ParallelExecutor", "run_step", "histogram"])
def test_a_step_has_one_way_to_run(what):
    """`run` is the one entry point of a step: no executor has a
    K-steps-a-dispatch twin (every cell's idle share is under the 3% such
    a twin could recover, ROADMAP D17), run_step takes no `steps`, and a
    step leaves no second histogram behind."""
    import inspect

    from paddle_tpu import observability as obs
    from paddle_tpu.core import executor as executor_mod

    if what in ("Executor", "ParallelExecutor"):
        assert not hasattr(getattr(fluid, what), "run_steps")
        assert "mode" not in inspect.signature(
            getattr(fluid, what).run).parameters
    elif what == "run_step":
        assert "steps" not in inspect.signature(
            executor_mod.run_step).parameters
    else:
        fluid.set_flags({"FLAGS_observability": True})
        obs.reset()
        try:
            out = fluid.layers.fill_constant([2], "float32", 1.0)
            fluid.Executor(fluid.CPUPlace()).run(fetch_list=[out])
            names = [m.name for m in obs.default_registry().metrics()]
        finally:
            obs.reset()
            fluid.set_flags({"FLAGS_observability": False})
        assert "paddle_tpu_executor_step_seconds" in names
        assert "paddle_tpu_executor_run_steps_seconds" not in names
