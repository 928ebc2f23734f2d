"""Flags tier + FLAGS_check_nan_inf (reference:
python/paddle/fluid/__init__.py:125 __bootstrap__ env gflags;
framework/operator.cc:777 nan/inf checking)."""

import numpy as np
import pytest

import paddle_tpu as fluid


def test_get_set_flags():
    flags = fluid.get_flags()
    assert "FLAGS_check_nan_inf" in flags
    assert flags["FLAGS_check_nan_inf"] is False
    fluid.set_flags({"FLAGS_check_nan_inf": True})
    try:
        assert fluid.get_flags("check_nan_inf")["FLAGS_check_nan_inf"] is True
    finally:
        fluid.set_flags({"FLAGS_check_nan_inf": False})
    with pytest.raises(KeyError):
        fluid.set_flags({"FLAGS_no_such_flag": 1})


def test_check_nan_inf_catches_diverged_step():
    fluid.reset_default_env()
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    # log of a negative number -> nan in the fetch
    out = fluid.layers.reduce_mean(fluid.layers.log(x))
    exe = fluid.Executor(fluid.CPUPlace())
    bad = np.full((2, 4), -1.0, dtype="float32")

    # flag off: nan flows through silently (reference default)
    (lv,) = exe.run(feed={"x": bad}, fetch_list=[out])
    assert np.isnan(lv).all()

    fluid.set_flags({"FLAGS_check_nan_inf": True})
    try:
        with pytest.raises(RuntimeError, match="nan/inf"):
            exe.run(feed={"x": bad}, fetch_list=[out])
        # clean inputs pass the check
        good = np.full((2, 4), 2.0, dtype="float32")
        (lv,) = exe.run(feed={"x": good}, fetch_list=[out])
        np.testing.assert_allclose(lv, np.log(2.0), rtol=1e-6)
    finally:
        fluid.set_flags({"FLAGS_check_nan_inf": False})


def test_check_nan_inf_names_state_var():
    """A diverging training step (lr too big -> inf weights) is caught and
    the error names a variable."""
    fluid.reset_default_env()
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    pred = fluid.layers.fc(x, 1)
    loss = fluid.layers.reduce_mean(
        fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(1e30).minimize(loss)  # guaranteed blow-up
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(8, 4).astype("float32") * 10,
            "y": rng.randn(8, 1).astype("float32")}
    fluid.set_flags({"FLAGS_check_nan_inf": True})
    try:
        with pytest.raises(RuntimeError, match="FLAGS_check_nan_inf"):
            for _ in range(3):
                exe.run(feed=feed, fetch_list=[loss])
    finally:
        fluid.set_flags({"FLAGS_check_nan_inf": False})


def test_env_bootstrap(monkeypatch):
    import importlib
    from paddle_tpu import flags as flagmod

    monkeypatch.setenv("FLAGS_check_nan_inf", "1")
    monkeypatch.setenv("FLAGS_paddle_num_threads", "4")
    try:
        flagmod._bootstrap()
        assert flagmod.flag("check_nan_inf") is True
        assert flagmod.flag("paddle_num_threads") == 4
    finally:
        monkeypatch.delenv("FLAGS_check_nan_inf")
        monkeypatch.delenv("FLAGS_paddle_num_threads")
        flagmod._bootstrap()


def test_conv_layout_nhwc_parity():
    """FLAGS_conv_layout=NHWC computes the same conv2d (internal layout
    only; program contract stays NCHW)."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import layers

    x = np.random.RandomState(0).randn(2, 3, 16, 16).astype("float32")

    outs = {}
    for layout in ("NCHW", "NHWC"):
        fluid.set_flags({"FLAGS_conv_layout": layout})
        try:
            fluid.reset_default_env()
            img = layers.data("img", [3, 16, 16])
            y = layers.conv2d(img, num_filters=4, filter_size=3, padding=1,
                              groups=1,
                              param_attr=fluid.ParamAttr(
                                  name=f"w_{layout}",
                                  initializer=fluid.initializer.Constant(0.1)))
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            out, = exe.run(feed={"img": x}, fetch_list=[y])
            outs[layout] = np.asarray(out)
        finally:
            fluid.set_flags({"FLAGS_conv_layout": "auto"})
    np.testing.assert_allclose(outs["NCHW"], outs["NHWC"],
                               rtol=1e-5, atol=1e-5)


def test_conv_layout_nhwc_pool_parity():
    """Under FLAGS_conv_layout=NHWC pool2d also pools channels-last behind
    boundary transposes; the conv->maxpool->avgpool chain (fwd AND the
    select-and-scatter backward, via one SGD step) matches NCHW."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import layers

    x = np.random.RandomState(1).randn(2, 3, 16, 16).astype("float32")

    results = {}
    for layout in ("NCHW", "NHWC"):
        fluid.set_flags({"FLAGS_conv_layout": layout})
        try:
            fluid.reset_default_env()
            img = layers.data("img", [3, 16, 16])
            y = layers.conv2d(img, num_filters=4, filter_size=3, padding=1,
                              param_attr=fluid.ParamAttr(
                                  name=f"wp_{layout}",
                                  initializer=fluid.initializer.Constant(0.1)))
            y = layers.pool2d(y, pool_size=3, pool_type="max", pool_stride=2,
                              pool_padding=1, ceil_mode=True)
            y = layers.pool2d(y, pool_size=2, pool_type="avg", pool_stride=2,
                              exclusive=True)
            loss = layers.reduce_mean(y)
            fluid.optimizer.SGD(learning_rate=0.5).minimize(loss)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            out, = exe.run(feed={"img": x}, fetch_list=[y])
            w, = exe.run(feed={"img": x}, fetch_list=[f"wp_{layout}"])
            results[layout] = (np.asarray(out), np.asarray(w))
        finally:
            fluid.set_flags({"FLAGS_conv_layout": "auto"})
    np.testing.assert_allclose(results["NCHW"][0], results["NHWC"][0],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(results["NCHW"][1], results["NHWC"][1],
                               rtol=1e-5, atol=1e-5)


def test_compile_cache_dir_flag_applies(tmp_path, monkeypatch):
    """FLAGS_compile_cache_dir points jax's persistent executable cache at
    the directory on first block compile (tiny compiles may fall under
    jax's min-compile-time threshold, so the assertion is on the applied
    config, not on cache files).  JAX_COMPILATION_CACHE_DIR wins over the
    flag: with the variable set, the flag changes nothing."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import flags as fl
    from paddle_tpu.core import compiler
    from paddle_tpu import layers

    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(compiler, "_compile_cache_applied_dir", None)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fl.set_flags({"FLAGS_compile_cache_dir": str(tmp_path)})
    try:
        x = layers.data("x", [2], dtype="float32")
        loss = layers.mean(layers.fc(x, size=2))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        exe.run(feed={"x": np.zeros((2, 2), "float32")}, fetch_list=[loss])
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)

        # pointing the flag at a NEW directory re-applies (ADVICE r3: the
        # old latch silently ignored every later set_flags)
        other = tmp_path / "second"
        fl.set_flags({"FLAGS_compile_cache_dir": str(other)})
        exe.run(feed={"x": np.zeros((2, 2), "float32")}, fetch_list=[loss])
        assert jax.config.jax_compilation_cache_dir == str(other)

        # clearing the flag restores the user's own pre-apply jax setting
        # (None here = disabled; cold-compile measurements depend on this)
        fl.set_flags({"FLAGS_compile_cache_dir": ""})
        assert jax.config.jax_compilation_cache_dir == prev

        # a typo'd flag elsewhere in the dict must not half-apply: the
        # cache stays untouched when validation fails
        import pytest as _pytest
        with _pytest.raises(ValueError):
            fl.set_flags({"FLAGS_compile_cache_dir": str(tmp_path),
                          "FLAGS_conv_layout": "NHCW"})
        assert jax.config.jax_compilation_cache_dir == prev

        # the variable wins: with it set, neither set_flags nor a fresh
        # block compile moves jax's cache directory
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        fl.set_flags({"FLAGS_compile_cache_dir": str(other)})
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    finally:
        fl.set_flags({"FLAGS_compile_cache_dir": ""})
        jax.config.update("jax_compilation_cache_dir", prev)


def test_auto_defaults_resolve_by_device_scope():
    """FLAGS_conv_layout defaults to "auto": NCHW outside a TPU trace
    scope (reference parity), NHWC inside one; un-set AMP resolves to
    keep-tier bf16 only inside the scope.  Explicit settings win over
    auto in both directions (VERDICT r3 item 5)."""
    from paddle_tpu import flags as fl
    from paddle_tpu.core import amp

    fluid.set_flags({"FLAGS_conv_layout": "auto"})  # the shipped default
    amp.reset_amp()  # clear any explicit policy left by earlier tests
    assert fl.conv_layout() == "NCHW"
    assert amp.state_key() is None
    with fl.tpu_trace_scope(True):
        assert fl.conv_layout() == "NHWC"
        assert amp.state_key() == ("bfloat16", True)
        assert fl.trace_key()[0] == "NHWC"

        # explicit pins win inside the scope
        fluid.set_flags({"FLAGS_conv_layout": "NCHW"})
        fluid.disable_amp()
        try:
            assert fl.conv_layout() == "NCHW"
            assert amp.state_key() is None
        finally:
            fluid.set_flags({"FLAGS_conv_layout": "auto"})
            amp.reset_amp()
    # back outside: auto resolves to parity defaults again
    assert fl.conv_layout() == "NCHW"
    assert amp.state_key() is None


def test_tpu_place_gets_tuned_defaults(monkeypatch):
    """A fresh Executor run against a TPU device picks keep-tier bf16 +
    NHWC with NO env vars or enable_amp calls: conv activations come back
    bfloat16 while params/loss stay fp32 master precision.  (The device
    check is monkeypatched and the place is the CPU's — the suite runs on
    the CPU backend, where TPUPlace() raises.)"""
    from paddle_tpu import layers
    from paddle_tpu.core import amp, executor as exec_mod

    amp.reset_amp()
    monkeypatch.setattr(exec_mod, "device_is_tpu", lambda d: True)
    fluid.reset_default_env()
    x = layers.data("x", [3, 8, 8], dtype="float32")
    c = layers.conv2d(x, num_filters=4, filter_size=3, padding=1)
    loss = layers.reduce_mean(c)
    fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xv = np.random.RandomState(3).randn(2, 3, 8, 8).astype("float32")
    w_name = next(op for op in fluid.default_main_program()
                  .global_block().ops
                  if op.type == "conv2d").input("Filter")[0]
    cv, wv = exe.run(feed={"x": xv}, fetch_list=[c, w_name],
                     return_numpy=False)
    import jax.numpy as jnp

    assert jnp.asarray(cv).dtype == jnp.bfloat16  # keep-tier activations
    assert jnp.asarray(wv).dtype == jnp.float32   # fp32 master weights

    # the same program on a non-TPU device stays fp32 (fresh executor;
    # the cache key includes the resolved policy so no stale reuse)
    monkeypatch.setattr(exec_mod, "device_is_tpu", lambda d: False)
    cv2, _ = exe.run(feed={"x": xv}, fetch_list=[c, loss],
                     return_numpy=False)
    assert jnp.asarray(cv2).dtype == jnp.float32


def test_compile_cache_coldstart_cross_process(tmp_path):
    """Cold-start drill: a fresh process must be able to REUSE executables
    persisted by an earlier process — zero recompiles, bit-identical
    training losses.  That is what lets a second bench run skip the
    minutes the first one compiled for; the two-process contract is
    proven on CPU via tools/cache_coldstart.py."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "cache_coldstart.py"),
         "--cache-dir", str(tmp_path / "xla_cache")],
        capture_output=True, text=True, timeout=600,
    )
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.strip().startswith("{")]
    assert out.returncode == 0, out.stdout + out.stderr
    verdict = lines[-1]
    assert verdict["coldstart_ok"] is True
    assert verdict["cold_cache_hits"] > 0
    assert verdict["cold_cache_misses"] == 0


# ---------------------------------------------------------------------------
# the flags tier after the conv-epilogue arms (PR 29) and the flash
# backward's flag (PR 30: the engine is read from the shape) went


@pytest.mark.parametrize("name", ["FLAGS_conv_epilogue",
                                  "FLAGS_fuse_conv_epilogue",
                                  "FLAGS_flash_bwd"])
def test_removed_flag_is_unknown_and_ignored_in_environment(name,
                                                            monkeypatch):
    """A removed flag raises KeyError where the program sets or reads it
    and, like any variable the tier does not define, is ignored where the
    environment still exports it."""
    from paddle_tpu import flags as flagmod

    with pytest.raises(KeyError):
        fluid.set_flags({name: "pallas"})
    with pytest.raises(KeyError):
        fluid.get_flags(name)
    monkeypatch.setenv(name, "pallas")
    before = fluid.get_flags()
    flagmod._bootstrap()
    assert fluid.get_flags() == before and name not in before


def test_trace_key_has_exactly_two_entries():
    """layout (resolved) and FLAGS_check_numerics: what changes the traced
    program or its executable, and nothing else."""
    from paddle_tpu import flags as flagmod

    assert flagmod.trace_key() == ("NCHW", False)
    with flagmod.tpu_trace_scope(True):
        assert flagmod.trace_key() == ("NHWC", False)
    assert len(flagmod._DEFS) == 24


@pytest.mark.parametrize("name,value,default", [
    ("FLAGS_conv_layout", "NHWC", "auto"),
    ("FLAGS_check_numerics", True, False),
])
def test_trace_key_flag_flip_lands_on_another_cached_entry(name, value,
                                                           default):
    """Executor.run keys its compiled entries on trace_key(): a flip
    between two runs of one program compiles again, and flipping back
    finds the first entry."""
    fluid.reset_default_env()
    x = fluid.layers.data(name="x", shape=[2, 4, 4], dtype="float32")
    out = fluid.layers.reduce_mean(fluid.layers.conv2d(x, 2, 3))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"x": np.ones((1, 2, 4, 4), "float32")}
    (first,) = exe.run(feed=feed, fetch_list=[out])
    entries = len(exe._cache)
    fluid.set_flags({name: value})
    try:
        (flipped,) = exe.run(feed=feed, fetch_list=[out])
        assert len(exe._cache) == entries + 1
    finally:
        fluid.set_flags({name: default})
    exe.run(feed=feed, fetch_list=[out])
    assert len(exe._cache) == entries + 1
    np.testing.assert_allclose(flipped, first, rtol=1e-5)
