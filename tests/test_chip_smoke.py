"""CPU-side guards of the chip path (fast): chip_smoke.py refuses a host
without a TPU before it builds anything, TPUPlace has no fallback, and the
compile cache's place comes from JAX_COMPILATION_CACHE_DIR when it is set."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

import paddle_tpu as fluid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_a_cpu_host_before_building_anything():
    t0 = time.perf_counter()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode not in (0, None), out.stdout + out.stderr
    assert time.perf_counter() - t0 < 60
    # no phase ran, no model was built, and nothing looks like a result
    assert "[train" not in out.stdout and "[serve" not in out.stdout
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_bench_without_a_chip_and_without_the_variable_fails():
    """bench.py takes TPUPlace() unless JAX_PLATFORMS says cpu: on a host
    with no chip and no variable it exits non-zero and measures nothing."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env.update(BENCH_MODELS="lenet", BENCH_TUNE="0", BENCH_STEPS="2",
               BENCH_COMPILE_CACHE="0", BENCH_DEADLINE_S="0")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO, env=env)
    assert out.returncode not in (0, None), out.stdout + out.stderr
    assert '"platform"' not in out.stdout  # no result row of any device


@pytest.mark.parametrize("place_cls", [fluid.TPUPlace, fluid.CUDAPlace])
def test_tpu_place_raises_without_a_tpu(place_cls):
    """No walk through other platforms, no jax.devices() fallback: on the
    CPU-only backend of this suite the place resolves to nothing."""
    with pytest.raises(RuntimeError):
        place_cls().jax_device()
    assert fluid.CPUPlace().jax_device().platform == "cpu"


def test_compile_cache_dir_variable_wins_over_the_flag(tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, neither a fresh CompiledBlock
    nor set_flags nor the entry points' helper moves jax's cache
    directory: the variable's value stays what jax itself took from it."""
    from paddle_tpu import layers
    from paddle_tpu.core import compiler

    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    monkeypatch.setattr(compiler, "_compile_cache_applied_dir", None)
    # what jax does with the variable at start-up
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", env_dir)
    try:
        assert compiler.default_compile_cache() == env_dir
        fluid.set_flags({"FLAGS_compile_cache_dir": str(tmp_path / "flag")})
        assert jax.config.jax_compilation_cache_dir == env_dir
        x = layers.data("x", [2], dtype="float32")
        loss = layers.mean(layers.fc(x, size=2))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        exe.run(feed={"x": np.zeros((2, 2), "float32")}, fetch_list=[loss])
        assert jax.config.jax_compilation_cache_dir == env_dir
    finally:
        fluid.set_flags({"FLAGS_compile_cache_dir": ""})
        jax.config.update("jax_compilation_cache_dir", prev)


def test_default_compile_cache_is_one_fixed_directory(monkeypatch):
    """Unset, the entry points share <checkout>/xla_cache — the same path
    on every call (the path is part of the cache key)."""
    from paddle_tpu.core import compiler

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compiler, "_compile_cache_applied_dir", None)
    prev = jax.config.jax_compilation_cache_dir
    try:
        first = compiler.default_compile_cache()
        assert first == os.path.join(REPO, "xla_cache")
        assert compiler.default_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        fluid.set_flags({"FLAGS_compile_cache_dir": ""})
        jax.config.update("jax_compilation_cache_dir", prev)
