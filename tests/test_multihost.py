"""Multi-host execution + sharded checkpointing (VERDICT r1 missing #6/#7;
reference pattern: test_dist_base.py:212 localhost subprocess clusters).

test_sharded_checkpoint_roundtrip runs in-process on the 8-device CPU mesh;
test_two_process_data_parallel spawns a real 2-process jax.distributed
cluster over localhost and asserts dist loss == serial loss."""

import json
import os
import socket
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import paddle_tpu as fluid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build_mlp(seed=7):
    fluid.reset_default_env()
    fluid.default_main_program().random_seed = seed
    fluid.default_startup_program().random_seed = seed
    x = fluid.layers.data(name="x", shape=[16], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    h = fluid.layers.fc(x, 32, act="relu")
    pred = fluid.layers.fc(h, 1)
    loss = fluid.layers.reduce_mean(
        fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(0.1).minimize(loss)
    return loss


def test_sharded_checkpoint_roundtrip():
    """Params sharded over a tp axis save per-shard and restore bitwise,
    re-placed on the mesh."""
    import jax
    from paddle_tpu.parallel import ParallelExecutor, make_mesh

    loss = _build_mlp()
    prog = fluid.default_main_program()
    # shard the first fc weight over tp (names depend on the session-wide
    # unique_name counter, so match by pattern)
    w_name = sorted(
        n for n in prog.global_block().vars
        if n.startswith("fc_") and ".w" in n
    )[0]
    prog.global_block().var(w_name).sharding = [None, "tp"]

    mesh = make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    pe = ParallelExecutor(loss_name=loss.name, mesh=mesh)
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(8, 16).astype("float32"),
            "y": rng.randn(8, 1).astype("float32")}
    pe.run(fetch_list=[loss], feed=feed)

    scope = fluid.global_scope()
    param_names = {
        n for n in prog.global_block().vars if n.startswith("fc_")
    }
    before = {
        n: np.asarray(fluid.io._to_host(scope.find_var(n))[0])
        for n in scope.local_var_names()
        if n in param_names
    }
    with tempfile.TemporaryDirectory() as d:
        fluid.io.save_sharded(d, prog, scope)
        # wipe and restore
        for n in before:
            scope.set_var(n, np.zeros_like(before[n]))
        fluid.io.load_sharded(d, prog, scope, mesh=mesh)
        for n, want in before.items():
            got = np.asarray(fluid.io._to_host(scope.find_var(n))[0])
            np.testing.assert_array_equal(got, want, err_msg=n)
        # restored param is re-placed with its mesh sharding
        v = scope.find_var(w_name)
        import jax as _jax
        assert isinstance(v, _jax.Array)
    # training continues after restore
    (l2,) = pe.run(fetch_list=[loss], feed=feed)
    assert np.isfinite(float(np.ravel(l2)[0]))


_WORKER = r"""
import json, os, sys
import numpy as np

sys.path.insert(0, {repo!r})
import paddle_tpu as fluid
from paddle_tpu import parallel

parallel.init_distributed()
import jax
assert jax.process_count() == 2, jax.process_count()

sys.path.insert(0, os.path.join({repo!r}, "tests"))
from test_multihost import _build_mlp

loss = _build_mlp()
prog = fluid.default_main_program()
exe = fluid.Executor(fluid.CPUPlace())
exe.run(fluid.default_startup_program())

from paddle_tpu.parallel import ParallelExecutor, make_mesh
mesh = make_mesh({{"dp": 4}}, devices=jax.devices())
pe = ParallelExecutor(loss_name=loss.name, mesh=mesh)

pid = jax.process_index()
rng = np.random.RandomState(0)
xs = rng.randn(8, 16).astype("float32")
ys = rng.randn(8, 1).astype("float32")
lo, hi = pid * 4, (pid + 1) * 4  # this process's batch shard

losses = []
for _ in range(3):
    (lv,) = pe.run(fetch_list=[loss], feed={{"x": xs[lo:hi], "y": ys[lo:hi]}})
    losses.append(float(np.ravel(np.asarray(lv))[0]))

# sharded checkpoint across the 2-process cluster
ckpt = os.path.join({outdir!r}, "ckpt")
os.makedirs(ckpt, exist_ok=True)
fluid.io.save_sharded(ckpt, prog, fluid.global_scope())

with open(os.path.join({outdir!r}, f"result_{{pid}}.json"), "w") as f:
    json.dump({{"losses": losses}}, f)
"""


@pytest.mark.timeout(300)
def test_two_process_data_parallel():
    port = socket.socket()
    port.bind(("127.0.0.1", 0))
    portno = port.getsockname()[1]
    port.close()

    with tempfile.TemporaryDirectory() as outdir:
        script = _WORKER.format(repo=REPO, outdir=outdir)
        procs = []
        for pid in range(2):
            env = dict(os.environ)
            env.update(
                JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=2",
                PADDLE_TRAINER_ENDPOINTS=(
                    f"127.0.0.1:{portno},127.0.0.1:{portno + 1}"
                ),
                PADDLE_TRAINER_ID=str(pid),
                PADDLE_TRAINERS_NUM="2",
            )
            procs.append(subprocess.Popen(
                [sys.executable, "-c", script], env=env, cwd=outdir,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            ))
        outs = [p.communicate(timeout=240)[0].decode() for p in procs]
        for p, o in zip(procs, outs):
            assert p.returncode == 0, f"worker failed:\n{o[-3000:]}"

        results = []
        for pid in range(2):
            with open(os.path.join(outdir, f"result_{pid}.json")) as f:
                results.append(json.load(f))
        # both processes observe the same (replicated) global loss
        np.testing.assert_allclose(results[0]["losses"],
                                   results[1]["losses"], rtol=1e-5)

        # serial reference: same program, full batch, one device
        loss = _build_mlp()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(0)
        xs = rng.randn(8, 16).astype("float32")
        ys = rng.randn(8, 1).astype("float32")
        serial = []
        for _ in range(3):
            (lv,) = exe.run(feed={"x": xs, "y": ys}, fetch_list=[loss])
            serial.append(float(np.ravel(lv)[0]))
        np.testing.assert_allclose(results[0]["losses"], serial, rtol=1e-4)

        # the cluster's sharded checkpoint reassembles on a fresh process
        ckpt = os.path.join(outdir, "ckpt")
        assert os.path.exists(os.path.join(ckpt, "meta.json"))
        scope2 = fluid.global_scope().new_scope()
        fluid.io.load_sharded(ckpt, scope=scope2)
        with open(os.path.join(ckpt, "meta.json")) as f:
            meta = json.load(f)
        w = [n for n in meta if ".w" in n][0]
        got = scope2.find_var(w)
        assert got is not None and list(np.shape(got)) == meta[w]["shape"]
"""worker stdout is attached on failure for debuggability."""


def test_async_sharded_checkpoint(tmp_path):
    """save_sharded(asynchronous=True): device state snapshots before the
    call returns, files write on a background thread, and later scope
    mutations (donated/overwritten buffers) don't leak into the
    checkpoint."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    fluid.reset_default_env()
    x = layers.data("x", [4], dtype="float32")
    pred = layers.fc(x, size=2, param_attr=fluid.ParamAttr(name="acp_w"),
                     bias_attr=False)
    loss = layers.mean(pred)
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    snap = np.asarray(scope.find_var("acp_w")).copy()

    d = str(tmp_path / "ckpt")
    handle = fluid.io.save_sharded(d, asynchronous=True)
    assert handle is not None
    # mutate AFTER the async save: a training step replaces the param
    exe.run(feed={"x": np.ones((2, 4), "float32")}, fetch_list=[loss])
    handle.wait()
    assert handle.done()

    fluid.reset_default_env()
    x = layers.data("x", [4], dtype="float32")
    layers.fc(x, size=2, param_attr=fluid.ParamAttr(name="acp_w"),
              bias_attr=False)
    exe2 = fluid.Executor(fluid.CPUPlace())
    exe2.run(fluid.default_startup_program())
    fluid.io.load_sharded(d)
    got = np.asarray(fluid.global_scope().find_var("acp_w"))
    np.testing.assert_array_equal(got, snap)


def test_async_checkpoint_overlapping_saves(tmp_path):
    """Two async saves to the same dirname serialize: the second joins the
    first's writer before touching the directory, so the final meta.json
    and shards all belong to the newest save (no stale-meta race)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.io import _inflight_saves

    fluid.reset_default_env()
    x = layers.data("x", [4], dtype="float32")
    pred = layers.fc(x, size=2, param_attr=fluid.ParamAttr(name="ov_w"),
                     bias_attr=False)
    loss = layers.mean(pred)
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()

    d = str(tmp_path / "ckpt")
    h1 = fluid.io.save_sharded(d, asynchronous=True)
    exe.run(feed={"x": np.ones((2, 4), "float32")}, fetch_list=[loss])
    h2 = fluid.io.save_sharded(d, asynchronous=True)
    # the second save must have joined the first before starting
    assert h1.done()
    exe.run(feed={"x": np.ones((2, 4), "float32")}, fetch_list=[loss])
    snap2 = np.asarray(scope.find_var("ov_w")).copy()
    # a SYNC save to the same dir also joins the in-flight async writer
    fluid.io.save_sharded(d)
    h2.wait()
    assert h2.done()
    # finished writers self-prune from the in-flight registry
    assert os.path.abspath(d) not in _inflight_saves

    fluid.reset_default_env()
    x = layers.data("x", [4], dtype="float32")
    layers.fc(x, size=2, param_attr=fluid.ParamAttr(name="ov_w"),
              bias_attr=False)
    exe2 = fluid.Executor(fluid.CPUPlace())
    exe2.run(fluid.default_startup_program())
    fluid.io.load_sharded(d)
    got = np.asarray(fluid.global_scope().find_var("ov_w"))
    np.testing.assert_array_equal(got, snap2)
