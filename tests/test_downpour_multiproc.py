"""Cross-process Downpour: the PS serves its tables over the ps_rpc TCP
transport in one subprocess; two trainer subprocesses run Hogwild workers
against it (reference pattern: test_dist_base.py:212 forks real
pserver+trainer subprocesses on localhost and asserts dist loss ~= local
loss)."""

import json
import os
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VOCAB = 100
EMB_DIM = 8

_COMMON = '''
import json, os, sys
import numpy as np
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
import paddle_tpu as fluid

VOCAB, EMB_DIM = {vocab}, {emb_dim}

def build_model():
    ids = fluid.layers.data(name="ids", shape=[1], dtype="int64")
    label = fluid.layers.data(name="label", shape=[1], dtype="float32")
    emb = fluid.layers.embedding(
        ids, size=[VOCAB, EMB_DIM], is_distributed=True,
        param_attr=fluid.ParamAttr(name="dist_emb"))
    fc1 = fluid.layers.fc(emb, size=16, act="relu")
    logit = fluid.layers.fc(fc1, size=1)
    return fluid.layers.reduce_mean(
        fluid.layers.sigmoid_cross_entropy_with_logits(logit, label))

def build_ps_param():
    from paddle_tpu.distributed import DownpourSGD
    fluid.default_startup_program().random_seed = 1  # the dense init
    loss = build_model()
    ps_param, _ = DownpourSGD(learning_rate=0.2, window=1).minimize(loss)
    # 0.005, not 0.05: see tests/test_downpour.py (at 0.05 whether the
    # run leaves log 2 hangs on how the Hogwild workers interleave)
    ps_param["server_param"]["downpour_server_param"][
        "downpour_table_param"][1]["accessor"]["dense_sgd_param"]["adam"][
        "learning_rate"] = 0.005
    return loss, ps_param
'''

_SERVER = _COMMON + '''
from paddle_tpu.distributed.ps_core import PSCore
from paddle_tpu.distributed.ps_rpc import serve_ps

port = int(sys.argv[1])
loss, ps_param = build_ps_param()
core = PSCore.from_server_desc(ps_param["server_param"])

# seed the dense table from a startup-program init, like init_model()
exe = fluid.AsyncExecutor(fluid.CPUPlace())
exe.init_worker(ps_param, ps=core)
fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
exe.init_model()

srv = serve_ps(core, port=port)
print("SERVING", srv.endpoint, flush=True)
srv.serve_forever if False else None
import threading, time
while True:
    time.sleep(0.2)
'''

_TRAINER = _COMMON + '''
from paddle_tpu.distributed.ps_rpc import RemotePS

endpoint, data_file, out_path = sys.argv[1], sys.argv[2], sys.argv[3]
loss, ps_param = build_ps_param()
exe = fluid.AsyncExecutor(fluid.CPUPlace())
exe.init_worker(ps_param, ps=RemotePS(endpoint))
fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())

desc = fluid.DataFeedDesc("""
name: "MultiSlotDataFeed"
batch_size: 32
multi_slot_desc {{
  slots {{ name: "ids" type: "uint64" is_dense: true is_used: true }}
  slots {{ name: "label" type: "float" is_dense: true is_used: true }}
}}
""")
for _ in range(4):
    exe.run(fluid.default_main_program(), desc, [data_file], thread_num=2,
            fetch=[loss])
open(out_path, "w").write("done")
print("TRAINED", flush=True)
'''

_EVAL = _COMMON + '''
from paddle_tpu.distributed.ps_rpc import RemotePS
from paddle_tpu.distributed.downpour import DENSE_TABLE_ID, SPARSE_TABLE_ID

endpoint, out_path = sys.argv[1], sys.argv[2]
loss, ps_param = build_ps_param()
exe = fluid.AsyncExecutor(fluid.CPUPlace())
ps = RemotePS(endpoint)
exe.init_worker(ps_param, ps=ps)
fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
exe._pull_dense_into_scope()

rng = np.random.RandomState(7)
ids = rng.randint(VOCAB, size=(64, 1)).astype(np.int64)
label = (ids % 2 == 0).astype(np.float32)
rows = ps.sparse(SPARSE_TABLE_ID).pull(ids.reshape(-1))
emb_out = exe._emb_map[0][1]
v = fluid.Executor(fluid.CPUPlace(), donate_states=False).run(
    program=exe._worker_program,
    feed={{"ids": ids, "label": label,
          emb_out: rows.reshape(64, EMB_DIM)}},
    fetch_list=[loss.name])
result = {{"loss": float(np.ravel(np.asarray(v[0]))[0]),
          "sparse_rows": len(ps.sparse(SPARSE_TABLE_ID))}}
open(out_path, "w").write(json.dumps(result))
print("EVAL", result, flush=True)
'''


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _write_ctr_file(path, lines=300, seed=0):
    rng = np.random.RandomState(seed)
    with open(path, "w") as fh:
        for _ in range(lines):
            i = int(rng.randint(VOCAB))
            label = 1.0 if i % 2 == 0 else 0.0
            fh.write(f"1 {i} 1 {label}\n")


def test_downpour_cross_process_convergence(tmp_path):
    fmt = dict(repo=REPO, vocab=VOCAB, emb_dim=EMB_DIM)
    server_py = str(tmp_path / "server.py")
    trainer_py = str(tmp_path / "trainer.py")
    eval_py = str(tmp_path / "eval.py")
    open(server_py, "w").write(_SERVER.format(**fmt))
    open(trainer_py, "w").write(_TRAINER.format(**fmt))
    open(eval_py, "w").write(_EVAL.format(**fmt))

    data = [str(tmp_path / f"part-{i}") for i in range(2)]
    for i, p in enumerate(data):
        _write_ctr_file(p, seed=i)

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    env.pop("XLA_FLAGS", None)
    port = _free_port()
    server = subprocess.Popen(
        [sys.executable, server_py, str(port)], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    trainers = []
    try:
        # wait for SERVING with a hard deadline: readline() alone would
        # block forever on a wedged-but-alive server (review r5) —
        # a reader thread + join(timeout) bounds it
        import queue as _queue
        import threading as _threading

        lines: "_queue.Queue[str]" = _queue.Queue()
        _threading.Thread(
            target=lambda: [lines.put(ln) for ln in server.stdout],
            daemon=True).start()
        line = ""
        deadline = time.time() + 240
        while time.time() < deadline:
            try:
                line = lines.get(timeout=5)
            except _queue.Empty:
                assert server.poll() is None, "server died silently"
                continue
            if "SERVING" in line:
                break
            assert server.poll() is None, "server died: " + line
        assert "SERVING" in line, "server never reported SERVING in 240s"
        endpoint = line.split()[1]

        # cold-start loss ~ log(2)
        eval0 = str(tmp_path / "eval0.json")
        r = subprocess.run(
            [sys.executable, eval_py, endpoint, eval0], env=env,
            capture_output=True, text=True, timeout=480)
        assert r.returncode == 0, r.stdout + r.stderr
        first = json.loads(open(eval0).read())["loss"]
        assert abs(first - np.log(2.0)) < 0.05

        # two REAL trainer processes, different file shards
        trainers += [
            subprocess.Popen(
                [sys.executable, trainer_py, endpoint, data[i],
                 str(tmp_path / f"done{i}")],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True)
            for i in range(2)
        ]
        for t in trainers:
            out, _ = t.communicate(timeout=600)
            assert t.returncode == 0, out
            assert "TRAINED" in out

        evalf = str(tmp_path / "evalf.json")
        r = subprocess.run(
            [sys.executable, eval_py, endpoint, evalf], env=env,
            capture_output=True, text=True, timeout=480)
        assert r.returncode == 0, r.stdout + r.stderr
        result = json.loads(open(evalf).read())
        final = result["loss"]
        # convergence parity with the in-process run
        # (tests/test_downpour.py asserts the same drop on one process)
        assert final < first - 0.05, f"loss did not drop: {first} -> {final}"
        assert 0 < result["sparse_rows"] <= VOCAB
    finally:
        # kill EVERYTHING: a hung/failed trainer must not outlive the
        # test spinning against a dead PS endpoint (review r5)
        for t in trainers:
            if t.poll() is None:
                t.kill()
                t.wait()
        server.kill()
        server.wait()
