"""Resilience chaos suite: every failure mode the fault-injection hooks
can produce must recover end-to-end (ISSUE 2 acceptance; reference
analogues: go/master recover tests + the pserver checkpoint/LoadCheckpoint
round-trip, service.go:346).

In-process tests (tier-1): manifest verification, corrupt/truncated shard
rejection naming the file, zero-coverage rejection, CheckpointManager
rotation/GC/auto-resume, NaN sentinel skip + raise, preemption drain,
RPC drop-once retry, master-restart backoff.  Subprocess tests: a writer
killed mid-shard-write (FAULT_CKPT_KILL_AFTER_BYTES); the SIGKILL+RPC-drop
ElasticTrainer run (marked slow+chaos — out of tier-1 by the
`-m 'not slow'` discipline)."""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.io import CheckpointCorruptError
from paddle_tpu.resilience import (
    CheckpointManager,
    NonFiniteStepError,
    PreemptionDrain,
    faultinject,
    retry_with_backoff,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _disarm_faults():
    """Every test starts and ends with no armed faults and default flags."""
    faultinject.reset()
    yield
    for k in ("FAULT_CKPT_KILL_AFTER_BYTES", "FAULT_CKPT_CORRUPT_SHARD",
              "FAULT_RPC_DROP_ONCE", "FAULT_NAN_AT_STEP"):
        os.environ.pop(k, None)
    faultinject.reset()
    fluid.set_flags({"FLAGS_check_numerics": False,
                     "FLAGS_check_numerics_max_consecutive": 3})


def _build_sgd(name="rw"):
    x = layers.data("x", [4], dtype="float32")
    y = layers.data("y", [1], dtype="float32")
    pred = layers.fc(x, size=1, param_attr=fluid.ParamAttr(name=name),
                     bias_attr=False)
    loss = layers.mean(layers.square(layers.elementwise_sub(pred, y)))
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    return exe, loss


def _feed(seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(4, 4).astype("float32"),
            "y": rng.randn(4, 1).astype("float32")}


# -----------------------------------------------------------------------
# verified checkpoints
# -----------------------------------------------------------------------
def test_manifest_records_every_shard_file(tmp_path):
    _build_sgd()
    d = str(tmp_path / "ck")
    fluid.io.save_sharded(d, step=11, extra={"note": "hi"})
    meta = json.load(open(os.path.join(d, "meta.json")))
    m = meta["__manifest__"]
    assert m["process_count"] == 1 and m["step"] == 11
    assert m["extra"] == {"note": "hi"} and m["wall_time"] > 0
    assert set(m["files"]) == {"shard_0.npz", "index_0.json"}
    for fn, rec in m["files"].items():
        assert rec["bytes"] == os.path.getsize(os.path.join(d, fn))
    # the loader hands the manifest back
    got = fluid.io.load_sharded(d)
    assert got["step"] == 11 and got["extra"] == {"note": "hi"}


def test_corrupt_shard_raises_naming_file(tmp_path):
    """Acceptance: one flipped byte can never load silently."""
    exe, loss = _build_sgd()
    d = str(tmp_path / "ck")
    fluid.io.save_sharded(d)
    bad = faultinject.corrupt_shard(d)
    with pytest.raises(CheckpointCorruptError, match="shard_0.npz"):
        fluid.io.load_sharded(d)
    assert bad.endswith("shard_0.npz")


def test_truncated_shard_raises_naming_file(tmp_path):
    _build_sgd()
    d = str(tmp_path / "ck")
    fluid.io.save_sharded(d)
    p = os.path.join(d, "shard_0.npz")
    with open(p, "r+b") as f:
        f.truncate(os.path.getsize(p) // 2)
    with pytest.raises(CheckpointCorruptError, match="truncated"):
        fluid.io.load_sharded(d)


def test_missing_shard_file_raises(tmp_path):
    _build_sgd()
    d = str(tmp_path / "ck")
    fluid.io.save_sharded(d)
    os.remove(os.path.join(d, "shard_0.npz"))
    with pytest.raises(CheckpointCorruptError, match="missing"):
        fluid.io.load_sharded(d)


def test_missing_meta_is_incomplete(tmp_path):
    _build_sgd()
    d = str(tmp_path / "ck")
    fluid.io.save_sharded(d)
    os.remove(os.path.join(d, "meta.json"))
    with pytest.raises(CheckpointCorruptError, match="meta.json"):
        fluid.io.load_sharded(d)


def test_zero_coverage_raises_even_without_manifest(tmp_path):
    """Satellite: pre-manifest checkpoints (no __manifest__) must STILL
    refuse to zero-fill a var whose shard entries are absent — the seed
    behavior silently loaded np.zeros."""
    _build_sgd(name="zc_w")
    d = str(tmp_path / "ck")
    fluid.io.save_sharded(d)
    # strip the manifest (legacy checkpoint) and delete the var's index
    # entries so no shard covers it
    meta = json.load(open(os.path.join(d, "meta.json")))
    meta.pop("__manifest__")
    json.dump(meta, open(os.path.join(d, "meta.json"), "w"))
    idx_p = os.path.join(d, "index_0.json")
    index = json.load(open(idx_p))
    index = {k: v for k, v in index.items() if v["var"] != "zc_w"}
    json.dump(index, open(idx_p, "w"))
    with pytest.raises(CheckpointCorruptError, match="zc_w"):
        fluid.io.load_sharded(d)


def test_partial_coverage_raises(tmp_path):
    """An index slice covering only part of a tensor is corruption, not
    'the rest is zeros' — handcrafted legacy checkpoint whose one shard
    covers half of pc_w."""
    d = str(tmp_path / "ck")
    os.makedirs(d)
    np.savez(os.path.join(d, "shard_0.npz"),
             **{"pc_w@@0": np.ones((2, 1), "float32")})
    json.dump(
        {"pc_w@@0": {"var": "pc_w", "index": [[0, 2, None], [0, 1, None]]}},
        open(os.path.join(d, "index_0.json"), "w"))
    json.dump({"pc_w": {"shape": [4, 1], "dtype": "float32"}},
              open(os.path.join(d, "meta.json"), "w"))
    with pytest.raises(CheckpointCorruptError, match="partially covered"):
        fluid.io.load_sharded(d)


def test_multiproc_async_handle_is_precompleted():
    """Satellite: the multi-process fallback hands back a pre-completed
    handle, no dummy thread spawned just to join it."""
    from paddle_tpu.io import AsyncCheckpoint

    h = AsyncCheckpoint.completed()
    assert h.done()
    h.wait()  # no-op, no raise
    assert h._thread is None


# -----------------------------------------------------------------------
# CheckpointManager: rotation, LATEST, auto-resume
# -----------------------------------------------------------------------
def test_manager_rotation_and_latest(tmp_path):
    exe, loss = _build_sgd()
    mgr = CheckpointManager(str(tmp_path / "run"), keep_last=2)
    for s in (1, 2, 3, 4):
        exe.run(feed=_feed(s), fetch_list=[loss])
        mgr.save(s, extra={"s": s})
    steps = mgr.valid_steps()
    assert steps == [3, 4], steps  # keep-last-2 GC
    assert mgr.latest_step() == 4
    latest = json.load(open(str(tmp_path / "run" / "LATEST")))
    assert latest == {"step": 4, "dir": "step_4"}


def test_manager_restore_falls_back_past_corruption(tmp_path):
    """Acceptance: corrupt the newest checkpoint's shard; restore_or_init
    resumes from the previous valid one with bit-identical params."""
    exe, loss = _build_sgd(name="fb_w")
    scope = fluid.global_scope()
    mgr = CheckpointManager(str(tmp_path / "run"), keep_last=3)
    exe.run(feed=_feed(1), fetch_list=[loss])
    w_good = np.asarray(scope.find_var("fb_w")).copy()
    mgr.save(1)
    exe.run(feed=_feed(2), fetch_list=[loss])
    mgr.save(2)
    faultinject.corrupt_shard(mgr.step_dir(2))
    # clobber live params, then auto-resume
    scope.set_var("fb_w", np.full_like(w_good, 7.0))
    res = mgr.restore_or_init()
    assert res is not None and res.step == 1
    np.testing.assert_array_equal(
        np.asarray(scope.find_var("fb_w")), w_good)


def test_manager_never_gcs_newest_valid(tmp_path):
    """keep_last=1 with a torn NEWER directory must not delete the only
    valid checkpoint."""
    exe, loss = _build_sgd()
    mgr = CheckpointManager(str(tmp_path / "run"), keep_last=1)
    mgr.save(1)
    # a torn newer checkpoint: directory exists, no meta.json
    os.makedirs(mgr.step_dir(2), exist_ok=True)
    open(os.path.join(mgr.step_dir(2), "shard_0.npz"), "wb").write(b"torn")
    mgr.gc()
    assert mgr.valid_steps() == [1]
    res = mgr.restore_or_init()
    assert res is not None and res.step == 1


def test_manager_init_fn_when_nothing_restorable(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "empty"))
    called = []
    assert mgr.restore_or_init(init_fn=lambda: called.append(1)) is None
    assert called == [1]


def test_manager_async_save_flips_latest_after_write(tmp_path):
    exe, loss = _build_sgd(name="as_w")
    scope = fluid.global_scope()
    mgr = CheckpointManager(str(tmp_path / "run"), keep_last=2)
    snap = np.asarray(scope.find_var("as_w")).copy()
    h = mgr.save(5, asynchronous=True)
    assert h is not None
    # training continues while the write drains
    exe.run(feed=_feed(9), fetch_list=[loss])
    h.wait()
    assert mgr.latest_step() == 5
    scope.set_var("as_w", np.zeros_like(snap))
    res = mgr.restore_or_init()
    assert res.step == 5
    np.testing.assert_array_equal(np.asarray(scope.find_var("as_w")), snap)


# -----------------------------------------------------------------------
# crash during save (subprocess: the writer dies mid-shard-write)
# -----------------------------------------------------------------------
_KILLED_WRITER = '''
import os, sys
sys.path.insert(0, {repo!r})
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.resilience import CheckpointManager

x = layers.data("x", [4], dtype="float32")
pred = layers.fc(x, size=1, param_attr=fluid.ParamAttr(name="kw"),
                 bias_attr=False)
loss = layers.mean(pred)
fluid.optimizer.SGD(0.1).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(fluid.default_startup_program())
mgr = CheckpointManager({run_dir!r}, keep_last=3)
mgr.save(1)  # a good checkpoint first
np.save({w_out!r}, np.asarray(fluid.global_scope().find_var("kw")))
exe.run(feed={{"x": np.ones((2, 4), "float32")}}, fetch_list=[loss])
os.environ["FAULT_CKPT_KILL_AFTER_BYTES"] = "64"
mgr.save(2)  # writer dies mid-shard-write: os._exit(43)
print("UNREACHABLE", flush=True)
'''


def test_crash_during_save_recovers_to_previous(tmp_path):
    """Satellite: kill the writer mid-npz; the loader rejects the torn
    step_2 and restore_or_init falls back to step_1 bit-identically."""
    run_dir = str(tmp_path / "run")
    w_out = str(tmp_path / "w.npy")
    script = _KILLED_WRITER.format(repo=REPO, run_dir=run_dir, w_out=w_out)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode == 43, p.stdout + p.stderr
    assert "UNREACHABLE" not in p.stdout
    # step_2 is torn: shard truncated, meta.json never written
    assert not os.path.exists(os.path.join(run_dir, "step_2", "meta.json"))
    with pytest.raises(CheckpointCorruptError):
        fluid.io.load_sharded(os.path.join(run_dir, "step_2"))

    # a fresh process restores the previous valid checkpoint
    _build_sgd(name="kw")
    mgr = CheckpointManager(run_dir, keep_last=3)
    res = mgr.restore_or_init()
    assert res is not None and res.step == 1
    np.testing.assert_array_equal(
        np.asarray(fluid.global_scope().find_var("kw")), np.load(w_out))


_OVERWRITE_WRITER = '''
import os, sys
sys.path.insert(0, {repo!r})
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.resilience import CheckpointManager

x = layers.data("x", [4], dtype="float32")
pred = layers.fc(x, size=1, param_attr=fluid.ParamAttr(name="ow"),
                 bias_attr=False)
loss = layers.mean(pred)
fluid.optimizer.SGD(0.1).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(fluid.default_startup_program())
mgr = CheckpointManager({run_dir!r}, keep_last=3)
mgr.save(1)
mgr.save(2)
exe.run(feed={{"x": np.ones((2, 4), "float32")}}, fetch_list=[loss])
os.environ["FAULT_CKPT_KILL_AFTER_BYTES"] = "64"
mgr.save(2)  # RE-save the same step (the preemption-drain shape): dies
print("UNREACHABLE", flush=True)
'''


def test_killed_overwrite_of_existing_step_cannot_masquerade(tmp_path):
    """Re-saving an existing step dir (preemption drain re-checkpoints
    the current cursor) invalidates the old meta.json BEFORE touching the
    shards: a kill mid-rewrite leaves a skippable torn dir, never the old
    manifest's digests over half-new shards."""
    run_dir = str(tmp_path / "run")
    script = _OVERWRITE_WRITER.format(repo=REPO, run_dir=run_dir)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode == 43, p.stdout + p.stderr
    # step_2's stale meta.json is GONE (not lying about the torn shards)
    assert not os.path.exists(os.path.join(run_dir, "step_2", "meta.json"))
    # restore walks back to the intact step_1
    _build_sgd(name="ow")
    mgr = CheckpointManager(run_dir, keep_last=3)
    res = mgr.restore_or_init()
    assert res is not None and res.step == 1


# -----------------------------------------------------------------------
# NaN sentinel (FLAGS_check_numerics)
# -----------------------------------------------------------------------
def test_sentinel_skips_injected_step_and_recovers():
    """Acceptance: NaN at step K skips the step (params untouched, still
    finite) and training continues."""
    exe, loss = _build_sgd(name="nw")
    scope = fluid.global_scope()
    fluid.set_flags({"FLAGS_check_numerics": True})
    feed = _feed(3)
    exe.run(feed=feed, fetch_list=[loss])
    w_before = np.asarray(scope.find_var("nw")).copy()
    os.environ["FAULT_NAN_AT_STEP"] = "0"
    faultinject.reset()
    (bad,) = exe.run(feed=feed, fetch_list=[loss])
    assert np.isnan(np.asarray(bad)).all()  # the fetch reports the trip
    np.testing.assert_array_equal(
        np.asarray(scope.find_var("nw")), w_before)  # step skipped
    # next (clean) step updates params again and stays finite
    exe.run(feed=feed, fetch_list=[loss])
    w_after = np.asarray(scope.find_var("nw"))
    assert np.isfinite(w_after).all()
    assert not np.array_equal(w_after, w_before)


def test_sentinel_raises_after_n_consecutive_naming_fetch():
    """Acceptance: after N consecutive trips the executor raises with the
    offending fetch named; params stay finite and un-updated."""
    exe, loss = _build_sgd(name="nw2")
    scope = fluid.global_scope()
    fluid.set_flags({"FLAGS_check_numerics": True,
                     "FLAGS_check_numerics_max_consecutive": 3})
    feed = _feed(4)
    exe.run(feed=feed, fetch_list=[loss])
    w_before = np.asarray(scope.find_var("nw2")).copy()
    os.environ["FAULT_NAN_AT_STEP"] = "0+"
    faultinject.reset()
    with pytest.raises(NonFiniteStepError) as ei:
        for _ in range(10):
            exe.run(feed=feed, fetch_list=[loss])
    assert ei.value.var_name == loss.name
    assert ei.value.consecutive == 3
    np.testing.assert_array_equal(
        np.asarray(scope.find_var("nw2")), w_before)
    assert np.isfinite(np.asarray(scope.find_var("nw2"))).all()


def test_sentinel_catches_real_nan_state():
    """No injection: genuinely poisoned feeds trip on the first non-finite
    fetch/state var and never write it back."""
    exe, loss = _build_sgd(name="nw3")
    scope = fluid.global_scope()
    fluid.set_flags({"FLAGS_check_numerics": True,
                     "FLAGS_check_numerics_max_consecutive": 2})
    good = _feed(5)
    exe.run(feed=good, fetch_list=[loss])
    poison = {"x": np.full((4, 4), np.nan, "float32"), "y": good["y"]}
    with pytest.raises(NonFiniteStepError):
        for _ in range(3):
            exe.run(feed=poison, fetch_list=[loss])
    assert np.isfinite(np.asarray(scope.find_var("nw3"))).all()


def test_elastic_trainer_reports_nonfinite_task_failed(tmp_path):
    """The sentinel raise must reach the master as task_failed (lease
    re-queues) — not a published poisoned checkpoint."""
    from paddle_tpu.elastic import InMemStore, MasterService, ElasticTrainer

    fluid.reset_default_env()
    x = fluid.layers.data(name="x", shape=[1], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    pred = fluid.layers.fc(x, size=1, param_attr=fluid.ParamAttr(name="tf_w"))
    loss = fluid.layers.reduce_mean(
        fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(0.3).minimize(loss)

    np.save(str(tmp_path / "c0.npy"), np.linspace(-1, 1, 8, dtype="float32"))
    m = MasterService(InMemStore(), chunks_per_task=1, timeout_dur=60,
                      failure_max=3)
    m.set_dataset([str(tmp_path / "c0.npy")])

    def feed_fn(chunk):
        xs = np.load(chunk).reshape(-1, 1)
        yield {"x": np.full_like(xs, np.nan), "y": xs}

    fluid.set_flags({"FLAGS_check_numerics": True,
                     "FLAGS_check_numerics_max_consecutive": 1})
    exe = fluid.Executor(fluid.CPUPlace())
    t = ElasticTrainer(m, exe, feed_fn, [loss], str(tmp_path / "ck"),
                       num_passes=1)
    with pytest.raises(NonFiniteStepError):
        t.train()
    # the failure was REPORTED: the task went back to todo immediately
    c = m.counts()
    assert c["pending"] == 0 and c["todo"] == 1, c
    # and no checkpoint of the poisoned attempt was published
    assert t.ckpt.valid_steps() == []
    m.shutdown()


# -----------------------------------------------------------------------
# preemption drain
# -----------------------------------------------------------------------
def test_preemption_drain_checkpoints_and_exits_cleanly(tmp_path):
    """SIGTERM mid-run: the trainer finishes the in-flight step, drains an
    emergency checkpoint, returns cleanly; the leased task is NOT reported
    done and a successor worker finishes the job."""
    from paddle_tpu.elastic import InMemStore, MasterService, ElasticTrainer

    fluid.reset_default_env()
    x = fluid.layers.data(name="x", shape=[1], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    pred = fluid.layers.fc(x, size=1, param_attr=fluid.ParamAttr(name="pd_w"))
    loss = fluid.layers.reduce_mean(
        fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(0.3).minimize(loss)

    rng = np.random.RandomState(0)
    for i in range(4):
        np.save(str(tmp_path / f"c{i}.npy"),
                rng.uniform(-1, 1, 32).astype("float32"))
    m = MasterService(InMemStore(), chunks_per_task=1, timeout_dur=0.3,
                      failure_max=5)
    m.set_dataset([str(tmp_path / "c*.npy")])

    fired = [0]

    def feed_fn(chunk):
        xs = np.load(chunk).reshape(-1, 1)
        for i in range(0, len(xs), 8):
            fired[0] += 1
            if fired[0] == 3:
                # the preemption notice arrives DURING training
                os.kill(os.getpid(), signal.SIGTERM)
            xb = xs[i:i + 8]
            yield {"x": xb, "y": (2.0 * xb - 1.0).astype("float32")}

    exe = fluid.Executor(fluid.CPUPlace())
    with PreemptionDrain() as drain:
        t = ElasticTrainer(m, exe, feed_fn, [loss], str(tmp_path / "ck"),
                           num_passes=2, drain=drain)
        t.train()  # returns cleanly instead of dying mid-step
        assert drain.requested
    # the emergency checkpoint landed and is valid — in a FRESH step dir
    # (save seq > tasks_done cursor), so a kill during the drain write
    # could never have torn the previous valid checkpoint
    steps = t.ckpt.valid_steps()
    assert steps != []
    mf = json.load(open(os.path.join(
        t.ckpt.step_dir(steps[-1]), "meta.json")))["__manifest__"]
    assert mf["extra"]["tasks_done"] < steps[-1], (mf["extra"], steps)
    # the in-flight task was NOT reported finished; its lease re-queues
    time.sleep(0.5)
    assert m.counts()["pending"] == 0

    # a successor worker resumes from the drained checkpoint and finishes
    t2 = ElasticTrainer(m, exe, feed_fn, [loss], str(tmp_path / "ck"),
                        num_passes=2)
    t2.train()
    assert t2.pass_id == 2
    assert m.counts()["cur_pass"] == 2
    w = np.ravel(np.asarray(fluid.global_scope().find_var("pd_w")))[0]
    assert abs(w - 2.0) < 0.3, f"did not converge: w={w}"
    m.shutdown()


# -----------------------------------------------------------------------
# RPC retry / backoff
# -----------------------------------------------------------------------
def test_retry_with_backoff_bounds_and_jitter():
    calls = []
    delays = []

    def flaky():
        calls.append(1)
        if len(calls) < 4:
            raise ConnectionError("nope")
        return "ok"

    out = retry_with_backoff(flaky, retries=5, base_delay=0.01,
                             max_delay=0.04, sleep=delays.append)
    assert out == "ok" and len(calls) == 4
    # exponential, capped, jittered upward only
    assert len(delays) == 3
    for i, d in enumerate(delays):
        lo = min(0.04, 0.01 * (2 ** i))
        assert lo <= d <= lo * 1.5 + 1e-9

    def always_down():
        raise ConnectionError("always")

    with pytest.raises(ConnectionError):
        retry_with_backoff(always_down, retries=2, base_delay=0.001,
                           sleep=lambda _: None)


def test_rpc_drop_once_is_absorbed():
    """FAULT_RPC_DROP_ONCE: one dropped RPC costs a retry, not the run."""
    from paddle_tpu.elastic.master import InMemStore, MasterService
    from paddle_tpu.elastic.rpc import RemoteMaster, serve_master

    svc = MasterService(InMemStore(), failure_max=2)
    srv = serve_master(svc, port=0)
    try:
        m = RemoteMaster(srv.endpoint, max_retries=3,
                         retry_base_delay=0.01, retry_max_delay=0.05)
        os.environ["FAULT_RPC_DROP_ONCE"] = "counts"
        faultinject.reset()
        c = m.counts()
        assert c["cur_pass"] == 0
        assert "rpc_drop" in faultinject.fired  # the fault DID fire
        m.close()
    finally:
        srv.shutdown()
        srv.server_close()


def test_rpc_survives_master_restart():
    """Kill the master, restart it on the same port + store: in-flight
    worker calls ride the backoff across the outage."""
    import threading

    from paddle_tpu.elastic.master import InMemStore, MasterService
    from paddle_tpu.elastic.rpc import MasterServer, RemoteMaster

    store = InMemStore()
    svc = MasterService(store, failure_max=2)
    srv = MasterServer(svc, port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    host, port = srv.server_address
    m = RemoteMaster(f"{host}:{port}", max_retries=8,
                     retry_base_delay=0.02, retry_max_delay=0.2)
    assert m.counts()["cur_pass"] == 0

    srv.shutdown()
    srv.server_close()  # port freed (handler threads may linger...)
    m.close()  # ...so force the next call to reconnect through the outage

    def _restart():
        time.sleep(0.3)  # outage window: client must back off through it
        svc2 = MasterService(store, failure_max=2)
        srv2 = MasterServer(svc2, host=host, port=port)
        threading.Thread(target=srv2.serve_forever, daemon=True).start()
        _restart.srv = srv2

    t = threading.Thread(target=_restart)
    t.start()
    c = m.counts()  # spans the outage
    assert c["cur_pass"] == 0
    t.join()
    m.close()
    _restart.srv.shutdown()
    _restart.srv.server_close()


# -----------------------------------------------------------------------
# bench checkpoint cadence (BENCH_CKPT_DIR)
# -----------------------------------------------------------------------
def _run_bench(extra_env, timeout=560):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_TUNE": "0",
        "BENCH_DEADLINE_S": "0",
        "BENCH_COMPILE_CACHE": "0",
        "PYTHONPATH": REPO,
    })
    env.update(extra_env)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    line = next((ln for ln in out.stdout.splitlines()
                 if ln.strip().startswith("{")), None)
    assert line, f"no JSON line from bench.py:\n{out.stdout}\n{out.stderr}"
    return json.loads(line), out


def test_bench_ckpt_cadence_resumes(tmp_path):
    """BENCH_CKPT_DIR: the first run banks verified checkpoints on a
    cadence; a second run restores from the newest one instead of
    reinitializing."""
    ck = str(tmp_path / "bench_ck")
    env = {"BENCH_MODELS": "lenet", "BENCH_STEPS": "6", "BENCH_BS": "8",
           "BENCH_CKPT_DIR": ck, "BENCH_CKPT_EVERY": "2",
           "BENCH_CKPT_KEEP": "2"}
    res1, out1 = _run_bench(env)
    assert res1.get("metric") != "error", out1.stdout + out1.stderr
    assert res1["ckpt_every"] == 2
    mgr = CheckpointManager(os.path.join(ck, "lenet"))
    steps = mgr.valid_steps()
    assert steps and steps[-1] == 6, steps  # final sync save landed
    assert len(steps) <= 2  # BENCH_CKPT_KEEP rotation

    res2, out2 = _run_bench(env)
    assert res2.get("metric") != "error", out2.stdout + out2.stderr
    assert "resumed params from checkpoint step_6" in out2.stderr, (
        out2.stderr[-2000:])
    # the resumed segment numbers PAST the restored step (6 + 6), so its
    # checkpoints are not GC'd on arrival as older-than-newest-valid
    assert mgr.valid_steps()[-1] == 12, mgr.valid_steps()


# -----------------------------------------------------------------------
# end-to-end chaos: SIGKILL a trainer worker mid-task + drop one RPC
# (multiprocess; slow => out of tier-1 per the -m 'not slow' discipline)
# -----------------------------------------------------------------------
_CHAOS_SERVER = '''
import sys, time
sys.path.insert(0, {repo!r})
from paddle_tpu.elastic.master import FileStore, MasterService
from paddle_tpu.elastic.rpc import serve_master

svc = MasterService(FileStore(sys.argv[1]), chunks_per_task=1,
                    timeout_dur=3.0, failure_max=5)
svc.set_dataset([sys.argv[2]])
srv = serve_master(svc, port=0)
print("SERVING", srv.endpoint, flush=True)
while True:
    time.sleep(0.2)
'''

_CHAOS_WORKER = '''
import os, sys
sys.path.insert(0, {repo!r})
import numpy as np
import paddle_tpu as fluid
from paddle_tpu.elastic import ElasticTrainer
from paddle_tpu.elastic.rpc import RemoteMaster

endpoint, ckpt_dir, num_passes = sys.argv[1], sys.argv[2], int(sys.argv[3])

x = fluid.layers.data(name="x", shape=[1], dtype="float32")
y = fluid.layers.data(name="y", shape=[1], dtype="float32")
pred = fluid.layers.fc(x, size=1, param_attr=fluid.ParamAttr(name="cw"))
loss = fluid.layers.reduce_mean(fluid.layers.square_error_cost(pred, y))
fluid.optimizer.SGD(0.3).minimize(loss)

def feed_fn(chunk):
    xs = np.load(chunk).reshape(-1, 1)
    for i in range(0, len(xs), 8):
        xb = xs[i:i + 8]
        yield {{"x": xb, "y": (2.0 * xb - 1.0).astype("float32")}}

class Noisy:
    def __init__(self, m):
        self._m = m
    def __getattr__(self, n):
        return getattr(self._m, n)
    def task_finished(self, task_id):
        self._m.task_finished(task_id)
        print("TASK", task_id, flush=True)

m = RemoteMaster(endpoint, max_retries=8, retry_base_delay=0.05,
                 retry_max_delay=0.5)
exe = fluid.Executor(fluid.CPUPlace())
t = ElasticTrainer(Noisy(m), exe, feed_fn, [loss], ckpt_dir,
                   num_passes=num_passes, idle_wait=0.1)
t.train()
w = float(np.ravel(np.asarray(fluid.global_scope().find_var("cw")))[0])
print("DONE", t.pass_id, w, flush=True)
'''


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_chaos_sigkill_worker_and_dropped_rpc_recover(tmp_path):
    """Acceptance e2e: a worker SIGKILLed mid-task AND one dropped master
    RPC both recover to a completed run with the same final pass count as
    the fault-free run."""
    rng = np.random.RandomState(0)
    for i in range(6):
        np.save(str(tmp_path / f"chunk{i}.npy"),
                rng.uniform(-1, 1, 32).astype("float32"))
    glob_pat = str(tmp_path / "chunk*.npy")
    num_passes = 2

    # ---- fault-free reference run (in-process master, same protocol)
    from paddle_tpu.elastic import ElasticTrainer, FileStore, MasterService

    fluid.reset_default_env()
    x = fluid.layers.data(name="x", shape=[1], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    pred = fluid.layers.fc(x, size=1, param_attr=fluid.ParamAttr(name="cw"))
    loss = fluid.layers.reduce_mean(
        fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(0.3).minimize(loss)

    def feed_fn(chunk):
        xs = np.load(chunk).reshape(-1, 1)
        for i in range(0, len(xs), 8):
            xb = xs[i:i + 8]
            yield {"x": xb, "y": (2.0 * xb - 1.0).astype("float32")}

    m0 = MasterService(FileStore(str(tmp_path / "ref.snap")),
                       chunks_per_task=1, timeout_dur=3.0, failure_max=5)
    m0.set_dataset([glob_pat])
    exe = fluid.Executor(fluid.CPUPlace())
    t0 = ElasticTrainer(m0, exe, feed_fn, [loss],
                        str(tmp_path / "ref_ck"), num_passes=num_passes)
    t0.train()
    faultfree_passes = m0.counts()["cur_pass"]
    assert faultfree_passes == num_passes
    m0.shutdown()

    # ---- chaos run: real subprocesses
    snap = str(tmp_path / "chaos.snap")
    server_py = str(tmp_path / "server.py")
    worker_py = str(tmp_path / "worker.py")
    open(server_py, "w").write(_CHAOS_SERVER.format(repo=REPO))
    open(worker_py, "w").write(_CHAOS_WORKER.format(repo=REPO))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("FAULT_RPC_DROP_ONCE", None)
    ckpt = str(tmp_path / "chaos_ck")

    server = subprocess.Popen(
        [sys.executable, server_py, snap, glob_pat], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        line = server.stdout.readline()
        assert "SERVING" in line, line
        endpoint = line.split()[1]

        # worker A: drops one RPC (absorbed by backoff), then gets
        # SIGKILLed the moment it reports its first finished task —
        # i.e. mid-run, holding a leased task it will never finish
        env_a = {**env, "FAULT_RPC_DROP_ONCE": "*"}
        wa = subprocess.Popen(
            [sys.executable, worker_py, endpoint, ckpt, str(num_passes)],
            env=env_a, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        saw_task = False
        for line in wa.stdout:
            if line.startswith("TASK"):
                saw_task = True
                os.kill(wa.pid, signal.SIGKILL)
                break
        assert saw_task, "worker A never finished a task"
        wa.wait(timeout=60)
        assert wa.returncode == -signal.SIGKILL

        # worker B: clean env, resumes from A's checkpoint + the master
        # queue; A's leased task re-dispatches on lease expiry
        wb = subprocess.Popen(
            [sys.executable, worker_py, endpoint, ckpt, str(num_passes)],
            env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        ob, _ = wb.communicate(timeout=480)
        assert wb.returncode == 0, ob[-3000:]
        done = [ln for ln in ob.splitlines() if ln.startswith("DONE")]
        assert done, ob[-3000:]
        _, passes, w = done[0].split()
        # same final pass count as the fault-free run, converged params
        assert int(passes) == faultfree_passes
        assert abs(float(w) - 2.0) < 0.3, w
    finally:
        server.kill()
        server.wait()
