"""Downpour async parameter-server mode
(reference: python/paddle/fluid/distributed/ DownpourSGD/node/ps_instance +
async_executor.py pslib hooks; the executable server here is
paddle_tpu/distributed/ps_core.py instead of Baidu's closed PSLIB)."""

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.distributed import (
    DownpourSGD,
    PaddlePSInstance,
    PSCore,
    SparseTable,
)

VOCAB = 100
EMB_DIM = 8


def _write_ctr_files(tmp_path, n_files=2, lines=300, seed=0):
    """MultiSlot lines: '1 <id> 1 <label>'; label is a learnable function
    of the id (reference data: dist_ctr_reader-style synthetic slots)."""
    rng = np.random.RandomState(seed)
    files = []
    for f in range(n_files):
        path = str(tmp_path / f"part-{f}")
        with open(path, "w") as fh:
            for _ in range(lines):
                i = int(rng.randint(VOCAB))
                label = 1.0 if i % 2 == 0 else 0.0
                fh.write(f"1 {i} 1 {label}\n")
        files.append(path)
    return files


def _build_ctr_model():
    ids = fluid.layers.data(name="ids", shape=[1], dtype="int64")
    label = fluid.layers.data(name="label", shape=[1], dtype="float32")
    emb = fluid.layers.embedding(
        ids, size=[VOCAB, EMB_DIM], is_distributed=True,
        param_attr=fluid.ParamAttr(name="dist_emb"),
    )
    fc1 = fluid.layers.fc(emb, size=16, act="relu")
    logit = fluid.layers.fc(fc1, size=1)
    loss = fluid.layers.reduce_mean(
        fluid.layers.sigmoid_cross_entropy_with_logits(logit, label)
    )
    return loss


FEED_DESC = """
name: "MultiSlotDataFeed"
batch_size: 32
multi_slot_desc {
  slots { name: "ids" type: "uint64" is_dense: true is_used: true }
  slots { name: "label" type: "float" is_dense: true is_used: true }
}
"""


def test_downpour_minimize_descs():
    """minimize returns [ps_param, worker_skipped_ops] with the reference's
    desc structure (distributed/downpour.py:46)."""
    fluid.reset_default_env()
    loss = _build_ctr_model()
    ps_param, skipped = DownpourSGD(learning_rate=0.1, window=1).minimize(loss)

    assert skipped == ["lookup_table", "lookup_table_grad"]
    assert ps_param["table_name"] == "dist_emb"
    tables = ps_param["server_param"]["downpour_server_param"][
        "downpour_table_param"]
    assert [t["table_class"] for t in tables] == [
        "DownpourSparseTable", "DownpourDenseTable"]
    assert tables[0]["accessor"]["embedx_dim"] == EMB_DIM
    # dense table holds every non-embedding param element
    n_dense = sum(
        int(np.prod(p.shape))
        for p in loss.block.program.global_block().all_parameters()
        if p.name != "dist_emb"
    )
    assert tables[1]["accessor"]["fea_dim"] == n_dense
    trainer = ps_param["trainer_param"]
    assert trainer["sparse_table"][0]["slot_key"] == ["ids"]
    assert trainer["sparse_table"][0]["slot_gradient"][0].endswith("@GRAD")
    assert "dist_emb" not in trainer["dense_table"][0]["dense_variable_name"]


def test_downpour_trains_end_to_end(tmp_path):
    """Hogwild workers against the in-process PS: loss drops from the
    ~log(2) cold start, rows materialize lazily, checkpoints round-trip
    (reference flow: async_executor.py init_server/init_worker/run)."""
    fluid.reset_default_env()
    fluid.default_startup_program().random_seed = 1  # the dense init
    loss = _build_ctr_model()
    ps_param, _ = DownpourSGD(learning_rate=0.2, window=1).minimize(loss)
    # the dense table's rule has no bias correction (beta1 0.99, beta2
    # 0.9999): its first steps are lr whole, whatever the gradient's size.
    # From rows of +-1e-4 that is what decides the run: at 0.05 the hidden
    # biases outrun the embeddings, every relu can die in the first
    # batches, and whether it does hangs on how the two Hogwild threads
    # interleave (PR 46: 5 of 6 lone runs stayed at log 2).  At 0.005 the
    # rows grow first and every interleaving converges (0.69 -> under 0.05)
    ps_param["server_param"]["downpour_server_param"][
        "downpour_table_param"][1]["accessor"]["dense_sgd_param"]["adam"][
        "learning_rate"] = 0.005

    exe = fluid.AsyncExecutor(fluid.CPUPlace())
    exe.init_server(ps_param)
    exe.init_worker(ps_param)
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    # the distributed table must not materialize on the worker
    assert fluid.global_scope().find_var("dist_emb") is None
    exe.init_model()

    files = _write_ctr_files(tmp_path)
    desc = fluid.DataFeedDesc(FEED_DESC)

    def eval_loss():
        exe._pull_dense_into_scope()
        rng = np.random.RandomState(7)
        ids = rng.randint(VOCAB, size=(64, 1)).astype(np.int64)
        label = (ids % 2 == 0).astype(np.float32)
        rows = exe._ps.sparse(0).pull(ids.reshape(-1))
        emb_out = exe._emb_map[0][1]
        v = fluid.Executor(fluid.CPUPlace(), donate_states=False).run(
            program=exe._worker_program,
            feed={"ids": ids, "label": label,
                  emb_out: rows.reshape(64, EMB_DIM)},
            fetch_list=[loss.name],
        )
        return float(np.ravel(np.asarray(v[0]))[0])

    first = eval_loss()
    assert abs(first - np.log(2.0)) < 0.05  # cold start: logits ~ 0

    for _ in range(4):  # multiple passes over the files
        exe.run(fluid.default_main_program(), desc, files, thread_num=2,
                fetch=[loss])
    final = eval_loss()
    assert final < first - 0.05, f"loss did not drop: {first} -> {final}"
    # only touched rows exist — never the dense vocab
    assert 0 < len(exe._ps.sparse(0)) <= VOCAB

    # checkpoint round-trip (reference: save_model / PSLIB load)
    path = str(tmp_path / "ps_ckpt.npz")
    exe.save_model(path)
    ps2 = PSCore.from_server_desc(ps_param["server_param"])
    ps2.load(path)
    ids = np.array([2, 4, 6], dtype=np.int64)
    np.testing.assert_allclose(
        ps2.sparse(0).pull(ids), exe._ps.sparse(0).pull(ids), rtol=1e-6
    )
    np.testing.assert_allclose(ps2.dense(1).pull(), exe._ps.dense(1).pull())


def test_dense_rule_steps_by_its_rate_whatever_the_gradients_size():
    """Why test_downpour_trains_end_to_end's dense rate is 0.005 and not
    0.05: the dense table's rule (the reference's, beta1 0.99, beta2
    0.9999, no bias correction) moves a coordinate by the rate itself on
    its first push and by rate x sqrt(n) after n pushes of one sign,
    whether the gradient is 1e-4 or 1: from embedding rows of +-1e-4 the
    hidden biases move ~0.05 a step while the rows' signal is ~1e-5."""
    from paddle_tpu.distributed.ps_core import DenseTable

    for g in (1e-4, 1.0):
        t = DenseTable(dim=1, learning_rate=0.05)
        t.init(np.zeros(1, np.float32))
        t.push(np.full(1, g, np.float32))
        # sqrt(ada) is 1e-2 x g after one push: ada_epsilon (1e-8) is 1%
        # of it at g = 1e-4
        np.testing.assert_allclose(t.pull(), -0.05, rtol=2e-2)
        for _ in range(15):
            t.push(np.full(1, g, np.float32))
        step = t.pull().copy()
        t.push(np.full(1, g, np.float32))
        np.testing.assert_allclose(step - t.pull(), 0.05 * np.sqrt(17),
                                   rtol=0.1)


def test_sparse_table_uint64_ids_checkpoint(tmp_path):
    """Hashed uint64 feature ids (bit-pattern int64 from the MultiSlot
    parser, or raw ints >= 2**63) are one row either way, and survive a
    save/load round trip (state_dict keeps a uint64 id vector)."""
    t = SparseTable(dim=2, initial_range=0.1)
    big = 2 ** 63 + 17
    as_int64 = np.array([big], dtype=np.uint64).view(np.int64)  # negative
    row_a = t.pull(np.array([big], dtype=np.uint64))
    row_b = t.pull(as_int64)
    np.testing.assert_array_equal(row_a, row_b)
    assert len(t) == 1

    core = PSCore()
    core.tables[0] = t
    path = str(tmp_path / "u64.npz")
    core.save(path)
    t2 = SparseTable(dim=2)
    core2 = PSCore()
    core2.tables[0] = t2
    core2.load(path)
    np.testing.assert_array_equal(t2.pull(as_int64), row_a)
    assert len(t2) == 1


def test_async_executor_stop_restores_startup():
    """stop() re-inserts the distributed table's initializer so a later
    non-downpour run can materialize and train the table locally."""
    fluid.reset_default_env()
    loss = _build_ctr_model()
    ps_param, _ = DownpourSGD(learning_rate=0.1).minimize(loss)
    sp = fluid.default_startup_program()
    n_ops_before = len(sp.global_block().ops)

    exe = fluid.AsyncExecutor(fluid.CPUPlace())
    exe.init_server(ps_param)
    exe.init_worker(ps_param)
    assert len(sp.global_block().ops) < n_ops_before
    exe.stop()
    assert len(sp.global_block().ops) == n_ops_before
    assert len(sp.global_block().desc.ops) == n_ops_before
    # the restored startup program initializes the table again
    fluid.Executor(fluid.CPUPlace()).run(sp)
    tbl = fluid.global_scope().find_var("dist_emb")
    assert tbl is not None and np.asarray(tbl).shape == (VOCAB, EMB_DIM)


def test_sparse_table_accessor_semantics():
    """Row-wise adagrad with lazy init, duplicate-id merge, and weight
    bounds (reference: DownpourFeatureValueAccessor sparse_sgd_param)."""
    t = SparseTable(dim=2, learning_rate=1.0, initial_g2sum=0.0,
                    initial_range=0.0, weight_bounds=(-0.5, 0.5))
    w0 = t.pull(np.array([3]))
    np.testing.assert_allclose(w0, 0.0)  # initial_range=0 -> zero init

    # one push with a duplicated id accumulates before the update
    t.push(np.array([3, 3]), np.array([[1.0, 0.0], [1.0, 0.0]]))
    w1 = t.pull(np.array([3]))
    # g=2 merged, g2sum=4, step = lr*g/sqrt(g2sum) = 1.0 -> clipped to bound
    np.testing.assert_allclose(w1[0, 0], -0.5)
    np.testing.assert_allclose(w1[0, 1], 0.0)
    assert len(t) == 1


def test_ps_instance_role_math():
    """Rank->role assignment matches the reference's two modes
    (ps_instance.py _set_nodetype)."""
    import os

    env = {"PADDLE_TRAINER_ID": None, "PADDLE_TRAINERS": None}
    saved = {k: os.environ.get(k) for k in env}
    try:
        os.environ["PADDLE_TRAINERS"] = "4"  # 4 procs = 2 nodes x 2 procs
        roles_mode1 = []
        for rank in range(4):
            os.environ["PADDLE_TRAINER_ID"] = str(rank)
            inst = PaddlePSInstance(server_worker_mode=1, proc_per_node=2)
            roles_mode1.append(
                "s" if inst.is_server() else "w" if inst.is_worker() else "-"
            )
        assert roles_mode1 == ["s", "w", "s", "w"]  # interleaved per node

        roles_mode0 = []
        for rank in range(4):
            os.environ["PADDLE_TRAINER_ID"] = str(rank)
            inst = PaddlePSInstance(server_worker_mode=0, proc_per_node=2)
            roles_mode0.append("s" if inst.is_server() else "w")
        assert roles_mode0 == ["s", "s", "w", "w"]  # servers first

        os.environ["PADDLE_TRAINER_ID"] = "1"
        inst = PaddlePSInstance(server_worker_mode=1, proc_per_node=2)
        assert inst.get_worker_num() == 2 and inst.get_server_num() == 2
        assert inst.is_worker() and inst.get_worker_index() == 0
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
