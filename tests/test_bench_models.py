"""Benchmark model zoo parity (reference: benchmark/fluid/models/ — mnist,
resnet, vgg, stacked_dynamic_lstm, machine_translation, se_resnext)."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import models


def _train(spec, steps=3, lr=0.01):
    fluid.optimizer.AdamOptimizer(learning_rate=lr).minimize(spec.loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = spec.synthetic_batch(4)
    losses = []
    for _ in range(steps):
        (lv,) = exe.run(feed=batch, fetch_list=[spec.loss])
        losses.append(float(np.ravel(np.asarray(lv))[0]))
    assert np.isfinite(losses).all()
    return losses


def test_machine_translation_trains():
    spec = models.machine_translation(
        dict_size=100, embedding_dim=16, encoder_size=16, decoder_size=16
    )
    losses = _train(spec, steps=6, lr=0.005)
    assert losses[-1] < losses[0]


def test_se_resnext_trains():
    spec = models.se_resnext(
        class_num=10, layers_cfg=(1, 1, 1, 1), cardinality=8,
        reduction_ratio=4, img_shape=(3, 32, 32),
    )
    losses = _train(spec, steps=3)


def test_debugger_prints_program():
    x = fluid.layers.data("x", [4], dtype="float32")
    y = fluid.layers.fc(x, size=2)
    text = fluid.debugger.pprint_program_codes(fluid.default_main_program())
    assert "mul(" in text and "var x" in text
    dot = fluid.debugger.draw_block_graphviz(
        fluid.default_main_program().global_block(), path="/tmp/g.dot"
    )
    assert "digraph" in dot


def test_chunk_evaluator_accumulates():
    from paddle_tpu.core.lod import create_lod_tensor

    inf = fluid.layers.data("inf", [1], dtype="int64", lod_level=1)
    lab = fluid.layers.data("lab", [1], dtype="int64", lod_level=1)
    ev = fluid.evaluator.ChunkEvaluator(
        inf, lab, chunk_scheme="IOB", num_chunk_types=1
    )
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    seq = np.array([[0], [1], [2]], dtype="int64")
    for _ in range(3):
        exe.run(
            feed={"inf": create_lod_tensor([seq]),
                  "lab": create_lod_tensor([seq])},
            fetch_list=[ev.metrics[0]],
        )
    p, r, f1 = ev.eval(exe)
    assert float(p) == 1.0 and float(r) == 1.0 and float(f1) == 1.0


def test_vgg19_builds_and_infers():
    """VGG-19 (IntelOptimizedPaddle.md benchmark model): depth-19 block
    layout builds, and the for_test clone runs a forward pass."""
    spec = models.vgg19(class_num=10, img_shape=(3, 32, 32))
    # 19 = 16 convs + 3 fc; count conv2d ops in the program
    prog = fluid.default_main_program()
    n_convs = sum(1 for op in prog.global_block().ops if op.type == "conv2d")
    assert n_convs == 16
    test_prog = prog.clone(for_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = spec.synthetic_batch(2)
    (pred,) = exe.run(program=test_prog, feed=batch,
                      fetch_list=[spec.extras["predict"]])
    pred = np.asarray(pred)
    assert pred.shape == (2, 10)
    np.testing.assert_allclose(pred.sum(axis=1), 1.0, atol=1e-4)


def test_alexnet_googlenet_forward():
    """AlexNet + GoogLeNet (benchmark/paddle/image/{alexnet,googlenet}.py
    configs) build at benchmark shapes and produce valid softmax output."""
    for builder in (models.alexnet, models.googlenet):
        fluid.reset_default_env()
        spec = builder(class_num=10)
        test_prog = fluid.default_main_program().clone(for_test=True)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        batch = spec.synthetic_batch(2)
        (pred,) = exe.run(program=test_prog, feed=batch,
                          fetch_list=[spec.extras["predict"]])
        pred = np.asarray(pred)
        assert pred.shape == (2, 10), spec.name
        np.testing.assert_allclose(pred.sum(axis=1), 1.0, atol=1e-4,
                                   err_msg=spec.name)


def test_bench_survives_single_model_failure(monkeypatch, capsys):
    """One model crashing (e.g. a kernel lowering error) must not abort
    the other models' measurements: bench records the error per model and
    still prints a primary result line — and exits non-zero, because a
    requested model failed."""
    import json as _json

    import bench

    def fake_run_model(model, steps, peak_flops, amp="1", layout="NCHW"):
        if model == "transformer":
            raise ValueError("pallas lowering rejected block shape")
        return {"metric": f"{model}_train_examples_per_sec_per_chip",
                "value": 100.0, "unit": "examples/sec",
                "vs_baseline": None}

    monkeypatch.setattr(bench, "run_model", fake_run_model)
    monkeypatch.setenv("BENCH_MODELS", "lenet,transformer,deepfm")
    monkeypatch.setenv("BENCH_TUNE", "0")
    monkeypatch.setenv("BENCH_DEADLINE_S", "0")
    monkeypatch.setenv("BENCH_COMPILE_CACHE", "0")
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    rec = _json.loads(line)
    assert rec["metric"] == "lenet_train_examples_per_sec_per_chip"
    assert len(rec["extra_metrics"]) == 1
    assert rec["model_errors"][0]["model"] == "transformer"
    assert "block shape" in rec["model_errors"][0]["detail"]


def test_bench_all_models_failing_exits_2(monkeypatch, capsys):
    import bench

    def fake_run_model(model, steps, peak_flops, amp="1", layout="NCHW"):
        raise ValueError("boom")

    monkeypatch.setattr(bench, "run_model", fake_run_model)
    monkeypatch.setenv("BENCH_MODELS", "lenet,deepfm")
    monkeypatch.setenv("BENCH_TUNE", "0")
    monkeypatch.setenv("BENCH_DEADLINE_S", "0")
    monkeypatch.setenv("BENCH_COMPILE_CACHE", "0")
    try:
        bench.main()
        raised = False
    except SystemExit as e:
        raised = e.code == 2
    assert raised
    rec = __import__("json").loads(
        capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["metric"] == "error"


@pytest.mark.parametrize("amp,layout,want", [
    (None, None, ((None, False), "auto")),
    ("keep", None, (("bfloat16", True), "auto")),
    ("1", "NHWC", (("bfloat16", False), "NHWC")),
    ("0", "NCHW", ((None, False), "NCHW")),
])
def test_bench_apply_config_sets_only_what_is_given(amp, layout, want):
    """bench.py sets the AMP policy and FLAGS_conv_layout only where
    BENCH_AMP / BENCH_LAYOUT are given; with neither the policy stays
    unset and the layout "auto", which a TPU program resolves to the
    chip's measured winners, as the benchmark's cells rely on."""
    import bench
    from paddle_tpu import flags
    from paddle_tpu.core import amp as amp_policy

    fluid.enable_amp("bfloat16", keep_output=True)   # what a run before left
    fluid.set_flags({"FLAGS_conv_layout": "NHWC"})
    try:
        bench._apply_config(amp, layout)
        dtype, keep = amp_policy.amp_dtype(), amp_policy.keep_output()
        assert ((None if dtype is None else str(dtype), keep),
                flags.flag("conv_layout")) == want
        assert amp_policy._POLICY["explicit"] is (amp is not None)
        if amp is None and layout is None:
            with flags.tpu_trace_scope(True):
                assert str(amp_policy.amp_dtype()) == "bfloat16"
                assert amp_policy.keep_output() is True
                assert flags.conv_layout() == "NHWC"
    finally:
        amp_policy.reset_amp()
        fluid.set_flags({"FLAGS_conv_layout": "auto"})


def test_bench_main_passes_unset_amp_and_layout_through(monkeypatch, capsys):
    """Nothing exported: run_model gets amp=None, layout=None (no tuner,
    no "1"/NCHW default); BENCH_AMP / BENCH_LAYOUT go through as given."""
    import bench

    seen = []

    def fake_run_model(model, steps, peak_flops, amp="1", layout="NCHW"):
        seen.append((amp, layout))
        return {"metric": model, "value": 1.0, "unit": "x",
                "vs_baseline": None}

    monkeypatch.setattr(bench, "run_model", fake_run_model)
    monkeypatch.setenv("BENCH_MODELS", "lenet")
    monkeypatch.setenv("BENCH_DEADLINE_S", "0")
    monkeypatch.setenv("BENCH_COMPILE_CACHE", "0")
    for var in ("BENCH_AMP", "BENCH_LAYOUT", "BENCH_TUNE"):
        monkeypatch.delenv(var, raising=False)
    bench.main()
    monkeypatch.setenv("BENCH_AMP", "keep")
    monkeypatch.setenv("BENCH_LAYOUT", "NHWC")
    bench.main()
    capsys.readouterr()
    assert seen == [(None, None), ("keep", "NHWC")]


@pytest.mark.parametrize("name", ["BENCH_UNROLL", "BENCH_UNROLL_MODE"])
def test_bench_reads_no_steps_a_dispatch_variable(name):
    """bench.py steps through Executor.run alone: the two variables that
    chose K steps a dispatch (and how they were unrolled) are read nowhere
    in it, so setting one changes nothing."""
    import re

    import bench

    with open(bench.__file__) as f:
        read = set(re.findall(r"BENCH_[A-Z0-9_]+", f.read()))
    assert name not in read
    assert "BENCH_STEPS" in read  # the scan finds what the file does read
