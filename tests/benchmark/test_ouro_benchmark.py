"""What PR 27 adds to the benchmark: the ouro-2.6b configuration (its file
against the published config, its FLOP count, its reference against mutants
of itself), the scope reduction of a trace (benchmark/harness/scope_time.py)
and the four readers of `ouro-train-loop4`, on a small recorded trace."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import manifest, reference, scope_time

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELL = "ouro-train-loop4"
# https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json as the
# model-configs catalog has it: every number of the published config
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "num_attention_heads": 16, "num_hidden_layers": 48,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_theta": 1000000,
    "total_ut_steps": 4, "early_exit_threshold": 1, "vocab_size": 49152}
PUBLISHED_OTHER = {
    "hidden_act": "silu", "model_type": "ouro", "rope_scaling": None,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "layer_types": ["full_attention"] * 48}


def _config():
    return json.load(open(os.path.join(
        REPO, "benchmark", "configs", "ouro-2.6b.json")))


def _reader(name):
    return manifest.load_py(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"))


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------
def test_ouro_file_holds_the_published_config_and_cuts_the_depth_alone():
    cfg = _config()
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key, want in {**PUBLISHED, **PUBLISHED_OTHER}.items():
        if key in cfg["reduced"]:
            assert 4 <= cfg[key] <= 8 and cfg[key] < want
        else:
            assert cfg[key] == want, key
    entry = [c for c in MANIFEST["configs"] if c["name"] == "ouro-2.6b"][0]
    assert cfg["source"].startswith(entry["source"])
    assert entry["source"].endswith("Ouro-2.6B/blob/main/config.json")
    for key in ("sandwich_norm", "final_norm_in_loop", "gate", "entropy_beta",
                "optimizer", "max_length"):
        assert len(cfg["assumed"][key]) > 20, key
    # the cut is stated with the analysis that chose it
    for key in ("parameters", "step_temp_bytes", "step_argument_bytes",
                "beside_first_step_bytes"):
        assert cfg["memory"][key] > 0, key
    assert cfg["memory"]["beside_first_step_bytes"] < 15e9
    assert "pipeline" in cfg["deployment"]


def test_ouro_configuration_entry_and_files():
    """test_benchmark_manifest.py::test_configuration_entry_and_files for
    ouro-2.6b, but for its reading of `num_hidden_layers` as a width
    (conftest.py): a width is a key of the published config other than the
    depth, and none is in `reduced`."""
    cfg = [c for c in MANIFEST["configs"] if c["name"] == "ouro-2.6b"][0]
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    data = _config()
    for key in ("source", "reduced", "assumed", "deployment", "kind"):
        assert key in data, key
    assert data["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    widths = set(PUBLISHED) - {"num_hidden_layers"}
    assert not widths & set(cfg["reduced"])
    base = os.path.splitext(os.path.join(REPO, cfg["file"]))[0]
    assert os.path.isfile(base + ".py")
    assert data["kind"] == "train" and os.path.isfile(base + ".reference.py")
    assert {"loss_rtol", "grad_cos_min", "grad_norm_rtol",
            "param_norm_factor"} <= set(data["reference"])
    assert len(data["reduced_why"]) > 40
    assert any(w["config"] == cfg["name"] for w in MANIFEST["workloads"])
    for text in (cfg["why"], cfg["source"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_ouro_flops_are_counted_from_the_shapes():
    cfg = _config()
    mod = manifest.Cell(MANIFEST, CELL).config_module
    # a layer: q, k, v, o of 2048 x 2048 and gate, up, down of 2048 x 5632
    assert mod.layer_matmul_params(cfg) == 4 * 2048 ** 2 + 3 * 2048 * 5632 \
        == 51380224
    R, L, S = 4, cfg["num_hidden_layers"], 2048
    per_token = 6 * (R * L * 51380224 + R * 2048 * 49152) \
        + 3 * 4 * S * 2048 * R * L
    assert mod.flops_per_sample(cfg) == pytest.approx(S * per_token, rel=1e-12)
    # the uncut model: ~70 GFLOP a token, of which the head is 4%
    full = mod.flops_per_sample({**cfg, "num_hidden_layers": 48}) / S
    assert 70e9 < full < 72e9
    assert 6 * R * 2048 * 49152 / full == pytest.approx(0.035, abs=0.005)


def test_ouro_batch_is_packed_shifted_and_the_seeds():
    mod = manifest.Cell(MANIFEST, CELL, rehearse=True).config_module
    cfg = {"vocab_size": 49152, "max_length": 2048}
    spec = type("S", (), {"feed_names": ["tokens", "labels"]})
    a = mod.make_batch(cfg, spec, 2, 3000000019)
    b = mod.make_batch(cfg, spec, 2, 3000000019)
    c = mod.make_batch(cfg, spec, 2, 3000000020)
    assert a["tokens"].shape == a["labels"].shape == (2, 2048)
    assert a["tokens"].dtype == np.int64
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert (a["tokens"] != c["tokens"]).mean() > 0.99
    assert a["tokens"].min() >= 0 and 49000 < a["tokens"].max() < 49152


@pytest.fixture
def first_step():
    """The rehearsal's first step as the benchmark takes it: (FirstStep,
    its parameters, the fetched loss, the batch).  A test's own: FirstStep
    reads the gradient from the scope, and every test has a fresh one."""
    import jax
    import paddle_tpu as fluid

    cell = manifest.Cell(MANIFEST, CELL, rehearse=True)
    spec = cell.config_module.build(cell.config, 5)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = cell.config_module.make_batch(cell.config, spec, 2, 5)
    first = reference.FirstStep(cell, spec)
    loss = float(np.ravel(np.asarray(
        exe.run(feed=batch, fetch_list=[spec.loss])[0]))[0])
    return first, first.params, loss, jax.device_put(batch)


@pytest.mark.parametrize("wrong,refused_by", [
    (None, ()),
    ("three_trips", ("loss", "cosine")),
    ("last_trip_gradient", ("cosine", "norm")),
    ("entropy_dropped", ("loss",)),
    ("gate_dropped", ("loss", "cosine")),
    ("a_norm_left_out", ("loss", "cosine")),
    ("fp8_matmuls", ("cosine", "norm")),
])
def test_ouro_reference_refuses_what_is_wrong(first_step, wrong, refused_by):
    """The program against the reference: nothing to say.  Against a
    reference with one thing wrong (tools/ouro_reference_probe.py, which
    makes the same comparison on the chip at the real size): refused."""
    import types

    from tools import ouro_reference_probe as probe

    first, params, loss, batch = first_step
    first.params = params
    first.module = types.SimpleNamespace(loss_and_grad=probe.mutant(wrong))
    found, problems = first.compare(loss, batch, 2)
    if wrong is None:
        assert problems == []
        assert found["loss_rel"] < 1e-5 and found["grad_cos"] > 1 - 1e-6
        assert abs(found["grad_norm_ratio"] - 1) < 1e-4
        assert found["param_norm_far"] < 1.001
    for word in refused_by:
        assert any(word in p for p in problems), (word, problems)


def test_the_mutants_are_the_probes_and_an_unknown_one_is_an_error():
    from tools import ouro_reference_probe as probe

    assert set(probe.MUTANTS) == {
        "three_trips", "last_trip_gradient", "entropy_dropped",
        "gate_dropped", "a_norm_left_out", "fp8_matmuls"}
    with pytest.raises(KeyError):
        probe.mutant("no_such_mutant")


# ---------------------------------------------------------------------------
# device time by name scope, and the readers
# ---------------------------------------------------------------------------
def _xspace(name="trace_loop_scopes.textproto"):
    from jax.profiler import ProfileData

    return ProfileData.text_proto_to_serialized_xspace(
        open(os.path.join(DATA, name)).read())


def test_scope_paths_come_from_the_metadata_of_each_device_plane():
    scopes = scope_time.op_scopes(_xspace())
    assert sorted(scopes) == [0, 1]
    assert len(scopes[0]) == 10 and "fusion.9" not in scopes[0]
    assert scopes[0]["while.1"] == "jit(fn)/jvp(/loop.body/recurrence)/while:"
    assert "/loop.heads/mul" in scopes[0]["fusion.3"]
    assert scopes[1] == {"fusion.1": scopes[0]["fusion.1"]}
    # a trace without such stats has no scopes, and is not an error
    assert scope_time.op_scopes(_xspace("trace_small.textproto")) == {
        0: {}, 1: {}}


@pytest.mark.parametrize("scope,want_us", [
    ("loop.body", 68.0), ("loop.heads", 16.0), ("adam", 3.0),
    ("no.such.scope", None)])
def test_time_under_a_scope_is_a_union_on_the_first_device(scope, want_us):
    from jax.profiler import ProfileData

    raw = _xspace()
    got = scope_time.scope_ms(ProfileData.from_serialized_xspace(raw),
                              scope_time.op_scopes(raw), scope)
    assert got == (None if want_us is None else pytest.approx(want_us / 1e3))


OBS = {"kind": "train", "trace_steps": 2, "trace": {"n_ops": 11}}


@pytest.mark.parametrize("name,want_ms", [
    ("loop_body_ms.train", 68.0e-3 / 2), ("loop_heads_ms.train", 16.0e-3 / 2)])
def test_scope_reader_reads_its_scope_per_traced_step(name, want_ms,
                                                      trace_root):
    reader = _reader(name)
    trace_root("trace_loop_scopes.textproto")
    assert reader.read(OBS) == pytest.approx(want_ms)
    assert reader.read({}) is None
    assert reader.read({**OBS, "kind": "serve"}) is None
    assert reader.read({**OBS, "trace_steps": 0}) is None


@pytest.mark.parametrize("name", ["loop_body_ms.train", "loop_heads_ms.train"])
@pytest.mark.parametrize("trace", ["trace_step_spans.textproto", None])
def test_a_program_without_the_scopes_reports_nothing(name, trace,
                                                      trace_root):
    """The parent of the PR that added them, and a run with no trace."""
    if trace:
        trace_root(trace)
    assert _reader(name).read(OBS) is None


def test_hbm_peak_reader():
    reader = _reader("hbm_peak_gb.train")
    assert reader.read({"kind": "train", "memory_peak_bytes": 12.5e9}) == 12.5
    # a reading over what the chip holds is no reading; at it, it is one
    at = {"kind": "train", "memory_limit_bytes": 16.909e9}   # 15.75 GiB
    assert reader.read({**at, "memory_peak_bytes": 16.909e9}) == 16.909
    assert reader.read({**at, "memory_peak_bytes": 18.7e9}) is None
    assert reader.read({"kind": "train", "memory_limit_bytes": None,
                        "memory_peak_bytes": 18.7e9}) == 18.7
    assert reader.read({}) is None
    assert reader.read({"kind": "train", "memory_peak_bytes": 0}) is None
    assert reader.read({"kind": "serve", "memory_peak_bytes": 1e9}) is None


def test_bodies_lowered_reader_lowers_the_program_again_and_reads_the_span():
    import paddle_tpu as fluid
    from paddle_tpu import models, observability

    reader = _reader("loop_bodies_lowered.train")
    obs = {"kind": "train", "samples_per_step": 2, "chips": 1,
           "platform": "cpu"}
    fluid.reset_default_env()
    spec = models.looped_decoder(models.LoopedDecoderConfig(
        vocab_size=32, max_length=8, n_layer=1, n_head=2, head_dim=4,
        d_model=8, d_inner=16, loop_steps=3))
    fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(spec.loss)
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    assert not observability.enabled()
    assert reader.read(obs) == 1
    assert not observability.enabled()      # on for the lowering only
    assert reader.read({}) is None
    assert reader.read({**obs, "kind": "serve"}) is None
    # a program without a recurrence (every other configuration, and the
    # parent of this PR): nothing to read
    fluid.reset_default_env()
    x = fluid.layers.data("x", [4], dtype="float32")
    fluid.layers.mean(fluid.layers.fc(x, size=2))
    assert reader.read(obs) is None


def test_the_cells_readers_are_in_the_manifest(manifest_holds):
    """This file's entries are there, in their own order, with at least this
    cell; what later cells report is theirs to say (conftest.py)."""
    manifest_holds("per_layer", ["loop_body_ms.train", "loop_heads_ms.train",
                                 "loop_bodies_lowered.train",
                                 "hbm_peak_gb.train"],
                   cells=[CELL], moves="train_samples_per_s")
    cell = manifest.Cell(MANIFEST, CELL)
    mine = {m["name"] for m in cell.metrics("per_layer")}
    assert {"compiles_in_window.train", "mfu.train", "device_idle.train",
            "values_moved_per_step.train"} <= mine
    assert "collective_ms.train" not in mine          # one chip
    assert {"train_samples_per_s", "setup_s"} <= {
        m["name"] for m in cell.metrics("end_to_end")}
    assert cell.chips == 1 and cell.sizing["per_chip_batch"] == 2
