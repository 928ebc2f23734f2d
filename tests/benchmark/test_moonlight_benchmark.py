"""What PR 31 adds to the benchmark: the moonlight-16b-a3b configuration (its
file against the published config, its FLOP counts, its reference against
mutants of itself) and the five readers of `moonlight-train-ep8share`, on a
small recorded trace."""

import json
import os
import re
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import manifest, reference
from benchmark.harness.device import peaks

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELL, CONFIG = "moonlight-train-ep8share", "moonlight-16b-a3b"
# https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json
# as the model-configs catalog has it: every key of the published config
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16,
    "num_nextn_predict_layers": 0, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}
REDUCED = {"num_hidden_layers": 5, "n_routed_experts": 8,
           "vocab_size": 20480}
NEW_READERS = {"mla_ms.train": "mla", "moe_experts_ms.train": "moe.experts",
               "moe_dispatch_ms.train": "moe.dispatch",
               "moe_shared_ms.train": "moe.shared"}
ROOFLINE = "moe_experts_roofline.train"


def _config():
    return json.load(open(os.path.join(
        REPO, "benchmark", "configs", CONFIG + ".json")))


def _module():
    return manifest.load_py(os.path.join(
        REPO, "benchmark", "configs", CONFIG + ".py"))


def _reader(name):
    return manifest.load_py(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"))


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------
def test_file_holds_the_published_config_and_cuts_three_counts_alone():
    cfg = _config()
    assert cfg["reduced"] == list(REDUCED)
    for key, want in PUBLISHED.items():
        if key in REDUCED:
            assert cfg[key] == REDUCED[key] and cfg["published"][key] == want
        else:
            assert cfg[key] == want, key
    # the floors: the dense layer and four expert layers, 8 experts held,
    # an eighth of the vocabulary; the router keeps its width
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] == 4
    assert cfg["router_experts"] == 64 and cfg["expert_offset"] == 0
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    for key in ("bias_update_gamma", "seq_aux", "rotary_layout", "optimizer",
                "init", "max_length", "router_precision"):
        assert key in cfg["assumed"], key
    assert "8" in cfg["deployment"] and "data-parallel" in cfg["deployment"]
    entry = [c for c in MANIFEST["configs"] if c["name"] == CONFIG][0]
    assert cfg["source"].startswith(entry["source"])
    assert entry["source"].endswith("Moonlight-16B-A3B/blob/main/config.json")


def test_configuration_entry_and_files():
    """Everything test_benchmark_manifest.py::test_configuration_entry_and_
    files asks, with the width expression held to widths: `hidden_size`,
    not the `hidden` of num_hidden_layers (tests/conftest.py)."""
    entry = [c for c in MANIFEST["configs"] if c["name"] == CONFIG][0]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    data = _config()
    for key in ("source", "reduced", "assumed", "deployment", "kind",
                "equations", "memory", "reduced_why"):
        assert key in data, key
    assert data["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert not re.search(
            r"(_dim|_rank|hidden_size|intermediate|d_model|d_inner|head|"
            r"per_tok)", key), f"{key} is a width"
    base = os.path.join(REPO, "benchmark", "configs", CONFIG)
    assert os.path.isfile(base + ".py")
    assert os.path.isfile(base + ".reference.py")
    assert {"loss_rtol", "grad_cos_min", "grad_norm_rtol",
            "param_norm_factor", "rows_per_part"} <= set(data["reference"])
    assert len(data["reduced_why"]) > 40
    cells = [w for w in MANIFEST["workloads"] if w["config"] == CONFIG]
    assert [w["name"] for w in cells] == [CELL]
    assert cells[0]["chips"] == 1 and cells[0]["traffic"] == "train-steady"
    for text in (entry["why"], entry["source"], cells[0]["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    # what ISSUE 31 asked of the cell: 8 x 2048 tokens, or 4 x 2048 with the
    # memory table that forced it
    sizing = json.load(open(os.path.join(
        REPO, "benchmark", "cells", CELL + ".json")))
    assert sizing["per_chip_batch"] in (4, 8) and data["max_length"] == 2048
    if sizing["per_chip_batch"] == 4:
        assert {"rows_8", "rows_4"} <= set(data["memory"])
        assert data["memory"]["rows_8"]["step_peak_bytes"] > 15.75e9
        assert data["memory"]["rows_4"]["beside_first_step_bytes"] < 15.75e9


def test_flops_are_counted_from_the_shapes():
    mod, cfg = _module(), _config()
    S = cfg["max_length"]
    mla = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    assert mod.mla_matmul_params(cfg) == mla
    assert mod.expected_rows_per_token(cfg) == 0.75
    expert_layer = 2048 * 64 + 3 * 2048 * 2816 + 0.75 * 3 * 2048 * 1408
    matmul = 5 * mla + 3 * 2048 * 11264 + 4 * expert_layer + 2048 * 20480
    attn = 3 * 2 * S * 16 * (192 + 128) * 5
    assert mod.flops_per_sample(cfg) == pytest.approx(
        S * (6.0 * matmul + attn))
    # ISSUE 31's count: 1.97 GFLOP a token trained
    assert mod.flops_per_sample(cfg) / S == pytest.approx(1.97e9, rel=2e-3)


@pytest.mark.parametrize("use_recompute", [True, False])
def test_the_grouped_matmuls_count_the_algorithms_three_passes(
        use_recompute):
    """The cell's real shape, by hand: 3 passes (forward 1, backward 2:
    input and weight gradient) x 2 FLOPs a multiply-add x the expected rows
    (0.75 a token of 4 x 2048) x a row's 3 x 2048 x 1408 parameters x 4
    expert layers = 1.2756e12 a step, whatever the program recomputes."""
    cfg = {**_config(), "use_recompute": use_recompute}
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] == 4
    assert _module().expert_matmul_params(cfg) == 3 * 2048 * 1408 == 8650752
    got = _module().grouped_matmul_flops_per_step(cfg, 4 * 2048)
    assert got == 3 * 2 * (0.75 * 8192) * 8650752 * 4 == 1275605286912.0
    # the old rule (a fourth, recomputed pass) read 4/3 of it
    assert got * 4 / 3 == pytest.approx(1.7008e12, rel=1e-4)


def test_batch_is_packed_shifted_over_the_slice_and_the_seeds():
    mod = _module()
    cfg = {**_config(), "max_length": 64}
    spec = types.SimpleNamespace(feed_names=["tokens", "labels"])
    a = mod.make_batch(cfg, spec, 4, 3000000019)
    b = mod.make_batch(cfg, spec, 4, 3000000019)
    c = mod.make_batch(cfg, spec, 4, 3000000020)
    assert a["tokens"].shape == a["labels"].shape == (4, 64)
    assert a["tokens"].dtype == np.int64
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert (a["tokens"] != c["tokens"]).mean() > 0.99
    assert a["tokens"].min() >= 0 and 19000 < a["tokens"].max() < 20480


@pytest.fixture
def first_step():
    """The rehearsal's first step as the benchmark takes it, the selection
    biases off zero as the probe sets them at the real size: (FirstStep, its parameters, the
    fetched loss, the batch)."""
    import jax
    import paddle_tpu as fluid

    cell = manifest.Cell(MANIFEST, CELL, rehearse=True)
    spec = cell.config_module.build(cell.config, 5)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.default_rng(5)
    for p in fluid.default_main_program().all_parameters():
        if p.name.endswith("_router_bias"):
            # small beside the rehearsal's scores (0.5 +- 0.03), or the
            # bias alone would pick the same experts for every token
            fluid.global_scope().set_var(p.name, rng.uniform(
                -0.02, 0.02, p.shape).astype(np.float32))
    batch = cell.config_module.make_batch(cell.config, spec, 2, 5)
    first = reference.FirstStep(cell, spec)
    loss = float(np.ravel(np.asarray(
        exe.run(feed=batch, fetch_list=[spec.loss])[0]))[0])
    return first, first.params, loss, jax.device_put(batch)


@pytest.mark.parametrize("wrong,refused", [
    (None, False), ("top5", True), ("scaling_left_out", True),
    ("bias_in_weights", True), ("softmax_scores", True),
    ("kv_norm_left_out", True), ("fp8_matmuls", True),
    # at the rehearsal's 16 positions and 0.02 weights the scores are too
    # flat for the first two to show, and of its 32 tokens too few choose
    # the one expert for the third; the probe refuses them at the real size
    ("no_rotary_on_k", None), ("scores_by_sqrt128", None),
    ("expert_dropped", None)])
def test_reference_refuses_what_is_wrong(first_step, wrong, refused):
    """The program against the reference: nothing to say.  Against a
    reference with one thing wrong (tools/moonlight_reference_probe.py,
    which makes the same comparison on the chip at the real size):
    refused by at least one limit."""
    from tools import moonlight_reference_probe as probe

    first, params, loss, batch = first_step
    first.params = params
    first.module = types.SimpleNamespace(loss_and_grad=probe.mutant(wrong))
    found, problems = first.compare(loss, batch, 2)
    if wrong is None:
        assert problems == []
        assert found["loss_rel"] < 1e-5 and found["grad_cos"] > 1 - 1e-6
        assert abs(found["grad_norm_ratio"] - 1) < 1e-4
        assert found["param_norm_far"] < 1.001
    elif refused is not None:
        assert bool(problems) == refused, problems
    else:
        assert found["grad_cos"] < 1 - 1e-9 or found["loss_rel"] > 0


def test_the_mutants_are_the_probes_and_an_unknown_one_is_an_error():
    from tools import moonlight_reference_probe as probe

    assert set(probe.MUTANTS) == {
        "top5", "scaling_left_out", "bias_in_weights", "softmax_scores",
        "no_rotary_on_k", "kv_norm_left_out", "scores_by_sqrt128",
        "expert_dropped", "fp8_matmuls"}
    with pytest.raises(KeyError):
        probe.mutant("no_such_mutant")


# ---------------------------------------------------------------------------
# the readers
OBS = {"kind": "train", "trace_steps": 2, "trace": {"n_ops": 15},
       "platform": "tpu", "device_kind": "TPU v5 lite",
       "samples_per_step": 4}
WANT_US = {"mla_ms.train": 14.0, "moe_experts_ms.train": 38.0,
           "moe_dispatch_ms.train": 20.0, "moe_shared_ms.train": 10.0}


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_scope_reader_reads_its_scope_per_traced_step(name, trace_root):
    """The kernels inside the `conditional` that picks the row buffer count
    under the scope they were called in; the conditional itself under
    none."""
    reader = _reader(name)
    trace_root("trace_moe_scopes.textproto")
    assert reader.read(OBS) == pytest.approx(WANT_US[name] / 1e3 / 2)
    assert reader.read({}) is None
    assert reader.read({**OBS, "kind": "serve"}) is None
    assert reader.read({**OBS, "trace_steps": 0}) is None


def test_roofline_reader_divides_the_expected_flops_by_the_scopes_time(
        trace_root):
    reader = _reader(ROOFLINE)
    trace_root("trace_moe_scopes.textproto")
    flops = _module().grouped_matmul_flops_per_step(_config(), 4 * 2048)
    want = 100.0 * flops / (38.0e-6 / 2) / peaks("TPU v5 lite")["bf16_flops"]
    assert reader.read(OBS) == pytest.approx(want)
    assert reader.read({**OBS, "platform": "cpu"}) is None
    assert reader.read({**OBS, "trace_steps": 0}) is None


@pytest.mark.parametrize("name", sorted(NEW_READERS) + [ROOFLINE])
@pytest.mark.parametrize("trace", ["trace_loop_scopes.textproto", None])
def test_a_program_without_the_scopes_reports_nothing(name, trace,
                                                      trace_root):
    """The parent of the PR that added them (its traces have other scopes),
    and a run with no trace: nothing is read and nothing is raised."""
    if trace:
        trace_root(trace)
    assert _reader(name).read(OBS) is None


def test_the_cells_readers_are_in_the_manifest(manifest_holds):
    """This file's entries are there, in their own order, with at least this
    cell; what other cells report, and what stands behind these, is theirs
    to say (conftest.py)."""
    entries = manifest_holds(
        "per_layer", ["mla_ms.train", "moe_experts_ms.train",
                      "moe_dispatch_ms.train", "moe_shared_ms.train",
                      ROOFLINE],
        cells=[CELL], moves="train_samples_per_s", layer="training kernels",
        source="device_trace")
    assert entries[-1]["unit"] == "%" and entries[-1]["better"] == "higher"
    # the readers the cell shares with older cells name it too
    manifest_holds("per_layer", ["hbm_peak_gb.train"], cells=[CELL])
    manifest_holds("per_layer", ["loop_bodies_lowered.train"], cells=[CELL])
    cell = manifest.Cell(MANIFEST, CELL)
    mine = {m["name"] for m in cell.metrics("per_layer")}
    assert set(NEW_READERS) | {ROOFLINE, "mfu.train", "device_idle.train",
                               "compiles_in_window.train"} <= mine
    # one chip, and none of looped_decoder.py's scopes
    assert not {"collective_ms.train", "loop_body_ms.train",
                "loop_heads_ms.train"} & mine
    assert {"train_samples_per_s", "setup_s"} <= {
        m["name"] for m in cell.metrics("end_to_end")}
    assert cell.chips == 1 and cell.sizing["per_chip_batch"] == 4


def test_bodies_lowered_reads_one_lowering_of_every_layers_body():
    """Every layer is a one-trip `recurrence` (the unit of recomputation):
    each body is lowered once, as ouro's one body of four trips is."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    spec = models.expert_decoder(models.ExpertDecoderConfig(
        vocab_size=32, max_length=8, n_layer=5, d_model=16, d_inner=24,
        n_head=2, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        kv_lora_rank=8, n_routed_experts=8, experts_held=2, top_k=2,
        d_expert=8))
    fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(spec.loss)
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    ops = fluid.default_main_program().global_block().desc.ops
    assert [op.attr("trips") for op in ops if op.type == "recurrence"] == \
        5 * [1]
    assert _reader("loop_bodies_lowered.train").read(
        {"kind": "train", "samples_per_step": 2, "chips": 1,
         "platform": "cpu"}) == 1
