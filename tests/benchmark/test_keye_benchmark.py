"""What PR 33 adds to the benchmark: the keye-vl-2.0-30b-a3b configuration
(its file against the published config, its FLOP counts, its batch, its
reference against itself through the harness) and the five readers of
`keye-train-dsa16k`, on a small recorded trace."""

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
CELL, CONFIG = "keye-train-dsa16k", "keye-vl-2.0-30b-a3b"
# https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json
# as the model-configs catalog has it: every key of the published config
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 18992}
NEW_READERS = {"dsa_index_ms.train": "dsa.index",
               "dsa_select_ms.train": "dsa.select",
               "dsa_attend_ms.train": "dsa.attend",
               "dsa_kl_ms.train": "dsa.kl"}
ROOFLINE = "dsa_attend_roofline.train"
APPENDED = {"compiles_in_window.train", "mfu.train", "device_idle.train",
            "values_moved_per_step.train",
            "loop_bodies_lowered.train", "hbm_peak_gb.train",
            "moe_experts_ms.train", "moe_dispatch_ms.train"}


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
    # the floors: four layers (the period is 1, no leading dense layer), 16
    # experts held of the router's 128, an eighth of the vocabulary
    assert cfg["router_experts"] == 128 and cfg["expert_offset"] == 0
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["num_experts"] * 8 == PUBLISHED["num_experts"]
    for key in ("qk_norm", "index_key_norm", "index_rotary",
                "index_weight_scale", "index_precision", "chunk_sizes",
                "index_loss", "router_aux_loss", "vision_tower", "optimizer",
                "init", "max_length"):
        assert key in cfg["assumed"], key
    assert "8" in cfg["deployment"] and "data-parallel" in cfg["deployment"]
    assert "0-15" in cfg["deployment"] and "0-18991" in cfg["deployment"]
    entry = [c for c in MANIFEST["configs"] if c["name"] == CONFIG][0]
    assert cfg["source"].startswith(entry["source"])
    assert entry["source"].endswith(
        "Keye-VL-2.0-30B-A3B/blob/main/config.json")


def test_the_configurations_entry_and_files_are_there(manifest_holds):
    """The configuration's entry and its cell's, each there once (whatever a
    later PR appends behind them), the file with what a `train`
    configuration states, and no width among the cuts."""
    entry, = manifest_holds("configs", [CONFIG])
    manifest_holds("workloads", [CELL], config=CONFIG, chips=1,
                   traffic="train-steady")
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    data = _config()
    for key in ("source", "reduced", "assumed", "deployment", "kind",
                "equations", "memory", "reduced_why", "published"):
        assert key in data, key
    assert data["reduced"] == entry["reduced"] == list(REDUCED)
    for key in entry["reduced"]:
        assert not re.search(
            r"(_dim|_rank|hidden_size|intermediate|d_model|d_inner|head|"
            r"per_tok)", key), f"{key} is a width"
    base = os.path.join(REPO, "benchmark", "configs", CONFIG)
    assert os.path.isfile(base + ".py")
    assert os.path.isfile(base + ".reference.py")
    assert {"loss_rtol", "grad_cos_min", "grad_norm_rtol",
            "param_norm_factor", "rows_per_part", "query_block"} <= set(
        data["reference"])
    assert len(data["reduced_why"]) > 40
    cells = [w for w in MANIFEST["workloads"] if w["config"] == CONFIG]
    assert [w["name"] for w in cells] == [CELL]
    assert cells[0]["chips"] == 1 and cells[0]["traffic"] == "train-steady"
    for text in (entry["why"], entry["source"], cells[0]["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    # what ISSUE 33 asked of the cell: one sequence of 16384, the depth the
    # memory table allows
    sizing = json.load(open(os.path.join(
        REPO, "benchmark", "cells", CELL + ".json")))
    assert sizing["per_chip_batch"] == 1 and data["max_length"] == 16384
    depth = f"depth_{data['num_hidden_layers']}"
    assert data["memory"][depth]["beside_first_step_bytes"] < 15.75e9
    assert data["memory"]["parameters"] == 465391104


def test_flops_are_counted_from_the_shapes():
    mod, cfg = _module(), _config()
    S = cfg["max_length"]
    attention = (2 * 2048 * 4096 + 2 * 2048 * 512
                 + 2048 * (16 * 64 + 64 + 16))
    assert mod.attention_matmul_params(cfg) == attention
    assert mod.expected_rows_per_token(cfg) == 1.0
    assert mod.keys_selected(cfg) == 31_458_304
    assert mod.keys_causal(cfg) == 134_225_920
    layer = attention + 2048 * 128 + 1.0 * 3 * 2048 * 768
    matmul = 4 * layer + 2048 * 18992
    attend = 3 * (2 * 2 * 32 * 128) * 31_458_304
    index = (2 * 16 * 64) * (134_225_920 + 2 * 31_458_304)
    assert mod.flops_per_sample(cfg) == pytest.approx(
        S * 6.0 * matmul + 4 * (attend + index))
    # the chosen keys are 23.4% of the causal ones, and attention is the
    # largest share of the count
    assert mod.keys_selected(cfg) / mod.keys_causal(cfg) == pytest.approx(
        0.234, abs=1e-3)
    assert 4 * attend / mod.flops_per_sample(cfg) > 0.25


@pytest.mark.parametrize("use_recompute", [True, False])
def test_the_attentions_core_counts_the_algorithms_seven_products(
        use_recompute):
    """The cell's real shape, by hand: 7 block products (forward q.k and
    p.v, backward the scores again, dP, dV, dK, dQ) = 7/2 x a pair's forward
    FLOPs (2 products x 2 FLOPs x 32 heads x 128 = 16384) x the 31 458 304
    chosen keys of a 16384 sequence x 4 layers = 7.2158e12 a step, whatever
    the program recomputes."""
    mod = _module()
    cfg = {**_config(), "use_recompute": use_recompute}
    assert mod.attend_flops_per_pair(cfg) == 16384.0
    assert mod.keys_selected(cfg) == 2048 * 2049 // 2 + 14336 * 2048 \
        == 31_458_304
    got = mod.attend_flops_per_step(cfg, 1)
    assert got == 3.5 * 16384.0 * 31_458_304 * 4 == 7215779938304.0
    assert mod.attend_flops_per_step(cfg, 2) == 2 * got
    # the old rule (9 products, one forward recomputed) read 9/7 of it
    assert got * 9 / 7 == pytest.approx(9.2774e12, rel=1e-4)
    # and the expert block's grouped matmuls: 3 passes at 1.0 rows a token
    assert mod.grouped_matmul_flops_per_step(cfg, 16384) == \
        3 * 2 * 1.0 * 16384 * 3 * 2048 * 768 * 4


def test_batch_is_packed_text_over_the_slice_and_the_seeds():
    mod = _module()
    cfg = {**_config(), "max_length": 64}
    spec = types.SimpleNamespace(feed_names=["tokens", "labels",
                                             "positions"])
    a = mod.make_batch(cfg, spec, 3, 3000000019)
    b = mod.make_batch(cfg, spec, 3, 3000000019)
    c = mod.make_batch(cfg, spec, 3, 3000000020)
    assert a["tokens"].shape == a["labels"].shape == (3, 64)
    assert a["tokens"].dtype == np.int64
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert (a["tokens"] != c["tokens"]).mean() > 0.99
    assert a["tokens"].min() >= 0 and 17000 < a["tokens"].max() < 18992
    # text: the three position streams are the token's index
    assert a["positions"].shape == (3, 3, 64)
    assert a["positions"].dtype == np.int32
    np.testing.assert_array_equal(
        a["positions"], np.broadcast_to(np.arange(64), (3, 3, 64)))


def test_the_rehearsals_first_step_is_the_references():
    """The rehearsal's first step as the benchmark takes it, through the
    harness's FirstStep: nothing to say, index parameters included."""
    import jax
    import paddle_tpu as fluid

    cell = manifest.Cell(MANIFEST, CELL, rehearse=True)
    spec = cell.config_module.build(cell.config, 5)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = cell.config_module.make_batch(cell.config, spec, 2, 5)
    first = reference.FirstStep(cell, spec)
    assert any("_index_" in name for name in first.trainable)
    loss = float(np.ravel(np.asarray(
        exe.run(feed=batch, fetch_list=[spec.loss])[0]))[0])
    found, problems = first.compare(loss, jax.device_put(batch), 2)
    assert problems == []
    assert found["loss_rel"] < 1e-5 and found["grad_cos"] > 1 - 1e-6
    assert abs(found["grad_norm_ratio"] - 1) < 1e-4


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
OBS = {"kind": "train", "trace_steps": 2, "trace": {"n_ops": 16},
       "platform": "tpu", "device_kind": "TPU v5 lite",
       "samples_per_step": 1}
WANT_US = {"dsa_index_ms.train": 16.0, "dsa_select_ms.train": 14.0,
           "dsa_attend_ms.train": 36.0, "dsa_kl_ms.train": 7.0}


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_scope_reader_reads_its_scope_per_traced_step(name, trace_root):
    """The kernels and fusions inside the chunks' `while` count under the
    scope they were called in; the while itself, whose scope is the
    attention block's, under none of them."""
    reader = _reader(name)
    trace_root("trace_dsa_scopes.textproto")
    assert reader.read(OBS) == pytest.approx(WANT_US[name] / 1e3 / 2)
    assert reader.read({}) is None
    assert reader.read({**OBS, "kind": "serve"}) is None
    assert reader.read({**OBS, "trace_steps": 0}) is None


def test_the_expert_layers_readers_read_this_cells_trace_too(trace_root):
    trace_root("trace_dsa_scopes.textproto")
    assert _reader("moe_experts_ms.train").read(OBS) == pytest.approx(0.003)
    assert _reader("moe_dispatch_ms.train").read(OBS) == pytest.approx(0.002)


def test_roofline_reader_divides_the_chosen_keys_flops_by_the_scopes_time(
        trace_root):
    reader = _reader(ROOFLINE)
    trace_root("trace_dsa_scopes.textproto")
    flops = _module().attend_flops_per_step(_config(), 1)
    want = 100.0 * flops / (36.0e-6 / 2) / peaks("TPU v5 lite")["bf16_flops"]
    assert reader.read(OBS) == pytest.approx(want)
    assert reader.read({**OBS, "platform": "cpu"}) is None
    assert reader.read({**OBS, "trace_steps": 0}) is None


@pytest.mark.parametrize("name", sorted(NEW_READERS) + [ROOFLINE])
@pytest.mark.parametrize("trace", ["trace_moe_scopes.textproto", None])
def test_a_program_without_the_scopes_reports_nothing(name, trace,
                                                      trace_root):
    """The parent of the PR that added them (its traces have other scopes),
    and a run with no trace: nothing is read and nothing is raised."""
    if trace:
        trace_root(trace)
    assert _reader(name).read(OBS) is None


def test_the_cells_readers_are_in_the_manifest(manifest_holds):
    """This file's entries are there, in their own order, with at least this
    cell; what stands behind them, and what other cells report, is theirs to
    say (conftest.py)."""
    entries = manifest_holds(
        "per_layer", ["dsa_index_ms.train", "dsa_select_ms.train",
                      "dsa_attend_ms.train", "dsa_kl_ms.train", ROOFLINE],
        cells=[CELL], moves="train_samples_per_s", layer="training kernels",
        source="device_trace")
    for m in entries:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entries[-1]["unit"] == "%" and entries[-1]["better"] == "higher"
    # the generic .train readers and the expert block's two name the cell
    for name in sorted(APPENDED):
        manifest_holds("per_layer", [name], cells=[CELL])
    cell = manifest.Cell(MANIFEST, CELL)
    mine = {m["name"] for m in cell.metrics("per_layer")}
    assert set(NEW_READERS) | {ROOFLINE} | APPENDED <= mine
    assert {"train_samples_per_s", "setup_s"} <= {
        m["name"] for m in cell.metrics("end_to_end")}
    assert cell.chips == 1 and cell.sizing["per_chip_batch"] == 1


def test_bodies_lowered_reads_one_lowering_of_every_layers_body():
    """Every layer is a one-trip `recurrence` (the unit of recomputation):
    each body is lowered once."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    spec = models.sparse_decoder(models.SparseDecoderConfig(
        vocab_size=32, max_length=16, n_layer=4, d_model=16, n_head=4,
        n_kv_head=2, head_dim=8, mrope_section=(1, 1, 2), index_heads=2,
        index_dim=8, index_topk=4, q_chunk=8, kv_chunk=8,
        n_routed_experts=8, experts_held=2, top_k=2, d_expert=8))
    fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(spec.loss)
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    ops = fluid.default_main_program().global_block().desc.ops
    assert [op.attr("trips") for op in ops if op.type == "recurrence"] == \
        4 * [1]
    assert _reader("loop_bodies_lowered.train").read(
        {"kind": "train", "samples_per_step": 1, "chips": 1,
         "platform": "cpu"}) == 1
