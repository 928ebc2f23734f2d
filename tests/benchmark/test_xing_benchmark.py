"""What PR 50 adds to the benchmark: the xing4.0-29b-a4b configuration (its
file against the published config, its FLOP and byte counts at the real
shape, its batch, its reference against the program through the harness)
and the three readers of `xing-train-mhc4`, on a small recorded trace."""

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

from benchmark.harness import lowered_spans, manifest, reference
from benchmark.harness.device import peaks

MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELL, CONFIG = "xing-train-mhc4", "xing4.0-29b-a4b"
# https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json
# as the model-configs catalog has it: every key of the row
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 8, "vocab_size": 16384}
SCOPE_READERS = {"mhc_maps_ms.train": 13.0,
                 "mhc_mix_ms.train": 22.0}            # us in the fixture
ROOFLINE = "mhc_roofline.train"
NEW = set(SCOPE_READERS) | {ROOFLINE}
APPENDED = {"compiles_in_window.train", "mfu.train", "device_idle.train",
            "values_moved_per_step.train", "loop_bodies_lowered.train",
            "hbm_peak_gb.train", "mla_ms.train", "moe_experts_ms.train",
            "moe_dispatch_ms.train", "moe_shared_ms.train",
            "moe_router_ms.train", "turnaround_host_ms.train",
            "turnaround_runtime_ms.train", "turnaround_copy_ms.train",
            "turnaround_release_ms.train", "turnaround_caller_ms.train",
            "turnaround_entry_ms.train", "clock_skew_us.train"}
TRACE = "trace_mhc_scopes.textproto"


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
def test_file_holds_the_published_config_and_cuts_four_counts_alone():
    cfg = _config()
    assert cfg["reduced"] == list(REDUCED)
    for key, want in PUBLISHED.items():
        if key in REDUCED:
            assert cfg[key] == REDUCED[key] and cfg["published"][key] == want
        else:
            assert cfg[key] == want, key
    # the catalog's row, where this sandbox has the guide
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(rows):
        row = [json.loads(line) for line in open(rows)
               if '"Xing4.0-29B-A4B"' in line][0]
        assert row["config"] == PUBLISHED
    # the share: 8 chips share every layer
    assert cfg["router_experts"] == 64 and cfg["expert_offset"] == 0
    for key in ("n_routed_experts", "vocab_size"):
        assert cfg[key] * 8 == PUBLISHED[key], key
    # the heads: the published count stays (the accepted manifest test
    # takes no `reduced` key that contains "head"), the share beside it
    assert cfg["heads_held"] * 8 == cfg["num_attention_heads"] == 32
    assert "test_benchmark_manifest" in cfg["heads_held_why"]
    for key in ("mhc_equations", "mhc_order", "mhc_rms", "mhc_start",
                "streams", "mla", "mtp_left_out", "bias_update_gamma",
                "router_precision", "init", "optimizer", "auxiliary_loss"):
        assert key in cfg["assumed"], key
    assert "2512.24880" in cfg["assumed"]["mhc_equations"]
    assert "2409.19606" in cfg["assumed"]["streams"]
    # the block that is left out, and why
    assert cfg["num_nextn_predict_layers"] == 1
    left_out = cfg["assumed"]["mtp_left_out"]
    assert "789.6 M" in left_out and "15.79 GB" in left_out
    assert "mtp_loss_weight" not in cfg
    for said in ("0-3 of 32", "0-7 of the 64", "0-16383", "5 layers of 40"):
        assert said in cfg["deployment"], said
    entry = [c for c in MANIFEST["configs"] if c["name"] == CONFIG][0]
    assert cfg["source"].startswith(entry["source"])
    assert entry["source"].endswith(
        "XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json")


def test_configuration_entry_and_files():
    entry = [c for c in MANIFEST["configs"] if c["name"] == CONFIG][0]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    data = _config()
    for key in ("source", "reduced", "assumed", "deployment", "kind",
                "equations", "memory", "reduced_why", "published",
                "reference", "rehearsal", "optimizer"):
        assert key in data, key
    assert "TO BE MEASURED" not in json.dumps(data)
    assert data["reduced"] == entry["reduced"] == list(REDUCED)
    for key in entry["reduced"]:
        assert not re.search(
            r"(_dim|_rank|hidden_size|intermediate|d_model|d_inner|head|"
            r"per_tok|hc_mult)", key), f"{key} is a width"
    base = os.path.join(REPO, "benchmark", "configs", CONFIG)
    assert os.path.isfile(base + ".py")
    assert os.path.isfile(base + ".reference.py")
    assert {"loss_rtol", "grad_cos_min", "grad_norm_rtol",
            "param_norm_factor", "rows_per_part", "query_block",
            "tolerances"} <= set(data["reference"])
    cells = [w for w in MANIFEST["workloads"] if w["config"] == CONFIG]
    assert [w["name"] for w in cells] == [CELL]
    assert cells[0]["chips"] == 1 and cells[0]["traffic"] == "train-steady"
    for text in (entry["why"], entry["source"], cells[0]["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    # what ISSUE 50 asked of the cell: one packed sequence of 4096 tokens
    sizing = json.load(open(os.path.join(
        REPO, "benchmark", "cells", CELL + ".json")))
    assert sizing["per_chip_batch"] == 1 and data["max_length"] == 4096
    memory = data["memory"]
    assert memory["parameters"] == 656127246
    assert memory["tokens_4096"]["beside_first_step_bytes"] < 16.9e9
    # the reference runs beside the program's state
    assert memory["tokens_4096"]["step_argument_bytes"] \
        + memory["tokens_4096"]["reference_peak_bytes"] < 16.9e9


def test_the_reference_imports_nothing_of_the_program():
    text = open(os.path.join(REPO, "benchmark", "configs",
                             CONFIG + ".reference.py")).read()
    imports = re.findall(r"^\s*(?:import|from)\s+(\S+)", text, re.M)
    assert sorted(set(imports)) == ["jax", "jax.numpy", "math", "numpy"]
    # its own Sinkhorn loop and its own YaRN
    assert "def _sinkhorn(" in text and "def _inv_freq(" in text


def test_flops_and_bytes_are_counted_from_the_real_shapes():
    mod, cfg = _module(), _config()
    S, d = cfg["max_length"], 3584
    mla = d * 768 + 768 * 4 * 192 + d * 576 + 512 * 4 * 256 + 4 * 128 * d
    assert mod.mla_matmul_params(cfg) == mla == 7_766_016
    phi = 4 * d * 24
    assert mod.mhc_matmul_params(cfg) == phi == 344_064
    assert mod.mhc_sublayers(cfg) == 10
    assert mod.expected_rows_per_token(cfg) == 0.5
    expert_layer = d * 64 + 3 * d * 1024 + 0.5 * 3 * d * 1024
    matmul = 5 * mla + 10 * phi + 3 * d * 9216 + 4 * expert_layer \
        + d * 16384
    attn = 3 * 2 * S * 4 * (192 + 128) * 5
    assert mod.flops_per_sample(cfg) == pytest.approx(
        S * (6.0 * matmul + attn))
    # ISSUE 50's shares of a token's ~562 forward MFLOP (its arithmetic
    # takes the causal half off the scores, the harness's convention does
    # not): the dense MLP 198, the head 117, the shared experts 88, the
    # projections of five MLA shares 78, the held experts 44, mHC 7
    forward = mod.flops_per_sample(cfg) / 3 / S / 1e6
    assert forward - attn / 3 / 2 / 1e6 == pytest.approx(562, abs=6)
    assert 2 * 10 * phi / 1e6 == pytest.approx(6.9, abs=0.1)
    assert 2 * 5 * mla / 1e6 == pytest.approx(78, abs=1)
    # the hyper-connections: the op module's own count a sublayer
    from paddle_tpu.ops import hyper_connection_ops as hc
    assert mod.mhc_bytes_per_step(cfg, 1) == \
        10 * hc.moved_bytes(S, 4, d, 2, phi * 4) == 7_060_193_280
    assert mod.mhc_bytes_per_step(cfg, 2) - mod.mhc_bytes_per_step(cfg, 1) \
        == 10 * 24 * S * d * 2
    assert mod.mhc_flops_per_step(cfg, 1) == 6.0 * S * 10 * phi


def test_the_roofline_cannot_pass_100_percent_at_the_real_shape():
    """What the share divides is a lower bound on what ANY implementation
    does: a sublayer reads the streams and writes them (2 passes), its
    backward reads the streams and the cotangent of the new ones and writes
    the old ones' (3), x_in and y are written and read once each way (4
    passes over a [T, C] value), Phi is read once; no fp32 copy, no
    recomputed pass, no map traffic.  At the cell's shape the bytes bound
    it by far (the docstring of the reader says so)."""
    mod, cfg = _module(), _config()
    peak = peaks("TPU v5 lite")
    t_flops = mod.mhc_flops_per_step(cfg, 1) / peak["bf16_flops"]
    t_bytes = mod.mhc_bytes_per_step(cfg, 1) / peak["hbm_bytes_per_s"]
    assert t_bytes > 10 * t_flops
    assert t_bytes == pytest.approx(8.6e-3, rel=0.01)
    assert "BYTES bound it" in _reader(ROOFLINE).__doc__
    T, C = cfg["max_length"], cfg["hidden_size"]
    streams, value = 4 * T * C * 2, T * C * 2
    assert mod.mhc_bytes_per_step(cfg, 1) == 10 * (
        5 * streams + 4 * value + 14336 * 24 * 4)


def test_batch_is_packed_over_the_slice_and_the_seeds():
    mod = _module()
    cfg = {**_config(), "max_length": 64}
    spec = types.SimpleNamespace(feed_names=["tokens", "labels"])
    a = mod.make_batch(cfg, spec, 3, 3000000019)
    b = mod.make_batch(cfg, spec, 3, 3000000019)
    c = mod.make_batch(cfg, spec, 3, 3000000020)
    assert a["tokens"].shape == a["labels"].shape == (3, 64)
    assert a["tokens"].dtype == np.int64
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert (a["tokens"] != c["tokens"]).mean() > 0.99
    assert a["tokens"].min() >= 0 and 14000 < a["tokens"].max() < 16384


def test_the_rehearsals_first_step_is_the_references():
    """The rehearsal's first step as the benchmark takes it, through the
    harness's FirstStep: one expert layer, a share of 2 heads of 4 and 4
    experts of a router 16 wide."""
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
    found, problems = first.compare(loss, jax.device_put(batch), 2)
    assert problems == []
    assert found["loss_rel"] < 1e-5 and found["grad_cos"] > 1 - 1e-5
    assert abs(found["grad_norm_ratio"] - 1) < 1e-3


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
OBS = {"kind": "train", "trace_steps": 2, "trace": {"n_ops": 15},
       "platform": "tpu", "device_kind": "TPU v5 lite",
       "samples_per_step": 1}


@pytest.mark.parametrize("name", sorted(SCOPE_READERS))
def test_scope_reader_reads_its_scope_per_traced_step(name, trace_root):
    """Forward and backward of a scope's ops count, the projection before
    them and the kernels between them under neither."""
    reader = _reader(name)
    trace_root(TRACE)
    assert reader.read(OBS) == pytest.approx(SCOPE_READERS[name] / 1e3 / 2)
    assert reader.read({}) is None
    assert reader.read({**OBS, "kind": "serve"}) is None
    assert reader.read({**OBS, "trace_steps": 0}) is None


def test_the_older_scope_readers_read_this_cells_trace_too(trace_root):
    trace_root(TRACE)
    for name, us in (("mla_ms.train", 5.0), ("moe_experts_ms.train", 6.0),
                     ("moe_dispatch_ms.train", 3.0),
                     ("moe_shared_ms.train", 2.0),
                     ("moe_router_ms.train", 1.0)):
        assert _reader(name).read(OBS) == pytest.approx(us / 1e3 / 2), name


def test_roofline_reader_divides_the_floor_by_both_scopes_time(trace_root):
    reader = _reader(ROOFLINE)
    trace_root(TRACE)
    mod, cfg, peak = _module(), _config(), peaks("TPU v5 lite")
    floor = mod.mhc_bytes_per_step(cfg, 1) / peak["hbm_bytes_per_s"]
    assert floor > mod.mhc_flops_per_step(cfg, 1) / peak["bf16_flops"]
    us = sum(SCOPE_READERS.values())          # the two scopes never overlap
    assert reader.read(OBS) == pytest.approx(100.0 * floor / (us * 1e-6 / 2))
    assert reader.read({**OBS, "samples_per_step": 2}) > reader.read(OBS)
    assert reader.read({**OBS, "platform": "cpu"}) is None
    assert reader.read({**OBS, "trace_steps": 0}) is None


@pytest.mark.parametrize("name", sorted(SCOPE_READERS) + [ROOFLINE])
@pytest.mark.parametrize("trace", ["trace_kda_scopes.textproto", None])
def test_a_program_without_the_scopes_reports_nothing(name, trace,
                                                      trace_root):
    """The parent of the PR that added them (its traces have other scopes),
    and a run with no trace: nothing is read and nothing is raised."""
    if trace:
        trace_root(trace)
    assert _reader(name).read(OBS) is None


def _tiny_step(S=64):
    """A step of the model at widths cut to nothing, built and started on
    the CPU."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    spec = models.hyper_expert_decoder(models.HyperExpertDecoderConfig(
        vocab_size=32, max_length=S, n_layer=1, first_k_dense=0, d_model=16,
        d_inner=32,
        n_head=1, q_lora_rank=8, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, kv_lora_rank=8,
        n_routed_experts=8, experts_held=2, top_k=2, d_expert=8,
        hc_sinkhorn_iters=3))
    fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(spec.loss)
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    return spec


def test_lowered_spans_on_a_step_lowered_for_the_tpu():
    """benchmark/harness/lowered_spans.py on the program itself: under the
    TPU trace scope (AMP's keep tier) the streams are bf16 and a
    sublayer's `moved_bytes` is counted at 2 B an element."""
    import paddle_tpu as fluid
    from paddle_tpu.ops import hyper_connection_ops as hc

    _tiny_step()
    obs = {"kind": "train", "samples_per_step": 1, "chips": 1,
           "platform": "cpu"}
    with fluid.flags.tpu_trace_scope(True):
        spans = lowered_spans.of_step(obs, ["mhc.lower", "mla.lower",
                                            "moe.lower"])
    assert len(spans["mhc.lower"]) >= 2 and len(spans["mla.lower"]) >= 1
    assert all(s == {"streams": 4, "sinkhorn_iters": 3, "sublayers": 1,
                     "moved_bytes": hc.moved_bytes(64, 4, 16, 2, 64 * 24 * 4)}
               for s in spans["mhc.lower"])
    assert all(s["rope"] == "rotary" and s["heads"] == 1
               for s in spans["mla.lower"])
    assert all(m["experts_total"] == 8 and m["experts_held"] == 2
               and m["top_k"] == 2 for m in spans["moe.lower"])


def test_bodies_lowered_reads_one_lowering_of_every_layers_body():
    import paddle_tpu as fluid

    _tiny_step(S=32)
    ops = fluid.default_main_program().global_block().desc.ops
    assert [op.attr("trips") for op in ops if op.type == "recurrence"] == \
        [1]
    assert _reader("loop_bodies_lowered.train").read(
        {"kind": "train", "samples_per_step": 1, "chips": 1,
         "platform": "cpu"}) == 1


def test_the_cells_readers_are_in_the_manifest(manifest_holds):
    """This file's entries are there, in their own order, with at least this
    cell; what stands behind them, and what other cells report, is theirs to
    say (conftest.py)."""
    entries = {m["name"]: m for m in manifest_holds(
        "per_layer", ["mhc_maps_ms.train", "mhc_mix_ms.train", ROOFLINE],
        cells=[CELL], moves="train_samples_per_s", layer="training kernels",
        source="device_trace")}
    assert set(entries) == NEW
    for name, m in entries.items():
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics", name + ".py"))
    assert (entries[ROOFLINE]["unit"], entries[ROOFLINE]["better"]) == (
        "%", "higher")
    for name in SCOPE_READERS:
        assert (entries[name]["unit"], entries[name]["better"]) == (
            "ms", "lower")
    cell = manifest.Cell(MANIFEST, CELL)
    assert NEW | APPENDED <= {m["name"] for m in cell.metrics("per_layer")}
    assert {"train_samples_per_s", "setup_s"} <= {
        m["name"] for m in cell.metrics("end_to_end")}
    assert cell.chips == 1 and cell.sizing["per_chip_batch"] == 1


def test_the_older_readers_the_cell_reports_name_it(manifest_holds):
    """The generic .train readers, latent attention's and the expert
    block's scope readers and the seven turnaround readings have this cell
    among their `workloads`; the readers of other cells' own scopes do
    not."""
    for name in sorted(APPENDED):
        manifest_holds("per_layer", [name], cells=[CELL],
                       moves="train_samples_per_s")
    manifest_holds("end_to_end", ["train_samples_per_s"], cells=[CELL])
    reported = {m["name"] for m in
                manifest.Cell(MANIFEST, CELL).metrics("per_layer")}
    assert not {"collective_ms.train", "loop_body_ms.train",
                "loop_heads_ms.train", "moe_experts_roofline.train",
                "dsa_attend_roofline.train", "attn_full_ms.train",
                "cca_mix_ms.train", "kda_scan_ms.train",
                "kda_mix_ms.train", "kda_scan_roofline.train"} & reported
