"""What PR 47 adds to the benchmark: the kimi-linear-48b-a3b configuration
(its file against the published config, its FLOP and byte counts at the real
shape, its batch, its reference against the program through the harness) and
the three readers of `kimi-train-kda8k`, on a small recorded trace."""

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
CELL, CONFIG = "kimi-train-kda8k", "kimi-linear-48b-a3b"
# https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/
# config.json as the model-configs catalog has it: every key of the row
KDA_LAYERS = [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22,
              23, 25, 26]
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": KDA_LAYERS, "num_heads": 32,
        "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
REDUCED = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 20480}
SCOPE_READERS = {"kda_scan_ms.train": 33.0,
                 "kda_mix_ms.train": 17.0}            # us in the fixture
ROOFLINE = "kda_scan_roofline.train"
NEW = set(SCOPE_READERS) | {ROOFLINE}
APPENDED = {"compiles_in_window.train", "mfu.train", "device_idle.train",
            "values_moved_per_step.train", "loop_bodies_lowered.train",
            "hbm_peak_gb.train", "mla_ms.train", "moe_experts_ms.train",
            "moe_dispatch_ms.train", "moe_shared_ms.train",
            "moe_router_ms.train", "turnaround_host_ms.train",
            "turnaround_runtime_ms.train", "turnaround_copy_ms.train",
            "turnaround_release_ms.train", "turnaround_caller_ms.train",
            "turnaround_entry_ms.train", "clock_skew_us.train"}
TRACE = "trace_kda_scopes.textproto"


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
    # the floors: the dense layer and one whole 3:1 period, 8 experts held
    # of the router's 256 (one chip of 32), an eighth of each table
    assert cfg["router_experts"] == 256 and cfg["expert_offset"] == 0
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["num_experts"] * 32 == PUBLISHED["num_experts"]
    assert _module().layer_kinds(cfg) == ["kda", "kda", "kda", "mla", "kda"]
    for key in ("kda_equations", "kda_low_rank", "kda_conv",
                "kda_decay_start", "kda_norm", "mla", "head_dim",
                "bias_update_gamma", "router_precision", "router_gradient",
                "init", "optimizer", "auxiliary_loss"):
        assert key in cfg["assumed"] and "PLACEHOLDER" not in \
            cfg["assumed"][key], key
    assert "2510.26692" in cfg["assumed"]["kda_equations"]
    assert "32" in cfg["deployment"] and "0-7" in cfg["deployment"]
    assert "0-20479" in cfg["deployment"]
    assert "data-parallel" in cfg["deployment"]
    entry = [c for c in MANIFEST["configs"] if c["name"] == CONFIG][0]
    assert cfg["source"].startswith(entry["source"])
    assert entry["source"].endswith(
        "moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")


def test_configuration_entry_and_files():
    entry = [c for c in MANIFEST["configs"] if c["name"] == CONFIG][0]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    data = _config()
    for key in ("source", "reduced", "assumed", "deployment", "kind",
                "equations", "memory", "reduced_why", "published",
                "reference", "rehearsal", "optimizer"):
        assert key in data, key
    assert "PLACEHOLDER" not in json.dumps(data)
    assert data["reduced"] == entry["reduced"] == list(REDUCED)
    for key in entry["reduced"]:
        assert not re.search(
            r"(_dim|_rank|hidden_size|intermediate|d_model|d_inner|head|"
            r"per_tok|conv|linear_attn)", key), f"{key} is a width"
    base = os.path.join(REPO, "benchmark", "configs", CONFIG)
    assert os.path.isfile(base + ".py")
    assert os.path.isfile(base + ".reference.py")
    assert {"loss_rtol", "grad_cos_min", "grad_norm_rtol",
            "param_norm_factor", "rows_per_part", "query_block",
            "state_block", "tolerances"} <= set(data["reference"])
    assert len(data["reduced_why"]) > 40
    cells = [w for w in MANIFEST["workloads"] if w["config"] == CONFIG]
    assert [w["name"] for w in cells] == [CELL]
    assert cells[0]["chips"] == 1 and cells[0]["traffic"] == "train-steady"
    for text in (entry["why"], entry["source"], cells[0]["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    # what ISSUE 47 asked of the cell: one packed sequence of 8192 tokens,
    # or 4096, the one other value it allows, where the memory table says
    # 8192 does not fit
    sizing = json.load(open(os.path.join(
        REPO, "benchmark", "cells", CELL + ".json")))
    assert sizing["per_chip_batch"] == 1
    assert data["max_length"] == 4096
    assert data["memory"]["tokens_4096"]["beside_first_step_bytes"] < 16.9e9
    assert data["memory"]["tokens_8192"]["beside_first_step_bytes"] > 16.91e9
    assert data["memory"]["parameters"] == 602450816
    assert {"tokens_4096", "tokens_8192", "tokens_16384"} <= \
        set(data["memory"])


def test_the_reference_imports_nothing_of_the_program():
    text = open(os.path.join(REPO, "benchmark", "configs",
                             CONFIG + ".reference.py")).read()
    imports = re.findall(r"^\s*(?:import|from)\s+(\S+)", text, re.M)
    assert sorted(set(imports)) == ["jax", "jax.numpy"]
    # the recurrence one token at a time, never the chunked algebra
    assert "lax.scan(_token" in text and "tril" not in text


def test_flops_and_bytes_are_counted_from_the_real_shapes():
    mod, cfg = _module(), _config()
    S = cfg["max_length"]
    d, HD, D = 2304, 4096, 128
    kda = 4 * d * HD + 2 * (d * D + D * HD) + d * 32
    mla = d * 32 * 192 + d * 576 + 512 * 32 * 256 + 4096 * d
    assert mod.kda_matmul_params(cfg) == kda == 39_460_864
    assert mod.mla_matmul_params(cfg) == mla == 29_114_368
    assert mod.expected_rows_per_token(cfg) == 0.25
    # the scan: the kernel module's own count a site, times the KDA layers
    from paddle_tpu.kernels import gated_delta
    chunk = 5 * 64 * 64 * D + 64 ** 3 // 3 + 6 * 64 * D * D
    assert mod.scan_flops_per_chunk(cfg) == chunk
    assert mod.scan_flops_per_step(cfg, 1) == \
        4 * gated_delta.flops(1, S, 32, D, 64) == 3 * 4 * 32 * (S // 64) * chunk
    assert mod.scan_bytes_per_step(cfg, 1) == \
        4 * gated_delta.moved_bytes(1, S, 32, D, 2)
    assert mod.scan_flops_per_step(cfg, 2) == \
        2 * mod.scan_flops_per_step(cfg, 1)
    expert_layer = d * 256 + 3 * d * 1024 + 0.25 * 3 * d * 1024
    matmul = 4 * kda + mla + 3 * d * 9216 + 4 * expert_layer + d * 20480
    attn = 3 * 2 * S * 32 * (192 + 128)
    assert mod.flops_per_sample(cfg) == pytest.approx(
        S * (6.0 * matmul + attn) + mod.scan_flops_per_step(cfg, 1))
    # ISSUE 47's shares of a token's forward FLOPs (the harness's
    # convention does not take the causal half off MLA's scores, ISSUE 47's
    # arithmetic does): KDA as a whole over two fifths (44% at S 8192, 46%
    # at the cell's 4096, where MLA's scores are half as many a token)
    forward = mod.flops_per_sample(cfg) / 3 / S
    kda_share = (2 * 4 * kda + mod.scan_flops_per_step(cfg, 1) / 3 / S) \
        / (forward - attn / 3 / 2)
    assert kda_share == pytest.approx(0.456, abs=0.01)


def test_the_scans_roofline_cannot_pass_100_percent_at_the_real_shape():
    """What the share divides is a lower bound on what ANY engine does: the
    bytes are one read of every input and one write of every output of the
    two passes (each at its own dtype), the FLOPs the chunked algorithm's
    matmuls at their triangles, no recomputed pass, no state traffic.  At
    the cell's shape the bytes bound it (the docstring of the reader says
    so), and the engine this PR runs moves and multiplies more than both."""
    mod, cfg = _module(), _config()
    peak = peaks("TPU v5 lite")
    t_flops = mod.scan_flops_per_step(cfg, 1) / peak["bf16_flops"]
    t_bytes = mod.scan_bytes_per_step(cfg, 1) / peak["hbm_bytes_per_s"]
    assert t_bytes > 2 * t_flops
    assert "BYTES bound it" in _reader(ROOFLINE).__doc__
    S, wide = cfg["max_length"], cfg["max_length"] * 4096
    # the inputs and outputs alone, a layer: the forward reads q, k, v
    # (bf16), g and beta (fp32) and writes out (bf16): 12 B a channel; the
    # backward reads those and dOut and writes five gradients: 22 B
    rows = S * 32
    layer = (12 + 22) * wide + (4 + 8) * rows
    assert mod.scan_bytes_per_step(cfg, 1) == 4 * layer
    # the engine that runs holds at least the chunk states besides: what it
    # moves is above the count, so the time is above the floor
    from paddle_tpu.kernels import gated_delta
    tiles = gated_delta.plan(1, S, 32, 128)
    assert tiles == {"chunk": 64, "chunks": S // 64, "group": 8}
    assert gated_delta.state_bytes(1, 32, 128) * tiles["chunks"] > 0.1e9


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
    assert a["tokens"].min() >= 0 and 18000 < a["tokens"].max() < 20480


def test_the_rehearsals_first_step_is_the_references():
    """The rehearsal's first step as the benchmark takes it, through the
    harness's FirstStep: three layers by the rehearsal's own two lists
    (KDA, MLA, KDA), a share of 4 experts of a router 16 wide."""
    import jax
    import paddle_tpu as fluid

    cell = manifest.Cell(MANIFEST, CELL, rehearse=True)
    assert cell.config_module.layer_kinds(cell.config) == \
        ["kda", "mla", "kda"]
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
OBS = {"kind": "train", "trace_steps": 2, "trace": {"n_ops": 16},
       "platform": "tpu", "device_kind": "TPU v5 lite",
       "samples_per_step": 1}


@pytest.mark.parametrize("name", sorted(SCOPE_READERS))
def test_scope_reader_reads_its_scope_per_traced_step(name, trace_root):
    """The scan's `while` and the operation of its body inside it count
    once; the convolutions run again under the recomputation and the scan
    does not; the projection before them counts under neither."""
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


def test_roofline_reader_divides_the_larger_floor_by_the_scopes_time(
        trace_root):
    reader = _reader(ROOFLINE)
    trace_root(TRACE)
    mod, cfg, peak = _module(), _config(), peaks("TPU v5 lite")
    floor = max(mod.scan_flops_per_step(cfg, 1) / peak["bf16_flops"],
                mod.scan_bytes_per_step(cfg, 1) / peak["hbm_bytes_per_s"])
    assert floor == mod.scan_bytes_per_step(cfg, 1) / peak["hbm_bytes_per_s"]
    us = SCOPE_READERS["kda_scan_ms.train"]
    assert reader.read(OBS) == pytest.approx(100.0 * floor / (us * 1e-6 / 2))
    assert reader.read({**OBS, "platform": "cpu"}) is None
    assert reader.read({**OBS, "trace_steps": 0}) is None


@pytest.mark.parametrize("name", sorted(SCOPE_READERS) + [ROOFLINE])
@pytest.mark.parametrize("trace", ["trace_cca_scopes.textproto", None])
def test_a_program_without_the_scopes_reports_nothing(name, trace,
                                                      trace_root):
    """The parent of the PR that added them (its traces have other scopes),
    and a run with no trace: nothing is read and nothing is raised."""
    if trace:
        trace_root(trace)
    assert _reader(name).read(OBS) is None


def _tiny_step(S=128):
    """A step of the model at widths cut to nothing, built and started on
    the CPU."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    spec = models.hybrid_linear_decoder(models.HybridLinearDecoderConfig(
        vocab_size=32, max_length=S, n_layer=3, kda_layers=(1, 3),
        full_attn_layers=(2,), d_model=16, d_inner=32,
        kda_heads=2, kda_head_dim=8, n_head=2, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, kv_lora_rank=8,
        n_routed_experts=8, experts_held=2, top_k=2, d_expert=8))
    fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(spec.loss)
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    return spec


def test_lowered_spans_on_a_step_lowered_for_the_tpu():
    """benchmark/harness/lowered_spans.py on the program itself: under the
    TPU trace scope the tiny step's sites say what they were given."""
    import paddle_tpu as fluid

    _tiny_step()
    obs = {"kind": "train", "samples_per_step": 1, "chips": 1,
           "platform": "cpu"}
    with fluid.flags.tpu_trace_scope(True):
        spans = lowered_spans.of_step(obs, [
            "kda.lower", "mla.lower", "moe.lower", "router.lower"])
    assert len(spans["kda.lower"]) >= 2 and len(spans["mla.lower"]) >= 1
    assert all(s["engine"] == "xla" and s["heads"] == 2 and s["chunk"] == 64
               and s["chunks"] == 2 and s["kept"] == "out,states"
               for s in spans["kda.lower"])
    assert all(s["rope"] == "none" for s in spans["mla.lower"])
    assert all(m["experts_total"] == 8 and m["experts_held"] == 2
               and m["top_k"] == 2 for m in spans["moe.lower"])


def test_bodies_lowered_reads_one_lowering_of_every_layers_body():
    """Every layer is a one-trip `recurrence` (the unit of recomputation):
    each body is lowered once."""
    import paddle_tpu as fluid

    _tiny_step(S=64)
    ops = fluid.default_main_program().global_block().desc.ops
    assert [op.attr("trips") for op in ops if op.type == "recurrence"] == \
        3 * [1]
    assert _reader("loop_bodies_lowered.train").read(
        {"kind": "train", "samples_per_step": 1, "chips": 1,
         "platform": "cpu"}) == 1


def test_the_cells_readers_are_in_the_manifest(manifest_holds):
    """This file's entries are there, in their own order, with at least this
    cell; what stands behind them, and what other cells report, is theirs to
    say (conftest.py)."""
    entries = {m["name"]: m for m in manifest_holds(
        "per_layer", ["kda_scan_ms.train", "kda_mix_ms.train", ROOFLINE],
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
                "attn_full_roofline.train", "cca_mix_ms.train",
                "cca_attend_ms.train", "cca_attend_roofline.train"} & reported
