"""What PR 56 adds to the benchmark: the evabyte-6.5b configuration (its
file against the published config, its FLOP, pair and byte counts at the
real shape against a brute-force count of the two masks, its batch, its
reference against the program through the harness) and the three readers of
`evabyte-train-eva8k`, on a small recorded trace."""

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
CELL, CONFIG = "evabyte-train-eva8k", "evabyte-6.5b"
# https://huggingface.co/EvaByte/EvaByte/blob/main/config.json as the
# model-configs catalog has it: every key of the row
PUBLISHED = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048}
REDUCED = {"num_hidden_layers": 4}
SCOPE_READERS = {"eva_attend_ms.train": 20.0,
                 "eva_pool_ms.train": 8.0}            # us in the fixture
ROOFLINE = "eva_attend_roofline.train"
NEW = set(SCOPE_READERS) | {ROOFLINE}
APPENDED = {"compiles_in_window.train", "mfu.train", "device_idle.train",
            "values_moved_per_step.train", "loop_bodies_lowered.train",
            "loop_heads_ms.train", "hbm_peak_gb.train",
            "turnaround_host_ms.train",
            "turnaround_runtime_ms.train", "turnaround_copy_ms.train",
            "turnaround_release_ms.train", "turnaround_caller_ms.train",
            "turnaround_entry_ms.train", "clock_skew_us.train",
            "setup_import_s.train", "setup_startup_s.train",
            "setup_first_step_s.train", "setup_trace_lower_s.train",
            "setup_compile_s.train", "setup_cache_load_s.train",
            "setup_cache_misses.train", "setup_other_compile_s.train",
            "setup_cache_entries_mb.train", "setup_cache_evicted_mb.train"}
TRACE = "trace_eva_scopes.textproto"


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
def test_file_holds_the_published_config_and_cuts_the_depth_alone():
    cfg = _config()
    assert cfg["reduced"] == list(REDUCED)
    for key, want in PUBLISHED.items():
        if key in REDUCED:
            assert cfg[key] == REDUCED[key] and cfg["published"][key] == want
        else:
            assert cfg[key] == want, key
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(rows):
        row = [json.loads(line) for line in open(rows)
               if '"name": "EvaByte"' in line][0]
        assert row["config"] == PUBLISHED
    # the share of the heads: 4 chips share every layer; the published
    # count stays (the accepted manifest test takes no `reduced` key that
    # contains "head"), the share beside it
    assert cfg["heads_held"] * 4 == cfg["num_attention_heads"] == 32
    assert cfg["head_offset"] == 0
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    assert "test_benchmark_manifest" in cfg["heads_held_why"]
    for key in ("pooling_rule", "no_chunk_size_term", "summaries_seen",
                "pooling_vectors_start", "head_loss_weights", "init",
                "fp32_logits", "mixedp_attn", "rotary_layout", "optimizer",
                "max_length"):
        assert key in cfg["assumed"], key
    assert "ICLR 2023" in cfg["assumed"]["pooling_rule"]
    for said in ("0-7 of 32", "group of 4", "2560-wide head",
                 "Layers 0-3 of 32", "no code stands in"):
        assert said.lower() in cfg["deployment"].lower(), said
    assert "620,015,616" in cfg["reduced_why"]
    entry = [c for c in MANIFEST["configs"] if c["name"] == CONFIG][0]
    assert cfg["source"].startswith(entry["source"])
    assert entry["source"].endswith("EvaByte/EvaByte/blob/main/config.json")


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
    # what ISSUE 56 asked of the cell: one packed row of 8192 bytes
    sizing = json.load(open(os.path.join(
        REPO, "benchmark", "cells", CELL + ".json")))
    assert sizing["per_chip_batch"] == 1 and data["max_length"] == 8192
    memory = data["memory"]
    assert memory["parameters"] == 620015616
    assert memory["tokens_8192"]["beside_first_step_bytes"] < 16.9e9
    # the reference runs beside the program's state
    assert memory["tokens_8192"]["step_argument_bytes"] \
        + memory["tokens_8192"]["reference_peak_bytes"] < 16.9e9


def test_the_reference_imports_nothing_of_the_program():
    text = open(os.path.join(REPO, "benchmark", "configs",
                             CONFIG + ".reference.py")).read()
    imports = re.findall(r"^\s*(?:import|from)\s+(\S+)", text, re.M)
    assert sorted(set(imports)) == ["jax", "jax.numpy"]
    # the pooling as written and the two masks of its own
    assert "def _pool(" in text and "def _summaries_seen(" in text
    assert "paddle_tpu" not in text.replace(
        "paddle_tpu/models/eva_decoder.py", "")


def _brute_pairs(S, w, c):
    """(window pairs, summary pairs) by counting the two masks."""
    t = np.arange(S)[:, None]
    s = np.arange(S)[None, :]
    j = np.arange(S // c)[None, :]
    own = ((s // w == t // w) & (s <= t)).sum()
    far = ((j * c) // w < t // w).sum()
    return int(own), int(far)


def test_flops_pairs_and_bytes_are_counted_from_the_real_shapes():
    mod, cfg = _module(), _config()
    S, d = cfg["max_length"], 4096
    layer = 4 * d * 8 * 128 + 3 * d * 11008
    assert mod.layer_matmul_params(cfg) == layer == 152_043_520
    assert mod.head_matmul_params(cfg) == d * 8 * 320 == 10_485_760
    # the pairs: the formula at the real shape, and against a brute-force
    # count of the two masks where that is small enough to build
    assert mod.eva_pairs(cfg) == (4 * 2048 * 2049 // 2,
                                  128 * 2048 * (0 + 1 + 2 + 3))
    assert mod.eva_pairs(cfg) == (8_392_704, 1_572_864)
    for S_, w, c in ((512, 128, 16), (448, 128, 16), (96, 128, 16),
                     (300, 136, 8)):
        small = {**cfg, "max_length": S_, "window_size": w, "chunk_size": c}
        assert mod.eva_pairs(small) == _brute_pairs(S_, min(w, S_), c), S_
    pairs = sum(mod.eva_pairs(cfg))
    attend = 3 * (2 * 2 * 8 * 128) * pairs * 4
    assert mod.flops_per_sample(cfg) == pytest.approx(
        S * 6.0 * (4 * layer + 10_485_760) + attend)
    # ISSUE 56's arithmetic a byte and layer, forward MFLOP: the MLP 270.5,
    # the projections at 8 heads 33.6, EVA's pairs 5.0, the head 21.0 once;
    # ~30.9 TFLOP a step, 1.6% of it EVA's core
    assert 2 * 3 * d * 11008 / 1e6 == pytest.approx(270.5, abs=0.1)
    assert 2 * 4 * d * 1024 / 1e6 == pytest.approx(33.6, abs=0.1)
    assert 2 * 2 * 8 * 128 * pairs / S / 1e6 == pytest.approx(5.0, abs=0.05)
    assert mod.flops_per_sample(cfg) / 1e12 == pytest.approx(30.9, abs=0.05)
    assert attend / mod.flops_per_sample(cfg) == pytest.approx(0.016,
                                                               abs=0.001)
    # the roofline's count: 7 block products over the visible pairs
    assert mod.eva_attend_flops_per_step(cfg, 1) == \
        7 * 2 * 128 * 8 * pairs * 4 == attend * 7 / 6
    assert mod.eva_attend_flops_per_step(cfg, 2) == \
        2 * mod.eva_attend_flops_per_step(cfg, 1)
    # the pooling's bytes: 6144 pooled positions of K and V at 8 heads
    one = 2 * 8 * 6144 * 128 * 2
    assert mod.eva_pool_bytes_per_step(cfg, 1) == (3 + 2 / 16) * one * 4
    # the op's own span counts the same pairs and the forward's bytes
    from paddle_tpu.kernels import eva_attention as eva
    assert eva.pairs(S, 2048, 16) == mod.eva_pairs(cfg)


def test_the_roofline_cannot_pass_100_percent_at_the_real_shape():
    """What the share divides is the FLOPs of the pairs the masks let
    through, 7 products: the kernels compute whole blocks and mask what an
    edge cuts (a 2048 window's causal half in 512 x 512 or 1024 x 1024
    blocks; every window against all 384 pooled chunks where 0, 128, 256,
    384 are seen), so they run at least these and the share stays under
    100% whatever the time."""
    mod, cfg = _module(), _config()
    peak = peaks("TPU v5 lite")
    counted = mod.eva_attend_flops_per_step(cfg, 1)
    # what the two calls compute at the least: every causal block of the
    # window call at 1024 x 1024 forward (3 of 4) and 512 x 512 backward
    # (10 of 16), all of the summaries' 2048 x 384
    rows = 8 * 4
    per_pair = 2 * 128
    fwd = rows * (3 * 1024 * 1024 + 2048 * 384) * 2 * per_pair
    bwd = rows * (10 * 512 * 512 + 2048 * 384) * 5 * per_pair
    assert counted < (fwd + bwd) * 4
    assert counted / peak["bf16_flops"] == pytest.approx(2.9e-3, rel=0.02)
    assert "cannot pass 100%" in _reader(ROOFLINE).__doc__


def test_batch_is_bytes_shifted_by_one_to_eight_and_the_seeds():
    mod = _module()
    cfg = {**_config(), "max_length": 64}
    spec = types.SimpleNamespace(feed_names=["tokens", "labels"])
    a = mod.make_batch(cfg, spec, 3, 3000000019)
    b = mod.make_batch(cfg, spec, 3, 3000000019)
    c = mod.make_batch(cfg, spec, 3, 3000000020)
    assert a["tokens"].shape == (3, 64) and a["labels"].shape == (3, 64, 8)
    assert a["tokens"].dtype == a["labels"].dtype == np.int64
    for i in range(8):
        np.testing.assert_array_equal(a["labels"][:, :63 - i, i],
                                      a["tokens"][:, 1 + i:])
        assert (a["labels"][:, 63 - i:, i] == mod.IGNORED).all()
    assert (a["labels"] != mod.IGNORED).sum() == 3 * (8 * 64 - 36)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert (a["tokens"] != c["tokens"]).mean() > 0.98
    assert a["tokens"].min() >= 0 and 300 < a["tokens"].max() < 320
    # the program's own maker agrees
    from paddle_tpu.models.eva_decoder import shifted_labels
    np.testing.assert_array_equal(a["labels"],
                                  shifted_labels(a["tokens"], 8))


def test_the_rehearsals_first_step_is_the_references():
    """The rehearsal's first step as the benchmark takes it, through the
    harness's FirstStep: 2 layers, a share of 2 heads of 4, four windows,
    three prediction heads."""
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
    assert abs(found["grad_norm_ratio"] - 1) < 1e-4


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
OBS = {"kind": "train", "trace_steps": 2, "trace": {"n_ops": 12},
       "platform": "tpu", "device_kind": "TPU v5 lite",
       "samples_per_step": 1}


@pytest.mark.parametrize("name", sorted(SCOPE_READERS))
def test_scope_reader_reads_its_scope_per_traced_step(name, trace_root):
    """Forward, the recomputed pooling and the backward of a scope's ops
    count (the flash backward kernels sit under `flash.bwd` INSIDE
    `eva.attend`), the projection and the MLP under neither."""
    reader = _reader(name)
    trace_root(TRACE)
    assert reader.read(OBS) == pytest.approx(SCOPE_READERS[name] / 1e3 / 2)
    assert reader.read({}) is None
    assert reader.read({**OBS, "kind": "serve"}) is None
    assert reader.read({**OBS, "trace_steps": 0}) is None


def test_roofline_reader_divides_the_pairs_flops_by_the_scopes_time(
        trace_root):
    reader = _reader(ROOFLINE)
    trace_root(TRACE)
    mod, cfg, peak = _module(), _config(), peaks("TPU v5 lite")
    us = SCOPE_READERS["eva_attend_ms.train"]
    want = 100.0 * mod.eva_attend_flops_per_step(cfg, 1) \
        / (us * 1e-6 / 2) / peak["bf16_flops"]
    assert reader.read(OBS) == pytest.approx(want)
    assert reader.read({**OBS, "samples_per_step": 2}) == \
        pytest.approx(2 * want)
    assert reader.read({**OBS, "platform": "cpu"}) is None
    assert reader.read({**OBS, "trace_steps": 0}) is None


@pytest.mark.parametrize("name", sorted(SCOPE_READERS) + [ROOFLINE])
@pytest.mark.parametrize("trace", ["trace_mhc_scopes.textproto", None])
def test_a_program_without_the_scopes_reports_nothing(name, trace,
                                                      trace_root):
    """The parent of the PR that added them (its traces have other scopes),
    and a run with no trace: nothing is read and nothing is raised."""
    if trace:
        trace_root(trace)
    assert _reader(name).read(OBS) is None


def test_lowered_spans_and_bodies_lowered_on_the_program_itself():
    """benchmark/harness/lowered_spans.py on a tiny step: `eva.lower` a
    layer with the counts the readers' functions use, and every layer's
    body lowered once."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    spec = models.eva_decoder(models.EvaDecoderConfig(
        vocab_size=32, max_length=64, n_layer=2, d_model=16, d_inner=24,
        n_head=2, heads_held=1, head_dim=8, window_size=16, chunk_size=4,
        pred_heads=2))
    fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(spec.loss)
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    obs = {"kind": "train", "samples_per_step": 1, "chips": 1,
           "platform": "cpu"}
    spans = lowered_spans.of_step(obs, ["eva.lower"])["eva.lower"]
    assert len(spans) >= 2
    for s in spans:
        assert (s["windows"], s["chunks"], s["heads_held"], s["engine"]) == \
            (4, 12, 1, "xla")
        assert (s["window_pairs"], s["summary_pairs"]) == \
            _brute_pairs(64, 16, 4)
    ops = fluid.default_main_program().global_block().desc.ops
    assert [op.attr("trips") for op in ops if op.type == "recurrence"] == \
        [1, 1]
    assert _reader("loop_bodies_lowered.train").read(obs) == 1


def test_the_cells_readers_are_in_the_manifest(manifest_holds):
    """This file's entries are there, in their own order, with at least this
    cell; what stands behind them, and what other cells report, is theirs to
    say (conftest.py)."""
    entries = {m["name"]: m for m in manifest_holds(
        "per_layer", ["eva_attend_ms.train", "eva_pool_ms.train", ROOFLINE],
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
    """The generic .train readers, `loop_heads_ms.train` (the eight-head
    product and its cross entropy run under the scope `loop.heads`), the
    seven turnaround readings and the ten set-up readings have this cell
    among their `workloads`; the readers of other cells' own scopes do
    not."""
    for name in sorted(APPENDED):
        manifest_holds("per_layer", [name], cells=[CELL])
    manifest_holds("end_to_end", ["train_samples_per_s"], cells=[CELL])
    reported = {m["name"] for m in
                manifest.Cell(MANIFEST, CELL).metrics("per_layer")}
    assert not {"collective_ms.train", "loop_body_ms.train",
                "moe_experts_ms.train",
                "mla_ms.train", "attn_full_ms.train", "cca_mix_ms.train",
                "kda_scan_ms.train", "mhc_roofline.train"} & reported
