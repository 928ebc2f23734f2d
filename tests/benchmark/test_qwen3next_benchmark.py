"""What PR 65 adds to the benchmark: the qwen3-next-80b-a3b configuration
(its file against the published config, its FLOP, pair and byte counts at
the real shape against hand counts, its batch, its reference against the
program through the harness) and the four readers of
`qwen3next-train-gdn8k`, on a small recorded trace of their own."""

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
CELL, CONFIG = "qwen3next-train-gdn8k", "qwen3-next-80b-a3b"
REDUCED = {"num_hidden_layers": 4, "num_experts": 32, "vocab_size": 18992}
# us in the fixture
SCOPE_READERS = {"gdn_scan_ms.train": 19.0, "gdn_mix_ms.train": 11.0,
                 "attn_gate_ms.train": 5.0}
OLDER_SCOPE_READERS = {"attn_full_ms.train": 11.0}
ROOFLINE = "gdn_scan_roofline.train"
NEW = set(SCOPE_READERS) | {ROOFLINE}
APPENDED = {"compiles_in_window.train", "mfu.train", "device_idle.train",
            "values_moved_per_step.train", "loop_bodies_lowered.train",
            "loop_heads_ms.train", "hbm_peak_gb.train", "attn_full_ms.train",
            "attn_steps_skipped.train",
            "moe_router_ms.train", "moe_experts_ms.train",
            "moe_dispatch_ms.train", "moe_shared_ms.train",
            "turnaround_host_ms.train",
            "turnaround_runtime_ms.train", "turnaround_copy_ms.train",
            "turnaround_release_ms.train", "turnaround_caller_ms.train",
            "turnaround_entry_ms.train", "clock_skew_us.train",
            "setup_import_s.train", "setup_startup_s.train",
            "setup_first_step_s.train", "setup_trace_lower_s.train",
            "setup_compile_s.train", "setup_cache_load_s.train",
            "setup_cache_misses.train", "setup_other_compile_s.train",
            "setup_cache_entries_mb.train", "setup_cache_evicted_mb.train"}
TRACE = "trace_qwen3next_scopes.textproto"


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
    for key, want in REDUCED.items():
        assert cfg[key] == want and cfg["published"][key] != want, key
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                "vocab_size": 151936}
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(rows):
        row = [json.loads(line) for line in open(rows)
               if '"name": "Qwen3-Next-80B-A3B-Instruct"' in line][0]
        assert cfg["source"].startswith(row["source_url"])
        for key, want in row["config"].items():
            if key in REDUCED:
                assert cfg["published"][key] == want, key
            else:
                assert cfg[key] == want, key
    # one whole period, a sixteenth of the experts, an eighth of the tables
    assert cfg["num_hidden_layers"] == cfg["full_attention_interval"] == 4
    assert _module().layer_kinds(cfg) == ("gdn",) * 3 + ("attention",)
    assert cfg["num_experts"] * 16 == cfg["router_experts"] == 512
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert (cfg["expert_offset"], cfg["train_router"]) == (0, False)
    # no width is cut
    assert (cfg["hidden_size"], cfg["linear_num_key_heads"],
            cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], _module().rotary_dim(cfg),
            cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"]) \
        == (2048, 16, 32, 128, 128, 4, 16, 2, 256, 64, 512, 512, 10)
    for key in ("column_order", "convolution", "l2_norm", "gated_norm",
                "decay_start", "init", "rotary", "router", "optimizer",
                "mtp", "max_length"):
        assert key in cfg["assumed"], key
    for said in ("group of 16", "experts 0-31", "rows 0-18991 of 151936",
                 "over 8 of the chips", "no code stands in",
                 "Layers 0-3 of 48"):
        assert said.lower() in cfg["deployment"].lower(), said
    for said in ("625,667,136", "33,718,464", "27,263,488", "104,859,648",
                 "12.51 GB"):
        assert said in cfg["reduced_why"], said
    entry = [c for c in MANIFEST["configs"] if c["name"] == CONFIG][0]
    assert cfg["source"].startswith(entry["source"])
    assert entry["source"].endswith(
        "Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json")


def test_configuration_entry_and_files():
    entry = [c for c in MANIFEST["configs"] if c["name"] == CONFIG][0]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    data = _config()
    for key in ("source", "reduced", "assumed", "deployment", "kind",
                "equations", "memory", "reduced_why", "published",
                "reference", "rehearsal", "optimizer", "sample"):
        assert key in data, key
    assert "to be measured" not in json.dumps(data).lower()
    assert data["reduced"] == entry["reduced"] == list(REDUCED)
    base = os.path.join(REPO, "benchmark", "configs", CONFIG)
    assert os.path.isfile(base + ".py")
    assert os.path.isfile(base + ".reference.py")
    assert {"loss_rtol", "grad_cos_min", "grad_norm_rtol",
            "param_norm_factor", "rows_per_part", "query_block",
            "state_block", "key_head_block", "expert_block",
            "tolerances"} <= set(data["reference"])
    cells = [w for w in MANIFEST["workloads"] if w["config"] == CONFIG]
    assert [w["name"] for w in cells] == [CELL]
    assert cells[0]["chips"] == 1 and cells[0]["traffic"] == "train-steady"
    for text in (entry["why"], entry["source"], cells[0]["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    # what ISSUE 65 asked of the cell: one packed row of 8192 tokens
    sizing = json.load(open(os.path.join(
        REPO, "benchmark", "cells", CELL + ".json")))
    assert sizing["per_chip_batch"] == 1 and data["max_length"] == 8192
    memory = data["memory"]
    assert memory["parameters"] == 625667136
    assert memory["tokens_8192"]["beside_first_step_bytes"] < 16.0e9
    # the reference runs beside the program's state
    assert memory["tokens_8192"]["step_argument_bytes"] \
        + memory["tokens_8192"]["reference_peak_bytes"] < 16.9e9
    assert memory["on_the_chip"]["memory_peak_bytes"] \
        > 0.25 * memory["on_the_chip"]["memory_limit_bytes"]


def test_the_reference_imports_nothing_of_the_program():
    text = open(os.path.join(REPO, "benchmark", "configs",
                             CONFIG + ".reference.py")).read()
    imports = re.findall(r"^\s*(?:import|from)\s+(\S+)", text, re.M)
    assert sorted(set(imports)) == ["jax", "jax.numpy"]
    # the recurrence token by token and the dense masked scores of its own;
    # never the chunked form
    assert "def _token(" in text and "def _attend(" in text
    assert "cumsum" not in text and "tril" not in text
    assert "paddle_tpu" not in text.replace(
        "paddle_tpu/models/gated_delta_decoder.py", "")


def test_flops_pairs_and_bytes_are_counted_from_the_real_shapes():
    mod, cfg = _module(), _config()
    S, d = cfg["max_length"], 2048
    mixers = {"gdn": d * 12288 + d * 64 + 4096 * d,
              "attention": d * 8192 + 2 * d * 512 + 4096 * d}
    for kind, want in mixers.items():
        assert mod.mixer_matmul_params(cfg, kind) == want, kind
    assert mod.expected_rows_per_token(cfg) == 0.625
    block = d * 512 * 2 / 6 + 3 * d * 512 + d + 0.625 * 3 * d * 512
    assert mod.expert_layer_matmul_params(cfg) == pytest.approx(block)
    assert mod.visible_pairs(cfg) == S * (S + 1) // 2
    t, s = np.arange(96)[:, None], np.arange(96)[None]
    assert mod.visible_pairs({**cfg, "max_length": 96}) == int((s <= t).sum())
    assert mod.attend_flops_per_pair(cfg) == 2 * (256 + 256) * 16
    # the scans, by hand, a chunk of 64 tokens: a key head's two products at
    # their triangles; a value head's inverse, T on [K | V], the state read
    # twice and written once, P U'; forward, and twice that back
    key_head = 2 * 64 * 64 * 128
    value_head = 3 * 64 * 64 * 128 + 64 ** 3 // 3 + 6 * 64 * 128 * 128
    scans = 3.0 * 3 * (S // 64) * (16 * key_head + 32 * value_head)
    assert mod.scan_flops_per_step(cfg, 1) == scans
    assert mod.scan_flops_per_step(cfg, 3) == 3 * scans
    matmul = 3 * mixers["gdn"] + mixers["attention"] + 4 * block + d * 18992
    assert mod.flops_per_sample(cfg) == pytest.approx(
        S * 6.0 * matmul + 3 * 16384 * mod.visible_pairs(cfg) + scans)
    # ISSUE 65's arithmetic a token, forward MFLOP: three mixers'
    # projections 202.1, the attention layer's 54.5 and its visible pairs
    # 67.1, the head 77.8; the expert blocks 49.3 with a trained router
    assert 2 * 3 * mixers["gdn"] / 1e6 == pytest.approx(202.1, abs=0.1)
    assert 2 * mixers["attention"] / 1e6 == pytest.approx(54.5, abs=0.1)
    assert 16384 * mod.visible_pairs(cfg) / S / 1e6 \
        == pytest.approx(67.1, abs=0.1)
    assert 2 * d * 18992 / 1e6 == pytest.approx(77.8, abs=0.1)
    assert 2 * 4 * mod.expert_layer_matmul_params(
        {**cfg, "train_router": True}) / 1e6 == pytest.approx(49.3, abs=0.1)
    assert scans / 3 / S / 1e6 == pytest.approx(12.7, abs=0.1)
    assert mod.flops_per_sample(cfg) / 1e12 == pytest.approx(11.26, abs=0.02)
    # the scans' bytes a layer: 6 passes over [S, 2048] (q, k twice read,
    # dq, dk written) and 5 over [S, 4096] (v twice, out, its cotangent,
    # dv) in bf16, 6 over [S, 32] fp32 (g, beta twice, dg, dbeta)
    assert mod.scan_bytes_per_step(cfg, 1) \
        == 3 * S * (2 * (6 * 2048 + 5 * 4096) + 6 * 4 * 32)
    assert mod.scan_bytes_per_step(cfg, 2) == 2 * mod.scan_bytes_per_step(
        cfg, 1)
    # the op's own span counts the same passes and operations
    from paddle_tpu.kernels import gated_delta as kda
    assert 3 * kda.moved_bytes(1, S, 32, 128, 2, 16, True) \
        == mod.scan_bytes_per_step(cfg, 1)
    assert 3 * kda.flops(1, S, 32, 128, 64, 16) == scans


def test_the_roofline_cannot_pass_100_percent_at_the_real_shape():
    """What the share divides is the larger of the bytes' time and the
    matmuls' time at the MXU's peak; every pass that runs moves at least
    those bytes and does at least those operations, so the share stays
    under 100% whatever the time.  The bytes bound it."""
    mod, cfg = _module(), _config()
    peak = peaks("TPU v5 lite")
    by_bytes = mod.scan_bytes_per_step(cfg, 1) / peak["hbm_bytes_per_s"]
    by_flops = mod.scan_flops_per_step(cfg, 1) / peak["bf16_flops"]
    assert by_bytes > by_flops > 0.7 * by_bytes
    assert by_bytes / 3 == pytest.approx(0.663e-3, rel=0.01)
    assert by_flops / 3 == pytest.approx(0.529e-3, rel=0.01)
    doc = " ".join(_reader(ROOFLINE).__doc__.split())
    assert "cannot pass 100%" in doc and "BYTES bound it" in doc


def test_batch_is_ids_of_the_slice_shifted_by_one_and_the_seeds():
    mod = _module()
    cfg = {**_config(), "max_length": 64}
    spec = types.SimpleNamespace(feed_names=["tokens", "labels"])
    a = mod.make_batch(cfg, spec, 3, 3000000019)
    b = mod.make_batch(cfg, spec, 3, 3000000019)
    c = mod.make_batch(cfg, spec, 3, 3000000020)
    assert a["tokens"].shape == a["labels"].shape == (3, 64)
    assert a["tokens"].dtype == a["labels"].dtype == np.int64
    np.testing.assert_array_equal(a["labels"][:, :-1], a["tokens"][:, 1:])
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert (a["tokens"] != c["tokens"]).mean() > 0.98
    assert a["tokens"].min() >= 0 and 15000 < a["tokens"].max() < 18992


def test_the_rehearsals_first_step_is_the_references():
    """The rehearsal's first step as benchmark/run.py takes it, through the
    harness's FirstStep: one period of four layers at widths cut to
    nothing."""
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
    # the program itself: one `gdn.lower` a Gated DeltaNet layer with a
    # head's decay and half as many key heads, every layer's body lowered
    # once
    obs = {"kind": "train", "samples_per_step": 2, "chips": 1,
           "platform": "cpu"}
    spans = lowered_spans.of_step(obs, ["gdn.lower", "kda.lower"])
    assert [(s["engine"], s["decay"], s["key_heads"], s["heads"])
            for s in spans["gdn.lower"]][:3] == [("xla", "head", 2, 4)] * 3
    assert spans["kda.lower"] == []
    ops = fluid.default_main_program().global_block().desc.ops
    assert [op.attr("trips") for op in ops if op.type == "recurrence"] == \
        [1] * 4
    assert _reader("loop_bodies_lowered.train").read(obs) == 1


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
OBS = {"kind": "train", "trace_steps": 2, "trace": {"n_ops": 15},
       "platform": "tpu", "device_kind": "TPU v5 lite",
       "samples_per_step": 1}


@pytest.mark.parametrize("name", sorted({**SCOPE_READERS,
                                         **OLDER_SCOPE_READERS}))
def test_scope_reader_reads_its_scope_per_traced_step(name, trace_root):
    """Forward, what the recomputation makes again and the backward of a
    scope's ops count (the flash backward kernel sits under `flash.bwd`
    INSIDE `attn.full`, the scan's backward kernel and the sum of a key
    head's dq under `transpose(jvp(gdn.scan))`, the gate's pull under
    `transpose(jvp(attn.gate))`), the in-projection and Adam under none."""
    reader = _reader(name)
    trace_root(TRACE)
    us = {**SCOPE_READERS, **OLDER_SCOPE_READERS}[name]
    assert reader.read(OBS) == pytest.approx(us / 1e3 / 2)
    assert reader.read({}) is None
    assert reader.read({**OBS, "kind": "serve"}) is None
    assert reader.read({**OBS, "trace_steps": 0}) is None


def test_roofline_reader_divides_the_bytes_time_by_the_scopes_time(
        trace_root):
    reader = _reader(ROOFLINE)
    trace_root(TRACE)
    mod, cfg, peak = _module(), _config(), peaks("TPU v5 lite")
    us = SCOPE_READERS["gdn_scan_ms.train"]
    want = 100.0 * mod.scan_bytes_per_step(cfg, 1) \
        / (us * 1e-6 / 2) / peak["hbm_bytes_per_s"]
    assert reader.read(OBS) == pytest.approx(want)
    assert reader.read({**OBS, "samples_per_step": 2}) == \
        pytest.approx(2 * want)
    assert reader.read({**OBS, "platform": "cpu"}) is None
    assert reader.read({**OBS, "trace_steps": 0}) is None


@pytest.mark.parametrize("name", sorted(SCOPE_READERS) + [ROOFLINE])
@pytest.mark.parametrize("trace", ["trace_kda_scopes.textproto", None])
def test_a_program_without_the_scopes_reports_nothing(name, trace,
                                                      trace_root):
    """The parent of the PR that added them (its traces have other scopes:
    `kda.scan` is not `gdn.scan`), and a run with no trace: nothing is read
    and nothing is raised."""
    if trace:
        trace_root(trace)
    assert _reader(name).read(OBS) is None


def test_the_cells_readers_are_in_the_manifest(manifest_holds):
    """This file's entries are there, in their own order, with at least this
    cell; what stands behind them, and what other cells report, is theirs to
    say (conftest.py)."""
    entries = {m["name"]: m for m in manifest_holds(
        "per_layer", ["gdn_scan_ms.train", "gdn_mix_ms.train",
                      "attn_gate_ms.train", ROOFLINE],
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
    for name in sorted(APPENDED):
        manifest_holds("per_layer", [name], cells=[CELL])
    manifest_holds("end_to_end", ["train_samples_per_s"], cells=[CELL])
