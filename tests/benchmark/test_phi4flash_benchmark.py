"""What PR 60 adds to the benchmark: the phi-4-mini-flash configuration (its
file against the published config, its parameter, FLOP, pair and byte
counts at the real shape against brute-force counts, its batch, its
reference against the program through the harness) and the five readers of
`phi4flash-train-sambay`, on a small recorded trace."""

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
CELL, CONFIG = "phi4flash-train-sambay", "phi-4-mini-flash"
# https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/
# config.json as the model-configs catalog has it: every key of the row
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
REDUCED = {"num_hidden_layers": 6, "vocab_size": 25008}
# us in the fixture
SCOPE_READERS = {"ssm_scan_ms.train": 19.0, "ssm_mix_ms.train": 8.0,
                 "gmu_ms.train": 8.0, "attn_cross_ms.train": 9.0}
OLDER_SCOPE_READERS = {"attn_sliding_ms.train": 2.0,
                       "attn_full_ms.train": 3.0}
ROOFLINE = "ssm_scan_roofline.train"
NEW = set(SCOPE_READERS) | {ROOFLINE}
APPENDED = {"compiles_in_window.train", "mfu.train", "device_idle.train",
            "values_moved_per_step.train", "loop_bodies_lowered.train",
            "loop_heads_ms.train", "hbm_peak_gb.train",
            "attn_sliding_ms.train", "attn_full_ms.train",
            "turnaround_host_ms.train",
            "turnaround_runtime_ms.train", "turnaround_copy_ms.train",
            "turnaround_release_ms.train", "turnaround_caller_ms.train",
            "turnaround_entry_ms.train", "clock_skew_us.train",
            "setup_import_s.train", "setup_startup_s.train",
            "setup_first_step_s.train", "setup_trace_lower_s.train",
            "setup_compile_s.train", "setup_cache_load_s.train",
            "setup_cache_misses.train", "setup_other_compile_s.train",
            "setup_cache_entries_mb.train", "setup_cache_evicted_mb.train"}
TRACE = "trace_sambay_scopes.textproto"


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
def test_file_holds_the_published_config_and_cuts_depth_and_vocabulary():
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
               if '"name": "Phi-4-mini-flash-reasoning"' in line][0]
        assert row["config"] == PUBLISHED
    # an eighth of the vocabulary, one period of each of the three segments
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert (cfg["self_decoder_periods"], cfg["cross_decoder_periods"]) \
        == (1, 1)
    assert (cfg["published"]["self_decoder_periods"],
            cfg["published"]["cross_decoder_periods"]) == (8, 7)
    assert 2 * (8 + 1 + 7) == PUBLISHED["num_hidden_layers"]
    # no width is cut: Mamba-1's defaults at hidden 2560
    assert (cfg["mamba_expand"], cfg["mamba_d_state"], cfg["mamba_d_conv"],
            cfg["mamba_dt_rank"]) == (2, 16, 4, -(-2560 // 16))
    for key in ("mamba_sizes", "differential_attention", "attention_biases",
                "memory", "no_positions", "lambda_layer_index", "init",
                "optimizer", "max_length", "precision"):
        assert key in cfg["assumed"], key
    assert "arXiv:2410.05258" in cfg["assumed"]["differential_attention"]
    assert "NO key" in cfg["assumed"]["differential_attention"]
    for said in ("rows 0-25007 of 200064", "over 8 chips", "0, 1",
                 "16, 17", "18, 19", "no code stands in"):
        assert said.lower() in cfg["deployment"].lower(), said
    for said in ("697,094,272", "915.3 M", "NOT the published one"):
        assert said in cfg["reduced_why"], said
    entry = [c for c in MANIFEST["configs"] if c["name"] == CONFIG][0]
    assert cfg["source"].startswith(entry["source"])
    assert entry["source"].endswith(
        "microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json")


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
            "scan_block", "tolerances"} <= set(data["reference"])
    cells = [w for w in MANIFEST["workloads"] if w["config"] == CONFIG]
    assert [w["name"] for w in cells] == [CELL]
    assert cells[0]["chips"] == 1 and cells[0]["traffic"] == "train-steady"
    for text in (entry["why"], entry["source"], cells[0]["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    # what ISSUE 60 asked of the cell: one packed row of 8192 tokens
    sizing = json.load(open(os.path.join(
        REPO, "benchmark", "cells", CELL + ".json")))
    assert sizing["per_chip_batch"] == 1 and data["max_length"] == 8192
    memory = data["memory"]
    assert memory["parameters"] == 697094272
    assert memory["tokens_8192"]["beside_first_step_bytes"] < 16.9e9
    # the reference runs beside the program's state
    assert memory["tokens_8192"]["step_argument_bytes"] \
        + memory["tokens_8192"]["reference_peak_bytes"] < 16.9e9


def test_the_reference_imports_nothing_of_the_program():
    text = open(os.path.join(REPO, "benchmark", "configs",
                             CONFIG + ".reference.py")).read()
    imports = re.findall(r"^\s*(?:import|from)\s+(\S+)", text, re.M)
    assert sorted(set(imports)) == ["jax", "jax.numpy", "math"]
    # the scan token by token and the dense masked scores of its own
    assert "def _scan_tokens(" in text and "def _diff_attention(" in text
    assert "paddle_tpu" not in text.replace(
        "paddle_tpu/models/sambay_decoder.py", "")


def test_the_parameters_are_counted_as_the_issue_counts_them():
    """The program's parameters at the real widths, a layer's mixer by its
    kind, from the shapes the builder declares (no array is made)."""
    import paddle_tpu as fluid

    cfg = _config()
    _module().build({**cfg, "max_length": 64}, 1)
    sizes = {p.name: int(np.prod(p.shape))
             for p in fluid.default_main_program().all_parameters()}
    fluid.reset_default_env()

    def layer(i, what):
        return sum(n for name, n in sizes.items()
                   if name.startswith(f"l{i}_{what}"))

    assert [layer(i, m) for i, m in enumerate(
        ("ssm", "attn", "ssm", "attn", "gmu", "attn"))] == [
            41_241_600, 19_668_864, 41_241_600, 19_668_864, 26_214_400,
            13_112_704]
    assert all(layer(i, "mlp") + layer(i, "n1") + layer(i, "n2")
               == 78_653_440 for i in range(6))
    assert sizes["embed"] == 25008 * 2560 == 64_020_480
    assert sum(sizes.values()) == 697_094_272


def test_flops_pairs_and_bytes_are_counted_from_the_real_shapes():
    mod, cfg = _module(), _config()
    S, d, E = cfg["max_length"], 2560, 5120
    assert mod.layer_kinds(cfg) == ("mamba", "sliding", "memory", "full",
                                    "gmu", "cross")
    mixers = {"mamba": d * 2 * E + E * 192 + 160 * E + E * d,
              "sliding": d * 5120 + d * d, "gmu": 2 * d * E,
              "cross": 2 * d * d}
    for kind, want in mixers.items():
        assert mod.mixer_matmul_params(cfg, kind) == want, kind
    assert mod.mixer_matmul_params(cfg, "memory") == mixers["mamba"]
    assert mod.mixer_matmul_params(cfg, "full") == mixers["sliding"]
    # the pairs: the formula at the real shape, and a brute-force count of
    # the mask where that is small enough to build
    assert mod.visible_pairs(cfg, "full") == S * (S + 1) // 2
    assert mod.visible_pairs(cfg, "cross") == S * (S + 1) // 2
    assert mod.visible_pairs(cfg, "sliding") \
        == 512 * 513 // 2 + (S - 512) * 512
    for S_, w in ((96, 16), (40, 64), (64, 64)):
        small = {**cfg, "max_length": S_, "sliding_window": w}
        t, s = np.arange(S_)[:, None], np.arange(S_)[None]
        assert mod.visible_pairs(small, "sliding") \
            == int(((s <= t) & (t - s < w)).sum()), S_
    assert mod.attend_flops_per_pair(cfg) == 2 * (64 + 128) * 40
    matmul = 2 * mixers["mamba"] + 2 * mixers["sliding"] + mixers["gmu"] \
        + mixers["cross"] + 6 * 3 * d * 10240 + d * 25008
    pairs = sum(mod.visible_pairs(cfg, k)
                for k in ("sliding", "full", "cross"))
    scans = 21.0 * 2 * S * E * 16
    assert mod.scan_flops_per_step(cfg, 1) == scans
    assert mod.scan_flops_per_step(cfg, 3) == 3 * scans
    assert mod.flops_per_sample(cfg) == pytest.approx(
        S * 6.0 * matmul + 3 * 15360 * pairs + scans)
    # ISSUE 60's arithmetic a token, forward MFLOP: six MLPs 943.7, the
    # head 128.0, the Mamba projections 164.5, the GMU 52.4, attention's
    # projections 104.9 (the issue says 91.7), its pairs 7.6 + 62.9 + 62.9;
    # ~1.53 GFLOP, 37.6 TFLOP a step
    assert 2 * 6 * 3 * d * 10240 / 1e6 == pytest.approx(943.7, abs=0.1)
    assert 2 * d * 25008 / 1e6 == pytest.approx(128.0, abs=0.1)
    assert 2 * 2 * mixers["mamba"] / 1e6 == pytest.approx(164.5, abs=0.1)
    assert 2 * mixers["gmu"] / 1e6 == pytest.approx(52.4, abs=0.1)
    assert 2 * (2 * mixers["sliding"] + mixers["cross"]) / 1e6 \
        == pytest.approx(104.9, abs=0.1)
    assert 15360 * mod.visible_pairs(cfg, "full") / S / 1e6 \
        == pytest.approx(62.9, abs=0.1)
    assert 15360 * mod.visible_pairs(cfg, "sliding") / S / 1e6 \
        == pytest.approx(7.6, abs=0.1)
    assert mod.flops_per_sample(cfg) / 1e12 == pytest.approx(37.6, abs=0.1)
    assert scans / mod.flops_per_sample(cfg) < 1e-3
    # the scans' bytes: 8 passes over [S, E] and 6 over [S, N] a layer, at
    # the op's boundary in bf16
    assert mod.scan_bytes_per_step(cfg, 1) \
        == 2 * (8 * S * E * 2 + 6 * S * 16 * 2)
    assert mod.scan_bytes_per_step(cfg, 2) == 2 * mod.scan_bytes_per_step(
        cfg, 1)
    # the op's own span counts the same passes at the kernels' fp32
    from paddle_tpu.kernels import selective_scan as ss
    assert ss.moved_bytes(1, S, E, 16, 2) + 6 * S * 16 * 2 * 0 \
        == 8 * S * E * 2 + 6 * S * 16 * 4
    assert ss.flops(1, S, E, 16) * 2 == scans


def test_the_roofline_cannot_pass_100_percent_at_the_real_shape():
    """What the share divides is the larger of the bytes' time and the
    operations' time at the MXU's peak; every pass that runs moves at
    least those bytes (the kernels move fp32 streams, twice the count) and
    the vector unit runs the operations far below the MXU's rate, so the
    share stays under 100% whatever the time."""
    mod, cfg = _module(), _config()
    peak = peaks("TPU v5 lite")
    by_bytes = mod.scan_bytes_per_step(cfg, 1) / peak["hbm_bytes_per_s"]
    by_flops = mod.scan_flops_per_step(cfg, 1) / peak["bf16_flops"]
    assert by_bytes > by_flops
    assert by_bytes == pytest.approx(1.64e-3, rel=0.02)
    from paddle_tpu.kernels import selective_scan as ss
    assert 2 * ss.moved_bytes(1, cfg["max_length"], 5120, 16, 4) \
        > mod.scan_bytes_per_step(cfg, 1)
    doc = _reader(ROOFLINE).__doc__
    assert "cannot pass 100%" in doc and "VPU-bound" in doc


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
    assert a["tokens"].min() >= 0 and 20000 < a["tokens"].max() < 25008


def test_the_rehearsals_first_step_is_the_references():
    """The rehearsal's first step as the benchmark takes it, through the
    harness's FirstStep: the six layers at widths cut to nothing."""
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


def test_lowered_spans_and_bodies_lowered_on_the_program_itself():
    """benchmark/harness/lowered_spans.py on the rehearsal's step: one
    `ssm.lower` a Mamba layer, one `shared.lower` a handed value, and every
    layer's body lowered once."""
    import paddle_tpu as fluid

    cell = manifest.Cell(MANIFEST, CELL, rehearse=True)
    cell.config_module.build(cell.config, 5)
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    obs = {"kind": "train", "samples_per_step": 2, "chips": 1,
           "platform": "cpu"}
    spans = lowered_spans.of_step(obs, ["ssm.lower", "shared.lower"])
    assert [s["engine"] for s in spans["ssm.lower"]][:2] == ["xla"] * 2
    assert {s["what"] for s in spans["shared.lower"]} == {"memory", "kv"}
    ops = fluid.default_main_program().global_block().desc.ops
    assert [op.attr("trips") for op in ops if op.type == "recurrence"] == \
        [1] * 6
    assert _reader("loop_bodies_lowered.train").read(obs) == 1


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
OBS = {"kind": "train", "trace_steps": 2, "trace": {"n_ops": 19},
       "platform": "tpu", "device_kind": "TPU v5 lite",
       "samples_per_step": 1}


@pytest.mark.parametrize("name", sorted({**SCOPE_READERS,
                                         **OLDER_SCOPE_READERS}))
def test_scope_reader_reads_its_scope_per_traced_step(name, trace_root):
    """Forward, what the recomputation makes again and the backward of a
    scope's ops count (the flash backward kernel sits under `flash.bwd`
    INSIDE `attn.cross`, the scan's backward kernel and the sum over dB's
    lanes under `transpose(jvp(ssm.scan))`), the in-projection and Adam
    under none."""
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
    us = SCOPE_READERS["ssm_scan_ms.train"]
    want = 100.0 * mod.scan_bytes_per_step(cfg, 1) \
        / (us * 1e-6 / 2) / peak["hbm_bytes_per_s"]
    assert reader.read(OBS) == pytest.approx(want)
    assert reader.read({**OBS, "samples_per_step": 2}) == \
        pytest.approx(2 * want)
    assert reader.read({**OBS, "platform": "cpu"}) is None
    assert reader.read({**OBS, "trace_steps": 0}) is None


@pytest.mark.parametrize("name", sorted(SCOPE_READERS) + [ROOFLINE])
@pytest.mark.parametrize("trace", ["trace_eva_scopes.textproto", None])
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
    entries = {m["name"]: m for m in manifest_holds(
        "per_layer", ["ssm_scan_ms.train", "ssm_mix_ms.train",
                      "gmu_ms.train", "attn_cross_ms.train", ROOFLINE],
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
    """The generic .train readers, `loop_heads_ms.train` (the tied head and
    its cross entropy run under the scope `loop.heads`),
    `attn_sliding_ms.train` and `attn_full_ms.train` (the two self-attention
    kinds' scopes are mellum's), the seven turnaround readings and the ten
    set-up readings have this cell among their `workloads`; the readers of
    other cells' own scopes, and the two attention rooflines (which count
    another configuration's pairs), do not."""
    for name in sorted(APPENDED):
        manifest_holds("per_layer", [name], cells=[CELL])
    manifest_holds("end_to_end", ["train_samples_per_s"], cells=[CELL])
    reported = {m["name"] for m in
                manifest.Cell(MANIFEST, CELL).metrics("per_layer")}
    assert not {"collective_ms.train", "loop_body_ms.train",
                "moe_experts_ms.train", "mla_ms.train", "cca_mix_ms.train",
                "kda_scan_ms.train", "mhc_roofline.train",
                "eva_attend_ms.train", "attn_sliding_roofline.train",
                "attn_full_roofline.train"} & reported
