"""What PR 63 adds to the benchmark: the granite-4.0-h-micro configuration
(its file against the published config, its parameter, FLOP, pair and byte
counts at the real shape against hand counts, its batch, its reference
against the program through the harness) and the three readers of
`granite-train-ssd8k`, on a small recorded trace of their own."""

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
CELL, CONFIG = "granite-train-ssd8k", "granite-4.0-h-micro"
REDUCED = {"num_hidden_layers": 10, "vocab_size": 12544}
# the share of the heads, beside the published counts (`heads_held_why`)
HELD = {"mamba_heads_held": (32, "mamba_n_heads", 64),
        "attention_heads_held": (16, "num_attention_heads", 32),
        "key_value_heads_held": (4, "num_key_value_heads", 8)}
# us in the fixture
SCOPE_READERS = {"ssd_scan_ms.train": 14.0, "ssd_mix_ms.train": 9.0}
OLDER_SCOPE_READERS = {"attn_full_ms.train": 8.0}
ROOFLINE = "ssd_scan_roofline.train"
NEW = set(SCOPE_READERS) | {ROOFLINE}
APPENDED = {"compiles_in_window.train", "mfu.train", "device_idle.train",
            "values_moved_per_step.train", "loop_bodies_lowered.train",
            "loop_heads_ms.train", "hbm_peak_gb.train", "attn_full_ms.train",
            "turnaround_host_ms.train",
            "turnaround_runtime_ms.train", "turnaround_copy_ms.train",
            "turnaround_release_ms.train", "turnaround_caller_ms.train",
            "turnaround_entry_ms.train", "clock_skew_us.train",
            "setup_import_s.train", "setup_startup_s.train",
            "setup_first_step_s.train", "setup_trace_lower_s.train",
            "setup_compile_s.train", "setup_cache_load_s.train",
            "setup_cache_misses.train", "setup_other_compile_s.train",
            "setup_cache_entries_mb.train", "setup_cache_evicted_mb.train"}
TRACE = "trace_granite_scopes.textproto"


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
def test_file_holds_the_published_config_a_share_of_heads_depth_vocabulary():
    cfg = _config()
    assert cfg["reduced"] == list(REDUCED)
    for key, want in REDUCED.items():
        assert cfg[key] == want and cfg["published"][key] != want, key
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(rows):
        row = [json.loads(line) for line in open(rows)
               if f'"name": "{CONFIG}"' in line][0]
        for key, want in row["config"].items():
            if key in REDUCED:
                assert cfg["published"][key] == want, key
            else:
                assert cfg[key] == want, key
    # half the heads of each mixer, one whole period, an eighth of the table
    for key, (held, of, published) in HELD.items():
        assert (cfg[key], cfg[of]) == (held, published) and held * 2 == \
            published, key
    assert "test_benchmark_manifest.py" in cfg["heads_held_why"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["layer_types"][:10] == ["mamba"] * 5 + ["attention"] \
        + ["mamba"] * 4 and len(cfg["layer_types"]) == 40
    # no width is cut
    assert (cfg["hidden_size"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_n_groups"], cfg["mamba_d_conv"], cfg["mamba_expand"],
            _module().head_dim(cfg), cfg["shared_intermediate_size"]) \
        == (2048, 64, 128, 1, 4, 2, 64, 8192)
    for key in ("split_order", "gated_norm", "dt_limits", "d_skip",
                "no_positions", "mlp", "head_dim", "layer_types", "init",
                "optimizer", "max_length", "precision"):
        assert key in cfg["assumed"], key
    for said in ("heads 0-31 of 64", "query heads 0-15 of 32",
                 "rows 0-12543 of 100352", "over 8 chips", "group of 2",
                 "no code stands in"):
        assert said.lower() in cfg["deployment"].lower(), said
    for said in ("652,970,080", "772.2 M", "593.4 M", "13.06 GB"):
        assert said in cfg["reduced_why"], said
    entry = [c for c in MANIFEST["configs"] if c["name"] == CONFIG][0]
    assert cfg["source"].startswith(entry["source"])
    assert entry["source"].endswith(
        "ibm-granite/granite-4.0-h-micro/blob/main/config.json")


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
            "scan_block", "head_block", "tolerances"} <= set(
                data["reference"])
    cells = [w for w in MANIFEST["workloads"] if w["config"] == CONFIG]
    assert [w["name"] for w in cells] == [CELL]
    assert cells[0]["chips"] == 1 and cells[0]["traffic"] == "train-steady"
    for text in (entry["why"], entry["source"], cells[0]["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    # what ISSUE 63 asked of the cell: one packed row of 8192 tokens
    sizing = json.load(open(os.path.join(
        REPO, "benchmark", "cells", CELL + ".json")))
    assert sizing["per_chip_batch"] == 1 and data["max_length"] == 8192
    memory = data["memory"]
    assert memory["parameters"] == 652970080
    assert memory["tokens_8192"]["beside_first_step_bytes"] < 16.9e9
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
    assert "def _scan_tokens(" in text and "def _attention(" in text
    assert "cumsum" not in text and "tril" not in text
    assert "paddle_tpu" not in text.replace(
        "paddle_tpu/models/ssd_hybrid_decoder.py", "")


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

    kinds = _module().layer_kinds(cfg)
    assert kinds == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    for i, kind in enumerate(kinds):
        assert layer(i, "ssm") == (13_186_400 if kind == "mamba" else 0)
        assert layer(i, "attn") == (0 if kind == "mamba" else 5_242_880)
        assert layer(i, "mlp") + layer(i, "n1") + layer(i, "n2") \
            == 50_331_648 + 4_096
    assert sizes["l0_ssm_in_w"] == 2048 * 4384 == 8_978_432
    assert sizes["l0_ssm_conv_w"] + sizes["l0_ssm_conv_b"] == 2304 * 5
    assert sizes["embed"] == 12544 * 2048 == 25_690_112
    assert sum(sizes.values()) == 652_970_080


def test_flops_pairs_and_bytes_are_counted_from_the_real_shapes():
    mod, cfg = _module(), _config()
    S, d = cfg["max_length"], 2048
    mixers = {"mamba": d * 4384 + 2048 * d,
              "attention": d * (1024 + 256 + 256) + 1024 * d}
    for kind, want in mixers.items():
        assert mod.mixer_matmul_params(cfg, kind) == want, kind
    assert mod.visible_pairs(cfg) == S * (S + 1) // 2
    t, s = np.arange(96)[:, None], np.arange(96)[None]
    assert mod.visible_pairs({**cfg, "max_length": 96}) == int((s <= t).sum())
    assert mod.attend_flops_per_pair(cfg) == 2 * (64 + 64) * 16
    # the scans, by hand: a token's scores once for the one group at the
    # published chunk's lower-triangular pairs, a head's masked product, its
    # read and its write of the 64 x 128 state; forward, and twice that back
    token = 2 * 128 * 128.5 + 32 * (2 * 64 * 128.5 + 4 * 128 * 64)
    scans = 3.0 * 9 * S * token
    assert mod.scan_flops_per_step(cfg, 1) == scans
    assert mod.scan_flops_per_step(cfg, 3) == 3 * scans
    matmul = 9 * mixers["mamba"] + mixers["attention"] \
        + 10 * 3 * d * 8192 + d * 12544
    assert mod.flops_per_sample(cfg) == pytest.approx(
        S * 6.0 * matmul + 3 * 4096 * mod.visible_pairs(cfg) + scans)
    # ISSUE 63's arithmetic a token, forward MFLOP: ten MLPs 1006.6, nine
    # mixers' projections 237.1, the scans' matmuls 14.5, the head 51.4,
    # attention's projections 10.5 and its visible pairs 16.8: 1.337 GFLOP,
    # 32.9 TFLOP a step
    assert 2 * 10 * 3 * d * 8192 / 1e6 == pytest.approx(1006.6, abs=0.1)
    assert 2 * 9 * mixers["mamba"] / 1e6 == pytest.approx(237.1, abs=0.1)
    assert 9 * token / 1e6 == pytest.approx(14.5, abs=0.1)
    assert 2 * d * 12544 / 1e6 == pytest.approx(51.4, abs=0.1)
    assert 2 * mixers["attention"] / 1e6 == pytest.approx(10.5, abs=0.1)
    assert 4096 * mod.visible_pairs(cfg) / S / 1e6 \
        == pytest.approx(16.8, abs=0.1)
    assert mod.flops_per_sample(cfg) / 1e12 == pytest.approx(32.9, abs=0.1)
    assert scans / mod.flops_per_sample(cfg) == pytest.approx(0.011, abs=1e-3)
    # the scans' bytes: 6 passes over [S, 2048], 6 over [S, 128] and 3 over
    # [S, 32] a layer, at the op's boundary in bf16
    assert mod.scan_bytes_per_step(cfg, 1) \
        == 9 * 2 * S * (6 * 2048 + 6 * 128 + 3 * 32)
    assert mod.scan_bytes_per_step(cfg, 2) == 2 * mod.scan_bytes_per_step(
        cfg, 1)
    # the op's own span counts the same passes and operations
    from paddle_tpu.kernels import ssd_scan as ssd
    assert 9 * ssd.moved_bytes(1, S, 32, 64, 128, 1, 2) \
        == mod.scan_bytes_per_step(cfg, 1)
    assert 9 * ssd.flops(1, S, 32, 64, 128, 1, 256) == scans


def test_the_roofline_cannot_pass_100_percent_at_the_real_shape():
    """What the share divides is the larger of the bytes' time and the
    matmuls' time at the MXU's peak; every pass that runs moves at least
    those bytes and does at least those operations, so the share stays
    under 100% whatever the time.  The bytes bound it, narrowly."""
    mod, cfg = _module(), _config()
    peak = peaks("TPU v5 lite")
    by_bytes = mod.scan_bytes_per_step(cfg, 1) / peak["hbm_bytes_per_s"]
    by_flops = mod.scan_flops_per_step(cfg, 1) / peak["bf16_flops"]
    assert by_bytes > by_flops > 0.7 * by_bytes
    assert by_bytes / 9 == pytest.approx(0.263e-3, rel=0.02)
    assert by_flops / 9 == pytest.approx(0.200e-3, rel=0.02)
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
    assert a["tokens"].min() >= 0 and 10000 < a["tokens"].max() < 12544


def test_the_rehearsals_first_step_is_the_references():
    """The rehearsal's first step as the benchmark takes it, through the
    harness's FirstStep: three layers (Mamba-2, attention, Mamba-2) at
    widths cut to nothing."""
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
    # the program itself: one `ssd.lower` a Mamba-2 layer, every layer's
    # body lowered once
    obs = {"kind": "train", "samples_per_step": 2, "chips": 1,
           "platform": "cpu"}
    spans = lowered_spans.of_step(obs, ["ssd.lower"])
    assert [s["engine"] for s in spans["ssd.lower"]][:2] == ["xla"] * 2
    ops = fluid.default_main_program().global_block().desc.ops
    assert [op.attr("trips") for op in ops if op.type == "recurrence"] == \
        [1] * 3
    assert _reader("loop_bodies_lowered.train").read(obs) == 1


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
OBS = {"kind": "train", "trace_steps": 2, "trace": {"n_ops": 13},
       "platform": "tpu", "device_kind": "TPU v5 lite",
       "samples_per_step": 1}


@pytest.mark.parametrize("name", sorted({**SCOPE_READERS,
                                         **OLDER_SCOPE_READERS}))
def test_scope_reader_reads_its_scope_per_traced_step(name, trace_root):
    """Forward, what the recomputation makes again and the backward of a
    scope's ops count (the flash backward kernel sits under `flash.bwd`
    INSIDE `attn.full`, the scan's backward kernel and the running sum of
    dcum under `transpose(jvp(ssd.scan))`), the in-projection and Adam
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
    us = SCOPE_READERS["ssd_scan_ms.train"]
    want = 100.0 * mod.scan_bytes_per_step(cfg, 1) \
        / (us * 1e-6 / 2) / peak["hbm_bytes_per_s"]
    assert reader.read(OBS) == pytest.approx(want)
    assert reader.read({**OBS, "samples_per_step": 2}) == \
        pytest.approx(2 * want)
    assert reader.read({**OBS, "platform": "cpu"}) is None
    assert reader.read({**OBS, "trace_steps": 0}) is None


@pytest.mark.parametrize("name", sorted(SCOPE_READERS) + [ROOFLINE])
@pytest.mark.parametrize("trace", ["trace_sambay_scopes.textproto", None])
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
        "per_layer", ["ssd_scan_ms.train", "ssd_mix_ms.train", ROOFLINE],
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
