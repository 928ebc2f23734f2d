"""What PR 38 adds to the benchmark: the mellum2-12b-a2.5b configuration
(its file against the published config, its FLOP counts, its batch, its
reference against itself through the harness) and the five readers of
`mellum-train-swa16k`, on a small recorded trace and on the spans of a
lowered step."""

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

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELL, CONFIG = "mellum-train-swa16k", "mellum2-12b-a2.5b"
# https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/
# config.json as the model-configs catalog has it: every key of the
# published config
PERIOD = 3 * ["sliding_attention"] + ["full_attention"]
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": 7 * PERIOD, "mlp_layer_types": 28 * ["sparse"],
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
REDUCED = {"num_hidden_layers": 4, "num_experts": 8, "vocab_size": 12288}
SCOPE_READERS = {"attn_sliding_ms.train": "attn.sliding",
                 "attn_full_ms.train": "attn.full"}
ROOFLINES = {"attn_sliding_roofline.train": "sliding",
             "attn_full_roofline.train": "full"}
SKIPPED = "attn_steps_skipped.train"
NEW = set(SCOPE_READERS) | set(ROOFLINES) | {SKIPPED}
APPENDED = {"compiles_in_window.train", "mfu.train", "device_idle.train",
            "values_moved_per_step.train",
            "loop_bodies_lowered.train", "hbm_peak_gb.train",
            "moe_experts_ms.train", "moe_dispatch_ms.train",
            "turnaround_host_ms.train", "turnaround_runtime_ms.train",
            "turnaround_copy_ms.train", "turnaround_release_ms.train",
            "turnaround_caller_ms.train", "turnaround_entry_ms.train",
            "clock_skew_us.train"}


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
    # the floors: one whole period and four layers, 8 experts held of the
    # router's 64, an eighth of the vocabulary
    assert cfg["router_experts"] == 64 and cfg["expert_offset"] == 0
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["num_experts"] * 8 == PUBLISHED["num_experts"]
    assert _module().layer_kinds(cfg) == 3 * ["sliding"] + ["full"]
    for key in ("router_scoring", "qk_norm", "router_aux_loss",
                "router_gradient", "rotary_layout", "yarn", "mtp_head", "intermediate_size",
                "sliding_window_keys", "optimizer", "init", "max_length"):
        assert key in cfg["assumed"], key
    assert "Qwen3" in cfg["assumed"]["qk_norm"]
    assert "8" in cfg["deployment"] and "data-parallel" in cfg["deployment"]
    assert "0-7" in cfg["deployment"] and "0-12287" in cfg["deployment"]
    entry = [c for c in MANIFEST["configs"] if c["name"] == CONFIG][0]
    assert cfg["source"].startswith(entry["source"])
    assert entry["source"].endswith(
        "Mellum2-12B-A2.5B-Instruct/blob/main/config.json")


def test_configuration_entry_and_files():
    """Everything test_benchmark_manifest.py::test_configuration_entry_and_
    files asks, with the width expression held to widths: `hidden_size`,
    not the `hidden` of num_hidden_layers (tests/conftest.py)."""
    entry = [c for c in MANIFEST["configs"] if c["name"] == CONFIG][0]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    data = _config()
    for key in ("source", "reduced", "assumed", "deployment", "kind",
                "equations", "memory", "reduced_why", "published",
                "reference", "rehearsal"):
        assert key in data, key
    assert data["reduced"] == entry["reduced"] == list(REDUCED)
    for key in entry["reduced"]:
        assert not re.search(
            r"(_dim|_rank|hidden_size|intermediate|d_model|d_inner|head|"
            r"per_tok|window)", key), f"{key} is a width"
    base = os.path.join(REPO, "benchmark", "configs", CONFIG)
    assert os.path.isfile(base + ".py")
    assert os.path.isfile(base + ".reference.py")
    assert {"loss_rtol", "grad_cos_min", "grad_norm_rtol",
            "param_norm_factor", "rows_per_part", "query_block",
            "tolerances"} <= set(data["reference"])
    assert len(data["reduced_why"]) > 40
    cells = [w for w in MANIFEST["workloads"] if w["config"] == CONFIG]
    assert [w["name"] for w in cells] == [CELL]
    assert cells[0]["chips"] == 1 and cells[0]["traffic"] == "train-steady"
    for text in (entry["why"], entry["source"], cells[0]["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    # what ISSUE 38 asked of the cell: one sequence of 16384, a depth the
    # memory table allows
    sizing = json.load(open(os.path.join(
        REPO, "benchmark", "cells", CELL + ".json")))
    assert sizing["per_chip_batch"] == 1 and data["max_length"] == 16384
    depth = f"depth_{data['num_hidden_layers']}"
    assert data["memory"][depth]["beside_first_step_bytes"] < 15.75e9
    assert data["memory"]["parameters"] == 340349184


def test_flops_are_counted_from_the_shapes():
    mod, cfg = _module(), _config()
    S = cfg["max_length"]
    attention = 2 * 2304 * 4096 + 2 * 2304 * 512
    assert mod.attention_matmul_params(cfg) == attention
    assert mod.expected_rows_per_token(cfg) == 1.0
    assert mod.pairs(cfg, "sliding") == 16_253_440
    assert mod.pairs(cfg, "full") == 134_225_920
    # the pairs inside causal AND window by looking at each, at a size
    # where that is cheap
    small = {**cfg, "max_length": 300, "sliding_window": 77}
    t, s = np.arange(300)[:, None], np.arange(300)[None, :]
    assert mod.pairs(small, "sliding") == int(
        ((t - s >= 0) & (t - s < 77)).sum())
    assert mod.pairs(small, "full") == int((s <= t).sum())
    # the router takes no gradient here: forward only, 2 of the 6
    assert cfg["train_router"] is False
    layer = attention + 2304 * 64 / 3 + 1.0 * 3 * 2304 * 896
    matmul = 4 * layer + 2304 * 12288
    pair = 2 * 2 * 32 * 128
    attend = 3 * pair * (3 * 16_253_440 + 134_225_920)
    assert mod.flops_per_sample(cfg) == pytest.approx(
        S * 6.0 * matmul + attend)
    # a sliding layer's pairs are 12.1% of a full one's; attention's core is
    # the largest single share of the count
    assert mod.pairs(cfg, "sliding") / mod.pairs(cfg, "full") == \
        pytest.approx(0.121, abs=1e-3)
    assert attend / mod.flops_per_sample(cfg) > 0.3
    # the attention's core a step: the forward (2 products; the recomputed
    # one is merged with it by the compiler) and the backward (5) over the
    # pairs, a kind's layers
    assert mod.attend_passes(cfg) == {"forward": 1, "backward": 1,
                                      "products": 7}
    assert mod.attend_flops_per_step(cfg, "sliding") == pytest.approx(
        3.5 * pair * 16_253_440 * 3)
    assert mod.attend_flops_per_step(cfg, "full", 2) == pytest.approx(
        3.5 * pair * 134_225_920 * 2)
    assert mod.attend_flops_per_step(
        {**cfg, "use_recompute": False}, "full") == pytest.approx(
        3.5 * pair * 134_225_920)
    # the expert block's grouped matmuls: forward 1 and backward 2 passes,
    # no recomputed one, with `use_recompute` or without
    for flag in (True, False):
        assert mod.grouped_matmul_flops_per_step(
            {**cfg, "use_recompute": flag}, S) == pytest.approx(
            3 * 2 * 1.0 * S * 3 * 2304 * 896 * 4)


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
    assert a["tokens"].min() >= 0 and 11000 < a["tokens"].max() < 12288


def test_the_rehearsals_first_step_is_the_references():
    """The rehearsal's first step as the benchmark takes it, through the
    harness's FirstStep: a window (12) shorter than the sequence (48),
    positions past the rehearsal's YaRN original length (16)."""
    import jax
    import paddle_tpu as fluid

    cell = manifest.Cell(MANIFEST, CELL, rehearse=True)
    assert cell.config["sliding_window"] < cell.config["max_length"]
    assert cell.config["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] < cell.config["max_length"]
    spec = cell.config_module.build(cell.config, 5)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = cell.config_module.make_batch(cell.config, spec, 2, 5)
    first = reference.FirstStep(cell, spec)
    loss = float(np.ravel(np.asarray(
        exe.run(feed=batch, fetch_list=[spec.loss])[0]))[0])
    found, problems = first.compare(loss, jax.device_put(batch), 2)
    assert problems == []
    assert found["loss_rel"] < 1e-5 and found["grad_cos"] > 1 - 1e-6
    assert abs(found["grad_norm_ratio"] - 1) < 1e-4


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
OBS = {"kind": "train", "trace_steps": 2, "trace": {"n_ops": 11},
       "platform": "tpu", "device_kind": "TPU v5 lite",
       "samples_per_step": 1}
WANT_US = {"attn_sliding_ms.train": 28.0, "attn_full_ms.train": 44.0}


@pytest.mark.parametrize("name", sorted(SCOPE_READERS))
def test_scope_reader_reads_its_scope_per_traced_step(name, trace_root):
    """The forward, the recomputed forward, the backward kernel and the
    fusion that adds a group's dK up count under their kind's scope; the
    projection before them under none."""
    reader = _reader(name)
    trace_root("trace_attn_scopes.textproto")
    assert reader.read(OBS) == pytest.approx(WANT_US[name] / 1e3 / 2)
    assert reader.read({}) is None
    assert reader.read({**OBS, "kind": "serve"}) is None
    assert reader.read({**OBS, "trace_steps": 0}) is None


def test_the_expert_layers_readers_read_this_cells_trace_too(trace_root):
    trace_root("trace_attn_scopes.textproto")
    assert _reader("moe_experts_ms.train").read(OBS) == pytest.approx(0.003)
    assert _reader("moe_dispatch_ms.train").read(OBS) == pytest.approx(0.002)


@pytest.mark.parametrize("name", sorted(ROOFLINES))
def test_roofline_reader_divides_the_masks_pairs_by_the_scopes_time(
        name, trace_root):
    reader, kind = _reader(name), ROOFLINES[name]
    trace_root("trace_attn_scopes.textproto")
    flops = _module().attend_flops_per_step(_config(), kind, 1)
    us = WANT_US[f"attn_{kind}_ms.train"]
    want = 100.0 * flops / (us * 1e-6 / 2) / peaks("TPU v5 lite")["bf16_flops"]
    assert reader.read(OBS) == pytest.approx(want)
    assert reader.read({**OBS, "platform": "cpu"}) is None
    assert reader.read({**OBS, "trace_steps": 0}) is None


@pytest.mark.parametrize("name", sorted(SCOPE_READERS) + sorted(ROOFLINES))
@pytest.mark.parametrize("trace", ["trace_dsa_scopes.textproto", None])
def test_a_program_without_the_scopes_reports_nothing(name, trace,
                                                      trace_root):
    """The parent of the PR that added them (its traces have other scopes),
    and a run with no trace: nothing is read and nothing is raised."""
    if trace:
        trace_root(trace)
    assert _reader(name).read(OBS) is None


def test_steps_skipped_is_the_share_the_mask_took_out_of_the_plans(
        monkeypatch):
    """Over every forward's and backward's plan a lowering leaves: skipped
    (above the diagonal + older than the window) over planned; a site the
    XLA engine keeps plans no grid; spans without the two counts (a
    program before PR 38) give nothing."""
    reader = _reader(SKIPPED)
    spans = {
        "flash.plan": [
            dict(k_steps=1024, k_steps_skipped=931, skipped_causal=496,
                 skipped_window=435, window=1024),
            dict(k_steps=256, k_steps_skipped=120, skipped_causal=120,
                 skipped_window=0, window=0)],
        "flash.bwd_plan": [
            dict(steps=124, steps_skipped=31, skipped_causal=16,
                 skipped_window=15, engine="pallas"),
            dict(steps=640, steps_skipped=112, skipped_causal=112,
                 skipped_window=0, engine="pallas"),
            dict(steps=1, steps_skipped=0, skipped_causal=0,
                 skipped_window=0, engine="xla")]}
    monkeypatch.setattr(lowered_spans, "of_step", lambda obs, names: {
        n: spans[n] for n in names})
    want = 100.0 * (931 + 120 + 31 + 112) / (1024 + 256 + 124 + 640)
    assert reader.read(OBS) == pytest.approx(want)
    old = {"flash.plan": [dict(k_steps=4, k_steps_skipped=1)],
           "flash.bwd_plan": [dict(steps=16, steps_skipped=6,
                                   engine="pallas")]}
    monkeypatch.setattr(lowered_spans, "of_step", lambda obs, names: old)
    assert reader.read(OBS) is None
    monkeypatch.setattr(lowered_spans, "of_step", lambda obs, names: {})
    assert reader.read(OBS) is None


def _tiny_step(S=512, window=128, width=16, head=8, expert=8, vocab=32):
    """A step of the model at heads and widths cut to nothing and a row
    long enough for the Pallas backward, built and started on the CPU."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    spec = models.windowed_decoder(models.WindowedDecoderConfig(
        vocab_size=vocab, max_length=S, d_model=width, n_head=2, n_kv_head=1,
        head_dim=head, sliding_window=window, n_routed_experts=8,
        experts_held=2, top_k=2, d_expert=expert))
    fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(spec.loss)
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    return spec


def test_lowered_spans_and_the_reader_on_a_step_lowered_for_the_tpu():
    """benchmark/harness/lowered_spans.py on the program itself, as the
    reader calls it on the chip: under the TPU trace scope the tiny step
    plans Pallas grids, and the share is the spans' own."""
    import paddle_tpu as fluid

    _tiny_step()
    obs = {"kind": "train", "samples_per_step": 1, "chips": 1,
           "platform": "cpu"}
    with fluid.flags.tpu_trace_scope(True):
        spans = lowered_spans.of_step(obs, ["flash.plan", "flash.bwd_plan",
                                            "attn.lower"])
        share = _reader(SKIPPED).read(obs)
    assert len(spans["attn.lower"]) >= 4 and len(spans["flash.bwd_plan"]) == 4
    assert all(b["engine"] == "pallas" for b in spans["flash.bwd_plan"])
    steps = sum(p["k_steps"] for p in spans["flash.plan"]) + sum(
        b["steps"] for b in spans["flash.bwd_plan"])
    skipped = sum(p["k_steps_skipped"] for p in spans["flash.plan"]) + sum(
        b["steps_skipped"] for b in spans["flash.bwd_plan"])
    assert share == pytest.approx(100.0 * skipped / steps)
    assert lowered_spans.of_step({"kind": "serve"}, ["flash.plan"]) == {}
    # off the TPU scope the CPU's engine plans no grid: nothing to read
    assert _reader(SKIPPED).read(obs) is None


def test_the_counted_passes_are_the_kernels_the_compiled_step_runs():
    """attend_passes (what the rooflines' FLOPs count) against the step as
    the v5e's compiler leaves it, chip-less, at the smallest widths the
    kernels lower at.  As traced, every layer calls the forward kernel twice
    (the step's forward, and again in the layer's recomputation) and the
    backward kernel once a trip of its outer loop; compiled, the recomputed
    forward is gone: a one-trip recurrence leaves no loop boundary between
    the two calls, their operands are the same, and XLA keeps one (the
    trace of PR 38 on the chip: 3 + 1 forward calls a step).  So a pass
    that runs is counted once and the count cannot go stale unseen (PERF.md
    7 (e) is the case where one did)."""
    import collections

    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.core import aot_tpu
    from paddle_tpu.kernels.flash_attention import _bwd_plan

    spec = _tiny_step(S=1024, window=256, width=128, head=128, expert=128,
                      vocab=256)
    cfg = spec.extras["config"]
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.flags.tpu_trace_scope(True):
        compiled, feed_vals, state_vals, rng = exe.capture_program(
            feed=spec.synthetic_batch(1, 0), fetch_list=[spec.loss])
        jaxpr = jax.make_jaxpr(compiled.raw_fn)(feed_vals, state_vals, rng)
        text = aot_tpu.trace_tpu(
            compiled.raw_fn, feed_vals, state_vals, rng,
            donate_argnums=(1,)).lower().compile().as_text()
    traced = collections.Counter()

    def count(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                traced[eqn.params["jaxpr"].debug_info.func_name] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                count(sub)

    count(jaxpr.jaxpr)
    chunks = sum(_bwd_plan(1024, 1024, 128, jnp.bfloat16, True, window=(
        256 if kind == "sliding" else None))["chunks"]
        for kind in cfg.layer_types)
    assert traced["_flash_kernel"] == (1 + cfg.use_recompute) * cfg.n_layer
    assert traced["_flash_bwd_kernel"] == chunks
    ran = collections.Counter(
        "backward" if "/flash.bwd/" in op else "forward"
        for op in re.findall(
            r'custom_call_target="tpu_custom_call"[^\n]*?op_name="'
            r'([^"]*/fused_attention/[^"]*pallas_call)"', text))
    passes = _module().attend_passes({"use_recompute": cfg.use_recompute})
    assert ran["forward"] == passes["forward"] * cfg.n_layer
    assert ran["backward"] == passes["backward"] * chunks
    assert passes["products"] == 2 * passes["forward"] + 5


def test_the_cells_readers_are_in_the_manifest(manifest_holds):
    """This file's entries are there, in their own order, with at least this
    cell; what stands behind them, and what other cells report, is theirs to
    say (conftest.py)."""
    entries = {m["name"]: m for m in manifest_holds(
        "per_layer", ["attn_sliding_ms.train", "attn_full_ms.train",
                      "attn_sliding_roofline.train",
                      "attn_full_roofline.train", SKIPPED],
        cells=[CELL], moves="train_samples_per_s", layer="training kernels")}
    assert set(entries) == NEW
    for name, m in entries.items():
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics", name + ".py"))
    for name in ROOFLINES:
        assert (entries[name]["unit"], entries[name]["better"],
                entries[name]["source"]) == ("%", "higher", "device_trace")
    for name in SCOPE_READERS:
        assert (entries[name]["unit"], entries[name]["better"],
                entries[name]["source"]) == ("ms", "lower", "device_trace")
    assert (entries[SKIPPED]["unit"], entries[SKIPPED]["better"],
            entries[SKIPPED]["source"]) == ("%", "higher", "program_span")
    cell = manifest.Cell(MANIFEST, CELL)
    assert NEW | APPENDED <= {m["name"] for m in cell.metrics("per_layer")}
    assert {"train_samples_per_s", "setup_s"} <= {
        m["name"] for m in cell.metrics("end_to_end")}
    assert cell.chips == 1 and cell.sizing["per_chip_batch"] == 1


def test_bodies_lowered_reads_one_lowering_of_every_layers_body():
    """Every layer is a one-trip `recurrence` (the unit of recomputation):
    each body is lowered once."""
    import paddle_tpu as fluid

    _tiny_step(S=16, window=4)
    ops = fluid.default_main_program().global_block().desc.ops
    assert [op.attr("trips") for op in ops if op.type == "recurrence"] == \
        4 * [1]
    assert _reader("loop_bodies_lowered.train").read(
        {"kind": "train", "samples_per_step": 1, "chips": 1,
         "platform": "cpu"}) == 1


def test_the_older_readers_the_cell_reports_name_it(manifest_holds):
    """The generic .train readers and the expert block's two scope readers
    have this cell among their `workloads`; the readers of other cells'
    own scopes do not."""
    for name in sorted(APPENDED):
        manifest_holds("per_layer", [name], cells=[CELL],
                       moves="train_samples_per_s")
    reported = {m["name"] for m in
                manifest.Cell(MANIFEST, CELL).metrics("per_layer")}
    assert not {"collective_ms.train", "loop_body_ms.train",
                "loop_heads_ms.train", "mla_ms.train", "moe_shared_ms.train",
                "moe_experts_roofline.train", "dsa_attend_roofline.train",
                "dsa_attend_ms.train"} & reported


def test_the_turnaround_readers_name_the_cell(manifest_holds):
    turnaround = manifest.load_py(os.path.join(
        REPO, "tests", "benchmark", "test_turnaround.py"))
    seven = list(turnaround.READERS) + [turnaround.SKEW]
    manifest_holds("per_layer", seven, cells=turnaround.TRAIN_CELLS + [CELL],
                   layer="program to step", source="program_span")
    reported = {m["name"] for m in
                manifest.Cell(MANIFEST, CELL).metrics("per_layer")}
    assert set(seven) <= reported
