"""What PR 43 adds to the benchmark: the zaya1-8b configuration (its file
against the published config, its FLOP counts, its batch, its reference
against itself through the harness) and the four readers of
`zaya-train-cca16k`, on a small recorded trace and on a lowered step."""

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
CELL, CONFIG = "zaya-train-cca16k", "zaya1-8b"
# https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json as the
# model-configs catalog has it: every key of the published config
ROPE = {"hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"}
PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "layer_types": 40 * ["hybrid"],
    "lm_head_bias": False, "max_position_embeddings": 131072,
    "model_type": "zaya", "moe_intermediate_size": 2048,
    "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 1,
    "num_hidden_layers": 40, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
    "rope_parameters": ROPE, "router_hidden_size": 256,
    "sliding_window": None, "tie_word_embeddings": True,
    "vocab_size": 262272}
REDUCED = {"num_hidden_layers": 4, "num_experts": 8, "vocab_size": 32784}
SCOPE_READERS = {"cca_mix_ms.train": 13.0, "cca_attend_ms.train": 36.0,
                 "moe_router_ms.train": 6.0}          # us in the fixture
ROOFLINE = "cca_attend_roofline.train"
NEW = set(SCOPE_READERS) | {ROOFLINE}
APPENDED = {"compiles_in_window.train", "mfu.train", "device_idle.train",
            "values_moved_per_step.train", "loop_bodies_lowered.train",
            "hbm_peak_gb.train", "moe_experts_ms.train",
            "moe_dispatch_ms.train", "turnaround_host_ms.train",
            "turnaround_runtime_ms.train", "turnaround_copy_ms.train",
            "turnaround_release_ms.train", "turnaround_caller_ms.train",
            "turnaround_entry_ms.train", "clock_skew_us.train"}
TRACE = "trace_cca_scopes.textproto"


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
    # the floors: four layers of period 1, 8 experts held of the router's
    # 16, an eighth of the one table
    assert cfg["router_experts"] == 16 and cfg["expert_offset"] == 0
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["num_experts"] * 2 == PUBLISHED["num_experts"]
    assert _module().rotary_dim(cfg) == 64
    for key in ("cca_convolutions", "cca_padding", "cca_qk_mean",
                "cca_value_shift", "cca_norm_and_temperature", "router",
                "router_precision", "rotary_layout", "init", "optimizer",
                "skip_class", "residual_scaling", "router_balancing_bias",
                "router_aux_loss", "router_gradient", "max_length"):
        assert key in cfg["assumed"], key
    for left_out in ("skip_class", "residual_scaling",
                     "router_balancing_bias"):
        assert "left out" in cfg["assumed"][left_out].lower(), left_out
    assert "2510.04476" in cfg["assumed"]["cca_convolutions"]
    assert "2511.17127" in cfg["assumed"]["router"]
    assert "2 chips" in cfg["deployment"] and "0-7" in cfg["deployment"]
    assert "0-32783" in cfg["deployment"]
    assert "data-parallel" in cfg["deployment"]
    entry = [c for c in MANIFEST["configs"] if c["name"] == CONFIG][0]
    assert cfg["source"].startswith(entry["source"])
    assert entry["source"].endswith("Zyphra/ZAYA1-8B/blob/main/config.json")


def test_configuration_entry_and_files():
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
            r"per_tok|window|time)", key), f"{key} is a width"
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
    # what ISSUE 43 asked of the cell: one sequence of 16384, a depth the
    # memory table allows
    sizing = json.load(open(os.path.join(
        REPO, "benchmark", "cells", CELL + ".json")))
    assert sizing["per_chip_batch"] == 1 and data["max_length"] == 16384
    depth = f"depth_{data['num_hidden_layers']}"
    assert data["memory"][depth]["beside_first_step_bytes"] < 16.9e9
    assert data["memory"]["parameters"] == 494758920


def test_flops_are_counted_from_the_shapes():
    mod, cfg = _module(), _config()
    S = cfg["max_length"]
    attention = 2 * 2048 * 1024 + 2 * 2048 * 256 + 2 * 1280 * 128
    assert mod.latent_widths(cfg) == (1024, 256)
    assert mod.attention_matmul_params(cfg) == attention
    assert mod.router_matmul_params(cfg) == \
        2048 * 256 + 2 * 256 * 256 + 256 * 16
    assert mod.expected_rows_per_token(cfg) == 0.5
    assert mod.expert_matmul_params(cfg) == 3 * 2048 * 2048
    assert mod.pairs(cfg) == 134_225_920
    small = {**cfg, "max_length": 300}
    t, s = np.arange(300)[:, None], np.arange(300)[None, :]
    assert mod.pairs(small) == int((s <= t).sum())
    router = mod.router_matmul_params(cfg) * (
        1.0 if cfg["train_router"] else 1.0 / 3.0)
    layer = attention + router + 0.5 * 3 * 2048 * 2048
    # the head ONCE though the table is read twice
    matmul = 4 * layer + 2048 * 32784
    pair = 2 * 2 * 8 * 128
    attend = 3 * pair * 134_225_920 * 4
    assert mod.flops_per_sample(cfg) == pytest.approx(
        S * 6.0 * matmul + attend)
    # ISSUE 43's shares of a token's forward FLOPs: CCA as a whole about a
    # half, the head over a third
    forward = mod.flops_per_sample(cfg) / 3 / S
    cca = 2 * 4 * attention + pair * 8192.5 * 4
    assert cca / forward == pytest.approx(0.49, abs=0.01)
    assert 2 * 2048 * 32784 / forward == pytest.approx(0.365, abs=0.01)
    assert mod.attend_passes(cfg)["products"] == 7
    assert mod.attend_flops_per_step(cfg) == pytest.approx(
        3.5 * pair * 134_225_920 * 4)
    assert mod.attend_flops_per_step(cfg, 2) == pytest.approx(
        2 * mod.attend_flops_per_step({**cfg, "use_recompute": False}))


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
    assert a["tokens"].min() >= 0 and 28000 < a["tokens"].max() < 32784


def test_the_rehearsals_first_step_is_the_references():
    """The rehearsal's first step as the benchmark takes it, through the
    harness's FirstStep: three layers, so that the router's state crosses
    two recompute scopes; half a head turns."""
    import jax
    import paddle_tpu as fluid

    cell = manifest.Cell(MANIFEST, CELL, rehearse=True)
    assert cell.config["num_hidden_layers"] == 3
    assert cell.config_module.rotary_dim(cell.config) == 8
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
OBS = {"kind": "train", "trace_steps": 2, "trace": {"n_ops": 14},
       "platform": "tpu", "device_kind": "TPU v5 lite",
       "samples_per_step": 1}


@pytest.mark.parametrize("name", sorted(SCOPE_READERS))
def test_scope_reader_reads_its_scope_per_traced_step(name, trace_root):
    """The forward, the recomputed forward (which runs in this cell) and
    the backward with its glue count under their scope; the model's
    `moe.router` holds the op's own inside it; the projection before them
    counts under none."""
    reader = _reader(name)
    trace_root(TRACE)
    assert reader.read(OBS) == pytest.approx(SCOPE_READERS[name] / 1e3 / 2)
    assert reader.read({}) is None
    assert reader.read({**OBS, "kind": "serve"}) is None
    assert reader.read({**OBS, "trace_steps": 0}) is None


def test_the_expert_layers_readers_read_this_cells_trace_too(trace_root):
    trace_root(TRACE)
    assert _reader("moe_experts_ms.train").read(OBS) == pytest.approx(0.003)
    assert _reader("moe_dispatch_ms.train").read(OBS) == pytest.approx(0.0015)


def test_roofline_reader_divides_the_causal_pairs_by_the_scopes_time(
        trace_root):
    reader = _reader(ROOFLINE)
    trace_root(TRACE)
    flops = _module().attend_flops_per_step(_config(), 1)
    us = SCOPE_READERS["cca_attend_ms.train"]
    want = 100.0 * flops / (us * 1e-6 / 2) / peaks("TPU v5 lite")["bf16_flops"]
    assert reader.read(OBS) == pytest.approx(want)
    assert reader.read({**OBS, "platform": "cpu"}) is None
    assert reader.read({**OBS, "trace_steps": 0}) is None


@pytest.mark.parametrize("name", sorted(SCOPE_READERS) + [ROOFLINE])
@pytest.mark.parametrize("trace", ["trace_loop_scopes.textproto", None])
def test_a_program_without_the_scopes_reports_nothing(name, trace,
                                                      trace_root):
    """The parent of the PR that added them (its traces have other scopes),
    and a run with no trace: nothing is read and nothing is raised."""
    if trace:
        trace_root(trace)
    assert _reader(name).read(OBS) is None


def _tiny_step(S=512, width=16, head=8, expert=8, vocab=32, layers=2):
    """A step of the model at heads and widths cut to nothing and a row
    long enough for the Pallas backward, built and started on the CPU."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    spec = models.compressed_decoder(models.CompressedDecoderConfig(
        vocab_size=vocab, max_length=S, n_layer=layers, d_model=width,
        n_head=2, n_kv_head=1, head_dim=head, rotary_dim=head // 2,
        n_routed_experts=4, experts_held=2, d_expert=expert,
        router_dim=width))
    fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(spec.loss)
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    return spec


def test_lowered_spans_on_a_step_lowered_for_the_tpu():
    """benchmark/harness/lowered_spans.py on the program itself: under the
    TPU trace scope the tiny step's sites say what they were given, every
    backward on the Pallas kernel."""
    import paddle_tpu as fluid

    _tiny_step()
    obs = {"kind": "train", "samples_per_step": 1, "chips": 1,
           "platform": "cpu"}
    with fluid.flags.tpu_trace_scope(True):
        spans = lowered_spans.of_step(obs, [
            "cca.lower", "router.lower", "flash.bwd_plan", "moe.lower"])
    assert len(spans["cca.lower"]) >= 2 and len(spans["flash.bwd_plan"]) == 2
    assert all(b["engine"] == "pallas" for b in spans["flash.bwd_plan"])
    assert all(s["heads"] == 2 and s["kv_heads"] == 1 and s["rotary_dim"] == 4
               and s["conv_groups"] == 3 for s in spans["cca.lower"])
    assert all(s["carried"] == 16 and s["trained"] == 1 and s["width"] == 16
               for s in spans["router.lower"])
    assert all(m["row_buffers"] == 1 for m in spans["moe.lower"])


def test_the_counted_passes_are_the_kernels_the_compiled_step_runs():
    """attend_passes (what the roofline's FLOPs count, and what it says
    runs besides) against the step as the v5e's compiler leaves it,
    chip-less, at the smallest widths the kernels lower at: as traced,
    every layer calls the forward kernel twice (the step's forward, and
    again in the layer's recomputation) and the backward kernel once;
    compiled, the count of forward calls a layer is what attend_passes
    says RUNS (`forward` + `recomputed_forward`), of which the roofline
    counts `forward` alone."""
    import collections

    import jax
    import paddle_tpu as fluid
    from paddle_tpu.core import aot_tpu

    spec = _tiny_step(S=1024, width=128, head=128, expert=128, vocab=256)
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
    assert traced["_flash_kernel"] == 2 * cfg.n_layer
    assert traced["_flash_bwd_kernel"] == cfg.n_layer
    ran = collections.Counter(
        "backward" if "/flash.bwd/" in op else "forward"
        for op in re.findall(
            r'custom_call_target="tpu_custom_call"[^\n]*?op_name="'
            r'([^"]*cca\.attend[^"]*/fused_attention/[^"]*pallas_call)"',
            text))
    passes = _module().attend_passes({"use_recompute": cfg.use_recompute})
    assert ran["backward"] == passes["backward"] * cfg.n_layer
    assert passes["forward"] * cfg.n_layer <= ran["forward"] <= (
        passes["forward"] + passes["recomputed_forward"]) * cfg.n_layer
    assert passes["products"] == 2 * passes["forward"] + 5


def test_the_cells_readers_are_in_the_manifest(manifest_holds):
    """This file's entries are there, in their own order, with at least this
    cell; what stands behind them, and what other cells report, is theirs to
    say (conftest.py)."""
    entries = {m["name"]: m for m in manifest_holds(
        "per_layer", ["cca_mix_ms.train", "cca_attend_ms.train",
                      "moe_router_ms.train", ROOFLINE],
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


def test_bodies_lowered_reads_one_lowering_of_every_layers_body():
    """Every layer is a one-trip `recurrence` that carries two values (the
    unit of recomputation): each body is lowered once."""
    import paddle_tpu as fluid

    _tiny_step(S=16, layers=3)
    ops = fluid.default_main_program().global_block().desc.ops
    assert [op.attr("trips") for op in ops if op.type == "recurrence"] == \
        3 * [1]
    assert _reader("loop_bodies_lowered.train").read(
        {"kind": "train", "samples_per_step": 1, "chips": 1,
         "platform": "cpu"}) == 1


def test_the_older_readers_the_cell_reports_name_it(manifest_holds):
    """The generic .train readers, the expert block's two scope readers and
    the seven turnaround readings have this cell among their `workloads`;
    the readers of other cells' own scopes do not."""
    for name in sorted(APPENDED):
        manifest_holds("per_layer", [name], cells=[CELL],
                       moves="train_samples_per_s")
    manifest_holds("end_to_end", ["train_samples_per_s"], cells=[CELL])
    reported = {m["name"] for m in
                manifest.Cell(MANIFEST, CELL).metrics("per_layer")}
    assert not {"collective_ms.train", "loop_body_ms.train",
                "loop_heads_ms.train", "mla_ms.train", "moe_shared_ms.train",
                "moe_experts_roofline.train", "dsa_attend_roofline.train",
                "attn_full_ms.train", "attn_full_roofline.train"} & reported
