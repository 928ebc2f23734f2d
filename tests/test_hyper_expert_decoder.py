"""models/hyper_expert_decoder.py (four residual streams under
manifold-constrained hyper-connections around latent attention behind a
low-rank query under YaRN, and the sparse expert block) against the plain
reference of xing4.0-29b-a4b: loss and every parameter's gradient by name;
the structure of the program; the spans; what the controls of
tools/xing_reference_probe.py read at this size.  The share is tied to the
model in tests/test_hyper_expert_share.py."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import paddle_tpu as fluid
from benchmark.harness import manifest
from benchmark.harness import reference as harness_reference
from paddle_tpu import models, observability
from paddle_tpu.ops import hyper_connection_ops as hc

sys.path.insert(0, os.path.join(REPO, "tools"))
import xing_reference_probe as probe  # noqa: E402

# the module (models exports the function of the same name)
hyper = sys.modules["paddle_tpu.models.hyper_expert_decoder"]

ROPE = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
# one expert layer, two sublayers, 4 Sinkhorn iterations: the CPU compiles a
# layer and its backward for seconds, and a sublayer's 20 unrolled
# iterations for more (one case below and tests/test_hyper_connection_ops.py
# hold the 20; the benchmark's rehearsal, tests/benchmark, runs two layers)
TINY = dict(vocab_size=64, max_length=48, n_layer=1, first_k_dense=0,
            hc_sinkhorn_iters=4,
            d_model=32, d_inner=64, n_head=2, q_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            kv_lora_rank=24, rope_scaling=ROPE, n_routed_experts=16,
            experts_held=4, expert_offset=4, top_k=3, d_expert=24)
RTOL, ATOL = 2e-4, 2e-5
MHC = probe.MHC


def _ref_cfg(cfg: models.HyperExpertDecoderConfig, **over) -> dict:
    return {
        "num_hidden_layers": cfg.n_layer, "hidden_size": cfg.d_model,
        "hidden_act": "silu", "tie_word_embeddings": False,
        "first_k_dense_replace": cfg.first_k_dense,
        "heads_held": cfg.n_head,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "q_lora_rank": cfg.q_lora_rank, "rope_theta": cfg.rope_theta,
        "rope_scaling": cfg.rope_scaling, "rms_norm_eps": cfg.rms_norm_eps,
        "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
        "num_experts_per_tok": cfg.top_k,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "n_routed_experts": cfg.experts_held,
        "router_experts": cfg.n_routed_experts,
        "expert_offset": cfg.expert_offset, "hc_mult": cfg.hc_mult,
        "hc_sinkhorn_iters": cfg.hc_sinkhorn_iters, "hc_eps": cfg.hc_eps,
        "mhc_h_res_clamp_min": cfg.hc_clamp_min,
        "mhc_h_res_clamp_max": cfg.hc_clamp_max,
        "reference": {"query_block": 16}, **over}


def _opinions(scope, rng):
    """Every parameter that starts where a mistake could not show, moved:
    the norms' scales off 1, the selection's bias off 0, the
    hyper-connections' scalars at +-1, Phi large enough that a token's 24
    products are of size 1, b_res with no large diagonal (the maps then
    move with the data and the four streams come apart), and the maps that
    decide where a head looks and what the experts add made larger."""
    for p in fluid.default_main_program().all_parameters():
        v = np.asarray(scope.find_var(p.name))
        if p.name.endswith("_scale"):
            v = v + 0.3 * rng.randn(*v.shape)
        elif p.name.endswith("_router_bias"):
            v = v + 0.1 * rng.randn(*v.shape)
        elif p.name.endswith(("_a_pre", "_a_res")):
            v = 0 * v + 1.0
        elif p.name.endswith("_a_post"):
            v = 0 * v - 1.0
        elif p.name.endswith("_phi"):
            v = rng.randn(*v.shape) * 1.5 / np.sqrt(v.shape[0])
        elif p.name.endswith(("_b_pre", "_b_post")):
            v = v + 0.5 * rng.randn(*v.shape)
        elif p.name.endswith("_b_res"):
            v = rng.randn(*v.shape)
        elif p.name.endswith(("_qb_w", "_kva_w")):
            v = v * 20
        elif p.name.endswith("_router_w"):
            v = v * 5
        elif p.name.endswith(("_attn_o_w", "_down_w")):
            v = v * 30
        scope.set_var(p.name, v.astype(np.float32))


def _build(rows=2, **over):
    """(spec, params, batch, gradients, loss) of one forward-backward pass
    of a tiny model through the Executor."""
    fluid.reset_default_env()
    cfg = models.HyperExpertDecoderConfig(**{**TINY, **over})
    spec = models.hyper_expert_decoder(cfg)
    pairs = fluid.append_backward(spec.loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    _opinions(scope, np.random.RandomState(11))
    params = {p.name: np.asarray(scope.find_var(p.name))
              for p in fluid.default_main_program().all_parameters()}
    batch = spec.synthetic_batch(rows, seed=5)
    got = exe.run(feed=batch, fetch_list=[spec.loss] + [g for _, g in pairs])
    grads = {p.name: np.asarray(g) for (p, _), g in zip(pairs, got[1:])}
    return spec, params, batch, grads, float(np.ravel(got[0])[0])


def _reference_loss_and_grad(spec, params, batch, trainable, mod=None):
    loss, grad = jax.jit(functools.partial(
        (mod or probe.mutant(None)).loss_and_grad,
        cfg=_ref_cfg(spec.extras["config"]),
        feed_names=tuple(spec.feed_names), trainable=frozenset(trainable),
        micro=1))({k: jnp.asarray(v) for k, v in params.items()},
                  {k: jnp.asarray(v) for k, v in batch.items()})
    return float(loss), {k: np.asarray(v) for k, v in grad.items()}


@pytest.fixture(scope="module")
def one_step():
    """The share: 2 heads of 4, experts 4-7 of 16, an expert layer."""
    return _build()


@pytest.fixture(scope="module")
def its_reference(one_step):
    spec, params, batch, grads, _ = one_step
    return _reference_loss_and_grad(spec, params, batch, grads)


def test_the_share_against_the_plain_reference(one_step, its_reference):
    """Loss and every parameter's gradient, named parameter by named
    parameter: Phi, the three scalars and the three biases of both
    sublayers, the low-rank query's two maps and its norm among them."""
    _held_to_the_reference(one_step, its_reference)


def test_the_dense_layer_uncut_against_the_plain_reference():
    """The dense layer, every head, not recomputed, two streams, 8
    Sinkhorn iterations, plain rotary."""
    step = _build(first_k_dense=1, n_head=4, use_recompute=False, hc_mult=2,
                  hc_sinkhorn_iters=8, rope_scaling=None)
    spec, params, batch, grads, _ = step
    _held_to_the_reference(step, _reference_loss_and_grad(
        spec, params, batch, grads))


def _held_to_the_reference(step, reference):
    spec, params, batch, grads, loss = step
    ref_loss, ref_grads = reference
    assert loss == pytest.approx(ref_loss, rel=RTOL)
    assert set(grads) == set(ref_grads)
    cfg = spec.extras["config"]
    for i in range(cfg.n_layer):
        for sub in ("attn", "ffn"):
            for part in MHC:
                name = f"l{i}_hc_{sub}{part}"
                # the first sublayer's streams are four copies, which any
                # H_res with rows that add up to 1 leaves as they are
                alike = (i, sub) == (0, "attn") and part.endswith("_res")
                assert alike or np.abs(ref_grads[name]).max() > 0, name
        for part in ("qa_w", "qn_scale", "qb_w", "kva_w", "kvb_w", "o_w"):
            assert np.abs(ref_grads[f"l{i}_attn_{part}"]).max() > 0
    for name in sorted(ref_grads):
        scale = max(np.abs(ref_grads[name]).max(), 1.0)
        np.testing.assert_allclose(grads[name], ref_grads[name], rtol=RTOL,
                                   atol=ATOL * scale, err_msg=name)


def test_the_streams_are_one_carry_and_the_held_heads_size_the_maps():
    """A layer's recurrence carries one value [B, S, n, C]; W_qb, W_kvb and
    the output map are sized by the heads HELD, the two down-maps, the
    norms and all of mHC by nothing that is shared."""
    fluid.reset_default_env()
    spec = models.hyper_expert_decoder(models.HyperExpertDecoderConfig(
        **{**TINY, "n_layer": 2, "first_k_dense": 1}))
    program = fluid.default_main_program()
    shapes = {p.name: tuple(p.shape) for p in program.all_parameters()}
    assert shapes["l0_attn_qa_w"] == (32, 16)
    assert shapes["l0_attn_qb_w"] == (16, 2 * 24)
    assert shapes["l0_attn_kva_w"] == (32, 24 + 8)
    assert shapes["l0_attn_kvb_w"] == (24, 2 * 32)
    assert shapes["l0_attn_o_w"] == (2 * 16, 32)
    assert shapes["l1_hc_ffn_phi"] == (4 * 32, 24)
    assert shapes["l1_hc_ffn_b_res"] == (4, 4)
    assert shapes["l1_experts_gate_w"] == (4, 32, 24)
    assert shapes["l1_router_w"] == (32, 16)
    ops = [op for b in program.blocks for op in b.desc.ops]
    # a sublayer's maps and its read are ONE op: no mhc_maps, no mhc_read
    count = {t: sum(op.type == t for op in ops) for t in (
        "mhc_streams", "mhc_maps_read", "mhc_maps", "mhc_read", "mhc_write",
        "recurrence", "latent_attention")}
    assert count == {"mhc_streams": 1, "mhc_maps_read": 4, "mhc_maps": 0,
                     "mhc_read": 0, "mhc_write": 4, "recurrence": 2,
                     "latent_attention": 2}
    for op in ops:
        if op.type == "recurrence":
            assert len(op.attrs["__carry_names__"]) == 1
        if op.type == "latent_attention":
            assert op.attrs["yarn_factor"] == 64.0
            assert op.attrs["yarn_attention_factor"] == 1.0
            assert op.attrs["scale"] == pytest.approx(
                24 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)
    assert spec.extras["logits"].shape[-1] == 64


def test_the_other_decoders_build_the_programs_they_built():
    """expert_decoder and hybrid_linear_decoder, whose builder and model
    function this model shares: no hyper-connection op, no attribute of
    YaRN or scale, the query one map."""
    for build in (
        lambda: models.expert_decoder(models.ExpertDecoderConfig(
            vocab_size=64, max_length=16, n_layer=2, d_model=32, d_inner=64,
            n_head=2, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, kv_lora_rank=24, n_routed_experts=16,
            experts_held=4, top_k=3, d_expert=24)),
        lambda: models.hybrid_linear_decoder(
            models.HybridLinearDecoderConfig(
                vocab_size=64, max_length=64, n_layer=2, d_model=32,
                d_inner=64, kda_layers=(1,), full_attn_layers=(2,),
                kda_heads=2, kda_head_dim=16, n_head=2, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=24,
                n_routed_experts=16, experts_held=4, top_k=3, d_expert=24))):
        fluid.reset_default_env()
        build()
        program = fluid.default_main_program()
        ops = [op for b in program.blocks for op in b.desc.ops]
        assert not any(op.type.startswith("mhc_") for op in ops)
        # the final norm reads the last layer's carry itself
        assert sum(op.type == "reduce_sum" for op in ops) == 0
        mla = [op for op in ops if op.type == "latent_attention"]
        assert mla and all(
            not any(k.startswith("yarn_") or k == "scale" for k in op.attrs)
            for op in mla)
        names = {p.name for p in program.all_parameters()}
        assert any(n.endswith("_attn_q_w") for n in names)
        assert not any(n.endswith(("_qa_w", "_qb_w", "_phi")) for n in names)


# ---------------------------------------------------------------------------
# the spans
# ---------------------------------------------------------------------------
def test_the_spans_say_what_each_site_was_given():
    """One mhc.lower a sublayer (two a layer), one mla.lower a layer with
    rotary on, one moe.lower and router.lower an expert layer, one
    recurrence.lower a layer with one body."""
    names = ("mhc.lower", "mla.lower", "moe.lower", "router.lower",
             "recurrence.lower")
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        fluid.reset_default_env()
        spec = models.hyper_expert_decoder(models.HyperExpertDecoderConfig(
            **{**TINY, "n_layer": 2, "first_k_dense": 1,
               "n_routed_experts": 64, "experts_held": 8, "expert_offset": 0,
               "top_k": 4}))
        fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(spec.loss)
        fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
        observability.reset()
        compiled, feed_vals, state_vals, rng = fluid.Executor(
            fluid.CPUPlace()).capture_program(
                fluid.default_main_program(), feed=spec.synthetic_batch(2, 0))
        jax.eval_shape(compiled.raw_fn, feed_vals, state_vals, rng)
        spans = {n: [dict(s.args) for s in
                     observability.default_tracer().spans() if s.name == n]
                 for n in names}
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()
    assert len(spans["mhc.lower"]) == 4
    for site in spans["mhc.lower"]:
        assert site == {"streams": 4, "sinkhorn_iters": 4, "sublayers": 1,
                        "moved_bytes": hc.moved_bytes(2 * 48, 4, 32, 4,
                                                      128 * 24 * 4)}
    assert [(s["heads"], s["qk_dim"], s["rope"])
            for s in spans["mla.lower"]] == 2 * [(2, 24, "rotary")]
    assert len(spans["moe.lower"]) == len(spans["router.lower"]) == 1
    for site in spans["moe.lower"]:
        assert (site["experts_total"], site["experts_held"],
                site["top_k"]) == (64, 8, 4)
    assert [(s["trips"], s["bodies_lowered"])
            for s in spans["recurrence.lower"]] == 2 * [(1, 1)]


# ---------------------------------------------------------------------------
# the controls of tools/xing_reference_probe.py at the tiny size: the program
# is inside every tolerance of the plain reference (`one_step` above), each
# mutant of the reference outside at least one, and the program with its maps
# computed in bf16 outside a stated tolerance held by name
# ---------------------------------------------------------------------------
def _tolerances():
    cfg = manifest.read_json(os.path.join(
        REPO, "benchmark", "configs", "xing4.0-29b-a4b.json"))
    return cfg["rehearsal"]["reference"]


def _found(step, ref_loss, ref_grads):
    """(the harness's problems, the hyper-connections' parameters refused
    by name, the harness's readings) of a step against a reference."""
    spec, params, batch, grads, loss = step
    prods = {k: (float(np.vdot(grads[k], ref_grads[k])),
                 float(np.vdot(grads[k], grads[k])),
                 float(np.vdot(ref_grads[k], ref_grads[k])))
             for k in grads}
    found = harness_reference.judge(loss, ref_loss, prods)
    mhc = {k: v for k, v in probe.by_name(prods).items() if k.endswith(MHC)}
    return (harness_reference.problems(found, _tolerances()),
            probe.mhc_problems(mhc, _tolerances()), found)


def test_the_program_is_inside_every_tolerance(one_step, its_reference):
    """By the harness's judge and, for the hyper-connections' parameters,
    which the judge skips for their size, by name."""
    problems, by_name, found = _found(one_step, *its_reference)
    assert not problems and not by_name, (problems, by_name, found)


@pytest.mark.parametrize("name", probe.MUTANTS)
def test_the_reference_refuses_each_control(one_step, name):
    """Against each control on the reference's side (2 Sinkhorn
    iterations, the write gate not doubled, plain rotary, the softmax scale
    without YaRN's factor, fp8 matmuls) the program is outside at least one
    of the rehearsal's tolerances."""
    spec, params, batch, grads, _ = one_step
    problems, by_name, found = _found(one_step, *_reference_loss_and_grad(
        spec, params, batch, grads, mod=probe.mutant(name)))
    assert problems or by_name, (name, found)


def test_the_maps_in_bf16_fail_a_tolerance(its_reference):
    """The probe's control on the program's side: the same model with
    every value of the maps in bf16.  The streams it writes are within
    bf16's rounding of the exact maps', so the cosine hardly moves; the
    gradient's NORM does, one way (0.02% under the reference's at this one
    layer, 0.11-0.15% on the chip at the real size, PERF.md 6, PR 50), and
    `grad_norm_rtol` is set between that and the program's readings: the
    harness's own judge refuses it.  The gradients of the
    hyper-connections' own parameters, pulled back through 2 x
    `hc_sinkhorn_iters` normalisations in bf16, are off by name besides."""
    with probe.maps_bf16():
        step = _build()
    problems, by_name, found = _found(step, *its_reference)
    assert [p for p in problems if "gradient norm over" in p], found
    assert found["grad_cos"] > 0.9999
    assert by_name, found
    assert all(name.split(":")[0].endswith(MHC) for name in by_name)


def test_the_controls_are_the_probes_and_an_unknown_one_is_an_error():
    assert probe.MUTANTS + probe.PROGRAM_CONTROLS == (
        "sinkhorn_2", "write_gate_not_doubled", "plain_rope",
        "scale_left_out", "fp8_matmuls", "maps_bf16")
    with pytest.raises(KeyError):
        probe.mutant("no_such_control")
